"""Model checkpoint distribution over a TPU pod.

The reference's "LLM model distribution acceleration" use case
(README.md Case 3): pull checkpoint bytes once from the cache (warmed from
S3 by a load job) and fan them out to all devices. Replicated params are
dispatched tensor by tensor as their views land, no host copy: each
crosses the host link to one chip and is copied chip to chip onto the
others (on a mesh this process addresses whole). Params restored under a
layout (``spec_tree``) are first loaded whole into host memory — one
full owning copy of the checkpoint — and then placed leaf by leaf, each
chip receiving its own shard only; the layout is checked against the
manifest before the first tensor is opened.

Checkpoint formats: a JSON manifest ``manifest.json`` + one raw file a
tensor (what ``save_checkpoint`` writes), and the Hugging Face
safetensors layout — an index beside shard files of many tensors each,
read as byte ranges of the shards (``load_safetensors``). Both are
read through CurvineClient.
"""

from __future__ import annotations

import asyncio
import json
import logging
import math
import time
from contextlib import asynccontextmanager

import jax
import ml_dtypes
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from curvine_tpu.client import CurvineClient
from curvine_tpu.common import errors as err
from curvine_tpu.obs.trace import Timed, current_ctx

log = logging.getLogger(__name__)

_warned_pickle = False


def _tree_skeleton(tree):
    """JSON-safe structure encoding of a pytree built from dicts, lists,
    tuples and None — leaves become indices into the flat tensor list.
    Returns (skeleton, leaves). Dict keys iterate SORTED to match
    jax.tree.flatten's ordering. Raises TypeError on containers this
    encoding can't represent (custom pytree nodes) — callers fall back
    to the legacy pickled treedef."""
    leaves: list = []

    def enc(node):
        if isinstance(node, dict):
            if not all(isinstance(k, str) for k in node):
                raise TypeError("non-string dict key")
            return {"k": "dict",
                    "v": {k: enc(node[k]) for k in sorted(node)}}
        if isinstance(node, (list, tuple)):
            return {"k": "list" if isinstance(node, list) else "tuple",
                    "v": [enc(c) for c in node]}
        if node is None:
            return {"k": "none"}
        leaves.append(node)
        return {"k": "leaf", "i": len(leaves) - 1}

    return enc(tree), leaves


def _tree_build(skel, leaves):
    k = skel["k"]
    if k == "dict":
        return {key: _tree_build(c, leaves) for key, c in skel["v"].items()}
    if k == "list":
        return [_tree_build(c, leaves) for c in skel["v"]]
    if k == "tuple":
        return tuple(_tree_build(c, leaves) for c in skel["v"])
    if k == "none":
        return None
    return leaves[skel["i"]]


async def save_checkpoint(client: CurvineClient, path: str,
                          params: dict) -> None:
    """Write a pytree of arrays as manifest + raw tensor blobs. The tree
    structure is JSON-encoded INSIDE the manifest (safe to load); only
    trees with custom pytree nodes fall back to a pickled treedef
    side-file, which readers accept with a warn-once."""
    manifest = {"tensors": []}
    treedef = None
    try:
        skel, flat = _tree_skeleton(params)
        manifest["tree"] = skel
    except TypeError:
        flat, treedef = jax.tree.flatten(params)
    await client.meta.mkdir(path)
    for i, arr in enumerate(flat):
        arr = np.asarray(arr)
        name = f"t{i:05d}.bin"
        manifest["tensors"].append(
            {"name": name, "dtype": str(arr.dtype), "shape": list(arr.shape)})
        await client.write_all(f"{path}/{name}", arr.tobytes())
    await client.write_all(f"{path}/manifest.json",
                           json.dumps(manifest).encode())
    if treedef is not None:
        import pickle
        await client.write_all(f"{path}/treedef.pkl", pickle.dumps(treedef))


async def _load_manifest(client: CurvineClient, path: str,
                         allow_pickle: bool = False):
    """Parse a checkpoint's manifest. Returns (tensors, skel, treedef).

    A manifest without the JSON tree encoding needs the legacy pickled
    treedef side-file — and unpickling is arbitrary code execution for
    anyone who can write the checkpoint path, so it is an explicit
    opt-in (``allow_pickle=True``), not a silent fallback."""
    raw = json.loads(await _read_all(client, f"{path}/manifest.json"))
    if isinstance(raw, list):
        # legacy layout: bare tensor list + pickled treedef side-file
        manifest, skel = raw, None
    else:
        manifest, skel = raw["tensors"], raw.get("tree")
    treedef = None
    if skel is None:
        if not allow_pickle:
            raise ValueError(
                f"checkpoint {path!r} carries only a legacy pickled "
                f"treedef, which this reader does not load by default "
                f"(unpickling runs arbitrary code). Pass "
                f"allow_pickle=True if you trust the writer, or re-save "
                f"the checkpoint with save_checkpoint() to get the safe "
                f"JSON tree encoding.")
        global _warned_pickle
        if not _warned_pickle:
            _warned_pickle = True
            log.warning("loading legacy pickled treedef from %s; re-save "
                        "the checkpoint to use the safe JSON structure",
                        path)
        import pickle
        treedef = pickle.loads(await _read_all(client, f"{path}/treedef.pkl"))
    return manifest, skel, treedef


async def load_checkpoint(client: CurvineClient, path: str,
                          placer=None, allow_pickle: bool = False) -> dict:
    """Read tensors back (short-circuit mmap when co-located). Tensor
    fetches run CONCURRENTLY, and when ``placer`` is given (an arr→jax
    transfer fn), each tensor's host→device transfer is dispatched as
    soon as its bytes land — cache reads overlap device transfers instead
    of the round-2 read-everything-then-transfer-everything sequence."""
    async with _restore(client, path):
        manifest, skel, treedef = await _load_manifest(client, path,
                                                       allow_pickle)
        await _prime(client, path, (t["name"] for t in manifest))
        flat = await asyncio.gather(*(
            _load_tensor(client, path, t, placer) for t in manifest))
        if placer is not None:
            flat = _wait_ready(client, flat)
    return _unflatten(skel, treedef, flat)


def _unflatten(skel, treedef, flat):
    if skel is not None:
        return _tree_build(skel, flat)
    return jax.tree.unflatten(treedef, flat)


# ------------------------------------------------ Hugging Face safetensors

SAFETENSORS_INDEX = "model.safetensors.index.json"

# safetensors' dtype names → numpy's (bfloat16 and the 8-bit floats as
# JAX has them, through ml_dtypes)
_ST_DTYPES = {
    "BOOL": np.bool_, "U8": np.uint8, "I8": np.int8, "U16": np.uint16,
    "I16": np.int16, "U32": np.uint32, "I32": np.int32, "U64": np.uint64,
    "I64": np.int64, "F16": np.float16, "BF16": ml_dtypes.bfloat16,
    "F32": np.float32, "F64": np.float64,
    "F8_E4M3": ml_dtypes.float8_e4m3fn, "F8_E5M2": ml_dtypes.float8_e5m2,
}


async def load_safetensors(client: CurvineClient, root: str, placer=None,
                           select=None, dequant=None) -> dict:
    """A checkpoint in the Hugging Face safetensors layout, as one
    restore: the index ``model.safetensors.index.json`` (``weight_map``:
    tensor name → shard file) beside its shard files, each an 8-byte
    little-endian header length, a JSON header (each tensor's
    ``dtype``, ``shape`` and ``data_offsets`` from the header's end) and
    the tensors' bytes. → name → array for every tensor of the index
    that ``select(name)`` keeps (all, with no ``select``), in the index's
    order; with ``placer`` each is placed as `load_checkpoint` places a
    file, with none an owning host copy.

    First the index is read (span ``ckpt.index``; ``ckpt.index.s`` /
    ``.n``), its shards primed and each opened once, and every shard's
    header read and checked (span ``ckpt.headers``; ``ckpt.headers.s``
    / ``.n``; the first read of a shard fetches its first block, which
    its tensors then find held): a shard the index names that is not
    there, a tensor whose bytes lie outside its file or over another
    tensor's, a dtype this reader does not know, or a tensor the index
    names and its shard's header lacks fails the restore whole, as one
    ValueError naming the shard and the tensor, before any tensor is
    placed. Every selected tensor
    is a view of its byte range inside its shard (``mmap_view``: where
    the shm rung serves the blocks, a slice of mappings the shard's
    reader holds, each block granted, mapped and verified once however
    many tensors lie in it or cross it), placed as it lands (span
    ``ckpt.tensor``, attrs shard, offset, bytes, blocks, served_by;
    ``ckpt.place``); then the ready sweep. ``ckpt.bytes`` counts the
    bytes placed, not the shards'.

    ``dequant`` (a dtype, e.g. ``jnp.bfloat16``; needs a ``placer``)
    reads a block-scaled float8 checkpoint as DeepSeek-V3 publishes it:
    every ``F8_E4M3`` tensor ``X.weight`` comes with an ``F32`` tensor
    ``X.weight_scale_inv`` of ⌈o/b⌉ × ⌈i/b⌉ scales, ``b`` the
    ``quantization_config.weight_block_size`` of the ``config.json``
    beside the index (read with it). Both ranges are read and placed
    as two transfers, a kernel expands them on the weight's device
    (`pallas_ops.fp8_block_dequant`: span ``ckpt.dequant`` under the
    weight's ``ckpt.tensor``, attrs name, shape, blocks;
    ``ckpt.dequant.s`` / ``.n`` its dispatch, ``.bytes_in`` the fp8 and
    scale bytes, ``.bytes_out`` what it writes) and the fp8 and scale
    buffers are dropped once it is dispatched. ``X.weight`` is handed
    back in ``dequant``; the scale is not handed back, and ``select``
    is asked of weights alone: a weight brings its scale. An fp8 weight
    without its scale, a scale without its fp8 weight, a scale of
    another dtype or shape, or an fp8 weight with no block size fails
    the restore whole, before any tensor is placed. Tensors of other
    dtypes are placed as they are stored; without ``dequant`` fp8
    weights and their scales are handed back raw."""
    if dequant is not None and placer is None:
        raise ValueError("dequant expands on the device: give a placer")
    c = client.counters
    readers: dict[str, object] = {}
    block = None
    async with _restore(client, root):
        try:
            with Timed(c, "ckpt.index",
                       client.tracer.span("ckpt.index", detail=True)):
                weight_map = await _st_index(client, root)
                if dequant is not None:
                    block = await _st_block(client, root)
            shards = sorted(set(weight_map.values()))
            await _prime(client, root, shards)
            for shard in shards:
                readers[shard] = await _st_open(client, root, shard,
                                                weight_map)
            with Timed(c, "ckpt.headers",
                       client.tracer.span("ckpt.headers", detail=True)):
                headers = await _gather_all(
                    _st_header(readers[s], s) for s in shards)
                where = _st_locate(weight_map, dict(zip(shards, headers)))
                pairs = {} if dequant is None else _st_pairs(
                    where, block, root)
            scales = set(pairs.values())
            names = [n for n in weight_map if n not in scales
                     and (select is None or select(n))]

            def load(n: str):
                shard = weight_map[n]
                if n not in pairs:
                    return _load_range(client, readers[shard], shard, n,
                                       where[n], placer)
                s = pairs[n]
                return _load_pair(client, readers, weight_map, n, s,
                                  where[n], where[s], placer, block,
                                  dequant)

            flat = await _gather_all(load(n) for n in names)
            if placer is not None:
                flat = _wait_ready(client, flat)
            c["ckpt.bytes"] = c.get("ckpt.bytes", 0) + sum(
                where[m][3] - where[m][2] for n in names
                for m in ((n, pairs[n]) if n in pairs else (n,)))
        finally:
            for reader in readers.values():
                await reader.close()
    return dict(zip(names, flat))


async def load_safetensors_to_device(client: CurvineClient, root: str,
                                     device, select=None,
                                     dequant=None) -> dict:
    """`load_safetensors` onto one device: each selected tensor's
    transfer dispatched as its bytes land (with ``dequant``, each fp8
    weight expanded there)."""
    # without dequant, the call a bf16 load has always made
    extra = {} if dequant is None else {"dequant": dequant}
    return await load_safetensors(
        client, root, placer=lambda a: jax.device_put(a, device),
        select=select, **extra)


async def _gather_all(aws) -> list:
    """`asyncio.gather` that lets every awaitable end before the first
    error is raised: nothing of a failed restore is still reading when
    its readers close."""
    got = await asyncio.gather(*aws, return_exceptions=True)
    for res in got:
        if isinstance(res, BaseException):
            raise res
    return got


async def _st_index(client: CurvineClient, root: str) -> dict:
    """The index's ``weight_map``: tensor name → shard file name."""
    path = f"{root}/{SAFETENSORS_INDEX}"
    raw = json.loads(await _read_all(client, path))
    wm = raw.get("weight_map") if isinstance(raw, dict) else None
    if not isinstance(wm, dict) or not all(
            isinstance(v, str) and v and "/" not in v for v in wm.values()):
        raise ValueError(f"{path}: no weight_map of tensor name → shard "
                         f"file beside it")
    return wm


async def _st_open(client: CurvineClient, root: str, shard: str,
                   weight_map: dict):
    try:
        return await client.open(f"{root}/{shard}")
    except err.FileNotFound as e:
        tensor = next(n for n, s in weight_map.items() if s == shard)
        raise ValueError(f"safetensors shard {shard!r} of {root!r} is not "
                         f"there (the index names it for tensor "
                         f"{tensor!r}, among others)") from e


async def _st_header(reader, shard: str) -> tuple[int, int, dict]:
    """(where the tensors' bytes start, the file's length, the parsed
    header) of one shard, read through its reader."""
    head = await reader.pread(0, 8)
    n = int.from_bytes(head, "little") if len(head) == 8 else -1
    if not 0 < n <= reader.len - 8:
        raise ValueError(f"safetensors shard {shard!r}: a header length "
                         f"of {n} bytes in a file of {reader.len}")
    try:
        header = json.loads(await reader.pread(8, n))
    except ValueError as e:
        raise ValueError(f"safetensors shard {shard!r}: its header is "
                         f"not JSON: {e}") from e
    if not isinstance(header, dict):
        raise ValueError(f"safetensors shard {shard!r}: its header is "
                         f"not a JSON object")
    return 8 + n, reader.len, header


def _st_locate(weight_map: dict, headers: dict) -> dict:
    """tensor name → (dtype, shape, first byte, end) in its shard file,
    for every tensor the index names, every shard's header checked
    whole: dtype known, shape and bytes agreeing, bytes inside the file
    and over no other tensor's."""
    entries = {}
    for shard, (start, length, header) in headers.items():
        spans = []
        for name, t in header.items():
            if name == "__metadata__":
                continue

            def refuse(why: str):
                return ValueError(f"safetensors shard {shard!r}, tensor "
                                  f"{name!r}: {why}")

            try:
                dtype = _ST_DTYPES.get(t["dtype"])
                shape = tuple(int(d) for d in t["shape"])
                begin, end = (int(x) for x in t["data_offsets"])
            except (KeyError, TypeError, ValueError) as e:
                raise refuse(f"a header entry without dtype, shape and "
                             f"data_offsets ({e})") from e
            if dtype is None:
                raise refuse(f"dtype {t['dtype']!r} is not one this "
                             f"reader knows")
            dtype = np.dtype(dtype)
            if not 0 <= begin <= end or start + end > length:
                raise refuse(f"bytes [{begin}, {end}) after the header lie "
                             f"outside the file ({length - start} there)")
            if min(shape, default=0) < 0 or \
                    end - begin != math.prod(shape) * dtype.itemsize:
                raise refuse(f"{end - begin} bytes for shape {list(shape)} "
                             f"of {t['dtype']}")
            entries[(shard, name)] = (dtype, shape, start + begin,
                                      start + end)
            spans.append((begin, end, name))
        spans.sort()
        for (_, prev_end, prev), (begin, _, name) in zip(spans, spans[1:]):
            if begin < prev_end:
                raise ValueError(f"safetensors shard {shard!r}, tensor "
                                 f"{name!r}: its bytes overlap tensor "
                                 f"{prev!r}'s")
    out = {}
    for name, shard in weight_map.items():
        t = entries.get((shard, name))
        if t is None:
            raise ValueError(f"safetensors shard {shard!r}, tensor "
                             f"{name!r}: the index names it and the "
                             f"shard's header does not")
        out[name] = t
    return out


SAFETENSORS_CONFIG = "config.json"
_FP8 = np.dtype(ml_dtypes.float8_e4m3fn)
_SCALE = "_scale_inv"          # X.weight's scales are X.weight_scale_inv


async def _st_block(client: CurvineClient, root: str):
    """``quantization_config.weight_block_size`` of the ``config.json``
    beside the index, as (rows, cols), or None where there is none."""
    path = f"{root}/{SAFETENSORS_CONFIG}"
    try:
        raw = json.loads(await _read_all(client, path))
    except err.FileNotFound:
        return None
    q = raw.get("quantization_config") if isinstance(raw, dict) else None
    b = q.get("weight_block_size") if isinstance(q, dict) else None
    if b is None:
        return None
    if not (isinstance(b, list) and len(b) == 2 and all(
            isinstance(x, int) and x > 0 for x in b)):
        raise ValueError(f"{path}: weight_block_size {b!r} is not two "
                         f"positive integers")
    return tuple(b)


def _st_pairs(where: dict, block, root: str) -> dict:
    """fp8 weight name → its scale's name, for every ``F8_E4M3`` tensor
    the index names, each pair checked: the scale there, ``F32``, of
    ⌈o/b⌉ × ⌈i/b⌉ for a block size there; and no scale without its fp8
    weight."""
    pairs = {}
    for name, (dtype, shape, _, _) in where.items():
        if name.endswith(_SCALE):
            w = where.get(name[:-len(_SCALE)])
            if w is None or w[0] != _FP8:
                raise ValueError(f"safetensors tensor {name!r}: a scale "
                                 f"with no F8_E4M3 weight "
                                 f"{name[:-len(_SCALE)]!r} beside it")
            continue
        if dtype != _FP8:
            continue
        scale = name + _SCALE
        if scale not in where:
            raise ValueError(f"safetensors tensor {name!r}: an F8_E4M3 "
                             f"weight with no scale {scale!r}")
        if block is None:
            raise ValueError(f"safetensors tensor {name!r}: an F8_E4M3 "
                             f"weight and no quantization_config."
                             f"weight_block_size in "
                             f"{root}/{SAFETENSORS_CONFIG}")
        if len(shape) != 2:
            raise ValueError(f"safetensors tensor {name!r}: an F8_E4M3 "
                             f"weight of shape {list(shape)}, not a matrix")
        want = (-(-shape[0] // block[0]), -(-shape[1] // block[1]))
        s_dtype, s_shape, _, _ = where[scale]
        if s_dtype != np.float32 or s_shape != want:
            raise ValueError(f"safetensors tensor {scale!r}: scales of "
                             f"{s_dtype} {list(s_shape)} for weight "
                             f"{name!r} of {list(shape)} in blocks of "
                             f"{list(block)}; float32 {list(want)} wanted")
        pairs[name] = scale
    return pairs


async def _load_range(client: CurvineClient, reader, shard: str, name: str,
                      t: tuple, place, then=None):
    """One tensor as a byte range of its shard, from the cache to where
    ``place`` puts it, under the span ``ckpt.tensor``: a view where the
    shm rung serves its blocks, else a copy through ``read_range``.
    ``then(placed)``, where given, is awaited inside the span."""
    dtype, shape, begin, end = t
    with client.tracer.span("ckpt.tensor", attrs={
            "name": name, "shard": shard, "offset": begin},
            detail=True) as sp:
        arr = await reader.mmap_view(begin, end - begin)
        if arr is None:
            arr = await reader.read_range(begin, end - begin)
        sp.set_attr("blocks", reader.blocks_under(begin, end - begin))
        sp.set_attr("served_by", reader.served_by())
        sp.set_attr("bytes", arr.nbytes)
        out = _placed(client, arr, dtype, shape, place)
        if then is not None:
            out = await then(out)
        return out


async def _load_pair(client: CurvineClient, readers: dict, weight_map: dict,
                     name: str, scale: str, tw: tuple, ts: tuple, place,
                     block: tuple, dtype):
    """An fp8 weight and its scales as two ranges (each shard's own
    reader), placed as two transfers and expanded where they lie by
    `_expand`, inside the weight's ``ckpt.tensor``. The weight is placed
    as its bytes (uint8 codes, which the kernel decodes), the scales
    flat (it reads them as scalars)."""
    s_shard = weight_map[scale]
    scales = asyncio.ensure_future(_load_range(
        client, readers[s_shard], s_shard, scale,
        (ts[0], (math.prod(ts[1]),), ts[2], ts[3]), place))
    tw = (np.dtype(np.uint8),) + tuple(tw[1:])

    async def expand(wq):
        return _expand(client, name, wq, await scales, block, dtype)

    try:
        return await _load_range(client, readers[weight_map[name]],
                                 weight_map[name], name, tw, place, expand)
    except BaseException:
        await asyncio.gather(scales, return_exceptions=True)
        raise


def _expand(client: CurvineClient, name: str, wq, scales, block: tuple,
            dtype):
    """Dispatch the block dequant of one placed weight (``ckpt.dequant``)
    and drop its fp8 and scale buffers: the device frees them once the
    kernel has read them."""
    from curvine_tpu.tpu.pallas_ops import fp8_block_dequant
    c = client.counters
    with Timed(c, "ckpt.dequant", client.tracer.span(
            "ckpt.dequant", attrs={"name": name, "shape": list(wq.shape),
                                   "blocks": scales.size}, detail=True)):
        out = fp8_block_dequant(wq, scales, block, dtype)
    c["ckpt.dequant.bytes_in"] = c.get("ckpt.dequant.bytes_in", 0) \
        + wq.nbytes + scales.nbytes
    c["ckpt.dequant.bytes_out"] = c.get("ckpt.dequant.bytes_out", 0) \
        + out.nbytes
    wq.delete()
    scales.delete()
    return out


@asynccontextmanager
async def _restore(client: CurvineClient, path: str):
    """One whole restore: the span ``ckpt.restore``, the root of its
    trace — every tensor's spans share the trace id that its slow-op
    line prints — and ckpt.wall_s / ckpt.restores, a restore's mean
    seconds on /metrics. It ends, however it ends, by sending the read
    counts its tensors' readers left with the client (`_prime`): the
    worker's heat is complete when the restore returns."""
    t0 = time.perf_counter()
    with client.tracer.span("ckpt.restore", attrs={"path": path}):
        try:
            yield
        finally:
            await client.flush_reports()
    c = client.counters
    c["ckpt.wall_s"] = c.get("ckpt.wall_s", 0.0) + time.perf_counter() - t0
    c["ckpt.restores"] = c.get("ckpt.restores", 0) + 1


async def _prime(client: CurvineClient, path: str, names) -> None:
    """A restore knows every file it will open once it has the manifest
    (or the index): name them to the client at once, so that locations,
    block info and read reports cross once a peer and not once a
    file."""
    await client.prime([f"{path}/{name}" for name in names])


async def _load_tensor(client: CurvineClient, path: str, t: dict, place,
                       peer_hbm: bool = False):
    """One tensor from the cache to where ``place`` puts it, under the
    span ``ckpt.tensor`` (a step of the restore and the parent of the
    reader's phases; it raises no slow-op line of its own: in a
    many-way restore every tensor is slow). Bytes come
    as a short-circuit view (of one block, or of the file's blocks side
    by side) where the shm rung serves all of it, else as a
    copy through ``read_all``; with ``peer_hbm`` from a peer's HBM tier
    first. ``place(arr)`` is timed as ckpt.place (an async dispatch: the
    device copies while the next tensor is read); with no ``place`` the
    view is copied into host memory that outlives the reader, timed as
    ckpt.host_copy (with its bytes). The reader closes after either."""
    name = f"{path}/{t['name']}"
    with client.tracer.span("ckpt.tensor", attrs={"name": t["name"]},
                            detail=True) as sp:
        arr = await _hbm_source(client, name, client.counters) \
            if peer_hbm else None
        reader = None
        if arr is not None:
            sp.set_attr("served_by", "peer_hbm")
        else:
            reader = await client.open(name)
            arr = await reader.mmap_view(0, reader.len)
            if arr is None:
                arr = np.frombuffer(await reader.read_all(), dtype=np.uint8)
            sp.set_attr("blocks", len(reader.blocks.block_locs))
            sp.set_attr("served_by", reader.served_by())
        sp.set_attr("bytes", arr.nbytes)
        out = _placed(client, arr, np.dtype(t["dtype"]), t["shape"], place)
        if reader is not None:
            await reader.close()
        return out


def _placed(client: CurvineClient, arr: np.ndarray, dtype, shape, place):
    """A tensor's bytes as its dtype and shape, handed to ``place``
    (timed as ckpt.place: an async dispatch) or, with no ``place``,
    copied into host memory that outlives the reader."""
    arr = arr.view(dtype).reshape(shape)
    if place is None:
        return _host_copy(client, arr)
    with Timed(client.counters, "ckpt.place",
               client.tracer.span("ckpt.place", detail=True), cpu=True):
        return place(arr)


def _host_copy(client: CurvineClient, arr: np.ndarray) -> np.ndarray:
    """Own the tensor's bytes past its reader's close: one copy of the
    view, on the caller's thread."""
    c = client.counters
    with Timed(c, "ckpt.host_copy",
               client.tracer.span("ckpt.host_copy", detail=True), cpu=True):
        out = np.array(arr)
    c["ckpt.host_copy.bytes"] = c.get("ckpt.host_copy.bytes", 0) + out.nbytes
    return out


def _wait_ready(client: CurvineClient, flat: list) -> list:
    """The closing sweep: every transfer dispatched, wait for each."""
    with Timed(client.counters, "ckpt.ready_wait",
               client.tracer.span("ckpt.ready_wait", detail=True)):
        return [jax.block_until_ready(a) for a in flat]


async def _read_all(client: CurvineClient, path: str) -> bytes:
    reader = await client.open(path)
    try:
        return await reader.read_all()
    finally:
        await reader.close()


def broadcast_params(params, mesh: Mesh, spec_tree=None):
    """Place host params onto the mesh. spec_tree=None → fully replicated
    (classic model distribution); otherwise each leaf is placed under
    its PartitionSpec: ``params`` is one full copy on the host, and each
    chip receives its own shard of it, never a full copy per chip."""
    if spec_tree is None:
        sharding = NamedSharding(mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, sharding), params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, spec_tree)


def _leaf_shardings(path: str, manifest: list, skel, treedef, mesh: Mesh,
                    spec_tree) -> list:
    """The NamedSharding of every tensor of the manifest, in file order,
    from a ``spec_tree`` of PartitionSpecs. Same tree as the checkpoint,
    every named axis in the mesh, every sharded dimension divisible:
    else one ValueError that names the leaf. Reads no tensor."""
    from jax.tree_util import keystr, tree_flatten_with_path
    index_of = {keystr(k): i for k, i in tree_flatten_with_path(
        _unflatten(skel, treedef, list(range(len(manifest)))))[0]}
    spec_of = {keystr(k): s for k, s in tree_flatten_with_path(
        spec_tree, is_leaf=lambda x: isinstance(x, P))[0]}

    def refuse(leaf: str, why: str):
        return ValueError(f"checkpoint {path!r} cannot be placed under "
                          f"this spec_tree: leaf {leaf} {why}")

    for leaf in index_of.keys() - spec_of.keys():
        raise refuse(leaf, "has no PartitionSpec in the spec_tree")
    for leaf in spec_of.keys() - index_of.keys():
        raise refuse(leaf, "is in the spec_tree and not in the checkpoint")
    out: list = [None] * len(manifest)
    for leaf, i in index_of.items():
        spec, shape = spec_of[leaf], manifest[i]["shape"]
        if not isinstance(spec, P):
            raise refuse(leaf, f"has {spec!r} where a PartitionSpec "
                               f"belongs")
        if len(spec) > len(shape):
            raise refuse(leaf, f"of shape {shape} has fewer dimensions "
                               f"than {spec}")
        for dim, axes in zip(shape, spec):
            axes = () if axes is None else \
                axes if isinstance(axes, tuple) else (axes,)
            ways = 1
            for axis in axes:
                if axis not in mesh.shape:
                    raise refuse(leaf, f"names axis {axis!r}, and the "
                                       f"mesh has {tuple(mesh.shape)}")
                ways *= mesh.shape[axis]
            if dim % ways:
                raise refuse(leaf, f"of shape {shape} has a dimension "
                                   f"of {dim} that {ways} chips along "
                                   f"{spec} do not divide")
        out[i] = NamedSharding(mesh, spec)
    return out


async def _distribute_sharded(client: CurvineClient, path: str, mesh: Mesh,
                              spec_tree, allow_pickle: bool = False):
    """A restore under a layout, as one restore: the layout checked
    against the manifest, every tensor loaded into host memory (one full
    copy: ckpt.host_copy), then each placed under its PartitionSpec
    (ckpt.place, one sharded device_put a leaf: a chip receives its own
    shard only), then the ready sweep. ckpt.bytes counts the checkpoint
    once, ckpt.placed_bytes what all chips together received, from the
    shardings' shard shapes."""
    c = client.counters
    async with _restore(client, path):
        manifest, skel, treedef = await _load_manifest(client, path,
                                                       allow_pickle)
        shardings = _leaf_shardings(path, manifest, skel, treedef, mesh,
                                    spec_tree)
        await _prime(client, path, (t["name"] for t in manifest))
        host = await asyncio.gather(*(
            _load_tensor(client, path, t, None) for t in manifest))
        flat, once, placed = [], 0, 0
        for t, arr, sharding in zip(manifest, host, shardings):
            with Timed(c, "ckpt.place", client.tracer.span(
                    "ckpt.place", detail=True,
                    attrs={"name": t["name"], "spec": str(sharding.spec)}),
                    cpu=True):
                flat.append(jax.device_put(arr, sharding))
            once += arr.nbytes
            placed += mesh.size * arr.itemsize * math.prod(
                sharding.shard_shape(arr.shape))
        del host
        flat = _wait_ready(client, flat)
        c["ckpt.bytes"] = c.get("ckpt.bytes", 0) + once
        c["ckpt.placed_bytes"] = c.get("ckpt.placed_bytes", 0) + placed
    return _unflatten(skel, treedef, flat)


async def _hbm_source(client: CurvineClient, path: str,
                      counters: dict | None = None):
    """Source a cached file's bytes straight from a peer's HBM tier
    through the ICI device domain (tpu/ici_plane.py) — zero block-read
    RPCs when every block of the file is advertised. Returns a host
    uint8 view, or None (caller falls back to the mmap/RPC read path;
    the fallback is a counter, never an error)."""
    from curvine_tpu.tpu import ici_plane
    if not ici_plane.endpoints():
        return None
    try:
        fb = await client.meta.get_block_locations(path)
    except Exception:            # noqa: BLE001 — any miss → TCP rail
        return None
    if not fb.block_locs:
        return None
    parts = []
    for lb in fb.block_locs:
        got = None
        for loc in lb.locs:
            arr = ici_plane.fetch_device_block(loc.worker_id, lb.block.id)
            if arr is not None and arr.nbytes == lb.block.len:
                got = np.asarray(arr).reshape(-1).view(np.uint8)
                break
        if got is None:
            # all blocks or nothing — a half-device, half-TCP read
            # would serialize behind the slow half anyway
            if counters is not None:
                counters["ici.tcp_fallbacks"] = \
                    counters.get("ici.tcp_fallbacks", 0) + 1
            return None
        parts.append(got)
    if counters is not None:
        counters["ici.peer_pulls"] = \
            counters.get("ici.peer_pulls", 0) + len(parts)
        counters["ici.peer_pull_bytes"] = \
            counters.get("ici.peer_pull_bytes", 0) \
            + sum(p.nbytes for p in parts)
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


class _Fanout:
    """Replicated placement onto a fully addressable mesh of more than
    one device. A tensor crosses the host link once, to the mesh device
    that has received the fewest host bytes of this restore so far
    (``place``, timed as ckpt.place: the one-chip path's transfer). The
    tensors placed within one turn of the loop are then copied chip to
    chip onto every other device together, by one ``jax.device_put`` of
    their list onto the replicated sharding, dispatched once a turn on
    the loop (``flush``: counters ckpt.fanout.s, ckpt.fanout.n — tensors
    fanned out — and ckpt.fanout.bytes — bytes dispatched chip to chip,
    (devices − 1) × a tensor's bytes; detail span ``ckpt.fanout``, attrs
    tensors and bytes, under ``ckpt.restore``). ``place`` hands back a
    one-element list that ``flush`` fills with the replicated array, and
    ``results`` reads them out; the source array goes with its last
    reference (the replicated result holds its buffer as the source
    device's copy)."""

    def __init__(self, client: CurvineClient, mesh: Mesh):
        self.client = client
        self.devices = list(mesh.devices.flat)
        self.sharding = NamedSharding(mesh, P())
        self.host_bytes = [0] * len(self.devices)
        self.parent = current_ctx()
        self.pending: list[list] = []
        self.error: Exception | None = None

    def place(self, arr: np.ndarray) -> list:
        i = self.host_bytes.index(min(self.host_bytes))
        self.host_bytes[i] += arr.nbytes
        slot = [jax.device_put(arr, self.devices[i])]
        if not self.pending:
            asyncio.get_running_loop().call_soon(self.flush)
        self.pending.append(slot)
        return slot

    def flush(self) -> None:
        slots, self.pending = self.pending, []
        if not slots:
            return
        c = self.client.counters
        nbytes = (len(self.devices) - 1) * sum(s[0].nbytes for s in slots)
        t0 = time.perf_counter()
        try:
            with self.client.tracer.span(
                    "ckpt.fanout", parent=self.parent, detail=True,
                    attrs={"tensors": len(slots), "bytes": nbytes}):
                out = jax.device_put([s[0] for s in slots], self.sharding)
        except Exception as e:  # noqa: BLE001 — a loop callback: `results`
            self.error = e      # raises it in the restore
            return
        for slot, arr in zip(slots, out):
            slot[0] = arr
        c["ckpt.fanout.s"] = c.get("ckpt.fanout.s", 0.0) + \
            time.perf_counter() - t0
        c["ckpt.fanout.n"] = c.get("ckpt.fanout.n", 0) + len(slots)
        c["ckpt.fanout.bytes"] = c.get("ckpt.fanout.bytes", 0) + nbytes

    def results(self, slots: list) -> list:
        """The replicated arrays, the last turn's tensors fanned out
        first; a fan-out that failed fails the restore."""
        self.flush()
        if self.error is not None:
            raise self.error
        return [slot[0] for slot in slots]


async def _distribute_tree(client: CurvineClient, path: str, mesh: Mesh,
                           allow_pickle: bool = False):
    """Replicated distribution (docs/ici-plane.md):

    * tensors dispatch in LPT order (largest first) so the longest
      read→place chains start earliest and the pipeline drains evenly
    * on a fully addressable mesh of more than one device each tensor
      crosses the host link once, to one chip, and is copied chip to
      chip onto the others (`_Fanout`); a one-device mesh, or one with
      devices of other processes, takes one replicated ``device_put``
      of the host view a tensor
    * tensor bytes come from peer HBM over the device domain when the
      blocks are advertised (zero TCP block reads), with a transparent
      fallback to the mmap/RPC rail

    Bit-exact with the flat path — only the sourcing, order and route
    of the copies differ."""
    async with _restore(client, path):
        manifest, skel, treedef = await _load_manifest(client, path,
                                                       allow_pickle)
        await _prime(client, path, (t["name"] for t in manifest))
        counters = client.counters
        sharding = NamedSharding(mesh, P())
        fan = _Fanout(client, mesh) if mesh.devices.size > 1 \
            and sharding.is_fully_addressable else None
        t_read = time.perf_counter()

        def place(arr):
            return jax.device_put(arr, sharding)

        def size_of(t):
            n = 1
            for d in t["shape"]:
                n *= int(d)
            return n * np.dtype(t["dtype"]).itemsize

        lpt = sorted(range(len(manifest)),
                     key=lambda i: -size_of(manifest[i]))
        tasks = {i: asyncio.ensure_future(_load_tensor(
            client, path, manifest[i], place if fan is None else fan.place,
            peer_hbm=True)) for i in lpt}
        flat = [await tasks[i] for i in range(len(manifest))]
        if fan is not None:
            flat = fan.results(flat)
        flat = _wait_ready(client, flat)
        counters["ici.broadcast_bytes"] = \
            counters.get("ici.broadcast_bytes", 0) \
            + sum(size_of(t) for t in manifest)
        counters["ici.broadcast_ms"] = \
            counters.get("ici.broadcast_ms", 0) \
            + int((time.perf_counter() - t_read) * 1000)
    return _unflatten(skel, treedef, flat)


async def distribute_checkpoint(client: CurvineClient, path: str,
                                mesh: Mesh, spec_tree=None,
                                schedule: str = "tree",
                                allow_pickle: bool = False):
    """cache → pod as one restore; what comes back is ready on every
    chip. With ``spec_tree`` None every tensor is replicated, in one
    overlapped pass: each is dispatched to the mesh the moment its bytes
    land. With a ``spec_tree`` (a PartitionSpec a leaf) the checkpoint
    is restored under that layout: checked against the manifest before a
    tensor is opened (ValueError naming the leaf that cannot be placed),
    loaded whole into host memory, then placed leaf by leaf, each chip
    receiving its own shard (_distribute_sharded: load, then place — not
    yet overlapped, and one full host copy).

    ``schedule`` picks the replicated rail: "tree" (default) is
    `_distribute_tree` — LPT tensor order, peer-HBM device-domain
    sourcing, one host transfer a tensor and chip-to-chip copies for
    the other chips; "flat" is the legacy read→put baseline (a host
    transfer a tensor a chip), kept for A/B measurement. Both are
    bit-exact."""
    if spec_tree is None:
        if schedule == "tree":
            return await _distribute_tree(client, path, mesh,
                                          allow_pickle=allow_pickle)
        sharding = NamedSharding(mesh, P())
        return await load_checkpoint(
            client, path, placer=lambda a: jax.device_put(a, sharding),
            allow_pickle=allow_pickle)
    return await _distribute_sharded(client, path, mesh, spec_tree,
                                     allow_pickle=allow_pickle)


async def distribute_checkpoint_to_device(client: CurvineClient, path: str,
                                          device):
    """Single-chip variant: overlapped cache→HBM transfer of a whole
    checkpoint onto one device."""
    return await load_checkpoint(
        client, path, placer=lambda a: jax.device_put(a, device))
