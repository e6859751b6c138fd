"""Vector tables on the distributed cache.

Parity: curvine-lancedb/ (Lance columnar tables cached by Curvine, scanned
for embedding lookup). TPU-native rework: row groups are fixed-schema
columnar blobs cached as ordinary files (so they ride the short-circuit
mmap path), and KNN search runs as one bf16 matmul on the TPU — the MXU
does the scan, not a CPU ANN index.

Layout under `<path>/`:
  schema.json                  {"dim": D, "columns": {...}, "row_groups": N}
  rg-00000.vec ...             row groups: [n, D] float32 + packed columns
"""

from __future__ import annotations

import json
import logging

import numpy as np

from curvine_tpu.client import CurvineClient
from curvine_tpu.common import errors as err

log = logging.getLogger(__name__)

_DTYPES = {"f32": np.float32, "i32": np.int32, "i64": np.int64}

_SCAN_FNS: dict = {}


def _scan_fn(metric: str, k: int):
    """Jitted [Q,D]×[D,N] scan+top_k, cached per (metric, k) — a jit
    defined per call would recompile every time. The table array may be
    bf16 (half the HBM traffic of f32 — the scan is bandwidth-bound);
    the MXU accumulates in f32 either way
    (preferred_element_type)."""
    fn = _SCAN_FNS.get((metric, k))
    if fn is None:
        import jax
        import jax.numpy as jnp

        def scan_knn(q, v, ids):
            # v/ids carry a zero-vector sentinel row (id -1) at the end —
            # ONE padded device copy serves both this exact scan and the
            # IVF search's padded takes; the mask keeps the sentinel out
            dots = jax.lax.dot_general(
                q.astype(v.dtype), v,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [Q, N]
            if metric == "cosine":
                qn = jnp.linalg.norm(q, axis=1, keepdims=True).clip(1e-12)
                scores = dots / qn
            else:
                vv = jnp.sum(
                    v.astype(jnp.float32) * v.astype(jnp.float32), 1)
                scores = -(jnp.sum(q * q, 1)[:, None]
                           - 2 * dots + vv[None, :])
            scores = jnp.where(ids[None, :] < 0, -jnp.inf, scores)
            s, dense = jax.lax.top_k(scores, min(k, scores.shape[1]))
            return s, jnp.take(ids, dense)   # dense idx → global row id

        fn = _SCAN_FNS[(metric, k)] = jax.jit(scan_knn)
    return fn


class VectorTable:
    def __init__(self, client: CurvineClient, path: str, dim: int,
                 columns: dict[str, str], row_groups: int,
                 version: int = 0, rows: int | None = None):
        self.client = client
        self.path = path.rstrip("/")
        self.dim = dim
        self.columns = columns
        self.row_groups = row_groups
        self.version = version
        self.rows = rows          # physical rows (None: legacy manifest)
        # deleted global row ids (Lance-style delete vector; rows stay in
        # their row groups until compaction rewrites them out)
        self._deletes: set[int] | None = None
        # device-resident scan cache: the table's LIVE vectors pinned in
        # HBM (normalized per metric) + dense→global id map, so repeated
        # scans run at MXU speed instead of re-streaming host->device
        self._dev_cache: dict = {}
        # lazily-loaded IVF index (vector/index.py); None = not probed
        self._index = None
        self._index_missing = False
        # knn calls that wanted the index but fell back to the exact
        # brute-force scan because it was stale — a silent ~100x serving
        # slowdown otherwise; logged once, counted always
        self.stale_fallbacks = 0
        self._stale_warned = False

    # ---------------- lifecycle ----------------

    @staticmethod
    async def create(client: CurvineClient, path: str, dim: int,
                     columns: dict[str, str] | None = None) -> "VectorTable":
        columns = columns or {}
        for name, dt in columns.items():
            if dt not in _DTYPES:
                raise err.InvalidArgument(f"column {name}: bad dtype {dt}")
        t = VectorTable(client, path, dim, columns, 0, rows=0)
        await client.meta.mkdir(path)
        await t._write_schema()
        return t

    @staticmethod
    async def open(client: CurvineClient, path: str) -> "VectorTable":
        raw = await (await client.open(f"{path.rstrip('/')}/schema.json")
                     ).read_all()
        s = json.loads(raw)
        return VectorTable(client, path, s["dim"], s["columns"],
                           s["row_groups"], version=s.get("version", 0),
                           rows=s.get("rows"))

    async def _write_schema(self) -> None:
        await self.client.write_all(
            f"{self.path}/schema.json",
            json.dumps({"dim": self.dim, "columns": self.columns,
                        "row_groups": self.row_groups,
                        "version": self.version,
                        "rows": self.rows}).encode())

    # ---------------- delete vector ----------------

    async def _load_deletes(self) -> set[int]:
        if self._deletes is None:
            try:
                raw = await (await self.client.open(
                    f"{self.path}/deletes.bin")).read_all()
                self._deletes = set(
                    np.frombuffer(raw, dtype=np.int64).tolist())
            except err.FileNotFound:
                self._deletes = set()
            # any OTHER failure (timeout, connect) propagates WITHOUT
            # memoizing: caching an empty set would silently resurrect
            # tombstoned rows for the life of this instance
        return self._deletes

    async def _save_deletes(self) -> None:
        arr = np.array(sorted(self._deletes or ()), dtype=np.int64)
        await self.client.write_all(f"{self.path}/deletes.bin",
                                    arr.tobytes())

    # ---------------- append / scan ----------------

    def _validate_batch(self, vectors: np.ndarray,
                        columns: dict[str, np.ndarray] | None
                        ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
        columns = columns or {}
        vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        if vectors.ndim != 2 or vectors.shape[1] != self.dim:
            raise err.InvalidArgument(
                f"vectors must be [n, {self.dim}], got {vectors.shape}")
        n = vectors.shape[0]
        out = {}
        for name, dt in self.columns.items():
            if name not in columns:
                raise err.InvalidArgument(f"missing column {name!r}")
            col = np.ascontiguousarray(columns[name], dtype=_DTYPES[dt])
            if col.shape[0] != n:
                raise err.InvalidArgument(f"column {name} length mismatch")
            out[name] = col
        return vectors, out

    async def append(self, vectors: np.ndarray,
                     columns: dict[str, np.ndarray] | None = None) -> int:
        """Append one row group; returns its index."""
        vectors, columns = self._validate_batch(vectors, columns)
        n = vectors.shape[0]
        parts = [np.int64(n).tobytes(), vectors.tobytes()]
        for name in self.columns:
            parts.append(columns[name].tobytes())
        rg = self.row_groups
        await self.client.write_all(f"{self.path}/rg-{rg:05d}.vec",
                                    b"".join(parts))
        self.row_groups += 1
        if self.rows is not None:          # legacy manifests stay lazy
            self.rows += n
        self._dev_cache.clear()
        await self._write_schema()
        return rg

    async def read_group(self, rg: int) -> tuple[np.ndarray, dict]:
        reader = await self.client.open(f"{self.path}/rg-{rg:05d}.vec")
        view = await reader.mmap_view(0, reader.len)
        if view is None:
            view = np.frombuffer(await reader.read_all(), dtype=np.uint8)
        n = int(view[:8].view(np.int64)[0])
        off = 8
        vec_bytes = n * self.dim * 4
        vectors = view[off:off + vec_bytes].view(np.float32).reshape(
            n, self.dim)
        off += vec_bytes
        cols = {}
        for name, dt in self.columns.items():
            dtype = np.dtype(_DTYPES[dt])
            cols[name] = view[off:off + n * dtype.itemsize].view(dtype)
            off += n * dtype.itemsize
        return vectors, cols

    async def scan(self):
        """Async iterator over (vectors, columns) per row group."""
        for rg in range(self.row_groups):
            yield await self.read_group(rg)

    async def _physical_rows(self) -> int:
        if self.rows is not None:
            return self.rows
        total = 0                  # legacy manifest without a row count
        async for vectors, _ in self.scan():
            total += vectors.shape[0]
        self.rows = total
        return total

    async def count(self) -> int:
        """Live rows (deletes excluded)."""
        return await self._physical_rows() - len(await self._load_deletes())

    # ---------------- delete / update / compaction ----------------

    async def delete(self, row_ids) -> int:
        """Mark global row ids deleted (Lance-style delete vector: the
        bytes stay in their row groups until compact()). Returns how many
        NEW rows were deleted."""
        total = await self._physical_rows()
        ids = [int(r) for r in np.asarray(row_ids).reshape(-1)]
        bad = [r for r in ids if not 0 <= r < total]
        if bad:
            raise err.InvalidArgument(
                f"row ids out of range [0, {total}): {bad[:5]}")
        dels = await self._load_deletes()
        before = len(dels)
        dels.update(ids)
        await self._save_deletes()
        self._dev_cache.clear()
        return len(dels) - before

    async def update(self, row_ids, vectors: np.ndarray,
                     columns: dict[str, np.ndarray] | None = None) -> int:
        """delete + insert (the Lance update model): old versions are
        tombstoned, new versions appended as a fresh row group. Returns
        the row-group index holding the new versions."""
        vectors, columns = self._validate_batch(
            np.atleast_2d(np.asarray(vectors, dtype=np.float32)), columns)
        row_ids = np.asarray(row_ids).reshape(-1)
        if vectors.shape[0] != row_ids.size:
            raise err.InvalidArgument("update rows/vectors length mismatch")
        # validation above runs BEFORE the tombstones persist: an invalid
        # replacement must not delete the old versions
        await self.delete(row_ids)
        return await self.append(vectors, columns)

    async def compact(self) -> int:
        """Rewrite row groups dropping deleted rows; global row ids are
        renumbered densely (as with Lance compaction, ids are not stable
        across compactions). Returns live rows kept."""
        dels = await self._load_deletes()
        del_arr = np.fromiter(dels, dtype=np.int64) if dels else \
            np.empty(0, dtype=np.int64)
        old_groups = self.row_groups
        self.row_groups = 0
        self.rows = 0
        self.version += 1
        self._deletes = set()
        # clear the delete vector on disk BEFORE rewriting row groups: a
        # crash mid-compaction then resurrects tombstoned rows
        # (recoverable by re-deleting) instead of tombstoning arbitrary
        # renumbered rows
        await self._save_deletes()
        # stream group by group (no whole-table materialization): each old
        # group's live rows become one new group, in order, so renumbering
        # is dense and peak memory is one row group
        kept = 0
        base = 0
        for rg in range(old_groups):
            vectors, cols = await self.read_group(rg)
            n = vectors.shape[0]
            keep = np.nonzero(~np.isin(np.arange(n) + base, del_arr))[0]
            base += n
            if not keep.size:
                continue
            await self.append(vectors[keep],
                              {name: np.asarray(cols[name])[keep]
                               for name in self.columns})
            kept += int(keep.size)
        if kept == 0:
            await self._write_schema()
        # drop superseded row-group files past the rewritten prefix
        for rg in range(self.row_groups, old_groups):
            try:
                await self.client.meta.delete(f"{self.path}/rg-{rg:05d}.vec")
            except err.CurvineError:
                pass
        self._dev_cache.clear()
        return kept

    # ---------------- TPU knn ----------------

    async def _host_live(self) -> tuple[np.ndarray, np.ndarray]:
        """All LIVE rows as one host [N, D] array + dense→global row-id
        map, in ascending global-id order (index build and the pinned
        device array must agree on this dense ordering)."""
        import asyncio

        dels = await self._load_deletes()
        if self.row_groups == 0:
            raise err.FileNotFound(f"table {self.path} is empty")
        groups = await asyncio.gather(
            *(self.read_group(rg) for rg in range(self.row_groups)))
        host = (np.concatenate([v for v, _ in groups], axis=0)
                if len(groups) > 1 else groups[0][0])
        if dels:
            mask = ~np.isin(np.arange(host.shape[0]),
                            np.fromiter(dels, dtype=np.int64))
            live = np.nonzero(mask)[0].astype(np.int32)
            host = host[live]
        else:
            live = np.arange(host.shape[0], dtype=np.int32)
        if host.shape[0] == 0:
            raise err.FileNotFound(f"table {self.path} has no live rows")
        return host, live

    async def _device_vectors(self, metric: str, device,
                              dtype: str = "f32"):
        """LIVE rows of all row groups as ONE device-resident [N, D]
        array (normalized for cosine) plus a dense→global row-id map,
        pinned across calls — the table lives in HBM like an HBM-tier
        block, and the scan is a single MXU matmul. Row groups are
        fetched concurrently (prefetch) on a cache miss. dtype=\"bf16\"
        pins the table in bfloat16: half the HBM footprint AND half the
        bandwidth of the bandwidth-bound scan (scores still accumulate
        in f32 on the MXU); top-k order can differ for near-ties."""
        import jax
        import jax.numpy as jnp

        dels = await self._load_deletes()
        key = (metric, dtype, getattr(device, "id", device),
               self.row_groups, len(dels))
        hit = self._dev_cache.get(key)
        if hit is not None:
            return hit
        host, live = await self._host_live()
        # sentinel-padded: one extra zero row (id -1) so the IVF search's
        # padded takes stay in-bounds on the SAME resident array as the
        # exact scan (no second device copy of the table)
        host = np.concatenate(
            [host, np.zeros((1, host.shape[1]), dtype=host.dtype)], axis=0)
        live = np.concatenate([live, np.full(1, -1, dtype=live.dtype)])
        v = jax.device_put(host, device)
        if metric == "cosine":
            v = v / jnp.linalg.norm(v, axis=1, keepdims=True).clip(1e-12)
        if dtype == "bf16":
            v = v.astype(jnp.bfloat16)
        v = jax.block_until_ready(v)
        ids = jax.block_until_ready(jax.device_put(live, device))
        self._dev_cache = {key: (v, ids)}   # one resident copy per table
        return v, ids

    # ---------------- IVF index ----------------

    async def create_index(self, nlist: int | None = None,
                           metric: str = "cosine", iters: int = 10,
                           device=None, cap_pct: float = 95.0,
                           pq_m: int | None = None, pq_ksub: int = 256,
                           pq_iters: int = 8,
                           pq_sample: int = 65536) -> "IvfIndex":
        """Build (or rebuild) the IVF ANN index on device and persist it
        as a cached file. Follows the Lance model: the index is a
        snapshot — table mutations leave it stale, and knn falls back to
        the exact scan until the next create_index. `cap_pct` clips the
        inverted-list padding at that percentile of list lengths (spill
        lists absorb the overflow); `pq_m` additionally trains product-
        quantization codebooks with pq_m subspaces × pq_ksub codewords
        and packs uint8 codes, enabling the two-stage ADC + exact-rerank
        search (the Lance IVF_PQ analog). See vector/index.py for the
        TPU-first design."""
        import jax
        from curvine_tpu.vector.index import IvfIndex, table_snapshot

        if metric not in ("cosine", "l2"):
            raise err.InvalidArgument(f"metric {metric!r}")
        if pq_m and self.dim % pq_m:
            raise err.InvalidArgument(
                f"pq_m {pq_m} must divide dim {self.dim}")
        host, live = await self._host_live()
        if metric == "cosine":
            host = host / np.linalg.norm(
                host, axis=1, keepdims=True).clip(1e-12)
        n = host.shape[0]
        if nlist is None:
            nlist = max(1, int(np.sqrt(n)))     # the usual IVF default
        snap = table_snapshot(self)
        snap["metric"] = metric
        dev = device if device is not None else jax.local_devices()[0]
        idx = IvfIndex.build(host, live, nlist, snap, iters=iters,
                             device=dev, cap_pct=cap_pct, pq_m=pq_m,
                             pq_ksub=pq_ksub, pq_iters=pq_iters,
                             pq_sample=pq_sample)
        await self.client.write_all(f"{self.path}/index.ivf",
                                    idx.to_bytes())
        self._index = idx
        self._index_missing = False
        return idx

    async def _load_index(self):
        from curvine_tpu.vector.index import IvfIndex

        if self._index is not None or self._index_missing:
            return self._index
        try:
            raw = await (await self.client.open(
                f"{self.path}/index.ivf")).read_all()
        except err.FileNotFound:
            self._index_missing = True
            return None
        self._index = IvfIndex.from_bytes(raw)
        return self._index

    async def _fresh_index(self, metric: str):
        """The persisted index, or None when absent/stale/other-metric
        (knn then uses the exact scan)."""
        from curvine_tpu.vector.index import table_snapshot

        idx = await self._load_index()
        if idx is None:
            return None
        await self._load_deletes()
        snap = table_snapshot(self)
        snap["metric"] = metric
        return idx if idx.built_at == snap else None

    async def knn(self, query: np.ndarray, k: int = 10,
                  metric: str = "cosine", device=None,
                  materialize: bool = True, use_index: bool = True,
                  nprobe: int = 8, dtype: str = "f32",
                  use_pq: bool | str = "auto", rerank: int | None = None,
                  pallas: bool | str = "auto"):
        """Top-k nearest rows to `query` [D] or [Q, D].

        With a FRESH IVF index (create_index since the last mutation) and
        use_index=True, the scan is chained device stages — queries ×
        centroids, then a gather+dot over only the probed lists; with PQ
        codes (create_index(pq_m=...)) and use_pq, the probed lists are
        scored by the 8-bit ADC scan first and only the top-`rerank`
        survivors are gathered for the exact re-rank (see
        vector/index.py); results are approximate with recall set by
        `nprobe` (and `rerank` on the PQ path). Otherwise it is ONE
        exact [Q, D]×[D, N] matmul + top_k over the pinned table — no
        per-group host loop, no re-streaming (the round-2 per-group
        await+device_put pattern benched at Python speed, not MXU
        speed). A STALE index (mutations since create_index) silently
        degrading to the brute-force scan is a ~100x serving regression,
        so it is warned once and counted in `stale_fallbacks`.

        materialize=False returns device arrays without forcing a
        device→host sync — callers issuing a stream of scans can pipeline
        dispatches and block once (remote-dispatch RTT amortizes)."""
        import jax

        if metric not in ("cosine", "l2"):
            raise err.InvalidArgument(f"metric {metric!r}")
        if dtype not in ("f32", "bf16"):
            raise err.InvalidArgument(f"dtype {dtype!r}")
        query = np.atleast_2d(np.asarray(query, dtype=np.float32))
        if query.shape[1] != self.dim:
            raise err.InvalidArgument(f"query dim {query.shape[1]} != {self.dim}")
        dev = device if device is not None else jax.local_devices()[0]
        v, ids = await self._device_vectors(metric, dev, dtype=dtype)
        idx = await self._fresh_index(metric) if use_index else None
        if use_index and idx is None and self._index is not None:
            self.stale_fallbacks += 1
            if not self._stale_warned:
                self._stale_warned = True
                log.warning(
                    "table %s: IVF index is stale (or built for another "
                    "metric) — knn falling back to the exact brute-force "
                    "scan until create_index() rebuilds it (warned once; "
                    "see the stale_fallbacks counter)", self.path)
        if idx is not None:
            s, i = idx.search(query, v, ids, k, metric, nprobe, dev,
                              use_pq=use_pq, rerank=rerank, pallas=pallas)
        else:
            q = jax.device_put(query, dev)
            s, i = _scan_fn(metric, k)(q, v, ids)
        if not materialize:
            return i, s
        return np.asarray(i), np.asarray(s)

    async def take(self, row_ids: np.ndarray) -> tuple[np.ndarray, dict]:
        """Materialize rows by global row id (deleted rows are invalid)."""
        row_ids = np.asarray(row_ids).reshape(-1)
        dels = await self._load_deletes()
        bad = [int(r) for r in row_ids if int(r) in dels]
        if bad:
            raise err.InvalidArgument(f"row ids deleted: {bad[:5]}")
        out_vecs = np.zeros((row_ids.size, self.dim), dtype=np.float32)
        out_cols = {name: np.zeros(row_ids.size, dtype=_DTYPES[dt])
                    for name, dt in self.columns.items()}
        base = 0
        async for vectors, cols in self.scan():
            n = vectors.shape[0]
            mask = (row_ids >= base) & (row_ids < base + n)
            if mask.any():
                local = row_ids[mask] - base
                out_vecs[mask] = vectors[local]
                for name in self.columns:
                    out_cols[name][mask] = cols[name][local]
            base += n
        return out_vecs, out_cols
