"""DLIO npz data set: one file per sample, made from the seed.

The plain side of the large-sample feed (unet3d). `DataSet.make(i)` is
file `i` as bytes, what DLIO's generator writes with
`np.savez(path, x=records, y=labels)`; `decode(view)` is the reader's half
of the format (what `np.load(path)["x"]` does with it: find the member,
read the `.npy` header, check the member's CRC-32, hand back the array);
`DataSet.sample(i)` is what the device has to hold after file `i` went
through the cache. Nothing here imports the program.

The file is a zip as `numpy.savez` (zipfile, ZIP_STORED, force_zip64)
lays it out, to the byte:
  local header | x.npy | local header | y.npy | central directory | end
A local header is PK\\3\\4, version 45, no flags, stored, the zip epoch,
the member's CRC-32, both sizes 0xFFFFFFFF and a zip64 extra field that
carries them in 64 bits; the central directory repeats name, CRC-32 and
the sizes in 32 bits with the header's offset. A member is a `.npy`
(version 1.0): \\x93NUMPY, the header's length, a dict literal padded
with spaces to a multiple of 64 bytes, then the array's bytes in C order.
`x` is uint8 of shape (dim1, dim2, 1): DLIO's sample; `y` is the label,
int64 [0].

Sizes are DLIO's: each of a sample's two dimensions is normal about
sqrt(record_length) with deviation record_length_stdev / (2
sqrt(record_length)), and the sample holds dim1 * dim2 bytes. Every seed
gets the same set of sizes: both dimensions take that normal's quantiles
at (k + 1/2)/files, the second dimension's taken in strides (`_STRIDE`,
`_FIRST`) so that the two are uncorrelated, and the files get the pairs
in a seeded order: the distribution is the source's and no seed gives a
run more bytes than another. What is handed on from a sample is
`record_length_resize` bytes, DLIO's resize: here the array's first
bytes, so that what lies on the device can be held against what was
written. Without that key the whole array is handed on.

Order is DLIO's under the pytorch loader: `file_shuffle: seed` and
`sample_shuffle: seed` each a seeded permutation, anew each epoch (with
one sample a file, a permutation of a permutation).

Payload i = base[o_i : o_i + n_i] XOR k_i, where `base` is one seeded byte
string and (o_i, k_i) are drawn without repetition from the seed: every
sample differs from every other in most bytes, and one sample costs one
pass over memory, so set-up and the reference stay short."""

from __future__ import annotations

import ast
import math
import statistics
import struct
import zlib

import numpy as np

_SLACK = 4096           # offsets o_i are drawn from [0, _SLACK)
_STRIDE, _FIRST = 11, 9  # the second dimension of pair k is quantile
#                          (_STRIDE * k + _FIRST) mod files
MEMBER = b"x.npy"
_NPY_MAGIC = b"\x93NUMPY"
_LOCAL = struct.Struct("<IHHHHHIIIHH")             # 30 bytes
_ZIP64 = struct.Struct("<HHQQ")                    # 20 bytes
_CENTRAL = struct.Struct("<IHHHHHHIIIHHHHHII")     # 46 bytes
_END = struct.Struct("<IHHHHIIH")                  # 22 bytes
_LOCAL_SIG, _CENTRAL_SIG, _END_SIG = 0x04034B50, 0x02014B50, 0x06054B50
_VERSION = 45           # zip64, as zipfile writes under force_zip64
_DATE = (1 << 5) | 1    # 1980-01-01, numpy's fixed stamp
_MODE = 0o600 << 16


def _npy_header(descr: str, shape: tuple) -> bytes:
    """The head of a version-1.0 .npy, padded as numpy pads it."""
    text = (f"{{'descr': '{descr}', 'fortran_order': False, "
            f"'shape': {shape!r}, }}").encode("latin1")
    pad = -(len(_NPY_MAGIC) + 4 + len(text) + 1) % 64
    text += b" " * pad + b"\n"
    return _NPY_MAGIC + b"\x01\x00" + struct.pack("<H", len(text)) + text


_LABEL = _npy_header("<i8", (1,)) + struct.pack("<q", 0)       # y.npy


def _local(name: bytes, crc: int, size: int) -> bytes:
    return _LOCAL.pack(_LOCAL_SIG, _VERSION, 0, 0, 0, _DATE, crc,
                       0xFFFFFFFF, 0xFFFFFFFF, len(name), _ZIP64.size) \
        + name + _ZIP64.pack(1, 16, size, size)


def _central(name: bytes, crc: int, size: int, offset: int) -> bytes:
    return _CENTRAL.pack(_CENTRAL_SIG, _VERSION | 3 << 8, _VERSION, 0, 0, 0,
                         _DATE, crc, size, size, len(name), 0, 0, 0, 0,
                         _MODE, offset) + name


class DataSet:
    """The seeded data set of one run: shapes, base bytes, per-sample keys."""

    def __init__(self, seed: int, config: dict):
        self.files = int(config["num_files_train"])
        if int(config.get("num_samples_per_file", 1)) != 1:
            raise ValueError("dlio_npz writes one sample per file")
        rng = np.random.default_rng([seed, 1])
        self.dims = rng.permutation(_shapes(
            int(config["record_length"]),
            float(config.get("record_length_stdev", 0)), self.files))
        self.lengths = self.dims[:, 0] * self.dims[:, 1]
        resize = config.get("record_length_resize")
        self.resize = None if resize is None else int(resize)
        if self.resize is not None and self.resize > self.lengths.min():
            raise ValueError(
                f"record_length_resize {self.resize} is more than the "
                f"smallest sample holds ({self.lengths.min()})")
        self.shuffles = sum(config.get(k, "off") != "off"
                            for k in ("file_shuffle", "sample_shuffle"))
        self.base = rng.integers(0, 256, int(self.lengths.max()) + _SLACK,
                                 dtype=np.uint8)
        if self.files > _SLACK * 256:
            raise ValueError("more files than distinct (offset, key) pairs")
        picks = rng.permutation(_SLACK * 256)[: self.files]
        self.offsets = (picks // 256).astype(np.int64)
        self.keys = (picks % 256).astype(np.uint8)
        self.total_bytes = sum(self.file_bytes(i) for i in range(self.files))

    def path(self, root: str, i: int) -> str:
        return f"{root}/img_{i:07d}_of_{self.files:07d}.npz"

    def shape(self, i: int) -> tuple:
        return int(self.dims[i, 0]), int(self.dims[i, 1]), 1

    def file_bytes(self, i: int) -> int:
        """The length of file `i`, without making it."""
        framing = 2 * (_LOCAL.size + _ZIP64.size + _CENTRAL.size) \
            + 4 * len(MEMBER) + _END.size
        return framing + len(_npy_header("|u1", self.shape(i))) \
            + int(self.lengths[i]) + len(_LABEL)

    def payload(self, i: int, n: int | None = None) -> np.ndarray:
        """Sample `i` as flat bytes, or its first `n`."""
        o = int(self.offsets[i])
        n = int(self.lengths[i]) if n is None else n
        return self.base[o:o + n] ^ self.keys[i]

    def resized(self, payload: np.ndarray) -> np.ndarray:
        """What is handed on from one sample: its first bytes, flat."""
        flat = payload.reshape(-1)
        return flat if self.resize is None else flat[:self.resize]

    def sample(self, i: int) -> np.ndarray:
        """What the device has to hold after file `i` went through."""
        return self.payload(i, self.resize)

    def make(self, i: int) -> bytes:
        # (a member as the pieces it is made of: one copy of the sample
        # fewer than joining them first)
        members = ((MEMBER, (_npy_header("|u1", self.shape(i)),
                             self.payload(i).tobytes())),
                   (b"y.npy", (_LABEL,)))
        parts, directory, at = [], [], 0
        for name, pieces in members:
            crc, size = 0, sum(map(len, pieces))
            for piece in pieces:
                crc = zlib.crc32(piece, crc)
            if size >= 0xFFFFFFFF:
                raise ValueError("a member of 4 GiB wants a zip64 "
                                 "directory")
            head = _local(name, crc, size)
            directory.append(_central(name, crc, size, at))
            parts += [head, *pieces]
            at += len(head) + size
        end = _END.pack(_END_SIG, 0, 0, len(members), len(members),
                        sum(map(len, directory)), at, 0)
        return b"".join(parts + directory + [end])

    def epoch_order(self, seed: int, epoch: int) -> np.ndarray:
        """The order in which one epoch's samples are delivered."""
        rng = np.random.default_rng([seed, 2, epoch])
        order = np.arange(self.files)
        for _ in range(self.shuffles):
            order = order[rng.permutation(self.files)]
        return order


def _shapes(record_length: int, stdev: float, files: int) -> np.ndarray:
    """`files` pairs (dim1, dim2): both dimensions the quantiles at
    (k + 1/2)/files of normal(sqrt(record_length), stdev / (2
    sqrt(record_length))), paired in strides."""
    side = math.sqrt(record_length)
    if stdev <= 0:
        return np.full((files, 2), round(side), dtype=np.int64)
    if math.gcd(_STRIDE, files) != 1:
        raise ValueError(f"{files} files share a factor with the stride "
                         f"{_STRIDE}: the pairs would repeat")
    dist = statistics.NormalDist(side, stdev / (2 * side))
    q = [max(1, round(dist.inv_cdf((k + 0.5) / files)))
         for k in range(files)]
    return np.asarray([(q[k], q[(_STRIDE * k + _FIRST) % files])
                       for k in range(files)], dtype=np.int64)


def _fields(view: np.ndarray, at: int, layout: struct.Struct, what: str):
    if at < 0 or at + layout.size > len(view):
        raise ValueError(f"{what} at {at} lies outside the file")
    return layout.unpack(view[at:at + layout.size].tobytes())


def decode(view: np.ndarray, check: bool = True) -> np.ndarray:
    """One npz file → its member `x` as an array, a view of `view`.
    Raises ValueError on a framing or CRC-32 error, as zipfile does under
    numpy.load (BadZipFile). `check=False` skips the CRC-32: the feed
    driver uses it to hand on a sample it has already counted as failed,
    so that the run ends with a verdict and not a traceback."""
    if view.dtype != np.uint8 or view.ndim != 1:
        raise ValueError("decode wants a flat uint8 array")
    end_at = len(view) - _END.size
    sig, disk, cd_disk, here, total, cd_size, cd_at, comment = _fields(
        view, end_at, _END, "the end of the central directory")
    if sig != _END_SIG or disk or cd_disk or comment or here != total \
            or cd_at + cd_size != end_at:
        raise ValueError("not a zip: no end of central directory where "
                         "a file without a comment has it")
    found = None
    for _ in range(total):
        entry = _fields(view, cd_at, _CENTRAL, "a directory entry")
        if entry[0] != _CENTRAL_SIG:
            raise ValueError("corrupt central directory")
        name_len, extra_len, comment_len = entry[10:13]
        name = view[cd_at + _CENTRAL.size:
                    cd_at + _CENTRAL.size + name_len].tobytes()
        if name == MEMBER:
            found = entry
        cd_at += _CENTRAL.size + name_len + extra_len + comment_len
    if found is None:
        raise ValueError(f"no member {MEMBER.decode()} in the file")
    flags, method = found[3], found[4]
    crc, stored, size, offset = found[7], found[8], found[9], found[16]
    if flags & 0x1 or method != 0 or stored != size or size == 0xFFFFFFFF:
        raise ValueError("member x.npy is not a stored member under 4 GiB")
    local = _fields(view, offset, _LOCAL, "the member's local header")
    start = offset + _LOCAL.size + local[9] + local[10]
    if local[0] != _LOCAL_SIG or local[9] != len(MEMBER) \
            or start + size > end_at - cd_size:
        raise ValueError("corrupt local header of x.npy")
    member = view[start:start + size]
    if size < 10 or member[:8].tobytes() != _NPY_MAGIC + b"\x01\x00":
        raise ValueError("x.npy is not a version-1.0 .npy")
    (text_len,) = struct.unpack("<H", member[8:10].tobytes())
    head = 10 + text_len
    try:
        meta = ast.literal_eval(member[10:head].tobytes().decode("latin1"))
        shape = tuple(int(d) for d in meta["shape"])
        plain = meta["descr"] == "|u1" and meta["fortran_order"] is False
    except (ValueError, SyntaxError, KeyError, TypeError) as e:
        raise ValueError(f"corrupt .npy header: {e}") from None
    if not plain or math.prod(shape) != size - head:
        raise ValueError(f"x.npy says {meta}, and holds {size - head} "
                         f"bytes")
    if check and zlib.crc32(memoryview(member)) != crc:
        raise ValueError("corrupt member x.npy: CRC-32 mismatch")
    return member[head:].reshape(shape)
