"""DLIO TFRecord data set: one file per sample, made from the seed.

The plain side of the feed cells. `DataSet.make(i)` is file `i` as bytes;
`decode(view)` is the reader's half of the format (what
tf.data.TFRecordDataset does with one record: check both CRCs, hand back
the payload); `DataSet.sample(i)` is what the device has to hold after
file `i` went through the cache. Nothing here imports the program.

TFRecord framing: uint64 length | uint32 masked crc32c(length) | payload |
uint32 masked crc32c(payload), little-endian. DLIO's cosmoflow payload is
a serialized tf.train.Example; its bytes are opaque to a cache, so the
payload here is seeded bytes.

Sizes are DLIO's: record i holds n_i bytes, normal about `record_length`
with `record_length_stdev`. Every seed gets the same set of sizes, the
normal's quantiles at (k + 1/2)/files, dealt to the files in a seeded
order: the distribution is the source's and no seed gives a run more
bytes than another. What is handed on from a record is
`record_length_resize` bytes, DLIO's resize of the sample: here the
record's first bytes, so that what lies on the device can be held against
what was written. Without that key the whole payload is handed on.

Order is DLIO's: `file_shuffle: seed` shuffles the file list anew each
epoch, and `sample_shuffle: seed` passes the stream through a shuffle
buffer of `shuffle_size` samples, as tf.data's `shuffle` does.

Payload i = base[o_i : o_i + n_i] XOR k_i, where `base` is one seeded byte
string and (o_i, k_i) are drawn without repetition from the seed: every
sample differs from every other in most bytes, and one sample costs one
pass over memory, so set-up and the reference stay short."""

from __future__ import annotations

import statistics
import struct

import google_crc32c
import numpy as np

HEADER = 12
FOOTER = 4
_SLACK = 4096           # offsets o_i are drawn from [0, _SLACK)


def _masked_crc(data) -> int:
    crc = google_crc32c.value(data)
    return ((crc >> 15 | crc << 17) + 0xA282EAD8) & 0xFFFFFFFF


class DataSet:
    """The seeded data set of one run: sizes, base bytes, per-sample keys."""

    def __init__(self, seed: int, config: dict):
        self.files = int(config["num_files_train"])
        if int(config.get("num_samples_per_file", 1)) != 1:
            raise ValueError("dlio_tfrecord writes one sample per file")
        rng = np.random.default_rng([seed, 1])
        self.lengths = rng.permutation(_sizes(
            int(config["record_length"]),
            float(config.get("record_length_stdev", 0)), self.files))
        resize = config.get("record_length_resize")
        self.resize = None if resize is None else int(resize)
        if self.resize is not None and self.resize > self.lengths.min():
            raise ValueError(
                f"record_length_resize {self.resize} is more than the "
                f"shortest record holds ({self.lengths.min()})")
        self.shuffle_size = int(config.get("shuffle_size", 0)) \
            if config.get("sample_shuffle", "off") != "off" else 0
        self.file_shuffle = config.get("file_shuffle", "off") != "off"
        self.base = rng.integers(0, 256, int(self.lengths.max()) + _SLACK,
                                 dtype=np.uint8)
        if self.files > _SLACK * 256:
            raise ValueError("more files than distinct (offset, key) pairs")
        picks = rng.permutation(_SLACK * 256)[: self.files]
        self.offsets = (picks // 256).astype(np.int64)
        self.keys = (picks % 256).astype(np.uint8)
        self.total_bytes = int(self.lengths.sum()) \
            + self.files * (HEADER + FOOTER)

    def path(self, root: str, i: int) -> str:
        return f"{root}/img_{i:07d}_of_{self.files:07d}.tfrecord"

    def payload(self, i: int) -> np.ndarray:
        o = int(self.offsets[i])
        return self.base[o:o + int(self.lengths[i])] ^ self.keys[i]

    def resized(self, payload: np.ndarray) -> np.ndarray:
        """What is handed on from one record's payload."""
        return payload if self.resize is None else payload[:self.resize]

    def sample(self, i: int) -> np.ndarray:
        """What the device has to hold after file `i` went through."""
        return self.resized(self.payload(i))

    def make(self, i: int) -> bytes:
        body = self.payload(i)
        head = struct.pack("<Q", len(body))
        return b"".join((head, struct.pack("<I", _masked_crc(head)),
                         body.tobytes(),
                         struct.pack("<I", _masked_crc(body))))

    def epoch_order(self, seed: int, epoch: int) -> np.ndarray:
        """The order in which one epoch's samples are delivered."""
        rng = np.random.default_rng([seed, 2, epoch])
        order = rng.permutation(self.files) if self.file_shuffle \
            else np.arange(self.files)
        if self.shuffle_size < 2:
            return order
        held = list(order[:self.shuffle_size])
        out = []
        for nxt in order[self.shuffle_size:]:
            k = int(rng.integers(len(held)))
            out.append(held[k])
            held[k] = nxt
        out.extend(held[k] for k in rng.permutation(len(held)))
        return np.asarray(out, dtype=order.dtype)


def _sizes(mean: int, stdev: float, files: int) -> np.ndarray:
    """`files` sizes with the distribution normal(mean, stdev): its
    quantiles at (k + 1/2)/files, to the byte."""
    if stdev <= 0:
        return np.full(files, mean, dtype=np.int64)
    dist = statistics.NormalDist(mean, stdev)
    return np.asarray([round(dist.inv_cdf((k + 0.5) / files))
                       for k in range(files)], dtype=np.int64)


def decode(view: np.ndarray, check: bool = True) -> np.ndarray:
    """One TFRecord file of one record → its payload, as a view of `view`.
    Raises ValueError on a framing or CRC error, as TensorFlow's reader
    does (DataLossError). `check=False` skips the data CRC: the feed
    driver uses it to hand on a record it has already counted as failed,
    so that the run ends with a verdict and not a traceback."""
    if view.dtype != np.uint8 or view.ndim != 1:
        raise ValueError("decode wants a flat uint8 array")
    if len(view) < HEADER + FOOTER:
        raise ValueError(f"record of {len(view)} bytes is shorter than its "
                         f"framing")
    head = view[:8].tobytes()
    (n,) = struct.unpack("<Q", head)
    (hcrc,) = struct.unpack("<I", view[8:HEADER].tobytes())
    if hcrc != _masked_crc(head):
        raise ValueError("corrupt record: length CRC mismatch")
    if HEADER + n + FOOTER != len(view):
        raise ValueError(f"record says {n} bytes, file holds "
                         f"{len(view) - HEADER - FOOTER}")
    body = view[HEADER:HEADER + n]
    (bcrc,) = struct.unpack("<I", view[HEADER + n:].tobytes())
    if check and bcrc != _masked_crc(body):
        raise ValueError("corrupt record: data CRC mismatch")
    return body
