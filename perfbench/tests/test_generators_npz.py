"""The plain generator of the npz feed: the format against numpy's own
writer and reader, sizes, seeds and order."""

import io
import json
import os

import numpy as np
import pytest

from perfbench import harness

CONFIGS = os.path.join(harness.HERE, "configs")
BLOCK = 64 << 20


def load(kind, name):
    return harness.load_module(os.path.join(harness.HERE, kind, name + ".py"))


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def small(**kw):
    return dict(config("dlio-unet3d"), record_length=40000,
                record_length_stdev=18000, record_length_resize=5000, **kw)


def flat(data: bytes) -> np.ndarray:
    return np.frombuffer(data, dtype=np.uint8)


def test_numpy_reads_what_make_writes_and_decode_reads_the_same():
    """numpy's own reader is the independent reference: every file opens
    with numpy.load, and `decode` hands back what numpy reads."""
    gen = load("generators", "dlio_npz")
    ds, again, other = (gen.DataSet(s, small()) for s in (7, 7, 2**31 + 8))
    seen, written = set(), 0
    for i in range(ds.files):
        data = ds.make(i)
        written += len(data)
        assert len(data) == ds.file_bytes(i)
        with np.load(io.BytesIO(data)) as npz:
            assert sorted(npz.files) == ["x", "y"]
            x, y = npz["x"], npz["y"]
        got = gen.decode(flat(data))
        assert got.dtype == x.dtype == np.uint8
        assert got.shape == x.shape == ds.shape(i) and x.shape[2] == 1
        assert np.array_equal(got, x) and y.tolist() == [0]
        assert got.base is not None and not got.flags.owndata   # a view
        assert np.array_equal(x.reshape(-1), again.payload(i))
        assert np.array_equal(ds.resized(got), ds.sample(i))
        assert len(ds.sample(i)) == 5000 <= x.size == ds.lengths[i]
        assert not np.array_equal(ds.sample(i), other.sample(i))
        seen.add(ds.sample(i).tobytes())
    assert len(seen) == ds.files and written == ds.total_bytes
    # every seed gets the same sizes, dealt to the files in its own order
    assert sorted(ds.lengths) == sorted(other.lengths)
    assert list(ds.lengths) != list(other.lengths)
    assert ds.total_bytes == other.total_bytes


def test_make_writes_what_numpy_savez_writes():
    """To the byte (numpy 2.0 stamps its members 1980-01-01): stored
    members, zip64 local headers, a 64-byte-aligned .npy header."""
    gen = load("generators", "dlio_npz")
    ds = gen.DataSet(7, small())
    for i in (0, 11, 34):
        out = io.BytesIO()
        np.savez(out, x=ds.payload(i).reshape(ds.shape(i)), y=[0])
        assert ds.make(i) == out.getvalue()


@pytest.mark.parametrize("where", ["first", "middle", "last"])
def test_a_flipped_byte_of_the_member_raises(where):
    gen = load("generators", "dlio_npz")
    ds = gen.DataSet(7, small())
    data = ds.make(3)
    start = data.index(b"\x93NUMPY") + 128       # the array's first byte
    at = {"first": start, "middle": len(data) // 2,
          "last": start + int(ds.lengths[3]) - 1}[where]
    bad = bytearray(data)
    bad[at] ^= 0x10
    with pytest.raises(ValueError, match="CRC-32"):
        gen.decode(flat(bytes(bad)))
    # numpy's reader refuses the same file
    with pytest.raises(Exception, match="CRC"):
        np.load(io.BytesIO(bytes(bad)))["x"]
    # the driver's second try hands the sample on, counted as failed
    assert gen.decode(flat(bytes(bad)), check=False).shape == ds.shape(3)


@pytest.mark.parametrize("broken", ["truncated", "no_directory",
                                    "other_member", "short", "header"])
def test_a_framing_error_raises(broken):
    gen = load("generators", "dlio_npz")
    ds = gen.DataSet(7, small())
    data = ds.make(3)
    bad = {"truncated": data[:-1],
           "no_directory": data[:-22] + b"\0" * 22,
           "other_member": data.replace(b"x.npy", b"z.npy"),
           "short": data[:10],
           "header": data.replace(b"'descr': '|u1'", b"'descr': '<u2'"),
           }[broken]
    for check in (True, False):
        with pytest.raises(ValueError):
            gen.decode(flat(bad), check=check)
    with pytest.raises(ValueError, match="flat uint8"):
        gen.decode(flat(data).reshape(1, -1))


def test_published_sizes_are_the_normal_and_span_one_to_five_blocks():
    gen = load("generators", "dlio_npz")
    cfg = config("dlio-unet3d")
    a = gen.DataSet(2**31 + 8, cfg)      # (one: its base bytes are 310 MB)
    assert a.total_bytes == 5135781946
    assert a.lengths.mean() == pytest.approx(cfg["record_length"], rel=0.01)
    assert a.lengths.min() == 61608239 > cfg["record_length_resize"]
    assert a.lengths.max() == 310110946
    side = cfg["record_length"] ** 0.5
    for d in (0, 1):        # each dimension is the source's normal
        assert a.dims[:, d].mean() == pytest.approx(side, abs=1)
        assert a.dims[:, d].std() == pytest.approx(
            cfg["record_length_stdev"] / (2 * side), rel=0.03)
    assert abs(np.corrcoef(a.dims[:, 0], a.dims[:, 1])[0, 1]) < 0.05
    blocks = [-(-a.file_bytes(i) // BLOCK) for i in range(a.files)]
    assert np.bincount(blocks).tolist() == [0, 1, 16, 13, 4, 1]
    assert sum(blocks) == 93
    assert a.total_bytes < 0.6 * cfg["cluster"]["tier_bytes"] * 1.0001
    whole = gen.DataSet(7, dict(cfg, record_length_stdev=0,
                                record_length_resize=None,
                                num_files_train=3))
    assert len(whole.sample(1)) == round(side) ** 2
    with pytest.raises(ValueError, match="smallest sample"):
        gen.DataSet(7, dict(cfg, record_length_resize=61608240))
    with pytest.raises(ValueError, match="one sample per file"):
        gen.DataSet(7, dict(cfg, num_samples_per_file=2))
    with pytest.raises(ValueError, match="stride"):
        gen.DataSet(7, dict(cfg, num_files_train=33))


def test_order_is_a_seeded_permutation_anew_each_epoch():
    gen = load("generators", "dlio_npz")
    ds, again = gen.DataSet(7, small()), gen.DataSet(7, small())
    a, b = ds.epoch_order(7, 0), ds.epoch_order(7, 1)
    assert sorted(a) == sorted(b) == list(range(35)) and list(a) != list(b)
    assert list(a) == list(again.epoch_order(7, 0))
    assert list(a) != list(ds.epoch_order(8, 0))
    assert len(a) % ds.files == 0 and len(a) % 7 == 0     # whole batches
    one = gen.DataSet(7, small(sample_shuffle="off")).epoch_order(7, 0)
    assert sorted(one) == list(range(35)) and list(one) != list(a)
    assert list(gen.DataSet(7, small(file_shuffle="off",
                                     sample_shuffle="off"))
                .epoch_order(7, 0)) == list(range(35))


def test_unet3d_is_the_published_shape():
    cfg = config("dlio-unet3d")
    assert cfg["record_length"] == 146600628 and cfg["batch_size"] == 7
    assert cfg["record_length_stdev"] == 68341808
    assert cfg["record_length_resize"] == 2097152
    assert cfg["read_threads"] == 4 and cfg["num_samples_per_file"] == 1
    assert (cfg["format"], cfg["data_loader"]) == ("npz", "pytorch")
    assert (cfg["file_shuffle"], cfg["sample_shuffle"]) == ("seed", "seed")
    assert "shuffle_size" not in cfg and cfg["prefetch_depth"] == 2
    assert cfg["reduced"] == ["num_files_train"]
    assert cfg["num_files_train"] == 35
    assert cfg["published"] == {"num_files_train": 168}
    assert cfg["num_files_train"] % cfg["batch_size"] == 0 \
        == cfg["published"]["num_files_train"] % cfg["batch_size"]
    assert "unet3d_a100.yaml" in cfg["source"] and len(cfg["source"]) <= 200
    other = config("dlio-cosmoflow")
    assert cfg["cluster"] == other["cluster"]
    assert set(cfg) - {"format"} == set(other) - {"shuffle_size", "format"}
    assert len(cfg["guarantees"]) == 3


def test_the_generator_imports_nothing_of_the_program():
    with open(os.path.join(harness.HERE, "generators", "dlio_npz.py")) as f:
        source = f.read()
    imports = [ln for ln in source.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports and not [ln for ln in imports
                            if "curvine" in ln or "perfbench" in ln]
