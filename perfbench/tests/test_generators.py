"""The plain generators: formats, sizes and seeds."""

import json
import os

import numpy as np
import pytest

from perfbench import harness

CONFIGS = os.path.join(harness.HERE, "configs")


def load(kind, name):
    return harness.load_module(os.path.join(harness.HERE, kind, name + ".py"))


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_tfrecord_round_trip_and_crc():
    gen = load("generators", "dlio_tfrecord")
    cfg = dict(config("dlio-cosmoflow"), record_length=4099,
               record_length_stdev=100, record_length_resize=3000,
               num_files_train=40)
    ds = gen.DataSet(7, cfg)
    again = gen.DataSet(7, cfg)
    other = gen.DataSet(8, cfg)
    seen, written = set(), 0
    for i in range(ds.files):
        data = ds.make(i)
        written += len(data)
        assert len(data) == ds.lengths[i] + 16
        body = gen.decode(np.frombuffer(data, dtype=np.uint8))
        assert np.array_equal(body, again.payload(i))
        assert np.array_equal(ds.resized(body), ds.sample(i))
        assert len(ds.sample(i)) == 3000 <= len(body)
        assert not np.array_equal(ds.sample(i), other.sample(i))
        seen.add(ds.sample(i).tobytes())
    assert len(seen) == ds.files and written == ds.total_bytes
    bad = bytearray(ds.make(3))
    bad[1000] ^= 1
    with pytest.raises(ValueError, match="data CRC"):
        gen.decode(np.frombuffer(bytes(bad), dtype=np.uint8))
    with pytest.raises(ValueError):
        gen.decode(np.frombuffer(ds.make(3)[:-1], dtype=np.uint8))
    with pytest.raises(ValueError, match="shortest record"):
        gen.DataSet(7, dict(cfg, record_length_resize=4099))


def test_sizes_are_the_normal_and_the_same_for_every_seed():
    gen = load("generators", "dlio_tfrecord")
    cfg = config("dlio-cosmoflow")
    a, b = gen.DataSet(7, cfg), gen.DataSet(2**31 + 8, cfg)
    assert sorted(a.lengths) == sorted(b.lengths)
    assert list(a.lengths) != list(b.lengths)
    assert a.lengths.mean() == pytest.approx(cfg["record_length"], abs=1)
    assert a.lengths.std() == pytest.approx(cfg["record_length_stdev"],
                                            rel=0.01)
    inside = np.abs(a.lengths - cfg["record_length"]) \
        < cfg["record_length_stdev"]
    assert 0.67 < inside.mean() < 0.70
    assert a.total_bytes == b.total_bytes
    whole = gen.DataSet(7, dict(cfg, record_length_stdev=0,
                                record_length_resize=None,
                                num_files_train=3))
    assert len(whole.sample(1)) == cfg["record_length"]


def test_order_is_file_shuffle_through_a_buffer_of_two():
    gen = load("generators", "dlio_tfrecord")
    cfg = dict(config("dlio-cosmoflow"), record_length=4099,
               record_length_stdev=0, record_length_resize=None,
               num_files_train=40)
    ds, again = gen.DataSet(7, cfg), gen.DataSet(7, cfg)
    a, b = ds.epoch_order(7, 0), ds.epoch_order(7, 1)
    assert sorted(a) == sorted(b) == list(range(40)) and list(a) != list(b)
    assert list(a) == list(again.epoch_order(7, 0))
    plain = gen.DataSet(7, dict(cfg, file_shuffle="off"))
    moved = plain.epoch_order(7, 0) - np.arange(40)
    # a buffer of two holds a sample back, never sends one ahead by more
    # than one place
    assert moved.any() and moved.max() <= 1
    assert list(gen.DataSet(7, dict(cfg, file_shuffle="off",
                                    sample_shuffle="off"))
                .epoch_order(7, 0)) == list(range(40))


def test_cosmoflow_is_the_published_shape():
    cfg = config("dlio-cosmoflow")
    assert cfg["record_length"] == 2828486 and cfg["batch_size"] == 1
    assert cfg["record_length_stdev"] == 71878
    assert cfg["record_length_resize"] == 2097152
    assert cfg["read_threads"] == 4 and cfg["num_samples_per_file"] == 1
    assert (cfg["file_shuffle"], cfg["sample_shuffle"],
            cfg["shuffle_size"]) == ("seed", "seed", 2)
    assert cfg["reduced"] == ["num_files_train"]
    total = cfg["num_files_train"] * (cfg["record_length"] + 16)
    assert 4.3e9 < total < 4.4e9 < cfg["cluster"]["tier_bytes"]


def test_olmoe_share_is_what_the_issue_sized():
    gen = load("generators", "ckpt_manifest")
    cfg = config("ckpt-olmoe-1b-7b")
    specs = gen.tensors(cfg)
    sizes = sorted(2 * int(np.prod(s)) for _, s in specs)
    block = cfg["cluster"]["block_size"]
    assert len(specs) == 915 and len({n for n, _ in specs}) == 915
    assert sizes[len(sizes) // 2] == 4 << 20
    assert [s for s in sizes if s > block] == [50304 * 2048 * 2] * 2
    assert -(-sizes[-1] // block) == 4
    assert 4.1e9 < sum(sizes) < 4.2e9 < cfg["cluster"]["tier_bytes"]
    assert cfg["reduced"] == ["num_experts"]
    assert cfg["num_experts_per_tok"] == 8 and cfg["hidden_size"] == 2048


def test_checkpoint_bits_are_seeded_finite_bf16():
    import ml_dtypes
    gen = load("generators", "ckpt_manifest")
    cfg = dict(config("ckpt-olmoe-1b-7b"), hidden_size=32,
               intermediate_size=16, num_hidden_layers=1, vocab_size=100,
               num_experts=2)
    ds, again, other = (gen.DataSet(s, cfg) for s in (5, 5, 6))
    manifest = json.loads(ds.manifest())
    assert len(manifest["tensors"]) == len(ds) == 18
    assert set(manifest["tree"]["v"]) == {n for n, _ in ds.specs}
    for i, (name, shape) in enumerate(ds.specs):
        bits = ds.tensor(i)
        assert bits.dtype == np.uint16 and bits.shape == tuple(shape)
        assert np.array_equal(bits, again.tensor(i))
        vals = bits.view(ml_dtypes.bfloat16).astype(np.float32)
        assert np.isfinite(vals).all() and (np.abs(vals) < 2.0).all()
        assert manifest["tree"]["v"][name]["i"] == i
        assert manifest["tensors"][i]["name"] == ds.file_name(i)
    assert not np.array_equal(ds.tensor(0), other.tensor(0))
    big = [ds.tensor(i).tobytes() for i in range(len(ds))
           if ds.sizes[i] > 100]
    assert len(set(big)) == len(big)
