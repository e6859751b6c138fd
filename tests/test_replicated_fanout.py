"""A replicated restore on a mesh this process addresses whole: each
tensor crosses the host link once, to one device, and reaches the other
devices as device-to-device copies (`tpu/broadcast.py::_Fanout`). On
one, two and four CPU devices: bit-exact with the flat path for a 0-d
scalar, a one-block and a multi-block tensor, every leaf replicated on
every device; one `ckpt.place` a tensor; `ckpt.fanout.bytes` the bytes
copied chip to chip; and no host array handed to a multi-device
sharding."""

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from curvine_tpu.testing import MiniCluster
from curvine_tpu.tpu import broadcast

CPUS = jax.devices("cpu")
BLOCK = 64 * 1024


def make_params() -> dict:
    rng = np.random.default_rng(11)
    return {"step": np.int32(17),
            "bias": rng.standard_normal(256).astype(np.float32),
            "emb": rng.standard_normal((96, 640)).astype(np.float32),
            "w": {"q": rng.standard_normal((32, 48)).astype(np.float32)}}


class Spy:
    """Stands where `broadcast` looks up `jax`: every `device_put`
    recorded (its leaves and target), then passed through."""

    def __init__(self):
        self.calls = []

    def __getattr__(self, name):
        return getattr(jax, name)

    def device_put(self, x, target=None, **kw):
        self.calls.append((jax.tree.leaves(x), target))
        return jax.device_put(x, target, **kw)


@pytest.mark.parametrize("chips", [1, 2, 4])
async def test_replicated_restore_fans_out_chip_to_chip(
        tmp_path, monkeypatch, chips):
    from curvine_tpu.tpu.mesh import make_mesh
    devices = CPUS[:chips]
    mesh = make_mesh(devices=devices, axis_names=("data",))
    params = make_params()
    leaves = jax.tree.leaves(params)
    assert params["emb"].nbytes > 3 * BLOCK > params["bias"].nbytes
    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await broadcast.save_checkpoint(c, "/ckpt/fan", params)
        flat = await broadcast.distribute_checkpoint(
            c, "/ckpt/fan", mesh, schedule="flat")
        c.tracer.sample_rate = 1.0
        c.tracer.store.clear()
        before = dict(c.counters)
        spy = Spy()
        monkeypatch.setattr(broadcast, "jax", spy)
        tree = await broadcast.distribute_checkpoint(c, "/ckpt/fan", mesh)
        monkeypatch.undo()

        def grew(k):
            return c.counters.get(k, 0) - before.get(k, 0)

        for want, f, t in zip(leaves, jax.tree.leaves(flat),
                              jax.tree.leaves(tree)):
            assert t.shape == f.shape == want.shape
            assert t.dtype == f.dtype == want.dtype
            assert t.sharding.is_fully_replicated
            assert {s.device for s in t.addressable_shards} == set(devices)
            for s in t.addressable_shards:
                assert np.asarray(s.data).tobytes() == want.tobytes()
            assert np.asarray(t).tobytes() == np.asarray(f).tobytes()
        total = sum(a.nbytes for a in leaves)
        assert grew("ckpt.place.n") == len(leaves)
        assert grew("ckpt.fanout.bytes") == (chips - 1) * total
        assert grew("ckpt.fanout.n") == (len(leaves) if chips > 1 else 0)

        fanned = [(xs, s) for xs, s in spy.calls
                  if isinstance(s, NamedSharding) and s.num_devices > 1]
        assert bool(fanned) == (chips > 1)
        for xs, _ in fanned:
            assert all(isinstance(x, jax.Array) for x in xs)
        assert sum(len(xs) for xs, _ in fanned) == \
            (len(leaves) if chips > 1 else 0)

        spans = c.tracer.store.drain(4096)
        (root,) = [s for s in spans if s["op"] == "ckpt.restore"]
        fans = [s for s in spans if s["op"] == "ckpt.fanout"]
        assert {s["parent"] for s in fans} <= {root["span_id"]}
        assert sum(s["attrs"]["tensors"] for s in fans) == \
            grew("ckpt.fanout.n")
        assert sum(s["attrs"]["bytes"] for s in fans) == \
            grew("ckpt.fanout.bytes")
        await c.close()


async def test_a_failed_fanout_fails_the_restore(tmp_path, monkeypatch):
    """The fan-out runs as a loop callback; an error there is raised by
    the restore, never left behind as tensors on one device."""
    from curvine_tpu.tpu.mesh import make_mesh
    mesh = make_mesh(devices=CPUS[:4], axis_names=("data",))

    class Refusing(Spy):
        def device_put(self, x, target=None, **kw):
            if isinstance(target, NamedSharding) and target.num_devices > 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: no room for a copy")
            return jax.device_put(x, target, **kw)

    async with MiniCluster(workers=1, base_dir=str(tmp_path),
                           block_size=BLOCK) as mc:
        c = mc.client()
        await broadcast.save_checkpoint(c, "/ckpt/fan", make_params())
        monkeypatch.setattr(broadcast, "jax", Refusing())
        with pytest.raises(RuntimeError, match="no room for a copy"):
            await broadcast.distribute_checkpoint(c, "/ckpt/fan", mesh)
        monkeypatch.undo()
        assert c.counters.get("ckpt.fanout.n", 0) == 0
        assert c.counters.get("ckpt.restores", 0) == 0
        await c.close()
