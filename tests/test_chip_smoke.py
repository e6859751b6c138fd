"""chip_smoke.py driven at a tiny size on the 8-device CPU mesh (Pallas
kernels interpreted), plus the rules the smoke stands on: no result
without a TPU, a rate above the published peak is a failure, daemons stay
off JAX, and nothing on the tier-0 path falls back quietly."""

import os
import re
import subprocess
import sys

import jax
import pytest

import chip_smoke
from curvine_tpu.common.conf import ClusterConf
from curvine_tpu.tpu import compile_cache, model
from perfbench import peaks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


async def test_every_stage_at_tiny_size():
    lines = []
    res = await chip_smoke.smoke(0, chip_smoke.Sizes.tiny(), jax.devices(),
                                 None, emit=lines.append)
    st = res["stages"]
    assert list(st) == ["ingest", "tier0", "checkpoint", "feed", "vector",
                        "mesh"]
    assert len([ln for ln in lines if ln.startswith("[stage] ")]) == 6
    n = len(jax.devices())
    assert st["ingest"]["interpret"] is True          # CPU arrays only
    assert st["ingest"]["rung"] != "socket"           # co-located worker
    assert st["tier0"]["spills"] > 0 and st["tier0"]["autopinned"] == 2
    assert st["tier0"]["devices"] == list(range(n))
    assert st["tier0"]["tier_used"] <= st["tier0"]["tier_capacity"]
    assert st["checkpoint"]["tensors"] == 19
    assert st["feed"]["steps"] == 3 and st["feed"]["mosaic_calls"] == 0
    assert st["vector"]["recall_at_10"] >= 0.9
    assert all(key[4] and key[5] for key in st["vector"]["pq_search"])
    assert st["mesh"]["replica_holders"] == list(range(n))
    assert set(res["native"]["engines"].values()) == {"native"}


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "not a TPU" in out.err


def test_rate_above_the_published_peak_fails():
    ctx = chip_smoke.Ctx(0, chip_smoke.Sizes.tiny(), None, None,
                         jax.devices(), peaks.PEAKS["TPU v5 lite"], None)
    ctx.rate("plain elementwise pass", 2 << 30, 2 / 590)     # 590 GiB/s
    with pytest.raises(chip_smoke.SmokeError, match="above the published"):
        ctx.rate("hbm_tier_read", 1857 * (1 << 30), 1.0)


def test_unlisted_device_kind_is_an_error():
    assert peaks.peaks_of("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="TPU v6 lite"):
        peaks.peaks_of("TPU v6 lite")


def test_every_regression_stage_names_a_program_in_the_checkout():
    """A stage cannot outlive its program: whatever a `run_stage` line
    of scripts/regression.sh runs — a script, a test directory, a module
    it imports — is in the tree."""
    with open(os.path.join(REPO, "scripts", "regression.sh")) as f:
        text = f.read().replace("\\\n", " ")
    stages = [ln for ln in text.splitlines()
              if ln.lstrip().startswith("run_stage ")]
    named = []
    for ln in stages:
        named += re.findall(r"[\w./-]+\.(?:py|sh)\b|\b[\w.-]+/(?=\s|$)", ln)
        named += [m + ".py" for m in re.findall(r"\bimport (\w+)", ln)]
    assert {"tests/", "__graft_entry__.py", "chip_smoke.py"} <= set(named)
    missing = [n for n in named
               if not os.path.exists(os.path.join(REPO, n))]
    assert not missing, f"regression.sh stages run {missing}: not in the tree"


def test_daemon_imports_stay_off_jax():
    """One process per chip: `cv master`, a standalone worker, the FUSE
    daemon and the gateways must not even import JAX, or they would take
    the chip from the process that needs it."""
    code = ("import sys\n"
            "import curvine_tpu.cli.main, curvine_tpu.master\n"
            "import curvine_tpu.worker, curvine_tpu.client\n"
            "import curvine_tpu.fuse.ops, curvine_tpu.gateway.s3\n"
            "sys.exit(int('jax' in sys.modules))\n")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          timeout=120).returncode == 0


def test_compile_cache_is_placed_from_outside(monkeypatch):
    assert compile_cache.enable_compile_cache() is None      # CPU backend
    set_keys = {}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax.config, "update", set_keys.__setitem__)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert "jax_compilation_cache_dir" not in set_keys
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert set_keys["jax_compilation_cache_dir"] == fixed
    assert set_keys["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_flash_asked_for_and_not_eligible_raises_on_tpu(monkeypatch):
    cfg = model.ModelConfig.tiny()
    flash = model.ModelConfig(d_model=64, n_heads=4, use_flash_attention=True)
    assert model._use_flash(cfg, 128) is False
    assert model._use_flash(flash, 128) is False      # CPU: dense
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="head_dim 16"):
        model._use_flash(flash, 128)
    ok = model.ModelConfig(d_model=256, n_heads=2, use_flash_attention=True)
    assert model._use_flash(ok, 256) is True


def test_worker_with_tier0_on_does_not_start_without_it(monkeypatch,
                                                        tmp_path):
    from curvine_tpu.common.conf import TierConf
    from curvine_tpu.tpu import hbm
    from curvine_tpu.worker import WorkerServer

    def no_device(*a, **kw):
        raise RuntimeError("no device came up")

    monkeypatch.setattr(hbm, "MultiHbmTier", no_device)
    conf = ClusterConf()
    conf.worker.rpc_port = 0
    conf.worker.tiers = [TierConf(dir=str(tmp_path / "mem"))]
    conf.worker.hbm_capacity = 1 << 20
    with pytest.raises(RuntimeError, match="no device came up"):
        WorkerServer(conf)
