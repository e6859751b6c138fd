"""BENCHMARK.json against the contract's limits, and every name in it
against the file the harness will look for."""

import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import harness, peaks

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["perfbench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in bench["command"])
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
        assert 1 <= len(c["why"]) <= 200 and len(c["reduced"]) <= 16
        assert c["file"].startswith("perfbench/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
        assert w["chips"] in (1, 4)
        if w["chips"] == 4:      # only for what exists only across chips
            assert "chips" in w["why"], w["name"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in names
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_every_name_leads_to_its_file(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        used.add(w["config"])
        assert cell.config["name"] == w["config"]
        assert sorted(cell.config["reduced"]) \
            == sorted(configs[w["config"]]["reduced"])
        assert hasattr(cell.module("drivers", cell.traffic["driver"]),
                       "Driver")
        assert hasattr(cell.module("generators", cell.config["generator"]),
                       "DataSet")
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.end_to_end:
            assert callable(cell.module("end_to_end", m["name"]).read)
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert callable(cell.module("layer_metrics", m["name"]).read)
    assert used == set(configs)


def test_split_metrics_list_their_cells(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        if m["moves"] == "setup_s":
            continue
        assert set(m["workloads"]) <= cells
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= reporting, m["name"]


def test_unknown_chip_is_an_error():
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_of("TPU v9 imaginary")


def test_result_line_keys():
    res = {"correct": True, "attempted": 3, "failed": 0,
           "metrics": {"setup_s": 1.5}, "device": {"platform": "tpu"},
           "compared": {"tensors_mismatched": (0, 0)}, "breakdown": None,
           "units": {"setup_s": "s"}}
    line = harness.result_line(res)
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]
    assert out["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}
    assert out["compared"]["tensors_mismatched"] == {"value": 0, "limit": 0}
    line = harness.result_line(dict(
        res, correct=False, compared={"x": (1, 0)},
        breakdown={"device_ops": [], "idle_gaps": []}))
    assert list(json.loads(line))[-2:] == ["breakdown", "compared"]


def run_py(cwd, *args):
    return subprocess.run(
        [sys.executable, os.path.join("perfbench", "run.py"), *args],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


ARGS = ("--workload", "feed-cosmoflow", "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_no_tpu_no_result():
    p = run_py(ROOT, *ARGS)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    import shutil
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = run_py(tmp_path, *ARGS)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "nothing to measure" in p.stderr
