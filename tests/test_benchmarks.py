"""Tier-1 runs the benchmark's own tests (`perfbench/tests`: the CPU
rehearsal of every cell, the contract's limits on BENCHMARK.json, the
trace reduction against its recorded fixture), so that a PR which breaks
a metric reader, a driver or a generator fails here and not on the chip.

One case per test file, each in a process of its own: `perfbench/tests`
has its own conftest (four virtual devices, its own path set-up) and its
rehearsals start a `cv master` child and an embedded worker, which are
not to share this process's JAX or event loop. Each file has its own
time limit: alone the slowest takes 18 s."""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(os.path.basename(p) for p in glob.glob(
    os.path.join(REPO, "perfbench", "tests", "test_*.py")))
LIMIT_S = 300


def test_the_benchmark_has_tests():
    assert "test_rehearsal.py" in FILES and "test_files.py" in FILES


@pytest.mark.parametrize("name", FILES)
def test_perfbench(name):
    p = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join("perfbench", "tests", name), "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=REPO, capture_output=True, text=True, timeout=LIMIT_S,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    tail = (p.stdout + p.stderr)[-4000:]
    assert p.returncode == 0 and " passed" in p.stdout, tail
