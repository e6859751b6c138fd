"""Bring-up of the system under test, copied from chip_smoke.py: the
native libraries, a conf file, `cv master` as a child that never imports
JAX, the worker on a loop thread of its own in this process (the one
process that holds the chip), and clients from the same conf."""

from __future__ import annotations

import asyncio
import contextlib
import os
import socket
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE_LIBS = ("libcurvine_native.so", "libcurvine_kv.so",
               "libcurvine_meta.so", "libcurvine_sdk.so")


class BringUpError(Exception):
    """The system under test did not come up."""


def build_native() -> float:
    """Build (first run in a checkout) and load the four native
    libraries; seconds taken. One that does not build is an error: the
    benchmark does not measure the Python fallbacks."""
    from curvine_tpu.common import kvnative, native
    from curvine_tpu.master import fastmeta
    from curvine_tpu.sdk import native_sdk
    t0 = time.perf_counter()
    for so in NATIVE_LIBS:
        if native.build(so) is None:
            raise BringUpError(f"{so} did not build")
    loaded = {"checksum": native.available(), "kv": kvnative.available(),
              "fastmeta": fastmeta.available(),
              "sdk": native_sdk.available()}
    if not all(loaded.values()):
        raise BringUpError(f"native libraries did not load: {loaded}")
    return time.perf_counter() - t0


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_conf(workdir: str, tier_dir: str, spec: dict) -> str:
    """The cluster section of a configuration file → cluster.toml."""
    ports = [_free_port() for _ in range(4)]
    path = os.path.join(workdir, "cluster.toml")
    if not spec.get("master_journal", True):
        raise BringUpError("the master always journals; master_journal "
                           "must be true")
    with open(path, "w") as f:
        f.write(f'''cluster_name = "perfbench"
data_dir = "{workdir}"
[master]
hostname = "127.0.0.1"
rpc_port = {ports[0]}
web_port = {ports[1]}
journal_dir = "{workdir}/journal"
meta_engine = "{spec["master_meta_engine"]}"
[worker]
hostname = "127.0.0.1"
rpc_port = {ports[2]}
web_port = {ports[3]}
heartbeat_ms = 500
hbm_capacity = {int(spec.get("hbm_capacity", 0))}
[[worker.tiers]]
storage_type = "{spec["tier"]}"
dir = "{tier_dir}/{spec["tier"]}"
capacity = {int(spec["tier_bytes"])}
[client]
master_addrs = ["127.0.0.1:{ports[0]}"]
block_size = {int(spec["block_size"])}
''')
    return path


@contextlib.asynccontextmanager
async def cluster(workdir: str, tier_dir: str, spec: dict):
    """Yields (conf, worker): a master child and an embedded worker are
    up and registered. Clients are the caller's: CurvineClient(conf)."""
    from curvine_tpu.client import CurvineClient
    from curvine_tpu.common.conf import ClusterConf
    from curvine_tpu.worker.embedded import EmbeddedWorker

    if spec.get("masters", 1) != 1 or spec.get("workers", 1) != 1 \
            or not spec.get("worker_embedded", True):
        raise BringUpError("this bring-up knows one master child and one "
                           "embedded worker")
    conf_path = write_conf(workdir, tier_dir, spec)
    conf = ClusterConf.load(conf_path, env={})
    if conf.client.block_size != int(spec["block_size"]):
        raise BringUpError("conf file did not load as written")
    log = open(os.path.join(workdir, "master.out"), "wb")
    master = subprocess.Popen(
        [sys.executable, "-m", "curvine_tpu.cli.main", "--conf", conf_path,
         "master"], cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    embedded = EmbeddedWorker(conf)
    probe = None
    try:
        deadline = time.monotonic() + 60.0
        while True:
            if master.poll() is not None or time.monotonic() > deadline:
                with open(log.name, errors="replace") as f:
                    raise BringUpError("cv master did not come up: "
                                       + f.read()[-2000:])
            with socket.socket() as s:
                if s.connect_ex(("127.0.0.1", conf.master.rpc_port)) == 0:
                    break
            await asyncio.sleep(0.05)
        worker = await asyncio.to_thread(embedded.start)
        probe = CurvineClient(conf)
        while True:
            info = await probe.meta.master_info()
            if info.live_workers:
                break
            if time.monotonic() > deadline:
                raise BringUpError("worker never registered")
            await asyncio.sleep(0.05)
        if spec["master_meta_engine"] == "native" and not info.fast_addr:
            raise BringUpError("master serves no native fast-meta port")
        await probe.close()
        probe = None
        yield conf, worker
    finally:
        if probe is not None:
            await probe.close()
        await asyncio.to_thread(embedded.stop)
        master.terminate()
        try:
            master.wait(10)
        except subprocess.TimeoutExpired:
            master.kill()
            master.wait()
        log.close()


async def write_files(client, count: int, make, path_of,
                      writers: int = 8) -> float:
    """Write files 0..count-1 through the client, `writers` at a time:
    `make(i)` (bytes; run on a thread, it is numpy work) → `path_of(i)`.
    Seconds taken. Set-up only: no cell measures the write path."""
    t0 = time.perf_counter()
    todo = iter(range(count))
    loop = asyncio.get_running_loop()
    with ThreadPoolExecutor(writers) as pool:
        async def writer():
            for i in todo:
                data = await loop.run_in_executor(pool, make, i)
                await client.write_all(path_of(i), data)

        await asyncio.gather(*(writer() for _ in range(writers)))
    return time.perf_counter() - t0
