"""Multi-host (pod-scale) glue.

A TPU pod runs one process per host; JAX's distributed runtime connects
them so a single Mesh spans every chip. The cache integrates per-host:
each TPU VM runs a curvine worker (ici_coords from its pod position), and
each training process feeds from its local worker via short-circuit reads,
assembling global arrays with make_array_from_process_local_data
(curvine_tpu/tpu/ingest.put_sharded already handles process_count > 1).

This module is the thin initialization/ordering layer; everything else in
the framework is already written against global meshes."""

from __future__ import annotations

import os

import jax


def initialize(coordinator: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """Connect this process to the pod's JAX distributed runtime. With
    no coordinator given, jax.distributed autodetects from the pod's
    environment (TPU_WORKER_HOSTNAMES etc.). A failure raises: a process
    that was told to join a pod and could not must not train alone. A
    single-process run simply does not call this."""
    coordinator = coordinator or os.environ.get("CURVINE_COORDINATOR")
    if coordinator is None and num_processes is None:
        jax.distributed.initialize()
        return
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def pod_mesh(axis_names=("data", "model"), shape=None):
    """Mesh over every chip in the pod (all processes)."""
    from curvine_tpu.tpu.mesh import make_mesh
    return make_mesh(devices=jax.devices(), axis_names=axis_names,
                     shape=shape)


def local_ici_coords() -> list[int]:
    """Torus coordinates of this host's first chip — what the co-located
    worker should advertise as WorkerInfo.ici_coords."""
    local = jax.local_devices()
    if not local:
        return []
    coords = getattr(local[0], "coords", None)
    return list(coords) if coords is not None else []


def worker_conf_for_pod(conf) -> None:
    """Stamp pod-derived placement info onto a WorkerConf in place."""
    conf.worker.ici_coords = local_ici_coords()
