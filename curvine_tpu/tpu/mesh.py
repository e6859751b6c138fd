"""Device mesh + ICI topology helpers.

The sharding design follows the standard TPU recipe: pick a Mesh, annotate
array shardings with NamedSharding/PartitionSpec, let XLA insert the
collectives, keep collectives on ICI by putting the fast-varying axes
innermost. Axes used across the framework:

  data  — batch (DP): gradients all-reduced over this axis
  model — hidden/heads (TP): matmul-sharded, activations all-gathered
  seq   — sequence (SP/context parallel): ring attention ppermutes KV here
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def factor_mesh(n: int, axes: int = 2) -> tuple[int, ...]:
    """Balanced near-square factorization of n devices into `axes` dims,
    larger factor first (data axis gets the larger share)."""
    if axes == 1:
        return (n,)
    best = (n, 1)
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            best = (n // d, d)
    if axes == 2:
        return best
    rest = factor_mesh(best[1], axes - 1)
    return (best[0], *rest)


def make_mesh(devices=None, axis_names: tuple[str, ...] = ("data", "model"),
              shape: tuple[int, ...] | None = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        shape = factor_mesh(n, len(axis_names))
    if math.prod(shape) != n:
        raise ValueError(f"mesh shape {shape} != {n} devices")
    dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim over `axis`."""
    return NamedSharding(mesh, P(axis))


class IciTopology:
    """Model of a TPU pod's ICI torus used for placement decisions.

    Hosts own contiguous sub-blocks of chips; workers co-located with a
    host inherit its coordinates (WorkerInfo.ici_coords). The master's
    ``ici`` placement policy (curvine_tpu/master/placement.py) uses
    ``hops`` as its distance metric."""

    def __init__(self, mesh_shape: tuple[int, ...],
                 chips_per_host: int = 4):
        self.mesh_shape = tuple(mesh_shape)
        self.chips_per_host = chips_per_host

    def num_chips(self) -> int:
        return math.prod(self.mesh_shape)

    def num_hosts(self) -> int:
        return max(1, self.num_chips() // self.chips_per_host)

    def coords_of(self, chip_index: int) -> tuple[int, ...]:
        coords = []
        rest = chip_index
        for dim in reversed(self.mesh_shape):
            coords.append(rest % dim)
            rest //= dim
        return tuple(reversed(coords))

    def host_of(self, chip_index: int) -> int:
        return chip_index // self.chips_per_host

    def host_coords(self, host_index: int) -> tuple[int, ...]:
        return self.coords_of(host_index * self.chips_per_host)

    def hops(self, a: tuple[int, ...], b: tuple[int, ...]) -> int:
        total = 0
        for i, (x, y) in enumerate(zip(a, b)):
            d = abs(x - y)
            dim = self.mesh_shape[i]
            total += min(d, dim - d)   # torus wraparound
        return total
