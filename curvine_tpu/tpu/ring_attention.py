"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context path: Q stays put, K/V blocks rotate around the ``seq`` mesh
axis via ppermute while each step accumulates flash-style online-softmax
partial results. P steps of compute overlap P-1 ICI hops, so sequence
length scales linearly with the number of chips on the axis with no
all-gather of K/V (memory stays O(L/P) per chip).

Causal masking: with Q block index i fixed and the KV block visiting from
index j = (i - step) mod P, a block is fully visible when j < i, fully
masked when j > i, and diagonal (per-token causal) when j == i.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """One (q-block, kv-block) flash step → (out_unnorm, row_max, row_sum).

    q: [B, H, Lq, D], k/v: [B, H, Lk, D], mask broadcastable [Lq, Lk]."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)            # [B,H,Lq,1]
    m = jnp.maximum(m, NEG_INF)                            # avoid -inf - -inf
    p = jnp.exp(scores - m)
    p = jnp.where(mask, p, 0.0)
    s = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m, s


def _merge(o1, m1, s1, o2, m2, s2):
    """Merge two online-softmax partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return o1 * a1 + o2 * a2, m, s1 * a1 + s2 * a2


def ring_attention(q, k, v, axis_name: str, causal: bool = True):
    """Runs inside shard_map: q/k/v are the local shards [B, H, L/P, D].

    Returns the local attention output shard [B, H, L/P, D]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    lq = q.shape[2]

    q_pos = my_idx * lq + jnp.arange(lq)

    def step(carry, s):
        o, m, acc_s, kv_k, kv_v = carry
        kv_idx = (my_idx - s) % axis_size
        if causal:
            kv_pos = kv_idx * lq + jnp.arange(kv_k.shape[2])
            mask = q_pos[:, None] >= kv_pos[None, :]
        else:
            mask = jnp.ones((lq, kv_k.shape[2]), dtype=bool)
        o2, m2, s2 = _block_attn(q, kv_k, kv_v, mask)
        o, m, acc_s = _merge(o, m, acc_s, o2, m2, s2)
        # rotate kv to the next chip on the ring (skip after last step)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        kv_k = jax.lax.ppermute(kv_k, axis_name, perm)
        kv_v = jax.lax.ppermute(kv_v, axis_name, perm)
        return (o, m, acc_s, kv_k, kv_v), None

    o0 = jnp.zeros_like(q)
    m0 = jnp.full(q.shape[:3] + (1,), NEG_INF, dtype=q.dtype)
    s0 = jnp.zeros(q.shape[:3] + (1,), dtype=q.dtype)
    (o, m, s, _, _), _ = jax.lax.scan(
        step, (o0, m0, s0, k, v), jnp.arange(axis_size))
    return o / jnp.maximum(s, 1e-20)


def dense_attention(q, k, v, causal: bool = True):
    """Reference single-device attention (numerics check + small models)."""
    L = q.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones((L, L), dtype=bool))
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def ring_attention_sharded(q, k, v, mesh: Mesh, axis_name: str = "seq",
                           causal: bool = True):
    """shard_map wrapper: q/k/v are global [B, H, L, D] arrays sharded on
    L over `axis_name`; output has the same sharding."""
    spec = P(None, None, axis_name, None)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)(q, k, v)
