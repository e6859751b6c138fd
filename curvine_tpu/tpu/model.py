"""Demo training consumer: a pure-JAX decoder-only transformer LM.

This is the flagship compute consumer of the cache (fed by
curvine_tpu.tpu.loader): bf16 matmuls for the MXU, TP×DP×SP sharding via
NamedSharding + jit (XLA inserts the collectives), ring attention
(shard_map/ppermute) for the long-context path, optax AdamW training step.

Sharding recipe (Megatron-style TP over the ``model`` axis):
  embed [V, D]        → P(None, 'model')
  wq/wk/wv [D, D]     → P(None, 'model')   (heads sharded)
  wo [D, D]           → P('model', None)
  mlp w1 [D, F]       → P(None, 'model')
  mlp w2 [F, D]       → P('model', None)
  activations [B,L,D] → P('data', 'seq', None)
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from curvine_tpu.tpu.ring_attention import dense_attention, ring_attention_sharded


@dataclass(frozen=True)
class ModelConfig:
    vocab: int = 32_000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    dtype: str = "bfloat16"
    use_ring_attention: bool = False
    remat: bool = False        # jax.checkpoint each layer (HBM for FLOPs)
    moe_experts: int = 0       # >0: MoE FFN, experts sharded over 'ep'
    # Fused (Pallas) flash attention on TPU: no [B,H,L,L] score
    # materialization, O(L) memory. Requires head_dim % 128 == 0 and
    # seq % 128 == 0 — a TPU run asking for it with other shapes is an
    # error, not a dense run. The kernel is TPU-only: on the CPU test
    # mesh the same config traces dense_attention.
    use_flash_attention: bool = False
    # Cross-entropy in chunks of this many tokens (0 = one-shot): the
    # [B·L, vocab] f32 logits never materialize — each chunk's logits
    # are rematerialized in the backward pass. At vocab 32K, seq 1K the
    # one-shot path peaks >1 GiB of HBM in pure loss bookkeeping.
    ce_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @staticmethod
    def tiny() -> "ModelConfig":
        return ModelConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                           d_ff=128, max_seq=128)


def init_params(rng, cfg: ModelConfig) -> dict:
    dt = cfg.jax_dtype()
    keys = jax.random.split(rng, 2 + cfg.n_layers)

    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dt)

    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 7)
        layer = {
            "ln1": jnp.ones(cfg.d_model, dt),
            "wq": dense(k[0], cfg.d_model, (cfg.d_model, cfg.d_model)),
            "wk": dense(k[1], cfg.d_model, (cfg.d_model, cfg.d_model)),
            "wv": dense(k[2], cfg.d_model, (cfg.d_model, cfg.d_model)),
            "wo": dense(k[3], cfg.d_model, (cfg.d_model, cfg.d_model)),
            "ln2": jnp.ones(cfg.d_model, dt),
        }
        if cfg.moe_experts > 0:
            E = cfg.moe_experts
            layer["router"] = dense(k[6], cfg.d_model, (cfg.d_model, E))
            layer["ew1"] = dense(k[4], cfg.d_model,
                                 (E, cfg.d_model, cfg.d_ff))
            layer["ew2"] = dense(k[5], cfg.d_ff, (E, cfg.d_ff, cfg.d_model))
        else:
            layer["w1"] = dense(k[4], cfg.d_model, (cfg.d_model, cfg.d_ff))
            layer["w2"] = dense(k[5], cfg.d_ff, (cfg.d_ff, cfg.d_model))
        layers.append(layer)
    return {
        "embed": dense(keys[0], cfg.d_model, (cfg.vocab, cfg.d_model)),
        "pos": dense(keys[1], cfg.d_model, (cfg.max_seq, cfg.d_model)),
        "ln_f": jnp.ones(cfg.d_model, dt),
        "layers": layers,
    }


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale


def _use_flash(cfg: ModelConfig, L: int) -> bool:
    if not cfg.use_flash_attention or jax.default_backend() == "cpu":
        return False
    if cfg.head_dim % 128 or L % 128:
        raise ValueError(
            f"use_flash_attention needs head_dim % 128 == 0 and "
            f"seq % 128 == 0; got head_dim {cfg.head_dim}, seq {L}")
    return True


def _flash_attention(q, k, v):
    """Pallas TPU fused attention (public jax.experimental kernel):
    online-softmax tiles in VMEM, never materializing the [B,H,L,L]
    score matrix — the single biggest activation sink of the dense
    path at seq 1K+."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention,
    )
    return flash_attention(q, k, v, causal=True,
                           sm_scale=1.0 / float(np.sqrt(q.shape[-1])))


def _attention(x, layer, cfg: ModelConfig, mesh: Mesh | None):
    B, L, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    q = (x @ layer["wq"]).reshape(B, L, H, hd).transpose(0, 2, 1, 3)
    k = (x @ layer["wk"]).reshape(B, L, H, hd).transpose(0, 2, 1, 3)
    v = (x @ layer["wv"]).reshape(B, L, H, hd).transpose(0, 2, 1, 3)
    if cfg.use_ring_attention and mesh is not None and "seq" in mesh.axis_names:
        o = ring_attention_sharded(q, k, v, mesh, axis_name="seq", causal=True)
    elif _use_flash(cfg, L):
        o = _flash_attention(q, k, v)
    else:
        o = dense_attention(q, k, v, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, L, D)
    return o @ layer["wo"]


def _moe_ffn(x, layer, cfg: ModelConfig):
    """Expert-parallel FFN: experts sharded over the 'ep' mesh axis
    (weights P('ep', …)); XLA partitions the expert einsums across chips
    and inserts the combine all-reduce over 'ep'. Soft top-2 routing —
    dense compute, the sharding/collective pattern of EP without the
    dynamic-dispatch complexity (honest demo-scale MoE)."""
    gates = jax.nn.softmax(
        (x @ layer["router"]).astype(jnp.float32), axis=-1)
    # keep top-2 gates, renormalize (still differentiable & static-shape)
    top2 = jax.lax.top_k(gates, 2)[0][..., -1:]
    gates = jnp.where(gates >= top2, gates, 0.0)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    h = jnp.einsum("bld,edf->belf", x, layer["ew1"])
    h = jax.nn.gelu(h)
    y = jnp.einsum("belf,efd->beld", h, layer["ew2"])
    return jnp.einsum("beld,ble->bld", y, gates.astype(x.dtype))


def _block(x, layer, cfg: ModelConfig, mesh: Mesh | None):
    x = x + _attention(_rmsnorm(x, layer["ln1"]), layer, cfg, mesh)
    h = _rmsnorm(x, layer["ln2"])
    if cfg.moe_experts > 0:
        h = _moe_ffn(h, layer, cfg)
    else:
        h = jax.nn.gelu(h @ layer["w1"]) @ layer["w2"]
    return x + h


def forward_hidden(params: dict, tokens, cfg: ModelConfig,
                   mesh: Mesh | None = None):
    """tokens [B, L] int32 → final hidden states [B, L, D] (model dtype)."""
    B, L = tokens.shape
    x = params["embed"][tokens] + params["pos"][:L]
    block = _block
    if cfg.remat:
        block = jax.checkpoint(_block, static_argnums=(2,))
    for layer in params["layers"]:
        x = block(x, layer, cfg, mesh)
    return _rmsnorm(x, params["ln_f"])


def forward(params: dict, tokens, cfg: ModelConfig,
            mesh: Mesh | None = None):
    """tokens [B, L] int32 → logits [B, L, V] (dtype f32)."""
    x = forward_hidden(params, tokens, cfg, mesh)
    return (x @ params["embed"].T).astype(jnp.float32)


def _chunked_ce(x, targets, embed, chunk: int):
    """Cross entropy over [N, D] hidden states in `chunk`-token slices:
    each slice's [chunk, V] f32 logits live only inside its (remat'd)
    scan step, so peak loss memory is one chunk instead of the whole
    batch. targets < 0 are padding and contribute nothing."""
    N, D = x.shape
    pad = (-N) % chunk
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad), constant_values=-1)
    xc = x.reshape(-1, chunk, D)
    tc = targets.reshape(-1, chunk)
    emb_t = embed.T

    def step(total, xt):
        xs, ts = xt
        logits = (xs @ emb_t).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(ts, 0)[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(ts >= 0, nll, 0.0)), None

    total, _ = jax.lax.scan(jax.checkpoint(step), jnp.float32(0.0), (xc, tc))
    return total / N


def loss_fn(params, tokens, cfg: ModelConfig, mesh: Mesh | None = None):
    """Next-token cross entropy; last position predicts nothing."""
    x = forward_hidden(params, tokens, cfg, mesh)
    targets = tokens[:, 1:]
    x = x[:, :-1]
    if cfg.ce_chunk > 0:
        return _chunked_ce(x.reshape(-1, x.shape[-1]),
                           targets.reshape(-1),
                           params["embed"], cfg.ce_chunk)
    logits = (x @ params["embed"].T).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def make_optimizer(lr: float = 3e-4):
    return optax.adamw(lr, weight_decay=0.01)


def make_train_step(cfg: ModelConfig, optimizer=None,
                    mesh: Mesh | None = None):
    optimizer = optimizer or make_optimizer()

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# ---------------- shardings ----------------

_PARAM_SPECS = {
    "embed": P(None, "model"),
    "pos": P(None, None),
    "ln_f": P(None),
    "ln1": P(None), "ln2": P(None),
    "wq": P(None, "model"), "wk": P(None, "model"), "wv": P(None, "model"),
    "wo": P("model", None),
    "w1": P(None, "model"), "w2": P("model", None),
    # MoE: experts sharded over 'ep'
    "router": P(None, None),
    "ew1": P("ep", None, None), "ew2": P("ep", None, None),
}


def param_spec_tree(params: dict) -> dict:
    """PartitionSpec pytree matching init_params structure."""
    def spec_of(path_leaf):
        return _PARAM_SPECS.get(path_leaf, P())

    return {
        "embed": spec_of("embed"), "pos": spec_of("pos"),
        "ln_f": spec_of("ln_f"),
        "layers": [{k: spec_of(k) for k in layer}
                   for layer in params["layers"]],
    }


def _sanitize(spec: P, mesh: Mesh) -> P:
    """Drop axes the mesh doesn't have (e.g. 'ep' on a dp×tp mesh)."""
    return P(*(a if a in mesh.axis_names else None for a in spec))


def shard_params(params: dict, mesh: Mesh) -> dict:
    specs = param_spec_tree(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, _sanitize(s, mesh))),
        params, specs,
        is_leaf=lambda x: isinstance(x, jax.Array))


def batch_spec(mesh: Mesh) -> P:
    """tokens [B, L]: batch over data, seq over seq (when present)."""
    seq = "seq" if "seq" in mesh.axis_names else None
    return P("data", seq)
