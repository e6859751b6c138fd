"""Pipeline parallelism (pp axis): GPipe-style stage pipeline.

Layers are stacked ([L, ...] leading dim) and sharded over the ``pp``
mesh axis so each chip owns L/S contiguous layers. Microbatches flow
through the ring: at step t, stage s computes microbatch t-s and
ppermutes its activations to stage s+1 — M + S - 1 steps total, the
classic bubble. Embedding/unembedding stay outside the pipelined region.

The scan/ppermute idiom follows the public TPU scaling recipe: shard_map
over the stage axis, static per-stage layer loop inside, collectives on
ICI only."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from curvine_tpu.tpu.model import ModelConfig, _block, _rmsnorm


def stack_layers(params: dict) -> dict:
    """[{k: w} per layer] → {k: [L, ...]} for pp sharding."""
    layers = params["layers"]
    stacked = {k: jnp.stack([layer[k] for layer in layers])
               for k in layers[0]}
    out = dict(params)
    out["layers"] = stacked
    return out


def stacked_specs(params_stacked: dict) -> dict:
    """PartitionSpecs: stacked layer weights sharded over 'pp' dim 0."""
    base = {"embed": P(None, None), "pos": P(None, None), "ln_f": P(None)}
    layer_specs = {k: P("pp", *([None] * (v.ndim - 1)))
                   for k, v in params_stacked["layers"].items()}
    return {**base, "layers": layer_specs}


def shard_stacked(params_stacked: dict, mesh: Mesh) -> dict:
    specs = stacked_specs(params_stacked)
    out = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
           for k, v in params_stacked.items() if k != "layers"}
    out["layers"] = {
        k: jax.device_put(v, NamedSharding(mesh, specs["layers"][k]))
        for k, v in params_stacked["layers"].items()}
    return out


def pipeline_forward(params_stacked: dict, tokens, cfg: ModelConfig,
                     mesh: Mesh, microbatches: int = 2):
    """tokens [B, L] with B divisible by `microbatches` → logits [B, L, V].

    Stages = mesh.shape['pp']; cfg.n_layers must divide evenly."""
    S = mesh.shape["pp"]
    assert cfg.n_layers % S == 0, "n_layers must divide stages"
    per_stage = cfg.n_layers // S
    B, L = tokens.shape
    M = microbatches
    assert B % M == 0, "batch must divide microbatches"

    x = params_stacked["embed"][tokens] + params_stacked["pos"][:L]
    x = x.reshape(M, B // M, L, cfg.d_model)

    def stage_compute(layers_local, h):
        for i in range(per_stage):
            layer = {k: v[i] for k, v in layers_local.items()}
            h = _block(h, layer, cfg, None)
        return h

    def pipelined(layers_local, xs):
        stage = jax.lax.axis_index("pp")
        state = jnp.zeros_like(xs[0])
        out = jnp.zeros_like(xs)
        perm = [(i, (i + 1) % S) for i in range(S)]
        for t in range(M + S - 1):
            mb_in = jnp.clip(t, 0, M - 1)
            inp = jnp.where(stage == 0, xs[mb_in], state)
            h = stage_compute(layers_local, inp)
            done = t - (S - 1)
            if done >= 0:
                # only the last stage's value is real; mask others so the
                # replicating psum outside recovers it exactly
                mask = (stage == S - 1).astype(h.dtype)
                out = out.at[done].set(h * mask)
            state = jax.lax.ppermute(h, "pp", perm)
        return out

    layer_specs = {k: P("pp", *([None] * (v.ndim - 1)))
                   for k, v in params_stacked["layers"].items()}
    fn = jax.shard_map(
        pipelined, mesh=mesh,
        in_specs=(layer_specs, P()), out_specs=P("pp"), check_vma=False)
    # out_specs P('pp') stacks each stage's masked buffer: [S*M, mb, L, D];
    # summing the stage axis recovers the last stage's outputs
    stacked_out = fn(params_stacked["layers"], x)
    stacked_out = stacked_out.reshape(S, M, B // M, L, cfg.d_model)
    x = jnp.sum(stacked_out, axis=0).reshape(B, L, cfg.d_model)

    x = _rmsnorm(x, params_stacked["ln_f"])
    return (x @ params_stacked["embed"].T).astype(jnp.float32)


def pipeline_loss(params_stacked, tokens, cfg: ModelConfig, mesh: Mesh,
                  microbatches: int = 2):
    logits = pipeline_forward(params_stacked, tokens, cfg, mesh,
                              microbatches)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)
