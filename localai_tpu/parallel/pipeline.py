"""Pipeline parallelism — GPipe-style microbatched stages over a 'pipe' mesh axis.

The reference has no pipeline parallelism (SURVEY §2.4 lists it as the one
optional strategy; llama.cpp splits layers across GPUs but runs them
sequentially per token, and vllm's PP is torch-rpc based). The TPU-native
answer is the scaling-book recipe: shard the STACKED layer params
[L, ...] over a 'pipe' mesh axis (each stage holds L/S contiguous layers),
run the stage body under `jax.shard_map`, and rotate activations
stage-to-stage with `lax.ppermute` while microbatches stream in a GPipe
schedule. The whole loop is one `lax.scan` → one compiled program, fully
differentiable (ppermute's transpose is the reverse rotation), so the same
code serves forward and backward — no hand-written 1F1B scheduling, XLA
overlaps the ppermute with the next microbatch's compute.

Composes with data parallelism: tokens sharded on 'data', pipeline on
'pipe' ('model' must be 1 in this entry path — TP happens via GSPMD outside
shard_map and is a separate deployment shape; see parallel/mesh.py).

Schedule (S stages, M microbatches, T = M + S - 1 ticks):

    tick t:   stage s computes microbatch (t - s)   [valid when 0 <= t-s < M]
              then sends its output to stage s+1 via ppermute.

The bubble fraction is (S-1)/(M+S-1) — pick M >= 4*S for >80% utilization.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from localai_tpu.models.kv import prompt_attention
from localai_tpu.models.llama import (
    LlamaConfig, _block, _lm_head, param_specs, rms_norm,
)
from localai_tpu.ops.rope import rope_table


def pipeline_specs(cfg: LlamaConfig):
    """PartitionSpecs for pipeline parallelism: stacked layer params sharded
    on dim 0 (the layer axis) over 'pipe'; everything else replicated.
    Same tree shape as param_specs, so shard_params works unchanged."""
    def _strip(spec):
        return P(*[None if a == "model" else a for a in spec])

    specs = jax.tree_util.tree_map(_strip, param_specs(cfg))
    specs["layers"] = {
        k: P(*(("pipe",) + tuple(v)[1:])) for k, v in specs["layers"].items()
    }
    return specs


def _stage_layers(layers_local, x, cfg: LlamaConfig, cos, sin, positions,
                  lengths, attn):
    """Run this stage's L/S layers over one microbatch [mb, T, D]: the one
    block of models/llama.py with no activation-sharding hints
    (with_sharding_constraint is illegal inside shard_map — the manual axes
    already fix the layout) and no cache."""
    def layer(x, lp):
        return _block(
            cfg, x, lp, None, cos, sin, positions,
            lambda q, k, v: (attn(q, k, v, lengths,
                                  sliding_window=cfg.sliding_window), None),
            None)

    x, _ = jax.lax.scan(layer, x, layers_local)
    return x


def pipeline_hidden(params, cfg: LlamaConfig, tokens, *, mesh: Mesh,
                    n_micro: int, lengths=None):
    """Full-sequence causal forward → final hidden states [B, T, D], with the
    decoder layers executed as a pipeline over the mesh's 'pipe' axis.

    tokens [B, T] (B sharded on 'data' if present); n_micro microbatches per
    data shard. Output is replicated over 'pipe' (psum-broadcast from the
    last stage) and stays sharded on 'data'."""
    if "pipe" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'pipe' axis")
    S = mesh.shape["pipe"]
    if mesh.shape.get("model", 1) != 1:
        raise ValueError("pipeline entry path needs model=1 (TP is a "
                         "separate GSPMD deployment shape)")
    L = cfg.num_layers
    if L % S != 0:
        raise ValueError(f"num_layers {L} not divisible by {S} stages")
    if cfg.layer_types is not None:
        raise NotImplementedError(
            "pipeline stages run one kind of layer with one RoPE: a model "
            "with window and full layers (layer_types) is not taken")
    B, T = tokens.shape
    dsize = mesh.shape.get("data", 1)
    if B % (dsize * n_micro) != 0:
        raise ValueError(f"batch {B} not divisible by data {dsize} x "
                         f"n_micro {n_micro}")
    cos, sin = rope_table(cfg.rope, T)
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    attn = prompt_attention()
    emb = params["embed"].astype(cfg.jdtype)[tokens]          # [B, T, D]
    D = emb.shape[-1]
    positions = jnp.arange(T)[None, :]

    lspec = {k: P(*(("pipe",) + (None,) * (v.ndim - 1)))
             for k, v in params["layers"].items()}

    def body(layers_local, emb_local, len_local):
        stage = jax.lax.axis_index("pipe")
        mb = emb_local.shape[0] // n_micro
        mbs = emb_local.reshape(n_micro, mb, T, D)
        mlens = len_local.reshape(n_micro, mb)
        pos = jnp.broadcast_to(positions, (mb, T))
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            recv, out = carry
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            x = jnp.where(stage == 0, feed, recv)
            lens = jax.lax.dynamic_index_in_dim(
                mlens, jnp.clip(t - stage, 0, n_micro - 1), 0, keepdims=False)
            y = _stage_layers(layers_local, x, cfg, cos, sin, pos, lens, attn)
            widx = t - (S - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                out, y, jnp.clip(widx, 0, n_micro - 1), 0)
            out = jnp.where((stage == S - 1) & (widx >= 0), updated, out)
            recv = jax.lax.ppermute(y, "pipe", perm)
            return (recv, out), None

        # the carry is stage-varying (and data-varying): mark the zeros init
        # accordingly or the vma check rejects the scan
        init = (jnp.zeros((mb, T, D), emb_local.dtype),
                jnp.zeros((n_micro, mb, T, D), emb_local.dtype))
        init = jax.lax.pcast(init, ("data", "pipe"), to="varying")
        (_, out), _ = jax.lax.scan(tick, init, jnp.arange(n_micro + S - 1))
        # broadcast the last stage's collected outputs to every pipe rank
        out = jax.lax.psum(
            jnp.where(stage == S - 1, out, jnp.zeros_like(out)), "pipe")
        return out.reshape(-1, T, D)

    dax = "data" if "data" in mesh.axis_names else None
    x = jax.shard_map(
        body, mesh=mesh,
        in_specs=(lspec, P(dax, None, None), P(dax)),
        out_specs=P(dax, None, None),
    )(params["layers"], emb, lengths)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def pipeline_forward_train(params, cfg: LlamaConfig, tokens, *, mesh: Mesh,
                           n_micro: int):
    """forward_train twin on the pipeline path → logits [B, T, V] f32."""
    x = pipeline_hidden(params, cfg, tokens, mesh=mesh, n_micro=n_micro)
    return _lm_head(x.astype(jnp.float32), params)


def pipeline_loss(params, cfg: LlamaConfig, tokens, *, mesh: Mesh,
                  n_micro: int):
    """Next-token cross-entropy, numerically matching train.causal_lm_loss."""
    logits = pipeline_forward_train(params, cfg, tokens[:, :-1], mesh=mesh,
                                    n_micro=n_micro)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_pipeline_train_step(cfg: LlamaConfig, optimizer, mesh: Mesh,
                             n_micro: int):
    """train_step(params, opt_state, tokens) -> (params, opt_state, loss)
    with the forward+backward pipelined over 'pipe'. jit under the mesh with
    params sharded per pipeline_specs."""
    loss_fn = partial(pipeline_loss, mesh=mesh, n_micro=n_micro)

    def train_step(params, opt_state, tokens):
        import optax

        loss, grads = jax.value_and_grad(loss_fn)(params, cfg, tokens)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step
