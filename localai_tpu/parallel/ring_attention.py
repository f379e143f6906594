"""Ring attention: sequence-parallel causal attention over a mesh axis.

The reference has NO sequence/context parallelism (SURVEY §2.4 — long context
is handled per-device with RoPE scaling + context shift); this is the
framework's beyond-parity capability: contexts larger than one chip's HBM are
sharded over the `seq` mesh axis, and K/V chunks rotate around the ring via
`ppermute` (ICI neighbor exchange) while each device accumulates its local
queries' online-softmax state — compute and communication fully overlapped by
XLA, memory per chip O(S/n).

Layout: q/k/v sharded on the sequence axis [B, S/n, H, D]; output identical
sharding. Works on any mesh axis name; tested on the virtual CPU mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _local_block(q, k, v, lengths, q_pos, k_pos, scale, sliding_window,
                 m, l, acc):
    """Online-softmax accumulation of one K/V chunk into (m, l, acc)."""
    b, sq, kvh, g, d = q.shape
    logits = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    mask = (k_pos[None, :] <= q_pos[:, None])[None]          # [1,Sq,Sk] causal
    mask = mask & (k_pos[None, None, :] < lengths[:, None, None])
    if sliding_window is not None and sliding_window > 0:
        mask = mask & ((q_pos[:, None] - k_pos[None, :])
                       < sliding_window)[None]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
    p = jnp.exp(logits - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bkgst,btkd->bkgsd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def _ring_attn_shard(q, k, v, lengths, *, axis_name, scale, sliding_window):
    """Per-device body under shard_map. q/k/v: local [B, Sl, H|KVH, D]."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sl, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, sl, kvh, h // kvh, d)

    q_pos = idx * sl + jnp.arange(sl)
    m = jnp.full((b, kvh, h // kvh, sl), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, h // kvh, sl), jnp.float32)
    acc = jnp.zeros((b, kvh, h // kvh, sl, d), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = k, v
    for t in range(n):  # static unroll: n is the mesh axis size
        src = (idx - t) % n                      # owner of the current chunk
        k_pos = src * sl + jnp.arange(sl)
        m, l, acc = _local_block(qg, k_cur, v_cur, lengths, q_pos, k_pos,
                                 scale, sliding_window, m, l, acc)
        if t != n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)[..., None]             # [B,KVH,G,Sl,D]
    out = jnp.moveaxis(out, 3, 1).reshape(b, sl, h, d)
    return out.astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "sliding_window"))
def ring_prefill(q, k, v, lengths, mesh: Mesh, axis: str = "seq",
                 sliding_window: int | None = None):
    """Sequence-parallel causal GQA attention.

    q: [B, S, H, D]; k/v: [B, S, KVH, D]; lengths: [B]. S must divide by the
    `axis` mesh size. Returns [B, S, H, D] sharded like q. On a combined
    serving mesh ('data','model','seq') the batch/head axes keep their TP/DP
    sharding — the ring runs over `axis` only, with data/model as ordinary
    shard_map axes (the per-device body sees local B/H/KVH sizes).
    """
    d = q.shape[-1]
    scale = d ** -0.5
    data_ax = "data" if "data" in mesh.axis_names else None
    model_ax = "model" if "model" in mesh.axis_names else None
    qkv_spec = P(data_ax, axis, model_ax, None)
    fn = shard_map(
        functools.partial(_ring_attn_shard, axis_name=axis, scale=scale,
                          sliding_window=sliding_window),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, P(data_ax)),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, lengths)


def build_seq_mesh(n: int | None = None, devices=None) -> Mesh:
    """1-D ('seq',) mesh for sequence parallelism."""
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    n = n or len(devices)
    return Mesh(np.array(devices[:n]), ("seq",))
