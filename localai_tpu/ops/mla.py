"""Latent attention (MLA): the products between a layer's cached LATENT
rows and its heads (pure XLA; the decode kernel is ops/pallas/mla.py).

A latent layer caches, a token, one row of R + P values: the normalised
latent c (R = kv_lora_rank) and the rotated position key k_pe (P =
qk_rope_head_dim) that every head shares. A head's keys and values are made
of c by the layer's up-projection W_kvb [R, H x (N + V)] (N = qk_nope_head_dim,
V = v_head_dim): [k_nope | v][h] = c W_kvb[h]. Two forms give the same
attention over the same rows:

  expanding  `expand` makes k = [k_nope | k_pe] and v of a block of rows for
             all H heads, and the heads attend as heads of N + P / V do: a
             prompt chunk's form (models/kv.LatentKV.attend_window), R x H x
             (N + V) products a cached row ONCE a chunk, whatever its length;
  absorbed   the query takes W_UK in (`absorb`: q_nope[h] W_UK[h]^T, N -> R),
             attends over the rows as they lie (keys R + P wide, values the
             first R: one "KV head" for all H query heads), and the output
             goes through W_UV (`unabsorb`, R -> V): decode's form, where a
             row has one query a head and expanding it would cost H x (N + V)
             / (R + P) times its bytes.

Int8 weights ({"q", "s"}, a scale per OUTPUT channel of W_kvb): in `absorb`
the contraction runs over W_UK's output channels, so the scale is folded
into q_nope before the product; `unabsorb` scales its output, as qmatmul.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.ops.attention import NEG_INF
from localai_tpu.ops.quant import is_quantized, qmatmul


def _heads(w_kvb, heads: int):
    """W_kvb as (body [R, H, N + V], scale [H, N + V] or None)."""
    body, scale = ((w_kvb["q"], w_kvb["s"]) if is_quantized(w_kvb)
                   else (w_kvb, None))
    body = body.reshape(body.shape[0], heads, -1)
    return body, None if scale is None else scale.reshape(heads, -1)


def absorb(q, w_kvb, nope: int):
    """q [B, S, H, N + P] -> [B, S, H, R + P]: q_nope through W_UK^T, q_pe
    as it is."""
    body, scale = _heads(w_kvb, q.shape[-2])
    q_nope = q[..., :nope]
    if scale is not None:
        q_nope = (q_nope.astype(jnp.float32)
                  * scale[:, :nope].astype(jnp.float32)).astype(q.dtype)
    q_abs = jnp.einsum("bshn,rhn->bshr", q_nope,
                       body[..., :nope].astype(q.dtype),
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_abs.astype(q.dtype), q[..., nope:]], axis=-1)


def unabsorb(o, w_kvb, nope: int):
    """The heads' sums of latents o [B, S, H, R] -> their values
    [B, S, H, V], through W_UV."""
    body, scale = _heads(w_kvb, o.shape[-2])
    out = jnp.einsum("bshr,rhv->bshv", o, body[..., nope:].astype(o.dtype),
                     preferred_element_type=jnp.float32)
    if scale is not None:
        out = out * scale[:, nope:].astype(jnp.float32)
    return out.astype(o.dtype)


def expand(rows, w_kvb, heads: int, nope: int, rank: int):
    """Cached rows [B, T, R + P] -> (k [B, H, T, N + P], v [B, H, T, V]):
    every head's keys (its own k_nope beside the k_pe all share) and
    values."""
    b, t, _ = rows.shape
    kv = qmatmul(rows[..., :rank], w_kvb).reshape(b, t, heads, -1)
    k_pe = jnp.broadcast_to(rows[:, :, None, rank:],
                            (b, t, heads, rows.shape[-1] - rank))
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    return k.transpose(0, 2, 1, 3), kv[..., nope:].transpose(0, 2, 1, 3)


def mla_decode_xla(q, rows, lengths, rank: int, scale: float):
    """Absorbed decode attention, the Pallas kernel's twin: q [B, H, R + P]
    over rows [B, T, R + P] of which `lengths` [B] count (the token just
    written among them) -> the heads' weighted sums of the latents
    [B, H, R]. Products in the inputs' dtype, softmax in float32."""
    logits = jnp.einsum("bhc,btc->bht", q, rows,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(rows.shape[1])[None, :] < lengths[:, None]
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,btr->bhr", probs, rows[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)
