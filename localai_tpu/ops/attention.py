"""Attention reference implementations (pure XLA).

Layouts (chosen so the MXU sees large [tokens, head_dim] matmuls and the
sharding layer can shard the head axis over the `model` mesh axis):

  q:        [B, S, H, D]
  k/v:      [B, S, KVH, D]      (GQA: H % KVH == 0)
  kv cache: [B, KVH, T, D]      (slot-contiguous, head-major, T = max context —
                                 head-major keeps the Pallas decode kernel's
                                 trailing block dims at (seq, head_dim), the
                                 Mosaic-legal tiling)

Softmax is computed in float32; matmuls stay in the input dtype (bf16).
These XLA versions are the semantic reference and the CPU-mesh test path;
Pallas TPU kernels (when present under localai_tpu/ops/pallas/) are selected
by the engine on TPU and validated against these in tests. One is served as
it stands: `mha_extend_blocks`, a prompt chunk's attention over a dense cache
in blocks up to the context it has (models/kv.py: DenseKV.attend_window),
held to `mha_extend` in tests/test_chunk_attention.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _group_query_heads(q, num_kv_heads):
    b, s, h, d = q.shape
    return q.reshape(b, s, num_kv_heads, h // num_kv_heads, d)


def _softcap(logits, cap):
    if cap is None or cap <= 0:
        return logits
    return jnp.tanh(logits / cap) * cap


def mha_prefill(q, k, v, lengths, *, scale=None, softcap=None, sliding_window=None):
    """Causal self-attention over padded sequences.

    lengths: [B] int32 — valid token count per sequence; padded tail is masked.
    sliding_window: optional int — Mistral-style local attention window.
    Returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    scale = scale if scale is not None else d ** -0.5

    qg = _group_query_heads(q, kvh)  # [B,S,KVH,G,D]
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)

    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]                      # [S,T]
    valid = pos[None, :] < lengths[:, None]                    # [B,T]
    mask = causal[None, :, :] & valid[:, None, :]              # [B,S,T]
    if sliding_window is not None and sliding_window > 0:
        mask = mask & (pos[:, None] - pos[None, :] < sliding_window)[None]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def mha_extend(q, k_cache, v_cache, q_positions, *, scale=None,
               sliding_window=None):
    """Window attention against the cache: scores S new tokens whose K/V are
    already written at `q_positions` (speculative-verification forward).

    q: [B, S, H, D]; caches: [B, KVH, T, D]; q_positions: [B, S] global
    positions of the window tokens. Each query attends to every cache entry
    at position <= its own. Returns [B, S, H, D].
    """
    b, s, h, d = q.shape
    kvh = k_cache.shape[1]
    t = k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5

    qg = _group_query_heads(q, kvh)                             # [B,S,KVH,G,D]
    logits = jnp.einsum("bskgd,bktd->bkgst", qg, k_cache).astype(jnp.float32) * scale

    pos = jnp.arange(t)
    mask = pos[None, None, :] <= q_positions[:, :, None]        # [B,S,T]
    if sliding_window is not None and sliding_window > 0:
        mask = mask & (pos[None, None, :]
                       > q_positions[:, :, None] - sliding_window)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,bktd->bskgd", probs, v_cache)
    return out.reshape(b, s, h, d)


def block_span(lo_pos, hi_pos, t: int, block: int):
    """The blocks of `block` rows a window of queries visits in a row of T:
    [first, end) holds every position from lo_pos (the oldest any query
    sees) to hi_pos (the newest). NumPy or traced scalars (both clip): the
    engine counts with the arithmetic the device loops by."""
    blocks = -(-t // block)
    return (lo_pos // block).clip(0, blocks - 1), \
        (hi_pos // block + 1).clip(1, blocks)


def mha_extend_blocks(q, fetch, num_kv_heads, t, q_positions, start, *,
                      block, scale=None, sliding_window=None, v_dim=None):
    """mha_extend in work proportional to the context the window HAS: K and
    V are visited `block` rows at a time, from the block that holds the
    oldest position a query can see to the one that holds the newest
    (max(start) + S - 1), under a running maximum and sum (online softmax).
    No [S, T] score array exists and a block past the bound is never read;
    the trip count is a traced value, so one program serves every `start`.

    fetch(first) -> (k, v) [B, KVH, block, D]: rows first .. first + block
    of every query row's cache, dequantised; t: the cache's length T. Where
    `block` does not divide T the last block is moved back inside and the
    rows it shares with the one before are masked. Same mask and float32
    statistics as mha_extend, to which it agrees to the rounding of the
    products' dtype. A row past its query row's newest position weighs 0
    and its V is not multiplied (what an earlier tenant left there may not
    be finite). v_dim: the values' width where it is not the keys' (a
    latent layer's heads). Returns [B, S, H, v_dim or D]."""
    b, s, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    v_dim = v_dim or d
    windowed = sliding_window is not None and sliding_window > 0
    qg = _group_query_heads(q, num_kv_heads).transpose(0, 2, 3, 1, 4)
    newest = start + s - 1                                      # [B]
    oldest = jnp.min(start) - sliding_window + 1 if windowed else jnp.int32(0)
    lo, hi = block_span(oldest, jnp.max(newest), t, block)

    def visit(j, carry):
        m, l, acc = carry                   # [B,KVH,G,S] x 2, [B,KVH,G,S,D]
        first = jnp.minimum(j * block, t - block)
        k, v = fetch(first)
        pos = first + jnp.arange(block)
        logits = jnp.einsum("bkgsd,bktd->bkgst", qg, k,
                            preferred_element_type=jnp.float32) * scale
        mask = ((pos >= j * block)[None, None, :]
                & (pos[None, None, :] <= q_positions[:, :, None]))  # [B,S,t]
        if windowed:
            mask = mask & (pos[None, None, :]
                           > q_positions[:, :, None] - sliding_window)
        mask = mask[:, None, None, :, :]
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.where(mask, jnp.exp(logits - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        v = jnp.where((pos[None, :] <= newest[:, None])[:, None, :, None],
                      v, 0)
        acc = fade[..., None] * acc + jnp.einsum(
            "bkgst,bktd->bkgsd", p.astype(q.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, fade * l + jnp.sum(p, axis=-1), acc

    _, l, acc = jax.lax.fori_loop(lo, hi, visit, (
        jnp.full(qg.shape[:-1], NEG_INF, jnp.float32),
        jnp.zeros(qg.shape[:-1], jnp.float32),
        jnp.zeros((*qg.shape[:-1], v_dim), jnp.float32)))
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, v_dim).astype(
        q.dtype)


def mha_prefill_tiered(q, k, v, lengths, sinks, window, *, scale=None,
                       softcap=None):
    """mha_prefill with a PER-SLOT attention-sink + sliding-window mask
    (KV lifecycle tier, engine/kvtier.py): query at position p attends key
    at position t iff t <= p and (t > p - window[b] or t < sinks[b]).
    Full-policy slots ship sentinel window/sinks >= S and reduce to the
    plain causal mask. sinks/window: [B] int32."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    scale = scale if scale is not None else d ** -0.5

    qg = _group_query_heads(q, kvh)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)

    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]                      # [S,T]
    valid = pos[None, :] < lengths[:, None]                    # [B,T]
    mask = causal[None, :, :] & valid[:, None, :]              # [B,S,T]
    keep = (pos[None, None, :] > pos[None, :, None]
            - window[:, None, None]) \
        | (pos[None, None, :] < sinks[:, None, None])
    logits = jnp.where((mask & keep)[:, None, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def mha_extend_tiered(q, k_cache, v_cache, q_positions, kv_positions, kv_ok,
                      sinks, window, *, scale=None, drop_window=True):
    """mha_extend against a RESIDENT (ring-mapped) cache view whose rows
    carry explicit true positions (kv_positions [B, T]) and validity
    (kv_ok [B, T] — residency + freshness, ops/paged.resident_row_positions
    plus any cold-tier extension the caller concatenated).

    drop_window=True applies the sink_window retention mask per query
    (dropped-block semantics); False keeps every valid row <= the query —
    the quantize_cold case, where exited-window content is still readable
    (at int8) rather than evicted. sinks/window: [B] int32."""
    b, s, h, d = q.shape
    kvh = k_cache.shape[1]
    scale = scale if scale is not None else d ** -0.5

    qg = _group_query_heads(q, kvh)                             # [B,S,KVH,G,D]
    logits = jnp.einsum("bskgd,bktd->bkgst", qg,
                        k_cache).astype(jnp.float32) * scale

    mask = kv_ok[:, None, :] & (kv_positions[:, None, :]
                                <= q_positions[:, :, None])     # [B,S,T]
    if drop_window:
        mask = mask & (
            (kv_positions[:, None, :] > q_positions[:, :, None]
             - window[:, None, None])
            | (kv_positions[:, None, :] < sinks[:, None, None]))
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,bktd->bskgd", probs, v_cache)
    return out.reshape(b, s, h, d)


def mha_decode_masked(q, k_cache, v_cache, kv_mask, *, scale=None,
                      softcap=None):
    """Single-token decode attention with a caller-built per-row mask
    [B, T] instead of the implicit arange(T) < lengths — the KV-lifecycle
    read path, where the cache view is ring-mapped (+ optionally
    concatenated with the cold tier) and row validity is a function of
    residency, true position, window membership, and demotion state."""
    b, _, h, d = q.shape
    kvh = k_cache.shape[1]
    scale = scale if scale is not None else d ** -0.5

    qg = _group_query_heads(q, kvh)[:, 0]                       # [B,KVH,G,D]
    logits = jnp.einsum("bkgd,bktd->bkgt", qg,
                        k_cache).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)
    logits = jnp.where(kv_mask[:, None, None, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v_cache)
    return out.reshape(b, 1, h, d)


def mha_decode(q, k_cache, v_cache, lengths, *, scale=None, softcap=None,
               sliding_window=None):
    """Single-token decode attention against a slot-contiguous KV cache.

    q: [B, 1, H, D]; k_cache/v_cache: [B, KVH, T, D]; lengths: [B] — number of
    valid cache entries per slot INCLUDING the token being decoded.
    Returns [B, 1, H, D].
    """
    b, _, h, d = q.shape
    kvh = k_cache.shape[1]
    t = k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5

    qg = _group_query_heads(q, kvh)[:, 0]                       # [B,KVH,G,D]
    logits = jnp.einsum("bkgd,bktd->bkgt", qg, k_cache).astype(jnp.float32) * scale
    logits = _softcap(logits, softcap)

    pos = jnp.arange(t)
    mask = pos[None, :] < lengths[:, None]                      # [B,T]
    if sliding_window is not None and sliding_window > 0:
        mask = mask & (pos[None, :] >= lengths[:, None] - sliding_window)
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,bktd->bkgd", probs, v_cache)
    return out.reshape(b, 1, h, d)
