"""Batched, jit-safe token sampling — the PredictOptions knob surface.

The reference's sampling knobs live in PredictOptions
(/root/reference/backend/backend.proto:110-159) and are enforced inside
llama.cpp's sampler chain. Here the whole chain is a single vectorized
function over the slot batch, applied on-device every decode step:

  penalties (repeat/presence/frequency over a per-slot token-count table)
  → logit bias → temperature → top-k → top-p → min-p → typical-p → sample

All per-slot knobs are device arrays [B] so slots with different settings
share one jitted step (no recompilation per request mix). top_k/top_p/min_p
use one shared descending sort of the logits — O(B·V·logV) but a single fused
XLA op, MXU-free and bandwidth-bound, which is the right trade on TPU.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
TOPK_BLOCK = 128   # lanes a block of _top_k's first stage (a vector register's)


@dataclasses.dataclass
class SamplingParams:
    """Host-side per-request sampling configuration (proto PredictOptions names)."""
    temperature: float = 0.8
    top_k: int = 40            # <=0 disables
    top_p: float = 0.95        # >=1 disables
    min_p: float = 0.0         # <=0 disables
    typical_p: float = 1.0     # >=1 disables
    repeat_penalty: float = 1.0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    seed: int = -1             # <0 → draw from entropy
    logit_bias: dict[int, float] | None = None
    greedy: bool = False       # temperature<=0 → greedy

    def normalized(self) -> "SamplingParams":
        p = dataclasses.replace(self)
        if p.temperature is None or p.temperature <= 0:
            p.greedy = True
            p.temperature = 1.0
        if not p.top_k or p.top_k <= 0:
            p.top_k = 0
        if p.top_p is None or p.top_p <= 0:
            p.top_p = 1.0
        return p


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SamplerState:
    """Device-side batched sampler state, one row per engine slot (a pytree —
    flows through jit with buffer donation)."""
    temperature: jax.Array   # [B] f32
    top_k: jax.Array         # [B] i32 (0 = off)
    top_p: jax.Array         # [B] f32
    min_p: jax.Array         # [B] f32
    typical_p: jax.Array     # [B] f32
    repeat_penalty: jax.Array    # [B] f32
    presence_penalty: jax.Array  # [B] f32
    frequency_penalty: jax.Array # [B] f32
    greedy: jax.Array        # [B] bool
    key: jax.Array           # [B, 2] u32 PRNG keys
    token_counts: jax.Array  # [B, V] i32 — occurrences in prompt+generation
    logit_bias: jax.Array    # [B, V] f32

    @staticmethod
    def init(batch: int, vocab: int) -> "SamplerState":
        z = lambda d: jnp.zeros((batch,), d)
        return SamplerState(
            temperature=jnp.ones((batch,), jnp.float32),
            top_k=z(jnp.int32),
            top_p=jnp.ones((batch,), jnp.float32),
            min_p=z(jnp.float32),
            typical_p=jnp.ones((batch,), jnp.float32),
            repeat_penalty=jnp.ones((batch,), jnp.float32),
            presence_penalty=z(jnp.float32),
            frequency_penalty=z(jnp.float32),
            greedy=jnp.zeros((batch,), jnp.bool_),
            key=jnp.zeros((batch, 2), jnp.uint32),
            token_counts=jnp.zeros((batch, vocab), jnp.int32),
            logit_bias=jnp.zeros((batch, vocab), jnp.float32),
        )


def sampler_row(params: SamplingParams, vocab: int, fallback_seed: int,
                include_bias: bool = True) -> dict:
    """Host-side: build the per-slot row values (everything except
    token_counts, which the engine fills with prompt occurrence counts).
    `fallback_seed` is used when the request doesn't pin a seed.
    include_bias=False omits the [V]-sized logit_bias entirely (the engine's
    light-row path — building it here would already device-transfer it)."""
    import numpy as np

    p = params.normalized()
    bias = None
    if include_bias:
        bias = np.zeros((vocab,), np.float32)
        if p.logit_bias:
            for k, v in p.logit_bias.items():
                if 0 <= int(k) < vocab:
                    bias[int(k)] = v
    seed = p.seed if (p.seed is not None and p.seed >= 0) else fallback_seed
    row = dict(
        temperature=jnp.float32(p.temperature),
        top_k=jnp.int32(min(p.top_k, vocab)),
        top_p=jnp.float32(p.top_p),
        min_p=jnp.float32(p.min_p),
        typical_p=jnp.float32(p.typical_p),
        repeat_penalty=jnp.float32(p.repeat_penalty),
        presence_penalty=jnp.float32(p.presence_penalty),
        frequency_penalty=jnp.float32(p.frequency_penalty),
        greedy=jnp.bool_(p.greedy),
        key=jax.random.key_data(jax.random.PRNGKey(seed)).astype(jnp.uint32),
    )
    if bias is not None:
        row["logit_bias"] = jnp.asarray(bias)
    return row


def apply_penalties(logits, state: SamplerState):
    """llama.cpp-semantics penalties: repeat penalty divides positive logits /
    multiplies negative ones for seen tokens; presence/frequency subtract."""
    counts = state.token_counts
    seen = counts > 0
    rp = state.repeat_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    logits = jnp.where(seen, penalized, logits)
    logits = logits - seen.astype(jnp.float32) * state.presence_penalty[:, None]
    logits = logits - counts.astype(jnp.float32) * state.frequency_penalty[:, None]
    return logits


def pipeline_logits(logits, state: SamplerState, mask_bits=None):
    """Penalties → bias → temperature (the pre-truncation transform). The
    log_softmax of this is sample()'s logprob contract — OpenAI-style
    logprobs are NOT inflated by top-k/top-p renormalization."""
    b, v = logits.shape
    logits = logits.astype(jnp.float32)
    if mask_bits is not None:
        # two wire formats, one semantic: u8 rows are the host matcher's
        # per-step upload (LSB-first bytes); u32 rows are gathered from the
        # device-resident grammar table (LSB-first words) — identical bit
        # order, so either unpack yields the same allowed set
        if mask_bits.dtype == jnp.uint32:
            bits = (mask_bits[:, :, None]
                    >> jnp.arange(32, dtype=jnp.uint32)) & 1
        else:
            bits = (mask_bits[:, :, None]
                    >> jnp.arange(8, dtype=jnp.uint8)) & 1
        allowed = bits.reshape(b, -1)[:, :v].astype(bool)
        logits = jnp.where(allowed, logits, NEG_INF)
    logits = apply_penalties(logits, state)
    logits = logits + state.logit_bias
    return logits / jnp.maximum(state.temperature[:, None], 1e-6)


def _filtered_sorted(logits, state: SamplerState, mask_bits=None):
    """Shared pipeline: penalties → bias → temperature → truncation chain.
    Returns (masked_sorted_logits [B,V] desc with dropped entries at NEG_INF,
    order [B,V] mapping sorted rank → token id)."""
    b, v = logits.shape
    logits = pipeline_logits(logits, state, mask_bits)

    # shared descending sort powers top-k / top-p / min-p / typical-p
    sorted_logits = -jnp.sort(-logits, axis=-1)                 # [B,V] desc
    order = jnp.argsort(-logits, axis=-1)                       # [B,V]

    rank = jnp.arange(v)[None, :]
    # top-k first, then renormalize over the survivors: llama.cpp chains its
    # samplers sequentially, and the sort-free fast path (_sample_topk) can
    # only see the survivors — sequential semantics keep both paths equal in
    # distribution
    k = jnp.where(state.top_k > 0, state.top_k, v)[:, None]
    keep = rank < k
    probs = jax.nn.softmax(
        jnp.where(keep, sorted_logits, NEG_INF), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # top-p: keep smallest prefix with cum >= p (always keep rank 0)
    keep &= (cum - probs) < state.top_p[:, None]
    # min-p: prob >= min_p * max_prob
    keep &= probs >= state.min_p[:, None] * probs[:, :1]
    # typical-p: keep tokens closest to expected entropy until mass >= typ_p
    ent = -jnp.sum(probs * jnp.log(probs + 1e-10), axis=-1, keepdims=True)
    dev = jnp.abs(-jnp.log(probs + 1e-10) - ent)
    dev_order = jnp.argsort(dev, axis=-1)
    typ_cum = jnp.cumsum(jnp.take_along_axis(probs, dev_order, axis=-1), axis=-1)
    typ_keep_sorted_by_dev = (typ_cum - jnp.take_along_axis(probs, dev_order, axis=-1)) < state.typical_p[:, None]
    typ_keep = jnp.zeros((b, v), bool).at[
        jnp.arange(b)[:, None], dev_order
    ].set(typ_keep_sorted_by_dev)
    keep &= jnp.where(state.typical_p[:, None] >= 1.0, True, typ_keep)
    keep = keep.at[:, 0].set(True)

    masked = jnp.where(keep, sorted_logits, NEG_INF)
    return masked, sorted_logits, order


def sampling_probs(logits, state: SamplerState, mask_bits=None):
    """Full post-pipeline categorical distribution [B, V] in TOKEN order —
    exactly what sample() draws from (greedy rows → one-hot argmax). The
    speculative verifier needs this as an explicit density (Leviathan accept
    ratio + residual distribution)."""
    b, v = logits.shape
    masked, _, order = _filtered_sorted(logits, state, mask_bits)
    p_sorted = jax.nn.softmax(masked, axis=-1)
    rank0 = (jnp.arange(v)[None, :] == 0).astype(jnp.float32)
    p_sorted = jnp.where(state.greedy[:, None], rank0, p_sorted)
    return jnp.zeros((b, v), jnp.float32).at[
        jnp.arange(b)[:, None], order
    ].set(p_sorted)


@jax.named_scope("sampling")   # names the ops in a device trace; no key moves
def sample(logits, state: SamplerState, mask_bits=None, topk_width=None):
    """One sampling step. logits: [B, V] (any float dtype).

    mask_bits: optional [B, ceil(V/8)] u8 allowed-token bitmask (LSB-first)
    from the grammar matcher — disallowed tokens are hard-masked before the
    truncation chain (the llama.cpp grammar-sampler role, applied on-device).

    topk_width (static): decode fast path. A full [B, 128k] descending sort
    is the dominant non-matmul cost of a decode step on TPU; when every
    active slot has 0 < top_k <= width (the engine checks), lax.top_k over
    `width` lanes replaces the two full sorts and top-p/min-p apply WITHIN
    the top-k survivors — llama.cpp's sequential sampler-chain semantics.
    Chosen-token logprobs stay exact (full-vocab logsumexp, no sort needed).

    Returns (tokens [B] i32, new_keys [B,2], logprobs [B] f32 of chosen token).
    """
    if topk_width is not None:
        if mask_bits is not None:
            raise ValueError("grammar masks require the full sampling path "
                             "(topk_width must be None)")
        return _sample_topk(logits, state, topk_width)
    b, v = logits.shape
    masked, sorted_logits, order = _filtered_sorted(logits, state, mask_bits)
    sampled_rank, carry_keys = _draw(state, masked)
    tokens = jnp.take_along_axis(order, sampled_rank[:, None], axis=-1)[:, 0]

    # logprob of the chosen token under the PRE-truncation distribution
    # (post penalties/bias/temperature) — OpenAI-style logprobs must not be
    # inflated by top-k/top-p renormalization.
    logprobs_sorted = jax.nn.log_softmax(sorted_logits, axis=-1)
    tok_logprob = jnp.take_along_axis(logprobs_sorted, sampled_rank[:, None], axis=-1)[:, 0]
    return tokens.astype(jnp.int32), carry_keys, tok_logprob


def _draw(state: SamplerState, masked):
    """Shared PRNG step: split per-slot keys, invert the masked categorical's
    CDF at ONE scalar uniform per slot, greedy rows take rank 0.

    jax.random.categorical would be the obvious draw, but its Gumbel-max
    trick consumes randomness per LANE: the same key over a [B, V] full-sort
    row and a [B, W] top-k window yields different tokens even when the
    survivor distributions are identical, so escalating a slot onto the
    sort-free fast path silently changed its sampled stream. A scalar
    uniform + inverse CDF is width-independent by construction — dropped
    lanes sit at NEG_INF, carry exactly zero probability mass, and cannot
    move the threshold count.
    Returns (sampled_rank [B], carry_keys [B,2] u32)."""
    new_keys = jax.vmap(lambda kk: jax.random.split(
        jax.random.wrap_key_data(kk), 2))(state.key)
    step_keys = jax.vmap(jax.random.wrap_key_data)(
        jax.vmap(jax.random.key_data)(new_keys[:, 1]))
    u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(step_keys)
    # unnormalized weights: exp(NEG_INF - max) underflows to exactly 0, so
    # the cumsum prefix over the survivors is identical across widths
    w = jnp.exp(masked - masked[:, :1])      # rank 0 always survives
    cum = jnp.cumsum(w, axis=-1)
    r = u[:, None] * cum[:, -1:]
    # smallest rank with cum >= r; the constant tail (cum == total >= r)
    # never counts, so the rank stays within the survivor prefix
    sampled_rank = jnp.sum((cum < r).astype(jnp.int32), axis=-1)
    sampled_rank = jnp.where(state.greedy, 0, sampled_rank)
    carry_keys = jax.vmap(jax.random.key_data)(new_keys[:, 0]).astype(
        jnp.uint32)
    return sampled_rank, carry_keys


def topk_by_blocks(vocab: int, width: int) -> bool:
    """Whether the top-`width` of a [B, vocab] row is taken in two stages
    (_top_k): only where the `width` gathered blocks are less than the row.
    A static fact of the program; the engine's counter asks it too."""
    return vocab > width * TOPK_BLOCK


def _top_k(logits, width: int):
    """lax.top_k(logits, width) over float32 [B, V], to the bit, without
    its pass over the whole row (a custom call at 40 x the row's read on a
    200 k vocabulary): the `width` largest elements lie in the `width`
    blocks of TOPK_BLOCK lanes with the largest maxima, since an element
    with fewer than `width` elements ahead of it has fewer than `width`
    blocks ahead of its own (each holds an element ahead of it, ties to the
    lower index in both orders). The chosen blocks are gathered in the
    row's own order, so the second call breaks ties as the one call does."""
    b, v = logits.shape
    if not topk_by_blocks(v, width):
        return jax.lax.top_k(logits, width)
    n = -(-v // TOPK_BLOCK)
    # a ragged last block: lanes that come after every lane of the row
    blocks = jnp.pad(logits, ((0, 0), (0, n * TOPK_BLOCK - v)),
                     constant_values=-jnp.inf).reshape(b, n, TOPK_BLOCK)
    _, ids = jax.lax.top_k(blocks.max(axis=-1), width)
    ids = jnp.sort(ids, axis=-1)
    cand = jnp.take_along_axis(blocks, ids[:, :, None], axis=1)
    vals, pos = jax.lax.top_k(cand.reshape(b, width * TOPK_BLOCK), width)
    order = (jnp.take_along_axis(ids, pos // TOPK_BLOCK, axis=-1)
             * TOPK_BLOCK + pos % TOPK_BLOCK)
    return vals, order


def _sample_topk(logits, state: SamplerState, width: int):
    """Sort-free decode sampling over the top-`width` logits (see sample).
    Sequential-chain semantics identical to _filtered_sorted for any slot
    with 0 < top_k <= width and typical_p disabled."""
    b, v = logits.shape
    logits = pipeline_logits(logits, state, None)
    vals, order = _top_k(logits, width)                        # [B, W] desc
    rank = jnp.arange(width)[None, :]
    k = jnp.where(state.top_k > 0, state.top_k, width)[:, None]
    keep = rank < k
    # renormalize over the top-k survivors, THEN apply top-p/min-p — the
    # same sequential chain as the full path
    probs = jax.nn.softmax(jnp.where(keep, vals, NEG_INF), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (cum - probs) < state.top_p[:, None]
    keep &= probs >= state.min_p[:, None] * probs[:, :1]
    keep = keep.at[:, 0].set(True)
    masked = jnp.where(keep, vals, NEG_INF)

    sampled_rank, carry_keys = _draw(state, masked)
    tokens = jnp.take_along_axis(order, sampled_rank[:, None], axis=-1)[:, 0]

    # exact full-vocab logprob without a sort: val - logsumexp(all logits)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tok_logprob = jnp.take_along_axis(
        vals, sampled_rank[:, None], axis=-1)[:, 0] - lse
    return tokens.astype(jnp.int32), carry_keys, tok_logprob
