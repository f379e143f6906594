"""Batched speculative decoding inside the serving engine.

Reference parity: llama.cpp's DraftModel/NDraft serving knobs
(/root/reference/backend/backend.proto:218,150) — a small draft model
proposes gamma tokens, the target verifies them in one forward, and the
Leviathan et al. accept/residual rule preserves the target's sampling
distribution exactly.

TPU-first shape discipline: ONE jitted step serves ALL slots — the draft
loop is a lax.scan of gamma draft decode steps, verification is a single
target `extend` over the [next_token, d_1..d_gamma] window, and the accept
loop is a vectorized cumprod over the window (no per-token host round
trips — the round-3 standalone decoder's weakness). Per step each slot
emits 1..gamma+1 tokens.

Invariant (differs from the non-spec engine): instead of carrying
`last_logits` and sampling at the top of the next step, the spec engine
carries `next_tokens` [B] — the already-sampled, already-emitted token
whose KV is not yet written. The verify `extend` writes its KV along with
the drafts'; rejected draft KV beyond the new length is dead and is
overwritten by the next window.

The target distribution uses the slot's FULL sampling pipeline
(ops/sampling.sampling_probs): temperature, top-k/p, min-p, typical-p,
penalties — with token counts frozen at window start (the same
approximation llama.cpp's spec sampler makes). The draft proposes from a
temperature-only distribution; any proposal is distribution-safe under the
accept/residual rule.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from localai_tpu.models.llama import LlamaConfig, decode_step, extend
from localai_tpu.ops.sampling import (
    SamplerState, pipeline_logits, sample, sampling_probs,
)

TINY = 1e-30


def _draft_state(sampler: SamplerState) -> SamplerState:
    """Temperature-only proposal settings (greedy follows the slot)."""
    ones = jnp.ones_like(sampler.top_p)
    zeros = jnp.zeros_like(sampler.min_p)
    return dataclasses.replace(
        sampler,
        top_k=jnp.zeros_like(sampler.top_k),
        top_p=ones,
        min_p=zeros,
        typical_p=ones,
        repeat_penalty=jnp.ones_like(sampler.repeat_penalty),
        presence_penalty=zeros,
        frequency_penalty=zeros,
        token_counts=jnp.zeros_like(sampler.token_counts),
        logit_bias=jnp.zeros_like(sampler.logit_bias),
    )


def _slot_keys(key_data):
    return jax.vmap(jax.random.wrap_key_data)(key_data)


def build_spec_decode(cfg_t: LlamaConfig, cfg_d: LlamaConfig, gamma: int):
    """Returns the jittable all-slots speculative step.

    (params_t, params_d, cos_t, sin_t, cos_d, sin_d, kct, vct, kcd, vcd,
     sampler, lengths, next_tokens, active) →
    (tokens_out [B, gamma+1], n_out [B], logprobs_out [B, gamma+1],
     next_tokens', kct', vct', kcd', vcd', sampler', lengths')
    """

    def spec_decode(params_t, params_d, cos_t, sin_t, cos_d, sin_d,
                    kct, vct, kcd, vcd, sampler, lengths, next_tokens,
                    active, table=None):
        B = next_tokens.shape[0]
        G = gamma
        act_i = active.astype(jnp.int32)

        # one key split per step; all draws derive via fold_in
        new_keys = jax.vmap(
            lambda kk: jax.random.split(jax.random.wrap_key_data(kk), 2)
        )(sampler.key)
        carry_keys = jax.vmap(jax.random.key_data)(new_keys[:, 0]).astype(
            jnp.uint32)
        step_keys = new_keys[:, 1]          # [B] typed keys

        dstate = _draft_state(sampler)

        # ---- draft phase: scan gamma draft decode steps
        def draft_iter(carry, i):
            kcd, vcd, tok = carry
            logits_d, kcd, vcd = decode_step(
                params_d, cfg_d, tok, lengths + i, cos_d, sin_d, kcd, vcd,
                active)
            p_d = sampling_probs(logits_d, dstate)               # [B, V]
            # disjoint fold_in domains: drafts 100+i, uniforms 1, correction 2
            sub = jax.vmap(lambda k: jax.random.fold_in(k, 100 + i))(
                step_keys)
            d = jax.vmap(
                lambda k, p: jax.random.categorical(k, jnp.log(p + TINY))
            )(sub, p_d).astype(jnp.int32)
            return (kcd, vcd, d), (d, p_d)

        (kcd, vcd, d_last), (drafts, p_ds) = jax.lax.scan(
            draft_iter, (kcd, vcd, next_tokens), jnp.arange(G))
        # the loop wrote KV for next_token..d_{G-1}; ingest d_G too — on full
        # acceptance its position is committed, and a hole there would poison
        # every later draft proposal (junk attended forever)
        _, kcd, vcd = decode_step(params_d, cfg_d, d_last, lengths + G,
                                  cos_d, sin_d, kcd, vcd, active)
        d_tok = drafts.T                                         # [B, G]
        p_d_stack = jnp.moveaxis(p_ds, 0, 1)                     # [B, G, V]

        # ---- target verify: one extend over [next_token, d_1..d_gamma]
        window = jnp.concatenate([next_tokens[:, None], d_tok], axis=1)
        if table is None:
            # dense inactive redirect: start T-1 puts the first garbage row
            # at the never-readable last position; the rest fall out of
            # bounds and the scatter drops them
            T = kct.shape[3]
            start = jnp.where(active, lengths, T - 1)
            tlogits, kct, vct = extend(params_t, cfg_t, window, start,
                                       cos_t, sin_t, kct, vct)   # [B,G+1,V]
        else:
            # paged: out-of-bounds positions would CLAMP through the table
            # gather into a real block, so inactive rows route their whole
            # window to the trash block instead (models/llama.py extend
            # redirect)
            tlogits, kct, vct = extend(params_t, cfg_t, window, lengths,
                                       cos_t, sin_t, kct, vct, table=table,
                                       redirect=~active)         # [B,G+1,V]
        (tokens_out, n_out, logprobs_out, c, n_extra,
         sampler) = _verify_outputs(sampler, active, step_keys, carry_keys,
                                    d_tok, p_d_stack, tlogits, G)
        lengths = lengths + act_i * (1 + n_extra)
        next_tokens = jnp.where(active, c, next_tokens)
        n_out = n_out * act_i
        return (tokens_out, n_out, logprobs_out, next_tokens,
                kct, vct, kcd, vcd, sampler, lengths, n_extra * act_i)

    return spec_decode


def _verify_outputs(sampler, active, step_keys, carry_keys, d_tok,
                    p_d_stack, tlogits, G, mask_rows=None):
    """The spec program's verify tail:
    target distributions per window position, vectorized Leviathan accept,
    residual correction token, output assembly, sampler commit.

    mask_rows: optional [B, G+1, W32] u32 grammar mask per window position
    (the automaton state AFTER each draft prefix) — masked target probs
    reject grammar-invalid drafts through the ordinary accept test (p_t = 0
    → u < 0 never accepts) and the residual renormalizes over the allowed
    set, so the correction token is grammar-valid by construction. The
    draft proposes unmasked; any proposal is distribution-safe under the
    accept/residual rule.

    Returns (tokens_out [B, G+1], n_out [B] UNGATED (= n_extra+1),
    logprobs_out, c [B] correction token, n_extra [B], sampler')."""
    B = d_tok.shape[0]

    def _m(i):
        return None if mask_rows is None else mask_rows[:, i]

    ps_t = jnp.stack(
        [sampling_probs(tlogits[:, i], sampler, _m(i))
         for i in range(G + 1)],
        axis=1)                                              # [B,G+1,V]
    # logprobs use the PRE-truncation distribution — sample()'s contract
    lp_pre = jnp.stack(
        [jax.nn.log_softmax(pipeline_logits(tlogits[:, i], sampler, _m(i)),
                            axis=-1) for i in range(G + 1)],
        axis=1)                                              # [B,G+1,V]

    # ---- vectorized accept (Leviathan): u_i < p_t(d_i) / p_d(d_i)
    bidx = jnp.arange(B)[:, None]
    pt_d = ps_t[:, :G][bidx, jnp.arange(G)[None, :], d_tok]  # [B, G]
    pd_d = p_d_stack[bidx, jnp.arange(G)[None, :], d_tok]
    u_keys = jax.vmap(lambda k: jax.random.fold_in(k, 1))(step_keys)
    us = jax.vmap(lambda k: jax.random.uniform(k, (G,)))(u_keys)
    accept = us < pt_d / jnp.maximum(pd_d, TINY)
    acc_prefix = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    n_extra = acc_prefix.sum(axis=1)                         # [B] 0..G

    # ---- correction/bonus token from the residual distribution
    p_t_corr = jnp.take_along_axis(
        ps_t, n_extra[:, None, None], axis=1)[:, 0]          # [B, V]
    p_d_corr = jnp.take_along_axis(
        p_d_stack, jnp.minimum(n_extra, G - 1)[:, None, None],
        axis=1)[:, 0]
    p_d_corr = jnp.where((n_extra < G)[:, None], p_d_corr, 0.0)
    residual = jnp.maximum(p_t_corr - p_d_corr, 0.0)
    z = residual.sum(axis=-1, keepdims=True)
    resid = jnp.where(z > TINY, residual / jnp.maximum(z, TINY),
                      p_t_corr)
    c_keys = jax.vmap(lambda k: jax.random.fold_in(k, 2))(step_keys)
    c = jax.vmap(
        lambda k, p: jax.random.categorical(k, jnp.log(p + TINY))
    )(c_keys, resid).astype(jnp.int32)

    # ---- assemble outputs: accepted drafts then the correction token
    cols = jnp.arange(G + 1)[None, :]
    d_pad = jnp.concatenate(
        [d_tok, jnp.zeros((B, 1), jnp.int32)], axis=1)
    tokens_out = jnp.where(
        cols < n_extra[:, None], d_pad,
        jnp.where(cols == n_extra[:, None], c[:, None], 0))
    n_out = n_extra + 1
    lp_d = lp_pre[:, :G][bidx, jnp.arange(G)[None, :], d_tok]
    lp_d = jnp.concatenate([lp_d, jnp.zeros((B, 1), jnp.float32)], axis=1)
    lp_c = jnp.take_along_axis(
        lp_pre, n_extra[:, None, None], axis=1)[:, 0][jnp.arange(B), c]
    logprobs_out = jnp.where(
        cols < n_extra[:, None], lp_d,
        jnp.where(cols == n_extra[:, None], lp_c[:, None], 0.0))

    # ---- sampler commit (inactive slots' counts unchanged)
    valid = (cols < n_out[:, None]) & active[:, None]
    counts = sampler.token_counts.at[
        jnp.arange(B)[:, None], tokens_out
    ].add(valid.astype(jnp.int32))
    sampler = dataclasses.replace(sampler, key=carry_keys,
                                  token_counts=counts)
    return tokens_out, n_out, logprobs_out, c, n_extra, sampler


def build_spec_admit_tail(cfg_t: LlamaConfig):
    """Sample the FIRST token of a freshly-admitted slot from last_logits
    (full pipeline, that slot's key stream only) and count it. mask is the
    slot's grammar bitmask [1, ceil(V/8)] u8 (None for unconstrained) — a
    grammar slot's first token must respect the start state like every
    later one. Returns (token, logprob, sampler')."""

    def admit_tail(sampler, last_logits, slot, mask=None):
        row = jax.tree_util.tree_map(lambda a: a[slot][None], sampler)
        tok, keys, lp = sample(last_logits[slot][None], row, mask)
        counts = sampler.token_counts.at[slot, tok[0]].add(1)
        sampler = dataclasses.replace(
            sampler,
            key=sampler.key.at[slot].set(keys[0]),
            token_counts=counts)
        return tok[0], lp[0], sampler

    return admit_tail


def build_draft_ingest(cfg_d: LlamaConfig):
    """Write a prompt window into the DRAFT cache (KV only) — mirrors the
    target admission/chunk writes so the draft never needs host catch-up."""

    def ingest(params_d, cos_d, sin_d, kcd, vcd, tokens, start, slot):
        _, kcd, vcd = extend(params_d, cfg_d, tokens, start[None],
                             cos_d, sin_d, kcd, vcd, slot_map=slot[None],
                             with_logits=False)
        return kcd, vcd

    return ingest
