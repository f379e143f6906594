"""Pallas TPU kernel for latent attention's decode step (ops/mla.py: the
absorbed form).

mla_decode: every head's absorbed query of a row, q [B, H, R + P], over the
row's cached latents [L, B, T, R + P] where they lie in the layer stack. A
grid step fetches `block_k` tokens of ONE buffer and uses the block twice:
all R + P columns as the keys of all H heads, its first R columns as their
values. That is an MQA of group H: 2 H (R + P) + 2 H R operations for (R + P)
x itemsize bytes a cached token, 242 op/B at bf16 with H 128, R 512, P 64,
against this chip's ridge of 240: the products run in the cache's dtype on
the matrix unit with float32 accumulation (a float32 product would make the
kernel compute-bound several times over). The grid is (row, block); blocks
past a row's length are neither fetched nor multiplied (flash_attention's
fetch plan), a row of length 0 gives zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.flash_attention import (
    NEG_INF, _fetch_plan, _interpret,
)

# on a v5e, 30 rows of about 6 k tokens in rows of 640 bfloat16 values
# (tools/mla_kernel_bench.py; PERF.md section 5, PR 40): 0.632 ms a call at
# 512 tokens a grid step, 0.519 at 1024, 0.484 at 2048 (2.6 MB a block,
# twice in flight, beside 2 MB of float32 scores and probabilities)
_BLOCK_LADDER = (2048, 1024, 512, 256, 128)


def _block_k(t: int) -> int:
    """Tokens a grid step moves: the largest of the ladder that divides T,
    else the whole of a short cache, else 128 with a partial last block."""
    for bk in _BLOCK_LADDER:
        if t % bk == 0:
            return bk
    return t if t < _BLOCK_LADDER[0] else 128


def _mla_kernel(lengths_ref, layer_ref, plan_ref, q_ref, c_ref, o_ref,
                m_ref, l_ref, acc_ref, *, block_k: int, num_kb: int,
                t_total: int, rank: int):
    del layer_ref, plan_ref     # the index maps' own
    b, kb = pl.program_id(0), pl.program_id(1)
    length = lengths_ref[b]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * block_k

    @pl.when(start < length)    # never, for a row that is not decoding (0)
    def _compute():
        q = q_ref[0]                                       # [H, R + P]
        blk = c_ref[0]                                     # [BK, R + P]
        if t_total % block_k:
            # a partial last block's rows past the array's end are
            # undefined (NaN in the interpreter): 0 x undefined must not
            # reach the sums
            row = start + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            blk = jnp.where(row < t_total, blk, jnp.zeros_like(blk))
        s = jax.lax.dot_general(q, blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < jnp.minimum(length, t_total), s, NEG_INF)
        # m and l live lane-replicated in [H, 128] scratch
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p.astype(blk.dtype), blk[:, :rank],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "block_k"))
def mla_decode(q, cache, lengths, layer, *, rank: int, scale: float,
               block_k: int | None = None):
    """q [B, H, R + P] (absorbed, not yet scaled); cache [L, B, T, R + P],
    of which layer `layer` (i32 scalar) is read in place; lengths [B]: a
    row's entries, the token just written among them, 0 for a row that is
    not decoding (nothing of it is fetched or multiplied, its output is
    zeros). Returns the heads' weighted sums of the latents [B, H, R] in
    q's dtype. block_k: for tests and tools/mla_kernel_bench.py."""
    B, H, D = q.shape
    T = cache.shape[2]
    block_k = min(block_k or _block_k(T), T)
    num_kb = pl.cdiv(T, block_k)
    lengths = lengths.astype(jnp.int32)
    q = (q.astype(jnp.float32) * scale).astype(cache.dtype)
    prefetch = (lengths, jnp.asarray(layer, jnp.int32).reshape(1),
                _fetch_plan(lengths, block_k, num_kb, None, False))

    def q_map(b, kb, lens, lyr, plan):
        return (b, 0, 0)

    def c_map(b, kb, lens, lyr, plan):
        own = kb <= plan[1, b]
        return (lyr[0], jnp.where(own, b, plan[2, b]),
                jnp.where(own, kb, plan[3, b]), 0)

    return pl.pallas_call(
        functools.partial(_mla_kernel, block_k=block_k, num_kb=num_kb,
                          t_total=T, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, num_kb),
            in_specs=[pl.BlockSpec((1, H, D), q_map),
                      pl.BlockSpec((None, 1, block_k, D), c_map)],
            out_specs=pl.BlockSpec((1, H, rank), q_map),
            scratch_shapes=[
                pltpu.VMEM((H, 128), jnp.float32),      # m (lane-
                pltpu.VMEM((H, 128), jnp.float32),      # l  replicated)
                pltpu.VMEM((H, rank), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="mla_decode",
    )(*prefetch, q, cache)
