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


# ----------------------------------------------------- a prompt chunk

# heads a grid step serves: the step's block of rows is read once for them
# all, and a group's columns of W_kvb are made bfloat16 once for its steps.
# On a v5e at H 128, a chunk of 512 at 2 k / 6 k / 8 k / 12 k of context
# (tools/mla_kernel_bench.py; PERF.md section 6, PR 46): 1.58 / 3.78 / 4.88 /
# 7.03 ms at 2 heads, 1.48 / 3.75 / 4.87 / 7.12 at 4, 1.41 / 3.63 / 4.73 /
# 6.94 at 8 (the XLA loop: 3.15 / 9.14 / 12.13 / 18.12)
_CHUNK_HEADS = 8
# query rows a grid step holds: a served chunk whole
_CHUNK_Q = 512


def _chunk_kernel(start_ref, rows_ref, layer_ref, q_ref, c_ref, w_ref,
                  *refs, block: int, block_q: int, num_kb: int, t_total: int,
                  heads: int, rank: int, nope: int, quant: bool):
    del rows_ref, layer_ref     # the index maps' own
    if quant:
        s_ref, o_ref, m_ref, l_ref, acc_ref, w_bf = refs
    else:
        (o_ref, m_ref, l_ref, acc_ref), s_ref, w_bf = refs, None, w_ref
    b, qi, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    oldest = start_ref[b] + qi * block_q    # this block of queries' first
    newest = oldest + block_q - 1           # and last position
    per_head = w_ref.shape[1] // heads      # N + V
    vdim = per_head - nope
    q_width = q_ref.shape[2] // heads

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if quant:       # int8 -> the cache's dtype once a group of heads
            w_bf[...] = w_ref[...].astype(w_bf.dtype)

    first = kb * block

    def visit(masked: bool):
        c = c_ref[0]                                        # [BK, W]
        if masked:
            # rows past the newest position hold what an earlier tenant
            # left, and those of a partial last block past the array's end
            # nothing defined: not finite, maybe, and 0 x that must not
            # reach the sums (a padded final chunk may run past the end)
            row = first + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
            c = jnp.where(row <= jnp.minimum(newest, t_total - 1), c,
                          jnp.zeros_like(c))
            k_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block), 1)
            q_pos = oldest + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block), 0)
            mask = k_pos <= q_pos
            if t_total % block:     # a padding query past the array's end
                mask = jnp.logical_and(mask, k_pos < t_total)
        lat, k_pe = c[:, :rank], c[:, rank:]
        for h in range(heads):
            cols = slice(h * per_head, (h + 1) * per_head)
            kv = jnp.dot(lat, w_bf[:, cols],
                         preferred_element_type=jnp.float32)
            if quant:
                kv = kv * s_ref[:, cols]
            kv = kv.astype(c.dtype)                         # [BK, N + V]
            k = jnp.concatenate([kv[:, :nope], k_pe], axis=-1)
            q = q_ref[0, :, h * q_width:(h + 1) * q_width]  # [BQ, N + P']
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            # m and l live lane-replicated in [BQ, 128] scratch. Block 0 is
            # visited first and holds position 0, which every query sees: m
            # is finite from then on and exp(NEG_INF - m) is 0
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha[:, :1] + jnp.dot(
                p.astype(c.dtype), kv[:, nope:],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    # a block every query of this step sees whole needs no mask
    whole = first + block - 1 <= oldest
    pl.when(whole)(lambda: visit(False))
    pl.when(jnp.logical_and(jnp.logical_not(whole), first <= newest))(
        lambda: visit(True))

    @pl.when(kb == num_kb - 1)
    def _finish():
        for h in range(heads):
            out = acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[0, :, h * vdim:(h + 1) * vdim] = out.astype(o_ref.dtype)


def mla_chunk_vmem_bytes(block: int, block_q: int, heads: int, width: int,
                         rank: int, nope: int, vdim: int, quant: bool,
                         itemsize: int = 2) -> int:
    """What mla_chunk asks of VMEM: its blocks twice (the pipeline's two
    buffers), its scratch, and the float32 scores, probabilities and
    expanded rows of the head in hand, with half as much again to spare."""
    q_w = nope + width - rank
    blocks = (block_q * heads * q_w * itemsize + block * width * itemsize
              + rank * heads * (nope + vdim) * (1 if quant else itemsize)
              + block_q * heads * vdim * itemsize)
    scratch = heads * block_q * (2 * 128 + vdim) * 4 + quant * (
        rank * heads * (nope + vdim) * itemsize)
    body = block_q * block * (4 + 4 + itemsize) + block * (
        width * itemsize + (nope + vdim) * (4 + itemsize))
    return int(1.5 * (2 * blocks + scratch + body))


@functools.partial(jax.jit, static_argnames=(
    "rank", "nope", "scale", "block", "heads_per_step"))
def mla_chunk(q, cache, w_kvb, start, rows, layer, *, rank: int, nope: int,
              scale: float, block: int, heads_per_step: int | None = None):
    """A chunk's attention over a latent cache, EXPANDING, in one kernel.

    q [B, S, H, N + P]: the chunk's queries, row b's at positions start[b]
    .. start[b] + S - 1, whose cache rows are already written; cache
    [L, slots, T, W] (W = R + P padded to whole lane tiles with zeros), of
    which layer `layer` (i32 scalar), slot rows[b], is read in place; w_kvb
    [R, H (N + V)] or its int8 {"q", "s"}. Returns [B, S, H, V] in q's
    dtype.

    ops/attention.mha_extend_blocks' loop (kv.LatentKV.attend_window's twin)
    with nothing of a block but the cached rows read from memory: a grid
    step holds `block` rows of ONE slot and `heads_per_step` heads; it puts
    the rows through each head's columns of W_kvb (products in the cache's
    dtype, float32 accumulation, the int8 scale applied to the float32
    sums), attends the head's S queries over them under a running maximum
    and sum in float32, and keeps scores, probabilities, statistics and the
    output's accumulator in VMEM. The grid is (row, group of heads, block of
    queries, block of rows); the blocks visited are 0 .. that of the
    step's newest query position, the rest neither fetched nor multiplied.
    A cached row past a query's position weighs 0; one past the newest is
    zeroed before it is expanded (it may hold an earlier tenant's inf or
    NaN). A partial last block (where `block` does not divide T) takes the
    place of the loop's block moved back inside.

    Reads the cache and writes a fresh output: nothing is aliased.
    vmem_limit_bytes is computed from the blocks (mla_chunk_vmem_bytes:
    34 MB at the served shape, over the 16 MiB a v5e kernel gets by
    default and a quarter of what the chip has). `block`: the rows a grid
    step holds (kv.CHUNK_BLOCK; the whole of a shorter cache);
    heads_per_step: for tests and tools/mla_kernel_bench.py."""
    B, S, H, D = q.shape
    T, W = cache.shape[2:]
    quant = isinstance(w_kvb, dict)
    body = w_kvb["q"] if quant else w_kvb
    per_head = body.shape[-1] // H
    vdim = per_head - nope
    heads = heads_per_step or next(
        g for g in (_CHUNK_HEADS, 4, 2, 1) if H % g == 0)
    block = min(block, T)
    block_q = _CHUNK_Q if S % _CHUNK_Q == 0 else S
    num_kb = pl.cdiv(T, block)
    dtype = q.dtype
    # a head's query beside the row's columns past the latent: the position
    # key and the padding's zeros
    q = (q.astype(jnp.float32) * scale).astype(cache.dtype)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, nope + W - rank - D),))
    q = q.reshape(B, S, H * q.shape[-1])
    prefetch = (start.astype(jnp.int32), rows.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1))

    def q_map(b, g, qi, kb, start, rows, lyr):
        return (b, qi, g)

    def c_map(b, g, qi, kb, start, rows, lyr):
        last = jnp.minimum((start[b] + (qi + 1) * block_q - 1) // block,
                           num_kb - 1)
        return (lyr[0], rows[b], jnp.minimum(kb, last), 0)

    def w_map(b, g, qi, kb, start, rows, lyr):
        return (0, g)

    operands = [q, cache, body.reshape(body.shape[-2:])]
    in_specs = [pl.BlockSpec((1, block_q, heads * q.shape[-1] // H), q_map),
                pl.BlockSpec((None, 1, block, W), c_map),
                pl.BlockSpec((rank, heads * per_head), w_map)]
    scratch = [pltpu.VMEM((heads, block_q, 128), jnp.float32),    # m (lane-
               pltpu.VMEM((heads, block_q, 128), jnp.float32),    # l  repl.)
               pltpu.VMEM((heads, block_q, vdim), jnp.float32)]   # acc
    if quant:
        operands.append(w_kvb["s"].reshape(1, -1).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((1, heads * per_head), w_map))
        scratch.append(pltpu.VMEM((rank, heads * per_head), cache.dtype))
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, block=block, block_q=block_q,
                          num_kb=num_kb, t_total=T, heads=heads, rank=rank,
                          nope=nope, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, H // heads, S // block_q, num_kb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, heads * vdim), q_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, S, H * vdim), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=mla_chunk_vmem_bytes(
                block, block_q, heads, W, rank, nope, vdim, quant,
                cache.dtype.itemsize)),
        interpret=_interpret(),
        name="mla_chunk",
    )(*prefetch, *operands)
    return out.reshape(B, S, H, vdim)
