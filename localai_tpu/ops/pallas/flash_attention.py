"""Pallas TPU attention kernels — the native compute tier.

Reference parity note: llama.cpp's flash-attention toggle
(/root/reference/backend/backend.proto:247) enables fused CUDA attention; here
the fused kernels are Mosaic/Pallas, written block-wise for the MXU with
online softmax so the [S, S] score matrix never hits HBM (memory O(block²)
instead of O(S²)).

Two kernels:
- flash_prefill: causal GQA attention over padded prompt batches
  [B, S, H, D]; per-row validity from `lengths`; optional sliding window.
- ragged_decode: one-token-per-slot decode attention against the slot KV
  cache [B, KVH, T, D]. The grid is (row, KV block): a grid step moves a few
  hundred tokens of EVERY KV head of a row (`_dense_block_k`, from the
  shapes), and a scalar-prefetched index map points the steps past a row's
  length at the next row's first block — Mosaic skips the DMA when
  consecutive grid steps map to the same block, so each slot streams only
  ceil(length/BLOCK) KV blocks from HBM and a slot of length 0 none. That is
  the "ragged" part: long-context decode is O(valid tokens) in both compute
  AND memory traffic, not O(max context). With `layer` the caches are the
  whole [L, B, KVH, T, D] stack and the index maps address layer `layer` of
  it: nothing slices a layer out for the kernel. (A block pool behind a
  table keeps the grid (row, head, block): its blocks are not contiguous.)

Mosaic tiling rule (the round-3 lesson): the LAST TWO dims of every block
shape must be (divisible by 8, divisible by 128) or equal to the array dims.
Heads therefore live in the grid or in a LEADING block dim, never in a
trailing one; every block is [..., seq_block, head_dim] over head-major
[B, H, S, D] layouts.

On CPU (tests) both run in interpreter mode; the math is identical. Real-TPU
lowering is validated by tests/test_tpu_real.py (TPU-gated), and at serving
time by LoadModel's warmup compiles: a kernel Mosaic refuses fails the load.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.testing import faults

# large-but-finite so exp(NEG_INF - NEG_INF) stays 0/1 instead of NaN when a
# row's first blocks are fully masked (sliding window, ragged tails)
NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _interpret() -> bool:
    """Interpreter mode off-TPU (tests; the backend's device report names it
    'pallas-interpret'). Every pallas_call in this package asks here at
    trace time, which makes it the chaos harness's one hook for "a kernel
    the device refuses" (LOCALAI_FAULT=kernel_raise)."""
    if faults.fire("kernel_raise") is not None:
        raise RuntimeError(
            "injected kernel lowering failure (LOCALAI_FAULT=kernel_raise)")
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------- prefill

def _prefill_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, *,
                    block_q: int, block_k: int, scale: float,
                    sliding_window: int | None):
    b = pl.program_id(0)
    qb = pl.program_id(2)
    length = lengths_ref[b]
    q = q_ref[0, 0].astype(jnp.float32) * scale                # [BQ, D]
    S = k_ref.shape[2]

    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # [BQ, BK]
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_pos <= q_pos) & (k_pos < length)
        if sliding_window is not None:
            mask &= k_pos > q_pos - sliding_window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))   # [BQ,1]
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    num_kb = pl.cdiv(S, block_k)
    # causal: only KV blocks up to (and including) this query block
    last_kb = jnp.minimum(
        (qb + 1) * block_q + block_k - 1, S + block_k - 1) // block_k
    last_kb = jnp.minimum(last_kb, num_kb)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, last_kb, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sliding_window", "block_q",
                                             "block_k"))
def flash_prefill(q, k, v, lengths, sliding_window=None,
                  block_q: int = 128, block_k: int = 128):
    """Causal GQA flash attention. q: [B, S, H, D]; k/v: [B, S, KVH, D];
    lengths: [B]. Returns [B, S, H, D] in q.dtype."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    group = H // KVH
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = D ** -0.5

    # pad K/V so block_k divides the KV length: pl.ds CLAMPS an out-of-range
    # start (it does not pad), which would silently misattribute key positions
    # in the final partial block. Zero padding is masked out by k_pos<length.
    Sk = pl.cdiv(S, block_k) * block_k
    if Sk != S:
        pad = [(0, 0), (0, Sk - S), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    # head-major layouts so trailing block dims are (seq, head_dim)
    qt = q.transpose(0, 2, 1, 3)                               # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)                               # [B, KVH, Sk, D]
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, pl.cdiv(S, block_q))
    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_k=block_k, scale=scale,
        sliding_window=sliding_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, qb, lens: (b, h, qb, 0)),
                pl.BlockSpec((1, 1, Sk, D),
                             lambda b, h, qb, lens: (b, h // group, 0, 0)),
                pl.BlockSpec((1, 1, Sk, D),
                             lambda b, h, qb, lens: (b, h // group, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, D),
                                   lambda b, h, qb, lens: (b, h, qb, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(lengths.astype(jnp.int32), qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# --------------------------------------------------------------- decode

def _ring_in_window(k_pos, length, t_total: int, sliding_window: int):
    """The cache is a ring of t_total rows (row p mod t_total holds position
    p; `length` counts every token so far, the newest included, and may pass
    t_total): the rows whose entry is among the newest min(length,
    sliding_window). Rows are then not in position order, which a decode
    row's softmax does not mind: K is stored rotated."""
    newest = jax.lax.rem(jnp.maximum(length, 1) - 1, t_total)
    back = newest - k_pos
    back = jnp.where(back < 0, back + t_total, back)
    return back < jnp.minimum(length, sliding_window)


def _decode_kernel(lengths_ref, *refs, quant: bool, paged: bool,
                   block_k: int, num_kb: int, t_total: int, scale: float,
                   sliding_window: int | None, ring: bool):
    """One grid step of ragged_decode / ragged_decode_q8: the KV heads its
    blocks hold (every head of a row on the dense path, one on the paged
    path) over block_k tokens, online softmax in float32 scratch.

    quant: K/V stream from HBM as int8 (half the decode bandwidth — the
    resource decode is bound by); token t's scale sits at [t // 128, t % 128]
    and a step applies block_k // 128 scale rows, to the score columns (K)
    and to p's columns before the p@v matmul (V), so the matmuls stay dense.
    The dense path's scale refs are the row's whole strips, the paged path's
    the single [1, 128] row of this physical block."""
    # (the other prefetched scalars feed the index maps only)
    if quant:
        (q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref,
         acc_ref) = refs[-9:]
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[-7:]
    b = pl.program_id(0)
    kb = pl.program_id(2 if paged else 1)
    length = lengths_ref[b]
    heads = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * block_k
    live = start < length       # never, for a row that is not decoding (0)
    if sliding_window is not None and not ring:
        live &= (start + block_k) > (length - sliding_window)

    def scales(ref, h):
        tiles = block_k // 128
        first = 0 if paged else kb * tiles
        return jnp.concatenate([ref[0, h, pl.ds(first + j, 1), :]
                                for j in range(tiles)], axis=1)  # [1, BK]

    @pl.when(live)
    def _compute():
        k_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], block_k), 1)
        mask = k_pos < jnp.minimum(length, t_total)
        if ring:
            mask &= _ring_in_window(k_pos, length, t_total, sliding_window)
        elif sliding_window is not None:
            mask &= k_pos >= length - sliding_window
        for h in range(heads):
            q = q_ref[0, h].astype(jnp.float32) * scale            # [G, D]
            k_blk = k_ref[0, h].astype(jnp.float32)                # [BK, D]
            v_blk = v_ref[0, h].astype(jnp.float32)
            if t_total % block_k:
                # final partial block: rows past the array end hold UNDEFINED
                # values (NaN in interpret mode) — zero them so 0·undef can't
                # poison the accumulator through the p@v matmul
                row_pos = start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, 1), 0)
                v_blk = jnp.where(row_pos < t_total, v_blk, 0.0)
            s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
            if quant:
                s = s * scales(ks_ref, h)                          # dequant K
            s = jnp.where(mask, s, NEG_INF)                        # [G, BK]
            # m/l live lane-replicated in [G, 128] scratch
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quant:
                p = p * scales(vs_ref, h)                          # dequant V
            acc_ref[h] = acc_ref[h] * alpha[:, :1] + jnp.dot(
                p, v_blk, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        for h in range(heads):
            out = acc_ref[h] / jnp.maximum(l_ref[h][:, :1], 1e-30)
            o_ref[0, h] = out.astype(o_ref.dtype)


def _check_ring(ring: bool, sliding_window, table):
    if ring and (table is not None or not sliding_window):
        raise ValueError("ring=True reads a contiguous cache under a "
                         "sliding_window")


def _check_layer(layer, cache, table):
    stacked = cache.ndim == 5
    if stacked != (layer is not None) or (stacked and table is not None):
        raise ValueError("`layer` goes with a contiguous [L, B, KVH, T, D] "
                         "cache stack, and only with one")


# K + V blocks of a dense grid step, double-buffered, may fill this much of
# the 16 MiB of VMEM a v5e kernel gets by default (the float32 copies of a
# head's blocks and the scale strips take their share of the rest). The
# ladder, on a v5e (tools/attn_kernel_bench.py; PERF.md §6, PR 30): 1024
# where it divides T, 512 for T = 1536; 2048 read no better than 1024
_KV_VMEM_BYTES = 4 << 20
_BLOCK_LADDER = (1024, 512, 256)


def _dense_block_k(t: int, kvh: int, d: int, itemsize: int) -> int:
    """Tokens a dense grid step moves, from the shapes alone: the largest of
    the ladder that divides T and fits the budget, else 128 (an int8 cache's
    T is a multiple of 128) or the whole of a shorter cache."""
    for bk in _BLOCK_LADDER:
        if t % bk == 0 and 4 * kvh * bk * d * itemsize <= _KV_VMEM_BYTES:
            return bk
    return min(128, t)


def _fetch_plan(lengths, block_k: int, num_kb: int, sliding_window, ring):
    """[4, B] i32 for the dense index maps: a row's first and last block to
    fetch (last −1: a row with nothing to read), and where its other grid
    steps point — the first block of the next row that has one, so that row's
    first DMA runs under this row's last product, and a row that is not
    decoding fetches nothing of its own; after the last such row, at the
    block already in hand (a repeated index moves nothing)."""
    nb = lengths.shape[0]
    hi = jnp.minimum(pl.cdiv(lengths, block_k), num_kb) - 1
    lo = jnp.zeros_like(hi)
    if sliding_window is not None and not ring:
        lo = jnp.maximum(lengths - sliding_window, 0) // block_k
    rows = jnp.arange(nb, dtype=jnp.int32)
    reads = hi >= 0
    nxt = jax.lax.cummin(jnp.where(reads, rows, nb), reverse=True)
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), nb, jnp.int32)])
    prv = jnp.maximum(jax.lax.cummax(jnp.where(reads, rows, -1)), 0)
    hop = jnp.where(nxt < nb, nxt, prv)
    hop_blk = jnp.where(nxt < nb, lo[hop], jnp.maximum(hi[hop], 0))
    return jnp.stack([lo, hi, hop, hop_blk])


def _ragged_decode(q, k, v, scales, lengths, sliding_window, block_k, table,
                   ring, layer):
    """ragged_decode and ragged_decode_q8 (scales: K's and V's) behind one
    pallas_call a call."""
    B, _, H, D = q.shape
    KVH = k.shape[-3]   # [(L,) B, KVH, T, D] / pool [NB, KVH, BS, D]
    group = H // KVH
    quant = scales is not None
    qg = q.reshape(B, KVH, group, D)
    _check_ring(ring, sliding_window, table)
    _check_layer(layer, k, table)
    lengths = lengths.astype(jnp.int32)

    def kv_specs(kv, scales):       # K, V, each before its scales
        return [kv, scales, kv, scales] if quant else [kv, kv]

    if table is not None:
        block_k = BS = k.shape[2]
        if quant and BS != 128:
            raise ValueError("paged int8 KV blocks must be 128 tokens")
        num_kb = table.shape[1]
        T = num_kb * BS
        heads = 1       # a pool's blocks are not contiguous in a row: the
        grid = (B, KVH, num_kb)     # grid walks (row, head, block)
        prefetch = (lengths, table.astype(jnp.int32))

        def q_map(b, h, kb, lens, tab):
            return (b, h, 0, 0)

        def kv_map(b, h, kb, lens, tab):
            # clamp beyond-length blocks to the last valid one: Mosaic skips
            # the DMA when the block index repeats, making traffic O(length)
            last = jnp.maximum(pl.cdiv(lens[b], BS) - 1, 0)
            return (tab[b, jnp.minimum(kb, last)], h, 0, 0)

        specs = kv_specs(pl.BlockSpec((1, 1, BS, D), kv_map),
                         pl.BlockSpec((1, 1, 1, 128), kv_map))
        semantics = ("parallel", "parallel", "arbitrary")
        vmem = None
    else:
        if layer is None:
            k, v, layer = k[None], v[None], 0
            scales = scales and tuple(s[None] for s in scales)
        T = k.shape[3]
        if quant and T % 128:
            raise ValueError("int8 KV cache length must be a multiple of 128")
        if block_k is None:
            block_k = _dense_block_k(T, KVH, D, k.dtype.itemsize)
        block_k = min(block_k, T)
        if quant and block_k % 128:
            raise ValueError("an int8 KV block is whole 128-token scale rows")
        num_kb = pl.cdiv(T, block_k)
        heads = KVH     # every KV head of a row rides one grid step
        grid = (B, num_kb)
        prefetch = (lengths, jnp.asarray(layer, jnp.int32).reshape(1),
                    _fetch_plan(lengths, block_k, num_kb, sliding_window,
                                ring))

        def q_map(b, kb, lens, lyr, plan):
            return (b, 0, 0, 0)

        def kv_map(b, kb, lens, lyr, plan):
            own = kb <= plan[1, b]
            return (lyr[0], jnp.where(own, b, plan[2, b]), 0,
                    jnp.where(own, jnp.maximum(kb, plan[0, b]), plan[3, b]),
                    0)

        def scale_map(b, kb, lens, lyr, plan):
            # the scales ride whole per row: two small DMAs, reused across
            # every KV block of the row
            return (lyr[0], jnp.where(kb <= plan[1, b], b, plan[2, b]),
                    0, 0, 0)

        specs = kv_specs(
            pl.BlockSpec((None, 1, KVH, block_k, D), kv_map),
            pl.BlockSpec((None, 1, KVH, T // 128, 128), scale_map))
        semantics = ("parallel", "arbitrary")
        # many KV heads of a wide dtype pass the budget even at 128 tokens
        in_flight = 4 * KVH * block_k * D * k.dtype.itemsize
        vmem = None if in_flight <= _KV_VMEM_BYTES else in_flight + (12 << 20)

    operands = [k, v] if not quant else [
        k, scales[0].astype(jnp.float32), v, scales[1].astype(jnp.float32)]
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, quant=quant, paged=table is not None,
            block_k=block_k, num_kb=num_kb, t_total=T, scale=D ** -0.5,
            sliding_window=sliding_window, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[pl.BlockSpec((1, heads, group, D), q_map), *specs],
            out_specs=pl.BlockSpec((1, heads, group, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((heads, group, 128), jnp.float32),  # m (lane-
                pltpu.VMEM((heads, group, 128), jnp.float32),  # l  replicated)
                pltpu.VMEM((heads, group, D), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem),
        interpret=_interpret(),
    )(*prefetch, qg, *operands)
    return out.reshape(B, 1, H, D)


@functools.partial(jax.jit,
                   static_argnames=("sliding_window", "block_k", "ring"))
def ragged_decode(q, k_cache, v_cache, lengths, sliding_window=None,
                  block_k: int | None = None, table=None, ring: bool = False,
                  layer=None):
    """Decode-step GQA attention. q: [B, 1, H, D]; caches [B, KVH, T, D];
    lengths: [B] valid entries incl. the newly-written token, 0 for a row
    that is not decoding: its blocks are neither fetched nor multiplied and
    its output is zeros. Returns [B, 1, H, D].

    A grid step moves block_k tokens of EVERY KV head of a row, K and V: the
    grid is (B, T // block_k), and block_k comes from the shapes
    (_dense_block_k; the argument is for tests and tools/attn_kernel_bench.py).
    One 128-token block of one head a step (16 KB of int8) left the kernel
    bound by the latency of its DMAs and the count of its grid steps, at a
    twentieth of the chip's bandwidth whatever the rows held (PERF.md §6,
    PR 28 and PR 30).

    `layer` (i32 scalar, contiguous caches): the caches are a layer stack
    [L, B, KVH, T, D] and the kernel reads layer `layer` of it in place (the
    decode step's layer scan carries the stack — models/llama.py).

    ring=True (contiguous caches, with a sliding_window): the cache is a
    ring of T rows and lengths may pass T — see _ring_in_window.

    Paged mode (`table` [B, MAXB] i32, ops/paged.py): caches are a block
    pool [NB, KVH, BS, D]; virtual KV block kb of slot b streams from
    physical block table[b, kb], one head a grid step (a pool's blocks are
    not contiguous in a row). Same O(valid tokens) traffic — the clamp
    repeats the physical index past the valid length and Mosaic skips the
    duplicate DMA."""
    return _ragged_decode(q, k_cache, v_cache, None, lengths, sliding_window,
                          block_k, table, ring, layer)


@functools.partial(jax.jit,
                   static_argnames=("sliding_window", "block_k", "ring"))
def ragged_decode_q8(q, k_q, k_s, v_q, v_s, lengths, sliding_window=None,
                     table=None, ring: bool = False, layer=None,
                     block_k: int | None = None):
    """Decode-step GQA attention over an int8 KV cache (ops/kvcache.py
    layout). q: [B, 1, H, D]; k_q/v_q: [B, KVH, T, D] int8;
    k_s/v_s: [B, KVH, T//128, 128] f32 (token t's scale at [t//128, t%128]);
    lengths: [B]. T must be a multiple of 128. Returns [B, 1, H, D].

    The grid, block_k, lengths of 0, ring=True and `layer` (all four arrays
    then [L, B, ...] stacks): as in ragged_decode; a row's scale strips ride
    whole beside its first block.

    Paged mode (`table` [B, MAXB] i32): k_q/v_q are a block pool
    [NB, KVH, 128, D] with scales [NB, KVH, 1, 128] (ops/paged.py)."""
    return _ragged_decode(q, k_q, v_q, (k_s, v_s), lengths, sliding_window,
                          block_k, table, ring, layer)
