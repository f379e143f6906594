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
  cache [B, KVH, T, D]; the KV-block axis lives in the GRID with a
  scalar-prefetched index map that clamps out-of-range blocks to the last
  valid one — Mosaic skips the DMA when consecutive grid steps map to the
  same block, so each slot streams only ceil(length/BLOCK) KV blocks from
  HBM. That is the "ragged" part: long-context decode is O(valid tokens) in
  both compute AND memory traffic, not O(max context). With `layer` the
  caches are the whole [L, B, KVH, T, D] stack and the index maps address
  layer `layer` of it: nothing slices a layer out for the kernel.

Mosaic tiling rule (the round-3 lesson): the LAST TWO dims of every block
shape must be (divisible by 8, divisible by 128) or equal to the array dims.
Heads therefore live in the grid, never in a trailing block dim; every block
is [..., seq_block, head_dim] over head-major [B, H, S, D] layouts.

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


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *,
                   block_k: int, num_kb: int, t_total: int, scale: float,
                   sliding_window: int | None, ring: bool = False):
    b = pl.program_id(0)
    kb = pl.program_id(2)
    length = lengths_ref[b]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * block_k
    live = start < length
    if sliding_window is not None and not ring:
        live &= (start + block_k) > (length - sliding_window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [G, D]
        k_blk = k_ref[0, 0].astype(jnp.float32)                # [BK, D]
        v_blk = v_ref[0, 0].astype(jnp.float32)
        if t_total % block_k:
            # final partial block: rows past the array end hold UNDEFINED
            # values (NaN in interpret mode) — zero them so 0·undef can't
            # poison the accumulator through the p@v matmul
            row_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (k_blk.shape[0], 1), 0)
            v_blk = jnp.where(row_pos < t_total, v_blk, 0.0)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # [G, BK]
        k_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = k_pos < jnp.minimum(length, t_total)
        if ring:
            mask &= _ring_in_window(k_pos, length, t_total, sliding_window)
        elif sliding_window is not None:
            mask &= k_pos >= length - sliding_window
        s = jnp.where(mask, s, NEG_INF)
        # m/l live lane-replicated in [G, 128] scratch
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _check_ring(ring: bool, sliding_window, table):
    if ring and (table is not None or not sliding_window):
        raise ValueError("ring=True reads a contiguous cache under a "
                         "sliding_window")


def _check_layer(layer, cache, table):
    stacked = cache.ndim == 5
    if stacked != (layer is not None) or (stacked and table is not None):
        raise ValueError("`layer` goes with a contiguous [L, B, KVH, T, D] "
                         "cache stack, and only with one")


def _layer_operand(layer):
    """The layer index as the [1] i32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _decode_kernel_stacked(lengths_ref, layer_ref, *refs, **kw):
    # the layer index is consumed by the index maps only (their K/V blocks
    # squeeze the layer axis away); the body math is identical
    _decode_kernel(lengths_ref, *refs, **kw)


def _decode_kernel_paged(lengths_ref, table_ref, *refs, **kw):
    # table is consumed by the index maps only; the body math is identical
    _decode_kernel(lengths_ref, *refs, **kw)


@functools.partial(jax.jit,
                   static_argnames=("sliding_window", "block_k", "ring"))
def ragged_decode(q, k_cache, v_cache, lengths, sliding_window=None,
                  block_k: int = 256, table=None, ring: bool = False,
                  layer=None):
    """Decode-step GQA attention. q: [B, 1, H, D]; caches [B, KVH, T, D];
    lengths: [B] valid entries incl. the newly-written token.
    Returns [B, 1, H, D].

    `layer` (i32 scalar, contiguous caches): the caches are a layer stack
    [L, B, KVH, T, D] and the kernel reads layer `layer` of it in place (the
    decode step's layer scan carries the stack — models/llama.py).

    ring=True (contiguous caches, with a sliding_window): the cache is a
    ring of T rows and lengths may pass T — see _ring_in_window.

    Paged mode (`table` [B, MAXB] i32, ops/paged.py): caches are a block
    pool [NB, KVH, BS, D]; virtual KV block kb of slot b streams from
    physical block table[b, kb]. Same O(valid tokens) traffic — the clamp
    repeats the physical index past the valid length and Mosaic skips the
    duplicate DMA."""
    B, _, H, D = q.shape
    KVH = k_cache.shape[-3]  # [(L,) B, KVH, T, D] / pool [NB, KVH, BS, D]
    group = H // KVH
    scale = D ** -0.5
    qg = q.reshape(B, KVH, group, D)
    _check_ring(ring, sliding_window, table)
    _check_layer(layer, k_cache, table)

    if table is not None:
        BS = k_cache.shape[2]            # pool [NB, KVH, BS, D]
        num_kb = table.shape[1]
        T = num_kb * BS

        def kv_map(b, h, kb, lens, tab):
            last = jnp.maximum(pl.cdiv(lens[b], BS) - 1, 0)
            return (tab[b, jnp.minimum(kb, last)], h, 0, 0)

        kernel = functools.partial(_decode_kernel_paged, block_k=BS,
                                   num_kb=num_kb, t_total=T, scale=scale,
                                   sliding_window=sliding_window)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, KVH, num_kb),
                in_specs=[
                    pl.BlockSpec((1, 1, group, D),
                                 lambda b, h, kb, lens, tab: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, BS, D), kv_map),
                    pl.BlockSpec((1, 1, BS, D), kv_map),
                ],
                out_specs=pl.BlockSpec((1, 1, group, D),
                                       lambda b, h, kb, lens, tab:
                                       (b, h, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((group, 128), jnp.float32),
                    pltpu.VMEM((group, 128), jnp.float32),
                    pltpu.VMEM((group, D), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(lengths.astype(jnp.int32), table.astype(jnp.int32), qg,
          k_cache, v_cache)
        return out.reshape(B, 1, H, D)

    if layer is None:
        k_cache, v_cache, layer = k_cache[None], v_cache[None], 0
    T = k_cache.shape[3]
    block_k = min(block_k, T)
    num_kb = pl.cdiv(T, block_k)

    def kv_map(b, h, kb, lens, lyr):
        # clamp beyond-length blocks to the last valid one: Mosaic skips the
        # DMA when the block index repeats, making traffic O(length)
        last = jnp.maximum(pl.cdiv(lens[b], block_k) - 1, 0)
        return (lyr[0], b, h, jnp.minimum(kb, last), 0)

    kernel = functools.partial(_decode_kernel_stacked, block_k=block_k,
                               num_kb=num_kb, t_total=T, scale=scale,
                               sliding_window=sliding_window, ring=ring)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KVH, num_kb),
            in_specs=[
                pl.BlockSpec((1, 1, group, D),
                             lambda b, h, kb, lens, lyr: (b, h, 0, 0)),
                pl.BlockSpec((None, 1, 1, block_k, D), kv_map),
                pl.BlockSpec((None, 1, 1, block_k, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D),
                                   lambda b, h, kb, lens, lyr: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((group, 128), jnp.float32),   # l
                pltpu.VMEM((group, D), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(lengths.astype(jnp.int32), _layer_operand(layer), qg, k_cache, v_cache)
    return out.reshape(B, 1, H, D)


# ----------------------------------------------------- int8 KV decode

def _decode_q8_kernel(lengths_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                      o_ref, m_ref, l_ref, acc_ref, *,
                      num_kb: int, t_total: int, scale: float,
                      sliding_window: int | None, paged: bool = False,
                      ring: bool = False):
    """ragged_decode against an int8 cache: K/V stream from HBM as int8 (half
    the decode bandwidth — the resource decode is bound by); scales are one
    aligned [1, 128] row per 128-token block, applied to score columns (K) and
    to p's columns before the p@v matmul (V) so the matmuls stay dense.
    paged=True: the scale ref is the single [1, 128] row of this physical
    block (table-mapped) instead of the slot's whole scale strip."""
    b = pl.program_id(0)
    kb = pl.program_id(2)
    length = lengths_ref[b]
    block_k = 128

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * block_k
    live = start < length
    if sliding_window is not None and not ring:
        live &= (start + block_k) > (length - sliding_window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [G, D]
        k_blk = kq_ref[0, 0].astype(jnp.float32)               # [BK, D]
        v_blk = vq_ref[0, 0].astype(jnp.float32)
        if paged:
            k_s = ks_ref[0, 0]                                 # [1, BK]
            v_s = vs_ref[0, 0]
        else:
            k_s = ks_ref[0, 0, pl.ds(kb, 1), :]                # [1, BK]
            v_s = vs_ref[0, 0, pl.ds(kb, 1), :]
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        s = s * k_s                                            # dequant K
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < jnp.minimum(length, t_total)
        if ring:
            mask &= _ring_in_window(k_pos, length, t_total, sliding_window)
        elif sliding_window is not None:
            mask &= k_pos >= length - sliding_window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p * v_s, v_blk, preferred_element_type=jnp.float32)  # dequant V
        m_ref[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _decode_q8_kernel_paged(lengths_ref, table_ref, *refs, **kw):
    _decode_q8_kernel(lengths_ref, *refs, paged=True, **kw)


def _decode_q8_kernel_stacked(lengths_ref, layer_ref, *refs, **kw):
    _decode_q8_kernel(lengths_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("sliding_window", "ring"))
def ragged_decode_q8(q, k_q, k_s, v_q, v_s, lengths, sliding_window=None,
                     table=None, ring: bool = False, layer=None):
    """Decode-step GQA attention over an int8 KV cache (ops/kvcache.py
    layout). q: [B, 1, H, D]; k_q/v_q: [B, KVH, T, D] int8;
    k_s/v_s: [B, KVH, T//128, 128] f32 (token t's scale at [t//128, t%128]);
    lengths: [B]. T must be a multiple of 128. Returns [B, 1, H, D].

    Paged mode (`table` [B, MAXB] i32): k_q/v_q are a block pool
    [NB, KVH, 128, D] with scales [NB, KVH, 1, 128] (ops/paged.py).
    ring=True and `layer` (all four arrays then [L, B, ...] stacks): as in
    ragged_decode."""
    B, _, H, D = q.shape
    KVH = k_q.shape[-3]
    group = H // KVH
    scale = D ** -0.5
    qg = q.reshape(B, KVH, group, D)
    _check_ring(ring, sliding_window, table)
    _check_layer(layer, k_q, table)

    if table is not None:
        BS = k_q.shape[2]
        if BS != 128:
            raise ValueError("paged int8 KV blocks must be 128 tokens")
        num_kb = table.shape[1]
        T = num_kb * BS

        def kv_map(b, h, kb, lens, tab):
            last = jnp.maximum(pl.cdiv(lens[b], BS) - 1, 0)
            return (tab[b, jnp.minimum(kb, last)], h, 0, 0)

        kernel = functools.partial(_decode_q8_kernel_paged, num_kb=num_kb,
                                   t_total=T, scale=scale,
                                   sliding_window=sliding_window)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, KVH, num_kb),
                in_specs=[
                    pl.BlockSpec((1, 1, group, D),
                                 lambda b, h, kb, lens, tab: (b, h, 0, 0)),
                    pl.BlockSpec((1, 1, BS, D), kv_map),
                    pl.BlockSpec((1, 1, 1, 128), kv_map),
                    pl.BlockSpec((1, 1, BS, D), kv_map),
                    pl.BlockSpec((1, 1, 1, 128), kv_map),
                ],
                out_specs=pl.BlockSpec((1, 1, group, D),
                                       lambda b, h, kb, lens, tab:
                                       (b, h, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((group, 128), jnp.float32),
                    pltpu.VMEM((group, 128), jnp.float32),
                    pltpu.VMEM((group, D), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=_interpret(),
        )(lengths.astype(jnp.int32), table.astype(jnp.int32), qg,
          k_q, k_s.astype(jnp.float32), v_q, v_s.astype(jnp.float32))
        return out.reshape(B, 1, H, D)

    if layer is None:
        k_q, k_s, v_q, v_s, layer = k_q[None], k_s[None], v_q[None], \
            v_s[None], 0
    T = k_q.shape[3]
    if T % 128:
        raise ValueError("int8 KV cache length must be a multiple of 128")
    num_kb = T // 128
    n_tiles = k_s.shape[3]

    def kv_map(b, h, kb, lens, lyr):
        last = jnp.maximum(pl.cdiv(lens[b], 128) - 1, 0)
        return (lyr[0], b, h, jnp.minimum(kb, last), 0)

    def scale_map(b, h, kb, lens, lyr):
        return (lyr[0], b, h, 0, 0)

    kernel = functools.partial(_decode_q8_kernel_stacked, num_kb=num_kb,
                               t_total=T, scale=scale,
                               sliding_window=sliding_window, ring=ring)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KVH, num_kb),
            in_specs=[
                pl.BlockSpec((1, 1, group, D),
                             lambda b, h, kb, lens, lyr: (b, h, 0, 0)),
                pl.BlockSpec((None, 1, 1, 128, D), kv_map),
                # scales ride whole per (slot, head): one small DMA, reused
                # across every KV block of the row
                pl.BlockSpec((None, 1, 1, n_tiles, 128), scale_map),
                pl.BlockSpec((None, 1, 1, 128, D), kv_map),
                pl.BlockSpec((None, 1, 1, n_tiles, 128), scale_map),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D),
                                   lambda b, h, kb, lens, lyr: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((group, 128), jnp.float32),   # l
                pltpu.VMEM((group, D), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(lengths.astype(jnp.int32), _layer_operand(layer), qg,
      k_q, k_s.astype(jnp.float32), v_q, v_s.astype(jnp.float32))
    return out.reshape(B, 1, H, D)
