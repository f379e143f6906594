"""Ragged paged attention — one kernel for mixed prefill + decode tokens.

The serving phase split (`prefill` buckets / `extend` chunks / `decode_step`)
makes admission wait on dispatch boundaries and pads every prompt to a bucket.
This kernel serves a FLAT token stream instead (arXiv:2604.15464): the engine
packs this tick's tokens — one row per live decode slot, plus as many
chunked-prefill rows as the token budget fits — into `q [T, H, D]`, and every
row attends to its own sequence's paged KV through the block table. No bucket
padding, no per-phase dispatch: a 10-token admission rides the same program
as its 8k-token neighbor's chunk and the whole batch's decode step.

Packing contract (the scheduler's side of the deal):
- rows are grouped by sequence, and every sequence's rows start at a
  `QBLK`-aligned row (its tail rows up to the next boundary are padding) —
  so each fixed QBLK-row q block belongs to exactly ONE sequence and the
  grid can gather that block's K/V through one table row;
- `block_seq [T/QBLK]` maps each q block to its sequence (−1 = dead block);
- `qstart/qlen [S]` give each sequence's first row and row count;
- `kvlen [S]` is the attended KV length INCLUDING this tick's new tokens
  (write-then-attend, the `decode_step` convention: the row at position p
  attends to positions 0..p);
- `tables [S, MAXB]` are the per-sequence block-table rows.

A decode sequence is simply qlen=1 (7 padding rows); a prefill chunk spans
`ceil(chunk/QBLK)` blocks. Padding rows produce finite garbage (their whole
score row is masked; the 1e-30 floor keeps the division defined) and callers
ignore them.

Traffic stays O(valid tokens) through the same table-clamp trick as
`ragged_decode` (flash_attention.py): beyond-length kv blocks repeat the last
valid physical index and Mosaic skips the duplicate DMA. Blocks of the SAME
sequence share each fetched kv block across QBLK rows — the reason rows pack
to QBLK granularity instead of fully dense.

Tiers match the rest of ops/pallas:
- `ragged_paged_attention`: bf16/f32 pools [NB, KVH, BS, D];
- `ragged_paged_attention_q8`: int8 pools + [NB, KVH, 1, BS] scales;
- `ragged_attention_xla` / `ragged_attention_xla_q8`: pure-XLA twins — the
  CPU-tier forward path AND the parity reference for the kernels (they
  gather only the table-mapped blocks, never the whole pool);
- `*_sharded`: shard_map wrappers over the pool's KV-head axis
  (models/llama.paged_pool_spec), same scheme as paged_scatter.py;
- `ragged_scatter_append[_q8]`: flat-stream KV writes — the paged_scatter
  kernel driven by per-row (physical block, row) targets, one grid step and
  one native tile per token, O(tokens) traffic.

Loop-carried metadata (ISSUE 16): every metadata input — block_seq,
qstart/qlen/kvlen, tables — is an ordinary traced array, never a static
argument, so the fused multi-step ragged tick (models/llama.build_ragged_loop)
can carry re-derived metadata through `lax.while_loop` iterations WITHOUT
re-tracing this kernel: one trace serves iteration 0's mixed pack and every
shape-identical dispatch after it. The only static inputs are the shapes
themselves (T, pool dims, MAXB) and `sliding_window`; keep it that way —
promoting any metadata value to Python int would re-specialize the program
per tick and break the zero-recompile invariant the compile-count tripwire
enforces.

On CPU everything runs in interpreter mode (LOCALAI_FORCE_PALLAS=1 in
tests); real-TPU lowering is covered by tests/test_tpu_real.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.flash_attention import (
    NEG_INF,
    _interpret,
)
from localai_tpu.ops.pallas.paged_scatter import (
    scatter_rows,
    scatter_rows_q8,
)

QBLK = 8   # q rows per grid block; every sequence's rows start on a boundary


def _q_blocked(q, kvh):
    """[T, H, D] → [NQB, KVH, QBLK*G, D] (kv-head-major rows, token-major
    within a block: row r of a block is token r//G, q-head-in-group r%G)."""
    t, h, d = q.shape
    g = h // kvh
    qb = q.reshape(t // QBLK, QBLK, kvh, g, d)
    return qb.transpose(0, 2, 1, 3, 4).reshape(t // QBLK, kvh, QBLK * g, d)


def _q_unblocked(o, t, h, d, kvh):
    g = h // kvh
    o = o.reshape(t // QBLK, kvh, QBLK, g, d).transpose(0, 2, 1, 3, 4)
    return o.reshape(t, h, d)


def _row_mask(i, group, shape, klen, qs, ql, start, sliding_window):
    """[R, BS] attention mask for q block i: row validity + causality
    (kv_pos <= q_pos, where q_pos = kvlen - qlen + row's offset into the
    sequence) + the optional sliding window."""
    rr = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    grow = i * QBLK + rr // group
    q_pos = klen - ql + (grow - qs)
    mask = (grow >= qs) & (grow < qs + ql)
    mask &= (kv_pos <= q_pos) & (kv_pos < klen)
    if sliding_window is not None:
        mask &= kv_pos > q_pos - sliding_window
    return mask


def _ragged_kernel(bseq_ref, qs_ref, ql_ref, kl_ref, tab_ref,
                   q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   bs: int, num_kb: int, group: int, scale: float,
                   sliding_window: int | None):
    i = pl.program_id(0)
    kb = pl.program_id(2)
    s_raw = bseq_ref[i]
    s = jnp.maximum(s_raw, 0)
    klen, qs, ql = kl_ref[s], qs_ref[s], ql_ref[s]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * bs
    live = (s_raw >= 0) & (start < klen)
    if sliding_window is not None:
        # lowest q_pos any row of this block holds — blocks entirely below
        # its window are dead (the per-row mask stays exact)
        live &= (start + bs) > (klen - ql + i * QBLK - qs) - sliding_window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [R, D]
        k_blk = k_ref[0, 0].astype(jnp.float32)                # [BS, D]
        v_blk = v_ref[0, 0].astype(jnp.float32)
        sc = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        mask = _row_mask(i, group, sc.shape, klen, qs, ql, start,
                         sliding_window)
        # a physical block's rows past klen hold other tenants' (finite)
        # data, never undefined memory — masking to NEG_INF underflows their
        # p to exactly 0, so no v zeroing is needed (cf. _decode_kernel's
        # contiguous-case t_total guard)
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _meta_i32(block_seq, qstart, qlen, kvlen, tables):
    return (block_seq.astype(jnp.int32), qstart.astype(jnp.int32),
            qlen.astype(jnp.int32), kvlen.astype(jnp.int32),
            tables.astype(jnp.int32))


def _kv_map(bs):
    def kv_map(i, h, kb, bseq, qs, ql, kl, tab):
        s = jnp.maximum(bseq[i], 0)
        last = jnp.maximum(pl.cdiv(kl[s], bs) - 1, 0)
        return (tab[s, jnp.minimum(kb, last)], h, 0, 0)
    return kv_map


@functools.partial(jax.jit, static_argnames=("sliding_window",))
def ragged_paged_attention(q, k_pool, v_pool, block_seq, qstart, qlen,
                           kvlen, tables, sliding_window=None):
    """Flat-stream GQA attention over paged KV. q: [T, H, D] with T a
    multiple of QBLK; pools [NB, KVH, BS, D]; metadata per the module
    docstring. Returns [T, H, D] in q.dtype (padding rows garbage)."""
    t, h, d = q.shape
    if t % QBLK != 0:
        raise ValueError(
            f"ragged stream rows T={t} must be a multiple of QBLK={QBLK} "
            "(the engine's token budget is QBLK-aligned by construction)")
    kvh = k_pool.shape[1]
    bs = k_pool.shape[2]
    group = h // kvh
    num_kb = tables.shape[1]
    qg = _q_blocked(q, kvh)
    r = QBLK * group
    kernel = functools.partial(
        _ragged_kernel, bs=bs, num_kb=num_kb, group=group,
        scale=d ** -0.5, sliding_window=sliding_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(t // QBLK, kvh, num_kb),
            in_specs=[
                pl.BlockSpec((1, 1, r, d),
                             lambda i, h, kb, *s: (i, h, 0, 0)),
                pl.BlockSpec((1, 1, bs, d), _kv_map(bs)),
                pl.BlockSpec((1, 1, bs, d), _kv_map(bs)),
            ],
            out_specs=pl.BlockSpec((1, 1, r, d),
                                   lambda i, h, kb, *s: (i, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((r, 128), jnp.float32),   # m (lane-replicated)
                pltpu.VMEM((r, 128), jnp.float32),   # l
                pltpu.VMEM((r, d), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*_meta_i32(block_seq, qstart, qlen, kvlen, tables), qg,
      k_pool, v_pool)
    return _q_unblocked(out, t, h, d, kvh)


def _ragged_q8_kernel(bseq_ref, qs_ref, ql_ref, kl_ref, tab_ref,
                      q_ref, kq_ref, ks_ref, vq_ref, vs_ref,
                      o_ref, m_ref, l_ref, acc_ref, *,
                      bs: int, num_kb: int, group: int, scale: float,
                      sliding_window: int | None):
    i = pl.program_id(0)
    kb = pl.program_id(2)
    s_raw = bseq_ref[i]
    s = jnp.maximum(s_raw, 0)
    klen, qs, ql = kl_ref[s], qs_ref[s], ql_ref[s]

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = kb * bs
    live = (s_raw >= 0) & (start < klen)
    if sliding_window is not None:
        live &= (start + bs) > (klen - ql + i * QBLK - qs) - sliding_window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # [R, D]
        k_blk = kq_ref[0, 0].astype(jnp.float32)               # [BS, D]
        v_blk = vq_ref[0, 0].astype(jnp.float32)
        k_s = ks_ref[0, 0]                                     # [1, BS]
        v_s = vs_ref[0, 0]
        sc = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        sc = sc * k_s                                          # dequant K
        mask = _row_mask(i, group, sc.shape, klen, qs, ql, start,
                         sliding_window)
        sc = jnp.where(mask, sc, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new[:, :1])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p * v_s, v_blk, preferred_element_type=jnp.float32)  # dequant V
        m_ref[...] = m_new

    @pl.when(kb == num_kb - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sliding_window",))
def ragged_paged_attention_q8(q, k_q, k_s, v_q, v_s, block_seq, qstart,
                              qlen, kvlen, tables, sliding_window=None):
    """int8 twin: pools k_q/v_q [NB, KVH, BS, D] int8 with per-token scales
    k_s/v_s [NB, KVH, 1, BS] f32 (ops/paged.py layout, BS == 128)."""
    t, h, d = q.shape
    kvh = k_q.shape[1]
    bs = k_q.shape[2]
    if bs != 128:
        raise ValueError("paged int8 KV blocks must be 128 tokens")
    group = h // kvh
    num_kb = tables.shape[1]
    qg = _q_blocked(q, kvh)
    r = QBLK * group
    kernel = functools.partial(
        _ragged_q8_kernel, bs=bs, num_kb=num_kb, group=group,
        scale=d ** -0.5, sliding_window=sliding_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(t // QBLK, kvh, num_kb),
            in_specs=[
                pl.BlockSpec((1, 1, r, d),
                             lambda i, h, kb, *s: (i, h, 0, 0)),
                pl.BlockSpec((1, 1, bs, d), _kv_map(bs)),
                pl.BlockSpec((1, 1, 1, 128), _kv_map(bs)),
                pl.BlockSpec((1, 1, bs, d), _kv_map(bs)),
                pl.BlockSpec((1, 1, 1, 128), _kv_map(bs)),
            ],
            out_specs=pl.BlockSpec((1, 1, r, d),
                                   lambda i, h, kb, *s: (i, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((r, 128), jnp.float32),
                pltpu.VMEM((r, 128), jnp.float32),
                pltpu.VMEM((r, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(*_meta_i32(block_seq, qstart, qlen, kvlen, tables), qg,
      k_q, k_s.astype(jnp.float32), v_q, v_s.astype(jnp.float32))
    return _q_unblocked(out, t, h, d, kvh)


# ------------------------------------------------------------ XLA twins
# The pure-XLA formulation: gather each q block's table-mapped kv blocks
# (never the whole pool — [NQB, MAXB] indices, O(stream · context) output)
# and run one masked attention einsum. This is BOTH the non-Pallas serving
# tier (CPU, data-only meshes) and the parity reference the kernel tests
# compare against.

def _xla_core(q, kg, vg, block_seq, qstart, qlen, kvlen, sliding_window,
              scale, tier=None):
    """q: [T, H, D]; kg/vg: [NQB, KVH, C, D] f32 per-q-block gathered KV.

    tier (KV lifecycle, engine/kvtier.py): (pos [NQB, C], ok [NQB, C],
    sinks [NQB], window [NQB]) — the gathered view is ring-mapped, so kv row
    positions come from ops/paged.resident_row_positions instead of
    arange(C), and the retention mask (sink ∪ window) replaces the plain
    length mask. ok already folds residency + pos < kvlen."""
    t, h, d = q.shape
    nqb, kvh, c, _ = kg.shape
    g = h // kvh
    qb = q.reshape(nqb, QBLK, kvh, g, d).astype(jnp.float32) * scale
    sc = jnp.einsum("nqhgd,nhcd->nhqgc", qb, kg)
    s_b = jnp.maximum(block_seq, 0)
    klen = kvlen[s_b][:, None]                                 # [NQB, 1]
    qs, ql = qstart[s_b][:, None], qlen[s_b][:, None]
    grow = jnp.arange(t, dtype=jnp.int32).reshape(nqb, QBLK)
    q_pos = klen - ql + (grow - qs)                            # [NQB, QBLK]
    valid = (grow >= qs) & (grow < qs + ql) & (block_seq[:, None] >= 0)
    if tier is None:
        kv_pos = jnp.arange(c, dtype=jnp.int32)[None, None, :]
        mask = (valid[:, :, None] & (kv_pos <= q_pos[:, :, None])
                & (kv_pos < klen[:, :, None]))
        if sliding_window is not None:
            mask &= kv_pos > (q_pos[:, :, None] - sliding_window)
    else:
        pos, ok, sinks, window = tier
        kv_pos = pos[:, None, :]                               # [NQB, 1, C]
        mask = (valid[:, :, None] & ok[:, None, :]
                & (kv_pos <= q_pos[:, :, None]))
        mask &= ((kv_pos > q_pos[:, :, None] - window[:, None, None])
                 | (kv_pos < sinks[:, None, None]))
    sc = jnp.where(mask[:, None, :, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("nhqgc,nhcd->nqhgd", p, vg)
    return out.reshape(t, h, d).astype(q.dtype)


def _tier_blocks(block_seq, kvlen, tables, kvt):
    """Per-q-block tier metadata for _xla_core: true row positions +
    residency of the ring-mapped gathered view. kvt holds per-SEQUENCE
    [NSEQ] geometry arrays (engine ships them like tables)."""
    if kvt is None:
        return None
    from localai_tpu.ops.paged import resident_row_positions

    s_b = jnp.maximum(block_seq, 0).astype(jnp.int32)
    pos, ok = resident_row_positions(
        tables.shape[1], kvt["sb"].astype(jnp.int32)[s_b],
        kvt["rw"].astype(jnp.int32)[s_b], kvlen.astype(jnp.int32)[s_b])
    return (pos, ok, kvt["sinks"].astype(jnp.int32)[s_b],
            kvt["window"].astype(jnp.int32)[s_b])


def _gather_blocks(pool, block_seq, tables):
    """[NQB, KVH, MAXB*BS, D] per-q-block KV view through the table."""
    tab = tables[jnp.maximum(block_seq, 0)]                    # [NQB, MAXB]
    g = pool[tab]                                              # [NQB, MAXB, KVH, BS, D]
    nqb, maxb, kvh, bs, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(nqb, kvh, maxb * bs, d)


def ragged_attention_xla(q, k_pool, v_pool, block_seq, qstart, qlen, kvlen,
                         tables, sliding_window=None, kvt=None):
    kg = _gather_blocks(k_pool, block_seq, tables).astype(jnp.float32)
    vg = _gather_blocks(v_pool, block_seq, tables).astype(jnp.float32)
    return _xla_core(q, kg, vg, block_seq.astype(jnp.int32),
                     qstart.astype(jnp.int32), qlen.astype(jnp.int32),
                     kvlen.astype(jnp.int32), sliding_window,
                     q.shape[-1] ** -0.5,
                     tier=_tier_blocks(block_seq, kvlen, tables, kvt))


def _gather_scales(s_pool, block_seq, tables):
    """[NQB, KVH, MAXB*BS] dequant scales through the table
    (pool layout [NB, KVH, 1, BS])."""
    tab = tables[jnp.maximum(block_seq, 0)]
    g = s_pool[tab][:, :, :, 0, :]                             # [NQB, MAXB, KVH, BS]
    nqb, maxb, kvh, bs = g.shape
    return g.transpose(0, 2, 1, 3).reshape(nqb, kvh, maxb * bs)


def ragged_attention_xla_q8(q, k_q, k_s, v_q, v_s, block_seq, qstart, qlen,
                            kvlen, tables, sliding_window=None, kvt=None):
    kg = (_gather_blocks(k_q, block_seq, tables).astype(jnp.float32)
          * _gather_scales(k_s, block_seq, tables)[..., None])
    vg = (_gather_blocks(v_q, block_seq, tables).astype(jnp.float32)
          * _gather_scales(v_s, block_seq, tables)[..., None])
    return _xla_core(q, kg, vg, block_seq.astype(jnp.int32),
                     qstart.astype(jnp.int32), qlen.astype(jnp.int32),
                     kvlen.astype(jnp.int32), sliding_window,
                     q.shape[-1] ** -0.5,
                     tier=_tier_blocks(block_seq, kvlen, tables, kvt))


# -------------------------------------------------------- shard_map (TP)

def _head_axis(mesh):
    return "model" if "model" in mesh.axis_names else None


def ragged_paged_attention_sharded(mesh, q, k_pool, v_pool, block_seq,
                                   qstart, qlen, kvlen, tables,
                                   sliding_window=None):
    """TP wrapper: per-shard ragged kernel over the pool's KV-head axis
    (paged_pool_spec). q's head axis is kv-head-major, so an even KV-head
    split keeps whole GQA groups on one shard (the cfg.num_kv_heads % tp
    gate in models/llama). Metadata replicates; check_vma=False because the
    kernel body is opaque to the replication checker."""
    from jax.sharding import PartitionSpec as P

    ax = _head_axis(mesh)
    pool, qs_, rep = P(None, ax, None, None), P(None, ax, None), P()
    return _shard_map(
        lambda qq, kp, vp, bs_, q0, q1, kl, tb: ragged_paged_attention(
            qq, kp, vp, bs_, q0, q1, kl, tb,
            sliding_window=sliding_window),
        mesh=mesh,
        in_specs=(qs_, pool, pool, rep, rep, rep, rep, rep),
        out_specs=qs_, check_vma=False,
    )(q, k_pool, v_pool, block_seq, qstart, qlen, kvlen, tables)


def ragged_paged_attention_q8_sharded(mesh, q, k_q, k_s, v_q, v_s,
                                      block_seq, qstart, qlen, kvlen,
                                      tables, sliding_window=None):
    from jax.sharding import PartitionSpec as P

    ax = _head_axis(mesh)
    pool, qs_, rep = P(None, ax, None, None), P(None, ax, None), P()
    return _shard_map(
        lambda qq, a, b, c, d, bs_, q0, q1, kl, tb:
        ragged_paged_attention_q8(
            qq, a, b, c, d, bs_, q0, q1, kl, tb,
            sliding_window=sliding_window),
        mesh=mesh,
        in_specs=(qs_, pool, pool, pool, pool, rep, rep, rep, rep, rep),
        out_specs=qs_, check_vma=False,
    )(q, k_q, k_s, v_q, v_s, block_seq, qstart, qlen, kvlen, tables)


# ------------------------------------------------- flat-stream KV writes
# The scatter-append kernels from paged_scatter.py, driven by per-row
# (physical block, in-block row) targets derived from the per-sequence
# metadata (models/llama.ragged_forward). One grid step per row, each
# rewriting the row's whole native tile — 16 (bf16) or 32 (int8) rows for
# one: fine for a decode row, wasteful for a long prefill chunk (not
# measured). Padding rows target the trash block (physical 0).

def ragged_scatter_append(k_pool, v_pool, k_new, v_new, pb, off):
    """Write each flat row into its pool slot, in place. k_new/v_new:
    [T, KVH, D]; pb/off: [T] i32. Returns the aliased (k_pool, v_pool)."""
    return scatter_rows(k_pool, v_pool, k_new, v_new, pb, off)


def ragged_scatter_append_q8(kq, ks, vq, vs, k_new, v_new, pb, off):
    """int8 twin: quantize the flat rows and write int8 bodies + scale
    elements into the [NB, KVH, BS, D] / [NB, KVH, 1, BS] pools."""
    return scatter_rows_q8(kq, ks, vq, vs, k_new, v_new, pb, off)


def ragged_scatter_append_sharded(mesh, k_pool, v_pool, k_new, v_new,
                                  pb, off):
    from jax.sharding import PartitionSpec as P

    ax = _head_axis(mesh)
    pool, new, rep = P(None, ax, None, None), P(None, ax, None), P()
    return _shard_map(
        lambda kp, vp, kn, vn, p, o: ragged_scatter_append(
            kp, vp, kn, vn, p, o),
        mesh=mesh, in_specs=(pool, pool, new, new, rep, rep),
        out_specs=(pool, pool), check_vma=False,
    )(k_pool, v_pool, k_new, v_new, pb, off)


def ragged_scatter_append_q8_sharded(mesh, kq, ks, vq, vs, k_new, v_new,
                                     pb, off):
    from jax.sharding import PartitionSpec as P

    ax = _head_axis(mesh)
    pool = P(None, ax, None, None)
    new, rep = P(None, ax, None), P()
    return _shard_map(
        lambda a, b, c, d, kn, vn, p, o: ragged_scatter_append_q8(
            a, b, c, d, kn, vn, p, o),
        mesh=mesh, in_specs=(pool,) * 4 + (new, new, rep, rep),
        out_specs=(pool,) * 4, check_vma=False,
    )(kq, ks, vq, vs, k_new, v_new, pb, off)


def ragged_scatter_xla(k_pool, v_pool, k_new, v_new, pb, off):
    """XLA-tier flat-row scatter (the non-Pallas twin of
    ragged_scatter_append). Duplicate targets exist only among padding rows
    aimed at the trash block, whose content is dead — last-write-wins is
    fine there, so the scatter stays on the default (non-unique) path."""
    kvh = k_new.shape[1]
    hh = jnp.arange(kvh, dtype=jnp.int32)[None, :]
    k_pool = k_pool.at[pb[:, None], hh, off[:, None]].set(
        k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[pb[:, None], hh, off[:, None]].set(
        v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def ragged_scatter_xla_q8(kq, ks, vq, vs, k_new, v_new, pb, off):
    from localai_tpu.ops.kvcache import quantize_tokens

    kvh = k_new.shape[1]
    hh = jnp.arange(kvh, dtype=jnp.int32)[None, :]
    kq_n, ks_n = quantize_tokens(k_new)
    vq_n, vs_n = quantize_tokens(v_new)
    kq = kq.at[pb[:, None], hh, off[:, None]].set(kq_n.astype(kq.dtype))
    vq = vq.at[pb[:, None], hh, off[:, None]].set(vq_n.astype(vq.dtype))
    ks = ks.at[pb[:, None], hh, 0, off[:, None]].set(ks_n.astype(ks.dtype))
    vs = vs.at[pb[:, None], hh, 0, off[:, None]].set(vs_n.astype(vs.dtype))
    return kq, ks, vq, vs
