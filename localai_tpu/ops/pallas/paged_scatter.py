"""Pallas scatter-append — the paged-KV decode write path.

The XLA formulation of the per-step cache write (`models/kv.PagedKV._scatter`
with a `table`) scatters through GATHERED physical indices
(`pool.at[table[b, pos // BS], :, pos % BS].set(row)`). Inside the fused
multi-step decode block the scatter rides the layer scan's donated carry, and
whenever XLA cannot keep it on the in-place path (index uniqueness is only
host-knowledge; the compiler sees arbitrary computed indices) it falls back
to copying the ENTIRE block pool per layer per step — the paged-vs-dense
regression VERDICT.md Weak #2 measured at 8x on chip (CPU repro 42 ms →
6.6 s).

This kernel removes the question from the compiler entirely: the physical
destination of each slot's new token — block `table[b, len // BS]`, row
`len % BS` — is computed at trace time and shipped as scalar-prefetch
operands; grid step b maps the one native tile of the pool that holds that
row (block specs indexed through the prefetched targets), replaces the row in
VMEM and lets the pipeline write the tile back. The pool is aliased in place
via `input_output_aliases` (the Pallas analog of donation), so traffic is
O(slots) tiles, not O(pool); nothing else in the pool is touched.

Why a tile and not the row: HBM keeps sub-32-bit dtypes packed, 2 bf16 or 4
int8 rows to a sublane, so a one-row HBM→HBM DMA at a dynamic row offset is
not addressable — Mosaic on the v5e refuses it ("Slice shape along dimension
2 must be aligned to tiling (2), but is 1"; chip run, PR 21), as it refuses a
one-element scale DMA at a dynamic lane offset. The smallest unit that can
move is the native tile: 8 sublanes × 32 bits, i.e. 8 f32 / 16 bf16 / 32
int8 rows.

Inactive slots (admission racing a decode dispatch) redirect to the TRASH
block (physical 0, ops/paged.py) at a distinct per-slot row, mirroring the
XLA path's redirect semantics.

Two variants, matching the ragged decode kernels:
- `paged_scatter_append`: bf16/f32 pools [NB, KVH, BS, D].
- `paged_scatter_append_q8`: int8 pools + per-token scales
  [NB, KVH, 1, BS] (ops/kvcache layout with BS == SCALE_TILE); the new row
  is quantized in the wrapper (plain XLA — one token) and the kernel places
  the int8 row in its tile and the scale element in the block's scale row.

On CPU both run in interpreter mode (tests force LOCALAI_FORCE_PALLAS=1);
real-TPU lowering is covered by tests/test_tpu_real.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.flash_attention import _interpret


def _targets(positions, table, active, sb=None, rw=None):
    """(physical block [B], in-block row [B]) for each slot's new token.

    Computed at trace time from the scalar-prefetched table — the kernel
    never sees an index it could fail to prove unique. Inactive rows route
    to the trash block at row `b % BS` (distinct while B <= BS, the same
    bound the XLA redirect asserts — models/kv.PagedKV.write).

    sb/rw ([B] i32, optional): KV-lifecycle ring geometry
    (ops/paged.ring_block_map) — windowed slots' raw block indices fold into
    their O(window) ring columns before the table lookup, so the DMA kernel
    itself needs no ring knowledge. Full-policy slots ship the identity
    sentinel (sb >= table width)."""
    b = positions.shape[0]
    block = jnp.int32(_POOL_BS)
    raw = positions // block
    if sb is not None:
        from localai_tpu.ops.paged import ring_block_map

        raw = ring_block_map(raw, sb, rw)
    pb = table[jnp.arange(b), raw]
    off = positions % block
    if active is not None:
        pb = jnp.where(active, pb, 0)
        off = jnp.where(active, off, jnp.arange(b, dtype=jnp.int32) % block)
    return pb.astype(jnp.int32), off.astype(jnp.int32)


_POOL_BS = 128  # == ops.paged.BLOCK == kvcache.SCALE_TILE


def _tile_rows(dtype) -> int:
    """Rows in one native tile of `dtype` (8 sublanes x rows packed per
    32-bit sublane): f32 8, bf16 16, int8 32 — the row granularity a pool
    block can be cut at."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _fresh(pb_ref, off_ref, rows):
    """(this step's in-block row, whether its tile is a first visit, whether
    its block is). Consecutive rows landing in the same tile — a prefill chunk's
    run, dead rows in the trash block — keep the output block resident:
    Pallas neither writes it back nor refetches the input between them, so
    only the first visit may copy the input tile in.

    A tile revisited NON-consecutively would read stale data (its prefetch
    can overtake the earlier write-back). Callers never do that with live
    rows: each slot/sequence owns its blocks and writes them in position
    order; only dead rows aimed at the trash block repeat."""
    i = pl.program_id(0)
    pb, off = pb_ref[i], off_ref[i]
    prev = jnp.maximum(i - 1, 0)
    new_block = (i == 0) | (pb_ref[prev] != pb)
    new_tile = new_block | (off_ref[prev] // rows != off // rows)
    return off, new_tile, new_block


def _put_row(tile_ref, new_ref, r):
    """tile_ref block [1, KVH, rows, D] <- new_ref block [1, KVH, 1, D] at
    in-tile row r, as a select: no dynamic-offset store on a packed dtype."""
    tile = tile_ref[0]
    ridx = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    tile_ref[0] = jnp.where(ridx == r,
                            jnp.broadcast_to(new_ref[0], tile.shape), tile)


def _append_kernel(pb_ref, off_ref, knew_ref, vnew_ref, kin_ref, vin_ref,
                   kout_ref, vout_ref, *, rows: int):
    off, new_tile, _ = _fresh(pb_ref, off_ref, rows)

    @pl.when(new_tile)
    def _load():
        kout_ref[...] = kin_ref[...]
        vout_ref[...] = vin_ref[...]

    _put_row(kout_ref, knew_ref, off % rows)
    _put_row(vout_ref, vnew_ref, off % rows)


def scatter_rows(k_pool, v_pool, k_new, v_new, pb, off):
    """Write row t of k_new/v_new [T, KVH, D] into pool block pb[t], row
    off[t], in place. Returns the aliased (k_pool, v_pool)."""
    t, kvh, d = k_new.shape
    rows = _tile_rows(k_pool.dtype)
    kn = k_new.reshape(t, kvh, 1, d).astype(k_pool.dtype)
    vn = v_new.reshape(t, kvh, 1, d).astype(v_pool.dtype)
    new = pl.BlockSpec((1, kvh, 1, d), lambda i, pb, off: (i, 0, 0, 0))
    tile = pl.BlockSpec((1, kvh, rows, d),
                        lambda i, pb, off: (pb[i], 0, off[i] // rows, 0))
    return pl.pallas_call(
        functools.partial(_append_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t,),
            in_specs=[new, new, tile, tile],
            out_specs=[tile, tile],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # flat operand indices include the 2 scalar-prefetch args:
        # (pb, off, kn, vn, k_pool, v_pool) -> pools at 4 and 5
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(pb.astype(jnp.int32), off.astype(jnp.int32), kn, vn, k_pool, v_pool)


def paged_scatter_append(k_pool, v_pool, k_new, v_new, positions, table,
                         active=None, sb=None, rw=None):
    """Append one K/V token per slot into the paged pools, in place.

    k_pool/v_pool: [NB, KVH, BS, D]; k_new/v_new: [B, KVH, D] (this step's
    rope-applied K and raw V rows); positions: [B] write position (= the
    slot's current length); table: [B, MAXB] i32; active: [B] bool or None;
    sb/rw: [B] i32 or None — KV-lifecycle ring geometry (see _targets).
    Returns the updated (k_pool, v_pool) — aliased, not copies.
    """
    pb, off = _targets(positions, table, active, sb=sb, rw=rw)
    return scatter_rows(k_pool, v_pool, k_new, v_new, pb, off)


def _head_axis(mesh):
    """Mesh axis the pool's KV-head dim shards on (None on a data-only
    mesh — every shard then holds the full head set)."""
    return "model" if "model" in mesh.axis_names else None


def paged_scatter_append_sharded(mesh, k_pool, v_pool, k_new, v_new,
                                 positions, table, active=None,
                                 sb=None, rw=None):
    """TP wrapper: run the scatter-append kernel per-shard via shard_map
    over the pool's KV-head axis (models/llama.py paged_pool_spec).

    pallas_call has no GSPMD partitioning rule, so calling the kernel
    directly under a mesh would make the partitioner all-gather the whole
    pool — exactly the traffic the kernel exists to avoid. Inside shard_map
    each model-shard DMAs its local [KVH/tp, 1, D] rows; positions/table/
    active are replicated scalars-per-slot, so every shard computes the same
    block targets. check_vma=False: the kernel body is opaque to the
    replication checker."""
    from jax.sharding import PartitionSpec as P

    ax = _head_axis(mesh)
    pool, new, rep = P(None, ax, None, None), P(None, ax, None), P()
    # ring-map the write targets OUTSIDE shard_map (positions/table are
    # replicated anyway) so the inner body stays one shape for every
    # active/tier combination
    if sb is not None:
        from localai_tpu.ops.paged import ring_block_map

        b = positions.shape[0]
        raw = ring_block_map(positions // _POOL_BS, sb, rw)
        table = table[jnp.arange(b), raw][:, None]       # [B, 1] direct map
        positions = positions % _POOL_BS
    if active is None:
        return _shard_map(
            lambda kp, vp, kn, vn, pos, tab: paged_scatter_append(
                kp, vp, kn, vn, pos, tab),
            mesh=mesh, in_specs=(pool, pool, new, new, rep, rep),
            out_specs=(pool, pool), check_vma=False,
        )(k_pool, v_pool, k_new, v_new, positions, table)
    return _shard_map(
        lambda kp, vp, kn, vn, pos, tab, act: paged_scatter_append(
            kp, vp, kn, vn, pos, tab, act),
        mesh=mesh, in_specs=(pool, pool, new, new, rep, rep, rep),
        out_specs=(pool, pool), check_vma=False,
    )(k_pool, v_pool, k_new, v_new, positions, table, active)


def paged_scatter_append_q8_sharded(mesh, kq, ks, vq, vs, k_new, v_new,
                                    positions, table, active=None,
                                    sb=None, rw=None):
    """int8 twin of paged_scatter_append_sharded: the scale pools
    [NB, KVH, 1, BS] shard their KV-head axis alongside the int8 bodies."""
    from jax.sharding import PartitionSpec as P

    ax = _head_axis(mesh)
    pool = P(None, ax, None, None)
    new, rep = P(None, ax, None), P()
    if sb is not None:
        from localai_tpu.ops.paged import ring_block_map

        b = positions.shape[0]
        raw = ring_block_map(positions // _POOL_BS, sb, rw)
        table = table[jnp.arange(b), raw][:, None]       # [B, 1] direct map
        positions = positions % _POOL_BS
    specs4 = (pool, pool, pool, pool, new, new, rep, rep)
    if active is None:
        return _shard_map(
            lambda a, b, c, d, kn, vn, pos, tab: paged_scatter_append_q8(
                a, b, c, d, kn, vn, pos, tab),
            mesh=mesh, in_specs=specs4, out_specs=(pool,) * 4,
            check_vma=False,
        )(kq, ks, vq, vs, k_new, v_new, positions, table)
    return _shard_map(
        lambda a, b, c, d, kn, vn, pos, tab, act: paged_scatter_append_q8(
            a, b, c, d, kn, vn, pos, tab, act),
        mesh=mesh, in_specs=specs4 + (rep,), out_specs=(pool,) * 4,
        check_vma=False,
    )(kq, ks, vq, vs, k_new, v_new, positions, table, active)


def _put_scale(row_ref, new_ref, off):
    """row_ref block [1, KVH, 1, BS] <- new_ref block [1, KVH, 1, 1] at
    lane `off`."""
    row = row_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 2)
    row_ref[0] = jnp.where(lane == off, new_ref[0], row)


def _append_q8_kernel(pb_ref, off_ref, kq_new_ref, ks_new_ref, vq_new_ref,
                      vs_new_ref, kq_in, ks_in, vq_in, vs_in,
                      kq_ref, ks_ref, vq_ref, vs_ref, *, rows: int):
    off, new_tile, new_block = _fresh(pb_ref, off_ref, rows)

    @pl.when(new_tile)
    def _load_tiles():
        kq_ref[...] = kq_in[...]
        vq_ref[...] = vq_in[...]

    @pl.when(new_block)          # the scale row spans the whole block
    def _load_scales():
        ks_ref[...] = ks_in[...]
        vs_ref[...] = vs_in[...]

    _put_row(kq_ref, kq_new_ref, off % rows)
    _put_row(vq_ref, vq_new_ref, off % rows)
    _put_scale(ks_ref, ks_new_ref, off)
    _put_scale(vs_ref, vs_new_ref, off)


def scatter_rows_q8(kq, ks, vq, vs, k_new, v_new, pb, off):
    """int8 twin of scatter_rows: quantize k_new/v_new [T, KVH, D] per
    token (plain XLA) and write int8 rows + scale elements into the
    [NB, KVH, BS, D] / [NB, KVH, 1, BS] pools, in place."""
    from localai_tpu.ops.kvcache import quantize_tokens

    t, kvh, d = k_new.shape
    rows = _tile_rows(kq.dtype)
    kq_n, ks_n = quantize_tokens(k_new)          # [T, KVH, D], [T, KVH]
    vq_n, vs_n = quantize_tokens(v_new)
    kq_n = kq_n.reshape(t, kvh, 1, d)
    vq_n = vq_n.reshape(t, kvh, 1, d)
    ks_n = ks_n.reshape(t, kvh, 1, 1).astype(ks.dtype)
    vs_n = vs_n.reshape(t, kvh, 1, 1).astype(vs.dtype)
    new_q = pl.BlockSpec((1, kvh, 1, d), lambda i, pb, off: (i, 0, 0, 0))
    new_s = pl.BlockSpec((1, kvh, 1, 1), lambda i, pb, off: (i, 0, 0, 0))
    tile = pl.BlockSpec((1, kvh, rows, d),
                        lambda i, pb, off: (pb[i], 0, off[i] // rows, 0))
    srow = pl.BlockSpec((1, kvh, 1, _POOL_BS),
                        lambda i, pb, off: (pb[i], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_append_q8_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t,),
            in_specs=[new_q, new_s, new_q, new_s, tile, srow, tile, srow],
            out_specs=[tile, srow, tile, srow],
        ),
        out_shape=[jax.ShapeDtypeStruct(kq.shape, kq.dtype),
                   jax.ShapeDtypeStruct(ks.shape, ks.dtype),
                   jax.ShapeDtypeStruct(vq.shape, vq.dtype),
                   jax.ShapeDtypeStruct(vs.shape, vs.dtype)],
        # (pb, off, kq_n, ks_n, vq_n, vs_n, kq, ks, vq, vs)
        input_output_aliases={6: 0, 7: 1, 8: 2, 9: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(pb.astype(jnp.int32), off.astype(jnp.int32), kq_n, ks_n, vq_n, vs_n,
      kq, ks, vq, vs)


def paged_scatter_append_q8(kq, ks, vq, vs, k_new, v_new, positions, table,
                            active=None, sb=None, rw=None):
    """int8 variant: pools kq/vq [NB, KVH, BS, D] int8 with scales ks/vs
    [NB, KVH, 1, BS] f32 (one aligned scale row per block — ops/paged.py).
    k_new/v_new arrive dense [B, KVH, D]; quantization happens in
    scatter_rows_q8 (one token per slot — negligible next to the attention
    it feeds)."""
    pb, off = _targets(positions, table, active, sb=sb, rw=rw)
    return scatter_rows_q8(kq, ks, vq, vs, k_new, v_new, pb, off)
