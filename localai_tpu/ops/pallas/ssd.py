"""A Mamba-2 state-space layer's decode step as one Pallas kernel a layer
(ops/ssd.py has the mathematics and the XLA twin `ssd_step`).

`ssd_decode` reads a live row's state once, applies the head's decay and the
token's rank-one update, reads the output out of the new state, and writes
it once, in place in the [L, B, H, P, N] float32 stack the layer scan
carries. Grid (row, block of HEADS_BLK heads). A row that is not decoding
moves nothing: its grid steps point at a block a live row already holds
(ops/pallas/kda.py `_plan`, the same plan), so no DMA is issued for it and
the body is skipped.

Layout. A head's state tile is [P sublanes, N lanes]. The update dt x B^T
scales its ROWS by dt x, so dt x runs along sublanes: the wrapper lays the
block's heads out as the columns of one [P, HEADS_BLK] tile, and the kernel
broadcasts a column along the lanes. The decay (a scalar a head), B and C
(the head's group's) run along lanes, one [1, N] row a head. The output
y[p] = sum_n S[p, n] C[n] is a lane reduction, a column a head: the block's
columns leave as one [P, HEADS_BLK] tile and the wrapper turns them back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.flash_attention import _interpret
from localai_tpu.ops.pallas.kda import _plan

HEADS_BLK = 32      # heads a grid step: 1 MiB of state in, 1 MiB out


def _kernel(lyr_ref, plan_ref, dx_ref, decay_ref, b_ref, c_ref, s_ref,
            o_ref, s_out_ref, *, heads: int):
    b = pl.program_id(0)

    @pl.when(plan_ref[0, b] == 1)
    def _step():
        dx = dx_ref[0, 0]                                  # [P, heads]
        for h in range(heads):
            s = (decay_ref[0, pl.ds(h, 1), :] * s_ref[0, h]
                 + dx[:, h:h + 1] * b_ref[0, pl.ds(h, 1), :])   # [P, N]
            s_out_ref[0, h] = s
            o_ref[0, 0, :, pl.ds(h, 1)] = jnp.sum(
                s * c_ref[0, pl.ds(h, 1), :], axis=1, keepdims=True)

    @pl.when(plan_ref[3, 0] == 1)
    def _nobody():
        # no row decodes: every step sits at row 0's first block, which is
        # written back once: hand it through unchanged
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@jax.jit
def ssd_decode(x, dt, a, bm, cm, state, layer, active):
    """x [B, H, P]; dt [B, H] (after softplus); a [H] (< 0); bm, cm
    [B, G, N]; state the stack [L, B, H, P, N] float32 (updated in place at
    `layer` for the rows of `active` [B] bool, the others untouched).
    Returns (y [B, H, P] float32 without the D x term, zeros for a row that
    is not decoding; state)."""
    f32 = jnp.float32
    nb, nh, p = x.shape
    n = bm.shape[-1]
    hb = min(HEADS_BLK, nh)
    if nh % hb or n % 128 or p % 8 or nh % bm.shape[-2]:
        raise ValueError(f"ssd_decode: {nh} heads of {p} x {n} do not tile "
                         f"(blocks of {hb} heads, N a multiple of 128)")
    nblk = nh // hb
    x, dt, bm, cm = (v.astype(f32) for v in (x, dt, bm, cm))
    # [B, H, P] -> [B, nblk, P, hb]: a head's dt x a column
    dx = (dt[..., None] * x).reshape(nb, nblk, hb, p).transpose(0, 1, 3, 2)
    decay = jnp.broadcast_to(jnp.exp(dt * a.astype(f32))[..., None],
                             (nb, nh, n))
    per = nh // bm.shape[-2]
    bh, ch = (jnp.repeat(v, per, axis=1) for v in (bm, cm))   # [B, H, N]
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1), _plan(active))

    def at(b, hi, lyr, plan):
        live = plan[0, b] == 1
        return (jnp.where(live, b, plan[1, b]),
                jnp.where(live, hi,
                          jnp.where(plan[2, b] == 1, 0, nblk - 1)))

    def row_map(b, hi, lyr, plan):
        return (*at(b, hi, lyr, plan), 0)

    def col_map(b, hi, lyr, plan):
        return (*at(b, hi, lyr, plan), 0, 0)

    def state_map(b, hi, lyr, plan):
        return (lyr[0], *at(b, hi, lyr, plan), 0, 0)

    row = pl.BlockSpec((1, hb, n), row_map)
    col = pl.BlockSpec((1, 1, p, hb), col_map)
    state_spec = pl.BlockSpec((None, 1, hb, p, n), state_map)
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, nblk),
            in_specs=[col, row, row, row, state_spec],
            out_specs=[col, state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((nb, nblk, p, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="ssd_decode",
    )(*prefetch, dx, decay, bh, ch, state)
    y = o.transpose(0, 1, 3, 2).reshape(nb, nh, p)
    return jnp.where(active[:, None, None], y, 0.0), state
