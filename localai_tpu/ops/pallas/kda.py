"""The gated delta rule's decode step as one Pallas kernel a layer
(ops/kda.py has the mathematics and the XLA twin `kda_step`).

`kda_decode` reads a live row's state once, applies decay, delta update and
readout, and writes it once, in place in the [L, B, H, Dk, Dv] float32 stack
the layer scan carries. Grid (row, block of HEADS_BLK heads). A row that is
not decoding moves nothing: its grid steps point at a block a live row
already holds (the next live row's first, or the last live row's last), so
no DMA is issued for it and the body is skipped.

Layout. A head's state tile is [Dk sublanes, Dv lanes]. The decay and the
rank-one update scale its ROWS, so alpha, k, alpha*k and alpha*q have to run
along sublanes: the wrapper lays them out as columns of one [Dk, 128] tile a
head block (lane j * HEADS_BLK + h holds vector j of head h; a few hundred
KB a call), and the kernel broadcasts a column along the lanes. With
u = S^T (alpha k), w = S^T (alpha q) (two sublane reductions of one pass
over S) the step is

    d = beta (v - u);   o = w + d (k . q);   S <- alpha S + k d^T
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.flash_attention import _interpret

HEADS_BLK = 16      # heads a grid step: 1 MiB of state in, 1 MiB out
LANES = 128


def _plan(active):
    """[4, B] i32 for the index maps: whether a row decodes; for one that
    does not, the (row, head block) of a live row's block to stay at (the
    next live row's first block, after the last live row its last block);
    and, in [3, 0], whether no row decodes at all."""
    nb = active.shape[0]
    rows = jnp.arange(nb, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(active, rows, nb), reverse=True)
    prv = jnp.maximum(jax.lax.cummax(jnp.where(active, rows, -1)), 0)
    ahead = nxt < nb
    none = jnp.full((nb,), ~jnp.any(active), jnp.int32)
    return jnp.stack([active.astype(jnp.int32), jnp.where(ahead, nxt, prv),
                      ahead.astype(jnp.int32), none])


def _kernel(lyr_ref, plan_ref, cols_ref, v_ref, beta_ref, kq_ref, s_ref,
            o_ref, s_out_ref, *, heads: int):
    b = pl.program_id(0)

    @pl.when(plan_ref[0, b] == 1)
    def _step():
        cols = cols_ref[0, 0]                              # [Dk, 128]
        for h in range(heads):
            s = s_ref[0, h]                                # [Dk, Dv]
            alpha, kc, ak, aq = (cols[:, j * heads + h:j * heads + h + 1]
                                 for j in range(4))       # [Dk, 1] each
            u = jnp.sum(ak * s, axis=0, keepdims=True)     # [1, Dv]
            w = jnp.sum(aq * s, axis=0, keepdims=True)
            d = beta_ref[0, pl.ds(h, 1), :] * (v_ref[0, pl.ds(h, 1), :] - u)
            o_ref[0, pl.ds(h, 1), :] = w + d * kq_ref[0, pl.ds(h, 1), :]
            s_out_ref[0, h] = alpha * s + kc * d

    @pl.when(plan_ref[3, 0] == 1)
    def _nobody():
        # no row decodes: every step sits at row 0's first block, which is
        # written back once: hand it through unchanged
        s_out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@jax.jit
def kda_decode(q, k, v, g, beta, state, layer, active):
    """q, k, g [B, H, Dk]; v [B, H, Dv]; beta [B, H]; state the stack
    [L, B, H, Dk, Dv] float32 (updated in place at `layer` for the rows of
    `active` [B] bool, the others untouched). Returns (o [B, H, Dv] float32,
    zeros for a row that is not decoding; state)."""
    f32 = jnp.float32
    nb, nh, dk = q.shape
    dv = v.shape[-1]
    hb = min(HEADS_BLK, nh)
    if nh % hb or 4 * hb > LANES or dv % LANES or dk % 8:
        raise ValueError(f"kda_decode: {nh} heads of {dk} x {dv} do not tile "
                         f"(blocks of {hb} heads, Dv a multiple of {LANES})")
    nblk = nh // hb
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    alpha = jnp.exp(g)
    # [B, 4, H, Dk] -> [B, nblk, Dk, 4 * hb], lane j * hb + h
    cols = jnp.stack([alpha, k, alpha * k, alpha * q], axis=1)
    cols = cols.reshape(nb, 4, nblk, hb, dk).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(nb, nblk, dk, 4 * hb)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, LANES - 4 * hb),))
    lanes = lambda a: jnp.broadcast_to(a[..., None], (nb, nh, LANES))  # noqa: E731
    prefetch = (jnp.asarray(layer, jnp.int32).reshape(1), _plan(active))

    def at(b, hi, lyr, plan):
        live = plan[0, b] == 1
        return (jnp.where(live, b, plan[1, b]),
                jnp.where(live, hi,
                          jnp.where(plan[2, b] == 1, 0, nblk - 1)))

    def row_map(b, hi, lyr, plan):
        return (*at(b, hi, lyr, plan), 0)

    def cols_map(b, hi, lyr, plan):
        return (*at(b, hi, lyr, plan), 0, 0)

    def state_map(b, hi, lyr, plan):
        return (lyr[0], *at(b, hi, lyr, plan), 0, 0)

    row = lambda w: pl.BlockSpec((1, hb, w), row_map)  # noqa: E731
    state_spec = pl.BlockSpec((None, 1, hb, dk, dv), state_map)
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb, nblk),
            in_specs=[pl.BlockSpec((1, 1, dk, LANES), cols_map), row(dv),
                      row(LANES), row(LANES), state_spec],
            out_specs=[row(dv), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((nb, nh, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two prefetched scalars: the state is the 7th
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="kda_decode",
    )(*prefetch, cols, v, lanes(beta), lanes(jnp.sum(k * q, axis=-1)), state)
    return jnp.where(active[:, None, None], o, 0.0), state
