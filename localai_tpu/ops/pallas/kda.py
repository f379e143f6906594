"""The gated delta rule as Pallas kernels, one call a layer: a decode step
(`kda_decode`) and a prompt chunk (`kda_chunk`). ops/kda.py has the
mathematics and the XLA twins `kda_step` and `kda_chunk`.

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

`kda_chunk` is ops/kda.py:kda_chunk's chunkwise form with nothing of a
sub-chunk but q, k, g, v, beta read from memory and nothing but o written:
a grid step holds ONE row's whole chunk of a pair of heads (a head's [S, D]
is a column block of [B, S, H D], the layout the projections' products and
the convolution leave and the output's gate takes: no copy on the way in)
and the heads' states as VALUES (in: what StateKV._resume returns;
out: what StateKV._put stores; nothing aliased), walks the sub-chunks of
SUB tokens in a loop with the state as its carry, and keeps each
sub-chunk's [SUB, SUB] matrices and its transform in VMEM. Every product is
float32 (HIGHEST), the exponents are taken about the sub-chunk's middle as
the twin takes them. `unit_qk`: q and k arrive as the convolution's SiLU
left them and are made unit vectors a head here (q scaled D^-1/2), as
StateKV._qkv does for the twin: a reduction a head in XLA would cost each a
copy into another tiling and back.

The transform (I + L)^-1, L = diag(beta) stril(A), is solved exactly by
BLOCK DOUBLING: with X_h the inverse of the diagonal blocks of size h
(X_1 = I) and L_h the part of L that joins each odd block of size h to the
even one before it,

    X_2h = X_h - (X_h L_h) X_h

(a unit lower [[P, 0], [M, Q]] has the inverse [[P^-1, 0], [-Q^-1 M P^-1,
Q^-1]]): ten small products for SUB = 64, every intermediate an entry of
the true inverse; from blocks of 8 rows on, only the odd halves' rows are
multiplied. The nilpotent product (I - L)(I + L^2)... costs the same ten
and loses everything where keys repeat (L's powers reach 2^32 C(63, 31)
before they cancel), so it is not used. Then u = X beta (v - (k e^G) S_0):
W = X beta k e^G is never formed.

These products are small ([64, 64]: a quarter of the matrix unit) and at
float32 six passes each, so the kernel is bound by how many of them it
issues, not by memory: it walks the heads in PAIRS and holds a pair's
[SUB, SUB] matrices side by side in one [SUB, 2 SUB] array. As the streamed
operand that is one array for two heads; as the held operand it is laid out
block diagonal ([2 SUB, 2 SUB]), so one product serves both heads (on the
chip, one layer's [1, 512, 64, 128]: 1.17 ms a head at a time, 0.83 in
pairs; PERF.md section 6, PR 51).

The state is carried TRANSPOSED ([Dv, Dk], a transpose a head on the way in
and out): a sub-chunk's decay then scales its lanes, and the products with
it are q k^T-shaped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.kda import SUB
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


# ------------------------------------------------------------ prompt chunk

_HI = jax.lax.Precision.HIGHEST
# heads a grid step: ONE pair. 4, 8 and 16 time the same on the chip (and so
# does the sub-chunk loop unrolled), and each further pair is the loop's
# body traced, lowered and compiled once more at every start, for every
# shape (PERF.md section 6, PR 51)
CHUNK_HEADS = 2


def _below(row, col):
    """Whether A's entry (row, col) enters the transform: the STRICT lower
    triangle (a token's own key does not correct its own value)."""
    return row > col


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _levels():
    """The doubling's levels above the first as (half a block's rows, the
    rows of the odd halves): those rows are the ones a level changes."""
    out = []
    for lg in range(1, SUB.bit_length() - 1):
        h = 1 << lg
        out.append((h, [slice(r, r + h) for r in range(h, SUB, 2 * h)]))
    return out


def _odd(a, h, rows):
    """Of a [SUB, n] array the rows of the odd halves, where they are whole
    sublane tiles (h >= 8); else all of them (a level then computes the
    even halves' zeros too)."""
    return a if h < 8 else jnp.concatenate([a[r] for r in rows], axis=0)


def _chunk_kernel(n_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref, o_ref,
                  s_out_ref, *, heads: int, dk: int, dv: int, steps: int,
                  unit_qk: bool):
    f32 = jnp.float32
    n = n_ref[pl.program_id(0)]
    # a PAIR of heads' [SUB, SUB] matrices side by side, [SUB, 2 SUB]: the
    # left head's in the lanes under SUB
    ri = jax.lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, 2 * SUB), 1)
    ci, left = lane & (SUB - 1), lane < SUB
    strict, causal, eye = _below(ri, ci), ri >= ci, ri == ci
    # of the strict triangle `low`: what lies inside a pair of rows (level
    # 0); at a level of half-blocks of h = 2^lg rows, what lies in the same
    # block of 2 h and not in the same half: an odd half's rows against the
    # even half's columns. (Shifts, not // and %: a floor division is ten
    # operations to trace, lower and run.)
    pairs = (ri >> 1) == (ci >> 1)
    joins = []
    for h, _ in _levels():
        lg, r, c = h.bit_length() - 1, ri, ci
        if h >= 8:      # the odd halves' rows alone:
            # p -> 2 h (p // h) + h + p % h
            p = jax.lax.broadcasted_iota(jnp.int32, (SUB // 2, 2 * SUB), 0)
            r = ((p >> lg) << lg + 1) + h + (p & (h - 1))
            c = jax.lax.broadcasted_iota(jnp.int32, (SUB // 2, 2 * SUB),
                                         1) & (SUB - 1)
        joins.append((r >> lg + 1 == c >> lg + 1) & (r >> lg != c >> lg))
    row = jax.lax.broadcasted_iota(jnp.int32, (SUB, 1), 0)

    def side(m, of_left):
        """One head's half of a side-by-side pair, the other's lanes 0."""
        return jnp.where(left == of_left, m, 0.0)

    def diagonal(m):
        """[SUB, 2 SUB] side by side -> [2 SUB, 2 SUB] block diagonal."""
        return jnp.concatenate([side(m, True), side(m, False)], axis=0)

    def rows_of(h, i):
        """A head's sub-chunk: q, k, v, beta, the running log-decay."""
        at = pl.ds(pl.multiple_of(i * SUB, SUB), SUB)
        kcols = slice(h * dk, (h + 1) * dk)
        live = i * SUB + row < n
        q, k = q_ref[at, kcols], k_ref[at, kcols]
        if unit_qk:     # kv.StateKV._qkv's `unit`
            q, k = (a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
                for a in (q, k))
            q = q * dk ** -0.5
        beta = jnp.where(live, beta_ref[at, h:h + 1], 0.0)     # [SUB, 1]
        gc = jnp.where(live, g_ref[at, kcols], 0.0)
        shift = 1
        while shift < SUB:      # the running sum down the rows, by doubling
            gc = gc + jnp.where(row >= shift, pltpu.roll(gc, shift, 0), 0.0)
            shift *= 2
        return q, k, v_ref[at, h * dv:(h + 1) * dv], beta, gc

    def sub_chunk(h0, i, sts):
        two = [rows_of(h0 + m, i) for m in range(2)]
        mids = [gc[SUB // 2:SUB // 2 + 1] for *_, gc in two]
        # both heads' A = (k e^G)(k e^-G)^T and B = (q e^G)(k e^-G)^T in ONE
        # product; a head's own columns are then picked by lane (the rest,
        # one head's rows against the other's keys, is dropped)
        ab = _dot(
            jnp.concatenate([a * jnp.exp(gc - mid) for (q, k, _, _, gc), mid
                             in zip(two, mids) for a in (k, q)], axis=0),
            jnp.concatenate([k * jnp.exp(mid - gc) for (_, k, _, _, gc), mid
                             in zip(two, mids)], axis=0),
            ((1,), (1,)))                                   # [4 SUB, 2 SUB]
        beta2 = jnp.where(left, two[0][3], two[1][3])
        low = jnp.where(strict, beta2 * jnp.where(
            left, ab[:SUB], ab[2 * SUB:3 * SUB]), 0.0)
        b_mat = jnp.where(causal, jnp.where(
            left, ab[SUB:2 * SUB], ab[3 * SUB:]), 0.0)
        # x = (I + low)^-1 of both heads by block doubling (the module's
        # docstring): the odd halves' rows of a level are x22 (L21 x11) less.
        # Side by side as the streamed operand, block diagonal as the held
        # one: a product then serves both heads
        low_d = diagonal(low)
        x = jnp.where(eye, 1.0, 0.0) - jnp.where(pairs, low, 0.0)   # X_2
        for (half, rows), join in zip(_levels(), joins):
            less = _dot(jnp.where(join, _dot(_odd(x, half, rows), low_d),
                                  0.0), diagonal(x))
            if half < 8:
                x = x - less
            else:
                x = jnp.concatenate([
                    part for m, r in enumerate(rows) for part in (
                        x[r.start - half:r.start],
                        x[r] - less[m * half:(m + 1) * half])], axis=0)
        # u = x beta (v - (k e^G) S_0): W = x beta k e^G is never formed
        from_state = [
            _dot(jnp.concatenate([k * jnp.exp(gc), q * jnp.exp(gc)], axis=0),
                 st, ((1,), (1,)))                          # [2 SUB, Dv]
            for (q, k, _, _, gc), st in zip(two, sts)]
        rhs = jnp.concatenate([beta * (v - fs[:SUB]) for (_, _, v, beta, _),
                               fs in zip(two, from_state)], axis=0)
        us = [_dot(side(x, m == 0), rhs) for m in range(2)]
        both = jnp.concatenate(us, axis=0)
        at = pl.ds(pl.multiple_of(i * SUB, SUB), SUB)
        out = []
        for m, ((_, k, _, _, gc), st) in enumerate(zip(two, sts)):
            h = h0 + m
            o_ref[at, h * dv:(h + 1) * dv] = (
                from_state[m][SUB:] + _dot(side(b_mat, m == 0), both))
            last = gc[SUB - 1:]
            out.append(st * jnp.exp(last)
                       + _dot(us[m], k * jnp.exp(last - gc), ((0,), (0,))))
        return tuple(out)

    for h0 in range(0, heads, 2):
        sts = jax.lax.fori_loop(
            0, steps, functools.partial(sub_chunk, h0),
            tuple(s_ref[h0 + m].T.astype(f32) for m in range(2)))
        for m, st in enumerate(sts):
            s_out_ref[h0 + m] = st.T


def kda_chunk_vmem_bytes(tokens: int, heads: int, dk: int, dv: int) -> int:
    """What kda_chunk asks of VMEM: its blocks twice (the pipeline's two
    buffers; beta's lanes padded to a tile), and a sub-chunk's arrays of
    the pair of heads in hand (some thirty [SUB, 128]-sized float32 ones a
    head, the products' bfloat16 parts among them, and the two states),
    with half as much again to spare."""
    blocks = 4 * (tokens * heads * (3 * dk + 2 * dv) + tokens * LANES
                  + 2 * heads * dk * dv)
    body = 4 * 2 * (30 * SUB * max(dk, dv) + 2 * dk * dv)
    return int(1.5 * (2 * blocks + body))


@functools.partial(jax.jit, static_argnames=("unit_qk",))
def kda_chunk(q, k, v, g, beta, state, n_valid=None, *, unit_qk: bool = False):
    """ops/kda.py:kda_chunk in one kernel: q, k, g [B, S, H, Dk]; v
    [B, S, H, Dv]; beta [B, S, H]; state [B, H, Dk, Dv] float32, a value in
    and a value out; n_valid [B]: tokens from there on are padding and leave
    the state as it was (their outputs mean nothing). unit_qk: q and k are
    normalised a head here (q then scaled Dk^-1/2), not by the caller.
    Returns (o [B, S, H, Dv] float32, state)."""
    f32 = jnp.float32
    b, s, nh, dk = q.shape
    dv = v.shape[-1]
    hb = CHUNK_HEADS if nh % CHUNK_HEADS == 0 else nh
    if nh % hb or hb % 2 or dk % LANES or dv % LANES:
        raise ValueError(f"kda_chunk: {nh} heads of {dk} x {dv} do not tile "
                         f"(blocks of {hb} heads, pairs of heads, Dk and Dv "
                         f"multiples of {LANES})")
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    n = (jnp.full((b,), s, jnp.int32) if n_valid is None
         else jnp.minimum(n_valid.astype(jnp.int32), s))
    pad = -s % SUB
    if pad:     # past n: no decay, no write
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    # a head block's betas as columns of one small tile: [B, H / hb, S, hb]
    beta = beta.reshape(b, sp, nh // hb, hb).transpose(0, 2, 1, 3)

    def wide(a):        # [B, S, H, D] -> [B, S, H D]: as the caller made it
        return a.reshape(b, sp, -1)

    def heads_of(d):
        return pl.BlockSpec((None, sp, hb * d), lambda bi, hi, n: (bi, 0, hi))

    state_spec = pl.BlockSpec((None, hb, dk, dv),
                              lambda bi, hi, n: (bi, hi, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, dk=dk, dv=dv,
                          steps=sp // SUB, unit_qk=unit_qk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nh // hb),
            in_specs=[heads_of(dk), heads_of(dk), heads_of(dk), heads_of(dv),
                      pl.BlockSpec((None, None, sp, hb),
                                   lambda bi, hi, n: (bi, hi, 0, 0)),
                      state_spec],
            out_specs=[heads_of(dv), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, sp, nh * dv), f32),
                   jax.ShapeDtypeStruct((b, nh, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=kda_chunk_vmem_bytes(sp, hb, dk, dv)),
        interpret=_interpret(),
        name="kda_chunk",
    )(n, wide(q), wide(k), wide(g), wide(v), beta, state.astype(f32))
    return o.reshape(b, sp, nh, dv)[:, :s], state
