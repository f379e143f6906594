"""Grouped product for the routed expert layer: rows laid out in tiles of
one expert each, times that expert's matrix, read out of the weight stack
where it lies.

    out[t] = (a[t] @ W[layer, tile_e[t]]) * scale[layer, tile_e[t]]

for the tiles t < used, the prefix of the layout that holds a pair. The
grid walks (tile, output block, inner block) and ENDS at `used`: its first
bound is that traced count (at least 1: a call none of whose pairs landed
here multiplies one tile of zeros), on the chip and under the interpreter
alike, so a chip that holds a sixteenth of the router's experts does not
step over the fifteen sixteenths of the static worst case that stand empty
(0.17 us a step on a v5e, a seventh to a quarter of a chunk's product in
the cells that hold a share: PERF.md section 6, PR 53). THE ROWS OF THE
TILES PAST `used` ARE NOT WRITTEN: they hold what the buffer held (NaN
under the interpreter), and the caller keeps them out (_grouped_experts:
their weight and their column of the 0/1 matrix are 0, and a row that is
not finite is masked before the way back).
The index maps read the tile's expert from scalar prefetch, so the
pipeline fetches the next tile's weights (int8 as stored, or bf16/f32)
while this tile multiplies, and consecutive tiles of one expert fetch it
once. int8 weights are converted in the kernel and the per-output-channel
scale is applied to the product. The pattern is
jax.experimental.pallas.ops.tpu.megablox's; the XLA twin is
models/llama._grouped_experts' loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from localai_tpu.ops.pallas.flash_attention import _interpret

LANES = 128
# bytes of one weight block as stored: it is held twice (the pipeline's two
# buffers) and, int8, once more as float32 and once as bfloat16 while it is
# converted
BLOCK_BYTES = 4 << 20


def _split(n: int, most: int) -> int:
    """The largest divisor of n that is a multiple of LANES and <= most
    (n itself where it fits or has no such divisor)."""
    if n <= most:
        return n
    for d in range(most - most % LANES, 0, -LANES):
        if n % d == 0:
            return d
    return n


def _blocks(k: int, n: int, itemsize: int) -> tuple[int, int]:
    """(inner block, output block) of a [k, n] matrix."""
    bk = _split(k, 4096)
    return bk, _split(n, max(LANES, BLOCK_BYTES // (bk * itemsize)))


def _kernel(la_ref, te_ref, a_ref, w_ref, *rest, scaled, nk, keep):
    s_ref = rest[0] if scaled else None
    o_ref, acc_ref = rest[scaled:scaled + 2]
    t, kk = pl.program_id(0), pl.program_id(2)
    if keep:
        # the expert's whole matrix is one block: converted once for the
        # consecutive tiles that share it
        wc_ref = rest[-1]

        @pl.when((t == 0) | (te_ref[t] != te_ref[jnp.maximum(t - 1, 0)]))
        def _convert():
            wc_ref[...] = w_ref[...].astype(jnp.float32).astype(wc_ref.dtype)

        w = wc_ref[...]
    else:
        w = w_ref[...]
        if w.dtype != a_ref.dtype:
            w = w.astype(jnp.float32).astype(a_ref.dtype)
    # bfloat16 operands have one precision; a default set from outside (the
    # tests' float32) is for float32 operands, and Mosaic refuses it on these
    part = jnp.dot(a_ref[...], w, preferred_element_type=jnp.float32,
                   precision=None if a_ref.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT)

    @pl.when(kk == 0)
    def _first():
        acc_ref[...] = part

    @pl.when(kk > 0)
    def _more():
        acc_ref[...] += part

    @pl.when(kk == nk - 1)
    def _last():
        y = acc_ref[...]
        if scaled:
            y = y * s_ref[...]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blocks",))
def grouped_matmul(a, body, scale, tile_e, used, layer, blocks=None):
    """a [tiles, tm, K]; body [L, E, K, N] (int8 with scale [L, E, 1, N]
    float32, or a float dtype with scale None); tile_e [tiles] int32, the
    expert of each tile; used, layer: int32 scalars. Returns [tiles, tm, N]
    in a's dtype, the tiles [0, max(used, 1)) written and the rest NOT (the
    module's docstring). `blocks`: (inner, output) block sizes, for the
    bench."""
    tiles, tm, k = a.shape
    n = body.shape[-1]
    bk, bn = blocks or _blocks(k, n, body.dtype.itemsize)
    if k % bk or n % bn or tm % 8:
        raise ValueError(f"grouped_matmul: [{tm}, {k}] x [{k}, {n}] does not "
                         f"tile by blocks of {bk} x {bn}")
    nk, nn = k // bk, n // bn
    keep = nk == nn == 1 and body.dtype != a.dtype
    # fast memory asked for: what the blocks above need and no more (XLA
    # keeps buffers of its own there across the call)
    item = a.dtype.itemsize
    vmem = (2 * (tm * bk * item + bk * bn * body.dtype.itemsize
                 + tm * bn * item + bn * 4) + tm * bn * 4
            + bk * bn * (4 + item + (item if keep else 0)) + (8 << 20))
    # the grid's first bound: the tiles in use, a traced scalar
    used = jnp.clip(jnp.asarray(used, jnp.int32).reshape(()), 1, tiles)
    layer = jnp.asarray(layer, jnp.int32).reshape((1,))
    tile_e = tile_e.astype(jnp.int32)

    def a_map(t, j, kk, la, te):
        return (t, 0, kk)

    def w_map(t, j, kk, la, te):
        return (la[0], te[t], kk, j)

    def s_map(t, j, kk, la, te):
        return (la[0], te[t], 0, j)

    in_specs = [pl.BlockSpec((None, tm, bk), a_map),
                pl.BlockSpec((None, None, bk, bn), w_map)]
    operands = [a, body]
    if scale is not None:
        in_specs.append(pl.BlockSpec((None, None, 1, bn), s_map))
        operands.append(scale)
    return pl.pallas_call(
        functools.partial(_kernel, scaled=scale is not None, nk=nk,
                          keep=keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(used, nn, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, tm, bn),
                                   lambda t, j, kk, la, te: (t, 0, j)),
            scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)] + (
                [pltpu.VMEM((bk, bn), a.dtype)] if keep else []),
        ),
        out_shape=jax.ShapeDtypeStruct((tiles, tm, n), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=_interpret(),
        name="grouped_matmul",
    )(layer, tile_e, *operands)
