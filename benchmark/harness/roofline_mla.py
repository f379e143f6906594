"""Operations and bytes of latent attention (MLA) over a cache of latent
rows, from shapes alone, and the least time a chip could take for a call.
Kept with the benchmark so that no PR that claims a gain can change the
yardstick. Pure Python.

A cached token is ONE row of R + P values a layer (R = kv_lora_rank: the
normalised latent; P = qk_rope_head_dim: the rotated position key all H
heads share). A head's keys are N + P wide and its values V wide, made of a
row by the up-projection W_kvb [R, H (N + V)].

`mla_decode` (ops/pallas/mla.py), one call a latent layer a decode step,
over live rows whose lengths sum to `ctx_tokens`: the ABSORBED form. Every
cached row is read once, (R + P) x itemsize bytes, and used twice: as the
key of all H heads, 2 H (R + P) operations, and its first R columns as their
value, 2 H R. At H 128, R 512, P 64 and bfloat16 that is 278 528 operations
for 1152 bytes, 242 op/B, against the v5e's ridge of 240: neither bound is
far. The absorbed queries in and the heads' sums of latents out are counted
too (H (2 R + P) values a row). An implementation that pads a row, reads it
twice or multiplies in float32 moves or computes more; that shows as a lower
share, as it should.

A prompt chunk's attention (`mla_chunk_cost`), one call a latent layer a
chunk of S tokens from position `start`: every row up to start + S is read
once; a query sees the `start` rows before the chunk and, of the chunk's own
rows, those up to itself: S start + S (S + 1) / 2 pairs.
  expanding  every visited row through W_kvb, 2 R H (N + V) operations a row
             (once a chunk, whatever S), then heads of N + P / V: 2 H (N + P
             + V) a pair; W_kvb is read once;
  absorbed   the S queries through W_UK and the outputs through W_UV, 2 R H
             (N + V) a query, then 2 H (2 R + P) a pair.
They cross where S (S-pairs' difference) pays for the expansion: at H 128,
R 512, P 64, N = V = 128 near S = 171 tokens a chunk, at every context.
"""
from __future__ import annotations

# the roofline of a call from its cost: one definition for every kernel
from benchmark.harness.roofline_kda import (  # noqa: F401
    least_seconds, roofline_share,
)


def mla_decode_cost(ctx_tokens: float, rows: int, heads: int, rank: int,
                    rope: int, itemsize: float = 2.0) -> dict:
    width = rank + rope
    return {"ops": ctx_tokens * 2.0 * heads * (width + rank),
            "bytes": (ctx_tokens * width
                      + rows * heads * (width + rank)) * itemsize,
            "cache_bytes": ctx_tokens * width * itemsize}


def mla_chunk_cost(tokens: int, start: int, heads: int, rank: int, rope: int,
                   nope: int, vdim: int, form: str = "expanding",
                   itemsize: float = 2.0, weight_itemsize: float = 1.0
                   ) -> dict:
    if form not in ("expanding", "absorbed"):
        raise ValueError(f"form {form!r}: expanding or absorbed")
    ctx = start + tokens
    pairs = tokens * start + tokens * (tokens + 1) / 2.0
    through_w = 2.0 * rank * heads * (nope + vdim)
    if form == "expanding":
        project = ctx * through_w
        attend = pairs * 2.0 * heads * (nope + rope + vdim)
    else:
        project = tokens * through_w
        attend = pairs * 2.0 * heads * (2 * rank + rope)
    return {"ops": project + attend,
            "bytes": (ctx * (rank + rope) * itemsize
                      + tokens * heads * (nope + rope + vdim) * itemsize
                      + rank * heads * (nope + vdim) * weight_itemsize),
            "ops_by_part": {"project": project, "attend": attend},
            "pairs": pairs}


def crossing_tokens(heads: int, rank: int, rope: int, nope: int, vdim: int
                    ) -> float:
    """The chunk length from which the expanding form costs fewer
    operations than the absorbed one, for a context long beside the chunk
    (each visited row: through_w + S x pair_exp against S x pair_abs)."""
    through_w = 2.0 * rank * heads * (nope + vdim)
    pair_exp = 2.0 * heads * (nope + rope + vdim)
    pair_abs = 2.0 * heads * (2 * rank + rope)
    return through_w / (pair_abs - pair_exp)
