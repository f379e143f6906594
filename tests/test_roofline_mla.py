"""benchmark/harness/roofline_mla.py: the counts of latent attention's
decode kernel and of a chunk's attention in both forms, by hand at small
shapes and at the cell's."""
import json
import os

import pytest

from benchmark.harness import roofline_mla as rm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "peaks", "TPU_v5_lite.json")) as f:
    PEAKS = json.load(f)
CELL = dict(heads=128, rank=512, rope=64)


def test_decode_counts_by_hand():
    c = rm.mla_decode_cost(ctx_tokens=10, rows=2, heads=3, rank=4, rope=2,
                           itemsize=2)
    # a cached row: 6 values read once, used as 3 heads' keys (6 wide) and
    # as their values (4 wide)
    assert c["cache_bytes"] == 10 * 6 * 2
    assert c["ops"] == 10 * (2 * 3 * 6 + 2 * 3 * 4)
    # queries in (6) and sums of latents out (4), a head a row
    assert c["bytes"] == c["cache_bytes"] + 2 * 3 * (6 + 4) * 2


def test_decode_at_the_cells_shape_sits_at_the_ridge():
    """278 528 operations for 1152 bytes a cached token: 242 op/B against
    the chip's 197e12 / 819e9 = 240.5; at 30 rows of 6 k neither bound is
    5% from the other."""
    one = rm.mla_decode_cost(1, 0, **CELL)
    assert one["ops"] == 278528 and one["bytes"] == 1152
    assert one["ops"] / one["bytes"] == pytest.approx(241.8, abs=0.1)
    c = rm.mla_decode_cost(30 * 6144, 30, **CELL)
    t = rm.least_seconds(c, PEAKS)
    assert t["ops_s"] == pytest.approx(t["bytes_s"], rel=0.05)
    # 0.21 GB at 819 GB/s: a quarter of a millisecond a layer
    assert t["seconds"] == pytest.approx(0.00026, rel=0.05)
    assert rm.roofline_share(c, PEAKS, 4 * t["seconds"]) == pytest.approx(25)


@pytest.mark.parametrize("rows,ctx", [(0, 0), (1, 100), (31, 173600)])
def test_decode_scales_with_the_context_attended(rows, ctx):
    c = rm.mla_decode_cost(ctx, rows, **CELL)
    assert c["ops"] == ctx * 278528
    assert c["cache_bytes"] == ctx * 1152


def test_chunk_counts_by_hand():
    kw = dict(heads=2, rank=4, rope=2, nope=3, vdim=5, itemsize=2,
              weight_itemsize=1)
    e = rm.mla_chunk_cost(tokens=2, start=6, form="expanding", **kw)
    a = rm.mla_chunk_cost(tokens=2, start=6, form="absorbed", **kw)
    pairs = 2 * 6 + 3                   # 6 rows before, then 1 + 2 of its own
    assert e["pairs"] == a["pairs"] == pairs
    through_w = 2 * 4 * 2 * (3 + 5)
    assert e["ops_by_part"] == {"project": 8 * through_w,
                                "attend": pairs * 2 * 2 * (3 + 2 + 5)}
    assert a["ops_by_part"] == {"project": 2 * through_w,
                                "attend": pairs * 2 * 2 * (2 * 4 + 2)}
    # every row up to the chunk's end once, the queries in and outputs out,
    # W_kvb once
    assert e["bytes"] == a["bytes"] == (8 * 6 * 2 + 2 * 2 * (3 + 2 + 5) * 2
                                        + 4 * 2 * 8)
    with pytest.raises(ValueError, match="form"):
        rm.mla_chunk_cost(2, 6, form="flash", **kw)


def test_a_512_token_chunk_is_cheaper_expanded_at_every_context():
    """The issue's arithmetic: T x 33.6 M + T x 41.9 M a 512-token chunk
    expanding against T x 142.6 M absorbed, a cached row; the two cross at
    171 tokens a chunk, wherever the chunk starts."""
    kw = dict(heads=128, rank=512, rope=64, nope=128, vdim=128)
    assert rm.crossing_tokens(**kw) == pytest.approx(170.67, abs=0.01)
    for start in (0, 1536, 5632, 7680):
        e = rm.mla_chunk_cost(512, start, form="expanding", **kw)
        a = rm.mla_chunk_cost(512, start, form="absorbed", **kw)
        assert e["ops"] < a["ops"]
    far = rm.mla_chunk_cost(512, 7680, form="expanding", **kw)
    assert far["ops_by_part"]["project"] == 8192 * 33554432
    assert far["ops_by_part"]["attend"] / far["pairs"] == 81920
    assert rm.mla_chunk_cost(512, 7680, form="absorbed", **kw)[
        "ops_by_part"]["attend"] / far["pairs"] == 278528
    # a verify window of 8 tokens would be cheaper absorbed
    assert rm.mla_chunk_cost(8, 4096, form="absorbed", **kw)["ops"] < \
        rm.mla_chunk_cost(8, 4096, form="expanding", **kw)["ops"]
    assert rm.least_seconds(far, PEAKS)["bound"] == "compute"
