"""benchmark/harness/roofline_kda.py: the counts of the two linear-attention
kernels, by hand at small shapes and at the cell's."""
import json
import os

import pytest

from benchmark.harness import roofline_kda as rk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "peaks", "TPU_v5_lite.json")) as f:
    PEAKS = json.load(f)


def test_decode_counts_by_hand():
    c = rk.kda_decode_cost(rows=1, heads=1, dk=2, dv=4)
    # state 2 x 4 x 4 B read and written; q, k, g (2 each), v, o (4 each), beta
    assert c["state_bytes"] == 2 * 8 * 4
    assert c["bytes"] == (16 + 6 + 8 + 1) * 4
    assert c["ops"] == 7 * 8


def test_decode_at_the_cells_shape_is_bandwidth_bound():
    c = rk.kda_decode_cost(rows=32, heads=64, dk=128, dv=128)
    assert c["state_bytes"] == 32 * 64 * 2 * 128 * 128 * 4 == 268435456
    t = rk.least_seconds(c, PEAKS)
    assert t["bound"] == "bandwidth"
    # 0.27 GB at 819 GB/s
    assert t["seconds"] == pytest.approx(0.000330, rel=0.02)
    assert rk.roofline_share(c, PEAKS, 2 * t["seconds"]) == pytest.approx(50)


@pytest.mark.parametrize("rows", [0, 1, 7, 32])
def test_decode_scales_with_the_live_rows(rows):
    one = rk.kda_decode_cost(1, 64, 128, 128)
    c = rk.kda_decode_cost(rows, 64, 128, 128)
    assert c["bytes"] == rows * one["bytes"] and c["ops"] == rows * one["ops"]


def test_chunk_counts_by_hand():
    c = rk.kda_chunk_cost(tokens=4, heads=1, dk=2, dv=3, sub=2)
    assert c["sub_chunks"] == 2
    intra = 2 * 2 * 2 * 2 * 2            # A and B: 2 C C Dk each
    transform = 2 * 2 * (2 + 3)
    state = 2 * 2 * 2 * 2 * 3 + 2 * 2 * 2 * 3 + 2 * 2 * 2 * 3 + 2 * 3
    assert c["ops"] == 2 * (intra + transform + state)
    assert c["bytes"] == (4 * (3 * 2 + 2 * 3 + 1) + 2 * 2 * 3) * 4
    assert sum(c["ops_by_part"].values()) == c["ops"]


def test_chunk_at_the_cells_shape():
    c = rk.kda_chunk_cost(tokens=512, heads=64, dk=128, dv=128)
    assert c["sub_chunks"] == 8
    # 64 heads x 8 sub-chunks x 10.5 M operations: 5.4 G a call
    assert c["ops"] == pytest.approx(5.38e9, rel=0.01)
    t = rk.least_seconds(c, PEAKS)
    # q, k, g, v, o, beta of 512 tokens x 64 heads (84 MB) + the state
    # once in and once out (8 MB)
    assert c["bytes"] == 512 * 64 * 641 * 4 + 64 * 2 * 128 * 128 * 4
    assert t["bound"] == "bandwidth"
    assert t["seconds"] == pytest.approx(0.000113, rel=0.02)


def test_a_padded_last_sub_chunk_counts_whole():
    assert (rk.kda_chunk_cost(65, 1, 8, 8)["ops"]
            == rk.kda_chunk_cost(128, 1, 8, 8)["ops"])
    assert (rk.kda_chunk_cost(64, 2, 8, 8, rows=3)["ops"]
            == 6 * rk.kda_chunk_cost(64, 1, 8, 8)["ops"])
