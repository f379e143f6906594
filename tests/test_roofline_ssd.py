"""benchmark/harness/roofline_ssd.py: the counts of a state-space layer's
decode kernel and of its chunked form, by hand at small shapes and at the
cell's."""
import json
import os

import pytest

from benchmark.harness import roofline_ssd as rs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "peaks", "TPU_v5_lite.json")) as f:
    PEAKS = json.load(f)
CELL = dict(heads=128, p=64, n=128, groups=8)


def test_decode_counts_by_hand():
    c = rs.ssd_decode_cost(rows=3, heads=2, p=4, n=5, groups=1, taps=4,
                           tail_bytes=2)
    conv = 2 * 4 + 2 * 1 * 5
    assert rs.conv_channels(2, 4, 1, 5) == conv
    # a head's state read and written once: 2 x 4 bytes x 4 x 5
    assert c["state_bytes"] == 3 * 2 * 2 * 4 * 5 * 4
    # the tail: 3 inputs of 18 channels, read and written, 2 bytes each
    assert c["tail_bytes"] == 3 * 2 * 3 * conv * 2
    vectors = 3 * (2 * (2 * 4 + 2) + 2 * 5) * 4
    assert c["bytes"] == c["state_bytes"] + c["tail_bytes"] + vectors
    assert c["ops"] == 3 * (2 * 5 * 4 * 5 + 2 * 4 * conv)


def test_decode_at_the_cells_shape_is_bound_by_the_state():
    """32 rows x 128 heads x 2 x 32 KB of state: 268 MB a layer a step, a
    third of a millisecond at 819 GB/s; 5 operations for 8 bytes."""
    c = rs.ssd_decode_cost(32, **CELL)
    assert c["state_bytes"] == 32 * 128 * 2 * 64 * 128 * 4 == 268435456
    assert c["tail_bytes"] == 32 * 2 * 3 * 10240 * 2
    assert c["state_bytes"] / c["bytes"] > 0.97
    t = rs.least_seconds(c, PEAKS)
    assert t["bound"] == "bandwidth" and t["ops_s"] < t["bytes_s"] / 100
    assert t["seconds"] == pytest.approx(0.000336, rel=0.02)
    assert rs.roofline_share(c, PEAKS, 2 * t["seconds"]) == pytest.approx(50)


@pytest.mark.parametrize("rows", [0, 1, 27, 32])
def test_decode_scales_with_the_live_rows(rows):
    c = rs.ssd_decode_cost(rows, **CELL)
    one = rs.ssd_decode_cost(1, **CELL)
    assert c["bytes"] == pytest.approx(rows * one["bytes"])
    assert c["ops"] == pytest.approx(rows * one["ops"])


def test_chunk_counts_by_hand():
    c = rs.ssd_chunk_cost(tokens=6, heads=4, p=3, n=5, groups=2, chunk=2)
    assert c["chunks"] == 3
    assert c["ops_by_part"] == {
        "scores": 3 * 2 * 2 * 2 * 2 * 5,
        "intra": 3 * 4 * (2 * 2 * 2 + 2 * 2 * 2 * 3),
        "state_pass": 3 * 4 * (2 * 2 * 2 * 3 * 5 + 3 * 5)}
    assert c["ops"] == sum(c["ops_by_part"].values())
    assert c["bytes"] == (6 * (4 * (2 * 3 + 1) + 2 * 2 * 5)
                          + 4 * 2 * 3 * 5) * 4


def test_a_chunk_at_the_cells_shape():
    """512 tokens in 4 chunks of 128: 3.3 GFLOP a layer against 26 MB: the
    matrix unit's, a few tens of microseconds at its peak; a chunk that is
    cut shorter than 128 is one chunk of its own length."""
    c = rs.ssd_chunk_cost(512, **CELL)
    assert c["chunks"] == 4
    assert c["ops"] == pytest.approx(3.3e9, rel=0.05)
    t = rs.least_seconds(c, PEAKS)
    assert t["bound"] == "bandwidth" or t["ops_s"] > 0
    assert t["seconds"] < 1e-4
    short = rs.ssd_chunk_cost(64, **CELL)
    assert short["chunks"] == 1 and short["ops"] < c["ops"] / 8
    two = rs.ssd_chunk_cost(512, rows=2, **CELL)
    assert two["ops"] == 2 * c["ops"] and two["bytes"] == 2 * c["bytes"]


def test_the_kernel_bench_rehearses(tmp_path):
    """tools/ssd_kernel_bench.py at a tiny size on the CPU: the kernel in
    the interpreter against its twin, the chunked form against the
    recurrence; exit 3, never a time."""
    import subprocess
    import sys

    out = tmp_path / "bench.json"
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "ssd_kernel_bench.py"),
         "--cpu-rehearsal", "--out", str(out)],
        capture_output=True, text=True, timeout=600)
    assert run.returncode == 3, run.stderr[-2000:]
    rows = json.loads(out.read_text())["rows"]
    assert [r["name"].split(",")[0].split(" (")[0] for r in rows] == [
        "ssd_decode", "ssd_decode", "ssd_step", "ssd_chunk", "ssd_chunk"]
    assert all(r["ms"] is None and "roofline_pct" not in r for r in rows)
    assert rows[0]["max_err"] < 1e-3 and rows[3]["max_err"] < 1e-3
