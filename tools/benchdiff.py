"""benchdiff: noise-aware regression gate between two bench JSON artifacts.

    python -m tools.benchdiff old.json new.json
    python -m tools.benchdiff                # bench_runs/: previous vs latest
    python -m tools.benchdiff --runs-dir bench_runs --threshold 0.85

Compares two `bench.py` result lines (or archived bench_runs/ artifacts)
per (mode, metric). Absolute tok/s on shared CI boxes swings ~2x run to
run, so the gate leans on the RATIO metrics bench.py computes inside one
process against its own denominator (paged_over_dense, tp_over_single,
longctx_over_short) plus the scale-free health fields (draft acceptance,
MFU, pad-row fraction): those are self-relative and
stable, so a modest threshold on them is signal, not noise. Raw
throughput is reported but only FLAGGED, never gated, unless it collapses
below the --collapse floor (default 0.33x — beyond any plausible box
swing). Counter-like invariants (compile_count_delta) regress only when
they GROW.

Exit codes: 0 ok / 1 regression / 2 usage or unreadable input. The CI
step runs it advisory (continue-on-error) until the runner archives
enough artifacts to trust the floor.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# ratio metrics: higher is better, gate at threshold * old (floored at a
# small absolute slack so a 0.01 ratio wiggle on tiny numbers can't trip)
RATIO_KEYS = (
    "paged_over_dense", "tp_over_single", "longctx_over_short",
    # --mode session (ISSUE 17): turn-2 re-prefill TTFT over host-tier
    # re-admission TTFT — the self-relative speedup the host KV tier buys;
    # a re-admission regression shrinks it
    "readmit_speedup",
    # --mode session (ISSUE 19): re-prefill TTFT over survivor-pool resume
    # TTFT after a mid-decode preempt — the speedup the spill-drain
    # checkpoint buys; a resume-path regression shrinks it
    "resume_speedup",
    "draft_acceptance", "mfu", "stage_coverage",
)
# lower is better; gate when NEW exceeds threshold-scaled OLD.
# turn2_over_turn1_ttft is the session-mode re-admission gate (ISSUE 17):
# turn-2 TTFT through the host tier over turn-1 full-prefill TTFT — it
# GROWS when re-admission regresses, so it belongs on the inverse side
# (its RATIO_KEYS twin is readmit_speedup above)
INVERSE_KEYS = ("pad_rows_frac", "host_sync_wait_ms_per_token",
                "turn2_over_turn1_ttft")
# integer invariants: any growth is a regression (new compiles
# mid-stream) — these are exact, not noisy
GROWTH_KEYS = ("compile_count_delta",)
# informational throughput keys: flagged when they collapse, never gated
# at the ratio threshold
THROUGHPUT_KEYS = ("value", "tok_s_per_chip", "tok_s_global")


def load(path: str) -> dict:
    with open(path) as fh:
        data = json.load(fh)
    if not isinstance(data, dict):
        raise ValueError(f"{path}: not a bench result object")
    return data


def latest_two(runs_dir: str) -> tuple[str, str]:
    """(previous, latest) artifact paths by recorded_at-then-mtime order."""
    paths = []
    for fname in os.listdir(runs_dir):
        if not fname.endswith(".json"):
            continue
        p = os.path.join(runs_dir, fname)
        try:
            with open(p) as fh:
                data = json.load(fh)
        except (OSError, ValueError):
            continue
        if not isinstance(data, dict):
            continue
        paths.append(((data.get("recorded_at") or "", os.path.getmtime(p)),
                      p))
    if len(paths) < 2:
        raise FileNotFoundError(
            f"{runs_dir}: need at least two readable artifacts, "
            f"found {len(paths)}")
    paths.sort()
    return paths[-2][1], paths[-1][1]


def mode_of(result: dict) -> str:
    """The result's bench mode, recovered from the metric line (results
    don't carry an explicit mode field; the metric string is stable)."""
    return str(result.get("metric") or "?").split("(")[0].strip()


def _num(v):
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) \
        else None


def compare(old: dict, new: dict, threshold: float,
            collapse: float) -> tuple[list[str], list[str]]:
    """Returns (regressions, notes). Only keys present in BOTH results are
    compared — bench schema growth must not fail the gate."""
    regressions, notes = [], []
    mode = mode_of(new)
    if mode_of(old) != mode:
        notes.append(f"mode mismatch ({mode_of(old)!r} vs {mode!r}) — "
                     "ratio comparison only")
    for key in RATIO_KEYS:
        o, n = _num(old.get(key)), _num(new.get(key))
        if o is None or n is None or o <= 0:
            continue
        if n < o * threshold - 0.01:
            regressions.append(
                f"{mode}: {key} {o:.4f} -> {n:.4f} "
                f"({n / o:.2f}x, floor {threshold:.2f}x)")
        else:
            notes.append(f"{mode}: {key} {o:.4f} -> {n:.4f} ok")
    for key in INVERSE_KEYS:
        o, n = _num(old.get(key)), _num(new.get(key))
        if o is None or n is None:
            continue
        if n > o / max(threshold, 1e-9) + 0.01:
            regressions.append(
                f"{mode}: {key} {o:.4f} -> {n:.4f} (grew past "
                f"{1 / threshold:.2f}x)")
    for key in GROWTH_KEYS:
        o, n = _num(old.get(key)), _num(new.get(key))
        if o is None or n is None:
            continue
        if n > o:
            regressions.append(f"{mode}: {key} {o:.0f} -> {n:.0f} (grew)")
    for key in THROUGHPUT_KEYS:
        o, n = _num(old.get(key)), _num(new.get(key))
        if o is None or n is None or o <= 0:
            continue
        if n < o * collapse:
            regressions.append(
                f"{mode}: {key} collapsed {o:.2f} -> {n:.2f} "
                f"({n / o:.2f}x < {collapse:.2f}x floor)")
        elif n < o * 0.5:
            notes.append(f"{mode}: {key} {o:.2f} -> {n:.2f} "
                         f"({n / o:.2f}x — box noise or real?)")
    return regressions, notes


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="noise-aware diff of two bench.py result JSONs")
    p.add_argument("old", nargs="?", help="baseline result JSON")
    p.add_argument("new", nargs="?", help="candidate result JSON")
    p.add_argument("--runs-dir", default="bench_runs",
                   help="artifact dir when old/new not given")
    p.add_argument("--threshold", type=float, default=0.9,
                   help="ratio-metric floor: new >= threshold * old")
    p.add_argument("--collapse", type=float, default=0.33,
                   help="raw-throughput collapse floor (beyond box noise)")
    args = p.parse_args(argv)
    if bool(args.old) != bool(args.new):
        p.error("give both OLD and NEW, or neither (bench_runs mode)")
    try:
        if args.old:
            old_path, new_path = args.old, args.new
        else:
            old_path, new_path = latest_two(args.runs_dir)
        old, new = load(old_path), load(new_path)
    except (OSError, ValueError) as e:
        print(f"benchdiff: {e}", file=sys.stderr)
        return 2
    print(f"benchdiff: {old_path} -> {new_path}")
    regressions, notes = compare(old, new, args.threshold, args.collapse)
    for line in notes:
        print(f"  note: {line}")
    for line in regressions:
        print(f"  REGRESSION: {line}")
    if regressions:
        print(f"benchdiff: {len(regressions)} regression(s)")
        return 1
    print("benchdiff: no regressions")
    return 0


if __name__ == "__main__":
    sys.exit(main())
