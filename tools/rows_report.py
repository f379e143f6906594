"""Where a cell's 32 rows went and where a permit's seconds went, from the
two `/backend/monitor` samples of one benchmark run.

    python tools/rows_report.py --workload <cell> --seed <n> --seconds <s> [--trace 1] ...

runs `benchmark/run.py` as it stands, in this process, with the same
arguments, and keeps every `/backend/monitor` sample the run takes (the
window's start, its end, and the one after the last greedy check) in
`chiprun_out/rows/<cell>.<seed>.json`; then prints, over the window:

- both sides of the engine's identities in each sample: the five
  `decode_row_steps__*` against max_slots x `decode_steps_consumed`, and
  `decode_row_steps__live` against `tokens_generated`;
- the rows table: of max_slots rows a step, how many were live, spent, in
  prefill, free with a request queued, free with none, beside
  `decode_batch.over` (tokens per step DISPATCHED in the window);
- a permit's life: the mean of each stage the program times, their sum
  against `permit_hold`, and what is left (the two crossings between the
  HTTP process and the backend, which no single clock times);
- the gate: its permits and the model's slots, the grants of the window
  and how many of them were made ahead of a slot.

    python tools/rows_report.py --read chiprun_out/rows/<cell>.<seed>.json

prints the same from a kept file. The benchmark's result line stays the
last line of stdout but one (`ROWS_REPORT` follows it). Imports no JAX: the
chip belongs to the backend the benchmark starts.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STATES = ("live", "spent", "prefill", "free_queued", "free_starved")
# a permit's life, in order; `engine` is hist_e2e less the two before it
STAGES = ("stream_start", "queue_wait", "admit_to_join", "engine",
          "finish_to_reply", "reply_to_release")


def _hist(m: dict, name: str, field: str) -> float:
    return sum(v for k, v in m.items()
               if k.startswith(f"hist_{name}__") and k.endswith("__" + field))


def identities(m: dict, slots: int) -> dict:
    rows = {s: m.get(f"decode_row_steps__{s}", 0.0) for s in STATES}
    return {"rows_sum": sum(rows.values()),
            "slots_x_steps": slots * m.get("decode_steps_consumed", 0.0),
            "live": rows["live"],
            "tokens_generated": m.get("tokens_generated", 0.0)}


def report(samples: list, slots: int) -> dict:
    """`samples`: the run's /backend/monitor entries in the order taken;
    the window is the first two."""
    ms = [s["metrics"] for s in samples]
    before, after = ms[0], ms[1]

    def d(key: str) -> float:
        return after.get(key, 0.0) - before.get(key, 0.0)

    steps = d("decode_steps_consumed")
    rows = {s: d(f"decode_row_steps__{s}") / steps if steps else None
            for s in STATES}
    mean = {}
    for name in ("gate_wait", "stream_start", "queue_wait", "admit_to_join",
                 "e2e", "finish_to_reply", "reply_to_release", "permit_hold"):
        n = _hist(after, name, "count") - _hist(before, name, "count")
        mean[name] = ((_hist(after, name, "sum") - _hist(before, name, "sum"))
                      / n * 1e3 if n else None, int(n))
    stage = {k: mean[k][0] for k in STAGES if k != "engine"}
    if None not in (mean["e2e"][0], stage["queue_wait"],
                    stage["admit_to_join"]):
        stage["engine"] = (mean["e2e"][0] - stage["queue_wait"]
                           - stage["admit_to_join"])
    else:
        stage["engine"] = None
    known = [stage[k] for k in STAGES]
    total = sum(known) if None not in known else None
    hold = mean["permit_hold"][0]
    return {
        "identities": [identities(m, slots) for m in ms],
        "steps_consumed": steps,
        "rows_of_slots": rows,
        "decode_batch_dispatched": (
            d("tokens_generated") / d("decode_steps_dispatched")
            if d("decode_steps_dispatched") else None),
        "mean_ms": {k: v[0] for k, v in mean.items()},
        "observations": {k: v[1] for k, v in mean.items()},
        "stages_ms": stage, "stages_sum_ms": total,
        "permit_hold_ms": hold,
        # the gate's grants over the window and those made ahead of a slot
        # (ISSUE 39; a tree without the counters reads nothing)
        "gate": ({"grants": d("gate_grants"), "ahead": d("gate_grants_ahead"),
                  "slots": after["gate_slots"], "limit": after["gate_limit"]}
                 if "gate_grants" in after else None),
        "remainder_ms": (hold - total if None not in (hold, total)
                         else None)}


def render(r: dict, slots: int) -> str:
    out = []
    for i, ident in enumerate(r["identities"]):
        out.append(
            f"sample {i}: rows {ident['rows_sum']:.0f} = {slots} x steps "
            f"{ident['slots_x_steps']:.0f} "
            f"({'ok' if ident['rows_sum'] == ident['slots_x_steps'] else 'NO'})"
            f"; live {ident['live']:.0f} = tokens "
            f"{ident['tokens_generated']:.0f} "
            f"({'ok' if ident['live'] == ident['tokens_generated'] else 'NO'})")
    rows = r["rows_of_slots"]
    if rows["live"] is not None:
        out.append(f"rows of {slots} a step over {r['steps_consumed']:.0f} "
                   "steps consumed: " + ", ".join(
                       f"{s} {v:.2f}" for s, v in rows.items())
                   + f"; decode_batch (dispatched) "
                     f"{r['decode_batch_dispatched']:.2f}")

    def ms(v):
        return "-" if v is None else f"{v:.1f}"

    out.append("a permit's life, mean ms: " + " + ".join(
        f"{k} {ms(r['stages_ms'][k])}" for k in STAGES)
        + f" = {ms(r['stages_sum_ms'])} of permit_hold "
          f"{ms(r['permit_hold_ms'])}: {ms(r['remainder_ms'])} left; "
          f"gate_wait {ms(r['mean_ms']['gate_wait'])}; observations "
          f"{r['observations']}")
    g = r.get("gate")
    if g and g["grants"]:
        out.append(f"the gate: {g['limit']:.0f} permits for {g['slots']:.0f} "
                   f"slots; {g['grants']:.0f} grants in the window, "
                   f"{g['ahead']:.0f} ahead of a slot "
                   f"({g['ahead'] / g['grants']:.3f})")
    return "\n".join(out)


def main(argv: list) -> int:
    if argv[:1] == ["--read"]:
        with open(argv[1]) as f:
            kept = json.load(f)
        r = report(kept["samples"], kept["slots"])
        print(render(r, kept["slots"]))
        print("ROWS_REPORT " + json.dumps(r))
        return 0

    from benchmark import run as bench_run
    from benchmark.harness import server

    samples: list = []
    take = server.Server.monitor

    def kept_monitor(self):
        entry = take(self)
        samples.append(entry)
        return entry

    server.Server.monitor = kept_monitor
    sys.argv = ["benchmark/run.py"] + argv
    rc = bench_run.main()
    if len(samples) < 2:
        print(f"ROWS_REPORT none: {len(samples)} /backend/monitor samples",
              flush=True)
        return rc
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", default="1")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args, _ = ap.parse_known_args(argv)
    cell = args.workload
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == cell)
    cfg = bench_run.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == config))
    slots = int(server.serving(cfg, args.cpu_rehearsal)["parallel"])
    out_dir = os.path.join(ROOT, "chiprun_out", "rows")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{cell}.{args.seed}.json")
    with open(path, "w") as f:
        json.dump({"cell": cell, "argv": argv, "slots": slots,
                   "samples": samples}, f)
    r = report(samples, slots)
    print(render(r, slots), flush=True)
    print("ROWS_REPORT " + json.dumps(r), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
