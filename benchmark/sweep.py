#!/usr/bin/env python3
"""Find a configuration's knee, once, on the chip: one server, one set-up,
the cell's own traffic at rates 1.5x apart, each for --step seconds.

    python benchmark/sweep.py --workload mixtral-8x7b-d6.chat-over [--start 0.5] [--step 40]

`--workload` is any cell of the configuration in BENCHMARK.json: the sweep
takes its lengths and sets the rates itself.

A step is over the knee when the completed rate falls under 95% of the
offered rate or more requests wait for their first token at its end than at
its middle (and more than a handful). The knee is the highest rate below
that. The table goes to stdout and to chiprun_out/sweep_<config>.json; the
builder writes 0.8x and 1.5x the knee into the traffic files. Not part of a
check: benchmark/run.py never searches for a rate.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.harness import client, server, traffic  # noqa: E402


def waiting(records: list, at: float) -> int:
    """Requests sent by `at` that had no first token yet."""
    return sum(1 for r in records if r.sent is not None and r.sent <= at
               and (r.first is None or r.first > at)
               and (r.done is None or r.done > at))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=float, default=0.5)
    ap.add_argument("--factor", type=float, default=1.5)
    ap.add_argument("--step", type=float, default=40.0)
    ap.add_argument("--max-steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = bench_run.load_json(ROOT, cfg_entry["file"])
    spec = traffic.load_traffic(BENCH_DIR, cell["name"], cell["traffic"])
    work = server.fresh_dir(os.path.join(ROOT, ".bench_work", "sweep"))
    for d in ("models", "ctl", "trace"):
        os.makedirs(os.path.join(work, d))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    env = bench_run.clean_env(args.cpu_rehearsal, cache_dir, work)
    model = cell["config"]
    srv_fields = server.write_model(os.path.join(work, "models"), model,
                                    config, "llm", args.cpu_rehearsal)
    vocab = server.hf_config(config, args.cpu_rehearsal)["vocab_size"]
    srv = server.Server(ROOT, work, model, env, srv_fields["parallel"],
                        srv_fields.get("queue_depth", 8), None)
    rows = []
    try:
        loaded = srv.wait_loaded(bench_run.LOAD_LIMIT_S)
        dev = loaded["device"]
        bench_run.say(f"loaded on {dev.get('platform')} {dev.get('device_kind')!r}"
                      f" x{dev.get('device_count')}: {dev.get('load_seconds')}")
        if not args.cpu_rehearsal and dev.get("platform") != "tpu":
            raise server.BenchFailure("not a TPU")
        bench_run.shape_warmup(srv.port, model, srv_fields, vocab, args.seed)
        rate = args.start
        for step in range(args.max_steps):
            spec["rate_rps"] = rate
            reqs = traffic.schedule(spec, args.seed + step, args.step, vocab,
                                    srv_fields["context_size"],
                                    bench_run.TEMPLATE_TOKENS)
            t0 = time.monotonic() + 0.2
            end = t0 + args.step
            recs = asyncio.run(client.run_open_loop(
                srv.port, model, [("window", reqs, 0.0)], {}, t0, end))
            acct = client.account(recs, t0, end)
            # completed rate: over the second half, so the ramp is left out
            half = t0 + args.step / 2
            done_2nd = sum(1 for r in recs if r.done is not None and r.done >= half)
            row = {
                "rate_rps": rate, "offered": acct["attempted"],
                "finished": acct["finished"], "failed": acct["failed"],
                "completed_rps_2nd_half": done_2nd / (args.step / 2),
                "waiting_mid": waiting(recs, half),
                "waiting_end": waiting(recs, end - 0.05),
                "in_flight_end": acct["cut"],
                "ttft_p50_ms": client.percentile(acct["ttft_ms"], 0.5),
                "ttft_p90_ms": client.percentile(acct["ttft_ms"], 0.9),
                "tpot_p50_ms": (client.percentile(acct["tpot_ms"], 0.5)
                                if acct["tpot_ms"] else None),
                "tokens_per_s": acct["tokens_in_window"] / args.step,
            }
            row["over"] = bool(
                row["completed_rps_2nd_half"] < 0.95 * rate * (
                    1 - 1 / max(1.0, rate * args.step / 2) ** 0.5)
                or (row["waiting_end"] > row["waiting_mid"]
                    and row["waiting_end"] > 4))
            rows.append(row)
            bench_run.say(json.dumps(row))
            if row["over"] and step and rows[-2]["over"]:
                break
            time.sleep(4.0)       # let the engine drop what was cut
            rate *= args.factor
    finally:
        tail = srv.log_tail(15)
        srv.stop()
    under = [r["rate_rps"] for r in rows if not r["over"]]
    first_over = next((r["rate_rps"] for r in rows if r["over"]), None)
    knee = max((r for r in under if first_over is None or r < first_over),
               default=None)
    out = {"workload": args.workload, "device": dev.get("device_kind"),
           "step_s": args.step, "rows": rows, "knee_rps": knee}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"sweep_{cell['config']}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    if knee is None:
        print(tail, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
