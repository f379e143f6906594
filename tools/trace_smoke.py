"""CI telemetry smoke: serve a tiny model through the real process boundary
with the ring tracer on, then write the merged Chrome-trace artifact.

This is the scoreboard-path exerciser the tier-1 CI job uploads: a
ModelManager-spawned gRPC backend (the same surface /v1/chat/completions
rides), a few concurrent PredictStream requests, then GetTrace → one
Chrome-trace JSON whose spans cover rpc → grpc → the engine's phases and
each request's TTFT stages; GetMetrics must carry the always-on counters.

Usage: python tools/trace_smoke.py [--out trace_smoke.json]
Exit code is non-zero when the trace is missing the expected layers, so the
CI step is an assertion, not just an artifact producer.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["LOCALAI_TRACE"] = "1"
os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
os.environ["LOCALAI_NO_PREWARM"] = "1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="trace_smoke.json")
    ap.add_argument("--requests", type=int, default=3)
    args = ap.parse_args()

    from bench import write_synthetic_checkpoint

    from localai_tpu import telemetry
    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager

    tmp = tempfile.mkdtemp(prefix="trace-smoke-")
    ckpt = write_synthetic_checkpoint("tiny", os.path.join(tmp, "tiny"))
    mcfg = ModelConfig.from_dict({
        "name": "smoke", "backend": "llm", "context_size": 128,
        "parallel": 2, "dtype": "float32", "prefill_buckets": [32],
        "parameters": {"model": ckpt},
    })
    manager = ModelManager(AppConfig(models_path=tmp, parallel_requests=2))
    handle = manager.load(mcfg)

    def one(i: int):
        token = telemetry.set_request_id(f"smoke-{i}")
        try:
            for _ in handle.client.predict_stream(
                    prompt_ids=[1, 2, 3, 4 + i], tokens=6, ignore_eos=True,
                    temperature=0.0, timeout=600.0):
                pass
        finally:
            telemetry.reset_request_id(token)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(args.requests)]
    [t.start() for t in threads]
    [t.join() for t in threads]

    payload = handle.client.trace()
    metrics = handle.client.metrics()
    manager.stop_all()

    events = list(payload.get("spans") or []) + telemetry.chrome_events()
    events.sort(key=lambda e: e.get("ts", 0))
    names = {os.getpid(): "trace-smoke", payload.get("pid", 0): "backend"}
    with open(args.out, "w") as fh:
        json.dump(telemetry.chrome_trace(events, names), fh)

    got = {e["name"] for e in events}
    rids = {e["args"].get("request_id") for e in events
            if e["name"] == "engine.request"}
    phases = {k: v for k, v in metrics.items()
              if k.startswith(("engine_host_ms__", "engine_wait_ms__"))}
    print(f"wrote {args.out}: {len(events)} events, layers={sorted(got)[:8]}")
    print("engine thread: " + ", ".join(
        f"{k}={v:.1f}ms" for k, v in sorted(phases.items())))
    want = {"engine.admit", "engine.emit", "engine.stage.join_to_first",
            "grpc.PredictStream"}
    missing = want - got
    if missing:
        print(f"FAIL: trace missing layers {missing}", file=sys.stderr)
        return 1
    if not {f"smoke-{i}" for i in range(args.requests)} <= rids:
        print(f"FAIL: request ids did not round-trip ({rids})",
              file=sys.stderr)
        return 1
    always_on = ("engine_host_ms__dispatch", "engine_wait_ms__device",
                 "decode_dispatches_consumed", "decode_steps_consumed",
                 "requests_admitted", "xla_compiles_total")
    if any(k not in metrics for k in always_on):
        print(f"FAIL: GetMetrics lacks "
              f"{[k for k in always_on if k not in metrics]}",
              file=sys.stderr)
        return 1
    if metrics["requests_admitted"] < args.requests:
        print(f"FAIL: requests_admitted {metrics['requests_admitted']}",
              file=sys.stderr)
        return 1

    # SLO layer (ISSUE 11): the same scrape must carry the flat histogram
    # keys + headline percentiles, and GetTrace the percentile snapshot and
    # the flight-recorder rings with every smoke request's timeline
    from localai_tpu.telemetry import parse_flat, snapshot_from_hists

    if not any(k.startswith("hist_ttft__") for k in metrics):
        print("FAIL: GetMetrics carries no hist_ttft__* keys", file=sys.stderr)
        return 1
    if not metrics.get("ttft_ms_p50", 0) > 0:
        print("FAIL: no histogram-backed ttft_ms_p50", file=sys.stderr)
        return 1
    snap = snapshot_from_hists(parse_flat(metrics))
    n = (snap.get("ttft") or {}).get("count", 0)
    if n < args.requests:
        print(f"FAIL: SLO snapshot counts {n} requests, "
              f"expected >= {args.requests}", file=sys.stderr)
        return 1
    slo = payload.get("slo") or {}
    if (slo.get("e2e") or {}).get("count", 0) < args.requests:
        print(f"FAIL: GetTrace slo snapshot incomplete ({slo.keys()})",
              file=sys.stderr)
        return 1
    rec = payload.get("flightrec") or {}
    rec_ids = {r.get("request_id") for r in rec.get("requests") or []}
    want_ids = {f"smoke-{i}" for i in range(args.requests)}
    if not want_ids <= rec_ids:
        print(f"FAIL: flight recorder missing request timelines "
              f"({rec_ids})", file=sys.stderr)
        return 1
    print(f"SLO: ttft_p50={metrics['ttft_ms_p50']:.1f}ms "
          f"ttft_p95={metrics.get('ttft_ms_p95', 0):.1f}ms "
          f"flightrec={len(rec_ids)} timelines")

    # scheduler X-ray (ISSUE 13): the tick ledger must cross the scrape
    # boundary — sched_* keys in GetMetrics, the structured snapshot (with
    # the served ticks and at least one reason-code counter) in GetTrace
    if not metrics.get("sched_ticks_total", 0) > 0:
        print("FAIL: GetMetrics carries no sched_ticks_total", file=sys.stderr)
        return 1
    if not any(k.startswith("sched_reason__") for k in metrics):
        print("FAIL: GetMetrics carries no sched_reason__* keys",
              file=sys.stderr)
        return 1
    sched = payload.get("sched") or {}
    if sched.get("ticks_total", 0) <= 0 or not sched.get("reason_counters"):
        print(f"FAIL: GetTrace sched snapshot incomplete "
              f"({sorted(sched.keys())})", file=sys.stderr)
        return 1
    if not sched.get("recent_ticks"):
        print("FAIL: sched snapshot carries no tick records", file=sys.stderr)
        return 1
    print(f"sched: {sched['ticks_total']} ticks, "
          f"reasons={sched['reason_counters']}")
    print("trace smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
