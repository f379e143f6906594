"""Telemetry subsystem: end-to-end request tracing + device-step profiling.

Three pieces (see ISSUE 2 / ROADMAP open item #1 — the 33 ms decode step has
never been decomposed):

- `trace`: a lock-free ring-buffer span tracer with request-id propagation
  HTTP middleware → gRPC metadata → engine, exported as Chrome-trace JSON
  (`/debug/trace`, `local-ai util trace`, `bench.py --trace`).
- `profiler`: opt-in `block_until_ready`-fenced per-stage timing of the
  engine's device dispatches (admit / prefill / decode block / sample /
  shift), accumulated into histograms with tokens/s + MFU estimates
  (`/debug/profile`, GetMetrics `prof_*` keys, Prometheus series).
- exporters live with their surfaces: the HTTP server merges spans across
  processes via the backend GetTrace RPC.

- `metrics` (ISSUE 11): the serving SLO layer — per-request phase-timeline
  histograms (TTFT/TPOT/queue wait/prefill/e2e, labeled by decode path)
  exported via GetMetrics `hist_*` keys, true Prometheus histogram series,
  and `/debug/slo`; plus the crash/tripwire flight recorder
  (`/debug/flightrec`, auto post-mortem dumps).

- `sched` (ISSUE 13): the scheduler X-ray — a per-tick pack ledger with a
  registered reason-code taxonomy for every admission/fallback/demotion
  decision, plus XLA cost-analysis rooflines per compiled decode variant
  (`/debug/sched`, GetMetrics `sched_*` keys, `local-ai util sched`).

Enable with `LOCALAI_TRACE=1` (spans) and `LOCALAI_PROFILE=1` (fenced stage
timing). Both default off; the serving hot path is untouched when disabled.
SLO metrics default ON (`LOCALAI_METRICS=0` disables); the tick ledger
rides the same gate (`LOCALAI_SCHED=0` disables it alone).
"""
from localai_tpu.telemetry.trace import (  # noqa: F401
    Tracer,
    chrome_events,
    chrome_trace,
    current_request_id,
    maybe_tracer,
    new_request_id,
    reset_request_id,
    set_request_id,
    set_trace_enabled,
    span,
    trace_enabled,
    tracer,
)
from localai_tpu.telemetry.profiler import (  # noqa: F401
    StepProfiler,
    engine_profiler,
    profile_enabled,
    set_profile_enabled,
)
from localai_tpu.telemetry.metrics import (  # noqa: F401
    BUCKETS_S,
    FlightRecorder,
    Hist,
    SLORegistry,
    flightrec,
    maybe_slo,
    metrics_enabled,
    parse_flat,
    reset_flightrec,
    set_metrics_enabled,
    snapshot_from_hists,
)
from localai_tpu.telemetry.sched import (  # noqa: F401
    DISPATCH_CODES,
    REASON_CODES,
    TickLedger,
    current_tick,
    maybe_ledger,
    reason_category,
    roofline_entry,
    sched_enabled,
    set_current_tick,
    set_sched_enabled,
)
