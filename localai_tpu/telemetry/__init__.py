"""Telemetry: what the serving path says about itself, and to whom.

One instrumentation layer, three kinds of sink; nothing here touches the
device or adds a sync, and the always-on parts cost a few clock reads per
engine tick or per request, never per token or per step.

- `trace`: the lock-free ring-buffer span tracer (`LOCALAI_TRACE=1`, off by
  default; request-id propagation HTTP middleware → gRPC metadata → engine;
  Chrome-trace JSON at `/debug/trace`, `local-ai util trace`), and
  `PhaseClock`, the engine thread's time tiled into phases (`dispatch`,
  `admit`, `emit`, `kv`; waits `device`, `idle`): always-on cumulative
  `engine_host_ms__*` / `engine_wait_ms__*` in GetMetrics, a
  `jax.profiler.TraceAnnotation("engine.<phase>", tick=n)` on the profiler's
  own clock in any device trace of the process (`GET /debug/xprof`, reduced
  by `tools/trace_gaps.py`), and the ring span when the ring is on.
- `metrics`: the serving SLO layer (`LOCALAI_METRICS`, on by default) —
  per-request histograms over `BUCKETS_S`: `ttft` and its stages
  (`gate_wait`, `stream_start` in the HTTP process; `queue_wait`,
  `admit_to_join`, `join_to_first` in the engine, summing to `ttft`
  exactly), `tpot`, `e2e`, and a finished stream's tail (`finish_to_reply`
  in the backend's handler, `reply_to_release` and the permit's whole
  `permit_hold` at the HTTP gate),
  labeled by decode path; exported as GetMetrics `hist_*` keys, true
  Prometheus histogram series and `/debug/slo`. Plus the crash/tripwire
  flight recorder (`/debug/flightrec`, auto post-mortem dumps) and
  `CompileCounter`, the `jax.monitoring` listener behind
  `xla_compiles_total`, `xla_compile_ms_total` and `xla_compiles__<jit>`.
- `sched`: the scheduler X-ray — a per-tick pack ledger with a registered
  reason-code taxonomy for every admission/fallback/demotion decision, plus
  XLA cost-analysis rooflines per compiled decode variant (`/debug/sched`,
  GetMetrics `sched_*` keys, `local-ai util sched`; `LOCALAI_SCHED=0`
  disables it alone).

The engine's own counters (`engine.metrics`: dispatches and steps consumed,
the steps whose program took sampling's top-k by blocks
(`decode_steps__topk_blocks`), rows x steps by state
(`decode_row_steps__*`), requests admitted, tokens by path, ...) ride the
same GetMetrics map.
"""
from localai_tpu.telemetry.trace import (  # noqa: F401
    XPROF_MAX_S,
    PhaseClock,
    Tracer,
    chrome_events,
    chrome_trace,
    current_request_id,
    device_trace,
    maybe_tracer,
    new_request_id,
    reset_request_id,
    set_request_id,
    set_trace_enabled,
    span,
    trace_enabled,
    tracer,
)
from localai_tpu.telemetry.metrics import (  # noqa: F401
    BUCKETS_S,
    CompileCounter,
    FlightRecorder,
    Hist,
    SLORegistry,
    compile_counter,
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
