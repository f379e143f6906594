"""Serving SLO layer (ISSUE 11): streaming latency histograms + a crash
flight recorder.

Two pieces, both process-wide singletons the way `trace.py`'s tracer is:

- `SLORegistry`: lock-cheap streaming histograms over log-spaced buckets
  (`BUCKETS_S`), keyed (metric, path). The engine feeds TTFT, its three
  stages (queue wait / admit→join / join→first token), inter-token latency
  (TPOT) and e2e per request, labeled by the decode path that served it
  (loop / dense / spec); the HTTP process keeps the wait at its
  admission gate (`gate_wait`), from the permit to the stream's pump
  thread running (`stream_start`), from the stream's end to the permit's
  release (`reply_to_release`) and the permit's whole life (`permit_hold`)
  in one `Hist` each per model; the backend's gRPC handler adds
  `finish_to_reply`.
  Observations are plain int increments under the GIL — no lock on the hot
  path; snapshot readers (GetMetrics scrape, /debug/slo) tolerate a
  half-landed observation the same way the span ring does. Percentiles come
  from the bucket upper bounds (coarse but free). The whole registry
  flattens onto the GetMetrics
  str→double surface (`hist_<metric>__<path>__{bN,count,sum}`) so the HTTP
  layer can rebuild TRUE Prometheus histogram series (_bucket/_sum/_count)
  and percentile snapshots across the process boundary without a proto
  change.

- `FlightRecorder`: bounded rings of recent request timelines, engine-tick
  summaries, and tripwire/breaker/supervision events. Always recording (a
  deque append per rare event; request records ride the same enable gate as
  the histograms), dumpable via /debug/flightrec and `local-ai util
  flightrec`, and auto-dumped to a post-mortem JSON file when a tripwire
  trips, a breaker opens, a backend is reaped, or the engine loop dies —
  the black-box readout for "what was in flight when it crashed".

- `CompileCounter`: one `jax.monitoring` duration listener per process that
  counts XLA backend compiles and their seconds by jitted function; a hit
  in the persistent compilation cache is not a compile.

Enable gate: `LOCALAI_METRICS` (default ON — unlike the ring tracer this
layer is the serving SLO surface; set 0 to disable). Disabled cost in the
engine is one attribute load + branch.
"""
from __future__ import annotations

import collections
import json
import math
import os
import tempfile
import threading
import time

from localai_tpu.testing.lockdep import lockdep_lock

# histogram bucket upper bounds, in seconds (log-spaced 50 µs … 5 s + inf)
BUCKETS_S: tuple[float, ...] = (
    50e-6, 100e-6, 200e-6, 500e-6, 1e-3, 2e-3, 5e-3, 10e-3, 20e-3, 50e-3,
    100e-3, 200e-3, 500e-3, 1.0, 2.0, 5.0, math.inf,
)

# SLO metric names (seconds); the fixed set keeps the flat()/parse
# round-trip unambiguous and the exposition surfaces stable. A request's
# TTFT splits, in order, into gate_wait and stream_start (HTTP process; the
# second is streams only) and then queue_wait + admit_to_join +
# join_to_first (engine; these three sum to ttft exactly: they share their
# boundary timestamps). Its tail, once a stream that ran to the backend's
# finished reply: finish_to_reply (backend: the engine's finish decision,
# where e2e ends, -> gRPC has taken the final reply) and reply_to_release
# (HTTP process: the pump thread reads the stream's end -> the gate's permit
# is released); permit_hold (HTTP process) is the permit's whole life,
# granted -> released, the check on the sum of the stages.
METRICS = ("ttft", "tpot", "gate_wait", "stream_start", "queue_wait",
           "admit_to_join", "join_to_first", "e2e", "finish_to_reply",
           "reply_to_release", "permit_hold")

_FORCED: bool | None = None


def metrics_enabled() -> bool:
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("LOCALAI_METRICS", "1") not in ("", "0")


def set_metrics_enabled(value: bool | None) -> None:
    """Test hook mirroring set_trace_enabled: True/False force, None =
    re-read the environment."""
    global _FORCED, _SLO
    _FORCED = value
    _SLO = None   # next maybe_slo() re-resolves against the new gate


class Hist:
    """One streaming histogram over BUCKETS_S (seconds). `observe` is a few
    int/float increments under the GIL — deliberately lock-free; snapshot
    readers may see a sample's bucket before its sum (harmless skew)."""

    __slots__ = ("counts", "count", "sum")

    def __init__(self):
        self.counts = [0] * len(BUCKETS_S)
        self.count = 0
        self.sum = 0.0

    def observe(self, v: float, n: int = 1):
        """Record `n` samples of value `v` (weighted observe: the fused
        decode loop delivers token bursts whose amortized inter-token gap is
        one value covering many tokens)."""
        for i, ub in enumerate(BUCKETS_S):
            if v <= ub:
                self.counts[i] += n
                break
        self.count += n
        self.sum += v * n

    def percentile(self, q: float) -> float:
        """Value at quantile `q` (0..1) from the bucket upper bounds. The
        open-ended bucket reports its lower bound (the last finite edge) —
        an honest floor rather than an invented ceiling."""
        if self.count <= 0:
            return 0.0
        target = q * self.count
        acc = 0
        for i, n in enumerate(self.counts):
            acc += n
            if acc >= target and n:
                if math.isfinite(BUCKETS_S[i]):
                    return BUCKETS_S[i]
                return BUCKETS_S[i - 1] if i else 0.0
        return BUCKETS_S[-2]

    def merge(self, other: "Hist"):
        for i, n in enumerate(other.counts):
            self.counts[i] += n
        self.count += other.count
        self.sum += other.sum

    def flat(self, metric: str, path: str = "all") -> dict[str, float]:
        """This histogram under the GetMetrics key scheme
        `hist_<metric>__<path>__{b<i>,count,sum}` (double underscores so
        `parse_flat` splits unambiguously); zero buckets are skipped to
        keep the map small, count and sum are always there."""
        base = f"hist_{metric}__{path}__"
        out = {base + f"b{i}": float(n)
               for i, n in enumerate(self.counts) if n}
        out[base + "count"] = float(self.count)
        out[base + "sum"] = self.sum
        return out


class SLORegistry:
    """Histograms keyed (metric, path). The creation path takes a lock once
    per new key; established keys observe lock-free."""

    def __init__(self):
        self._hists: dict[tuple[str, str], Hist] = {}
        self._lock = lockdep_lock("telemetry.slo")

    def observe(self, metric: str, path: str, v: float, n: int = 1):
        h = self._hists.get((metric, path))
        if h is None:
            with self._lock:
                h = self._hists.setdefault((metric, path), Hist())
        h.observe(v, n)

    def reset(self):
        """Drop all samples (after warmup/prewarm, whose synthetic requests
        would pollute the serving percentiles)."""
        with self._lock:
            self._hists.clear()

    def merged(self, metric: str) -> Hist:
        """All paths of one metric folded together (the headline numbers)."""
        out = Hist()
        for (m, _), h in list(self._hists.items()):
            if m == metric:
                out.merge(h)
        return out

    def flat(self) -> dict[str, float]:
        """Flatten onto the GetMetrics str→double surface (Hist.flat's key
        scheme), plus the derived headline keys ttft_ms_p50 / ttft_ms_p95
        from the merged TTFT histogram."""
        out: dict[str, float] = {}
        for (metric, path), h in list(self._hists.items()):
            out.update(h.flat(metric, path))
        ttft = self.merged("ttft")
        if ttft.count:
            out["ttft_ms_p50"] = ttft.percentile(0.50) * 1e3
            out["ttft_ms_p95"] = ttft.percentile(0.95) * 1e3
        return out

    def snapshot(self) -> dict:
        """Structured percentile snapshot for /debug/slo: per (metric, path)
        and per-metric merged p50/p95/p99 + count + mean, in ms."""
        return snapshot_from_hists(dict(self._hists))


# ------------------------------------------------------- flat round-trip

def parse_flat(metrics: dict[str, float]) -> dict[tuple[str, str], Hist]:
    """Rebuild (metric, path) → Hist from a GetMetrics map containing
    `hist_*` keys (the scrape side of the process boundary)."""
    hists: dict[tuple[str, str], Hist] = {}
    for key, v in metrics.items():
        if not key.startswith("hist_"):
            continue
        parts = key[5:].split("__")
        if len(parts) != 3:
            continue
        metric, path, kind = parts
        h = hists.setdefault((metric, path), Hist())
        if kind == "count":
            h.count = int(v)
        elif kind == "sum":
            h.sum = float(v)
        elif kind.startswith("b"):
            try:
                i = int(kind[1:])
            except ValueError:
                continue
            if 0 <= i < len(BUCKETS_S):
                h.counts[i] = int(v)
    return hists


def snapshot_from_hists(hists: dict[tuple[str, str], Hist]) -> dict:
    """Percentile snapshot (ms) from a (metric, path) → Hist map — shared by
    the in-process registry and the scrape-side /debug/slo handler."""
    out: dict = {}
    for metric in METRICS:
        merged = Hist()
        paths = {}
        for (m, path), h in hists.items():
            if m != metric or not h.count:
                continue
            merged.merge(h)
            paths[path] = _quantiles_ms(h)
        if not merged.count:
            continue
        entry = _quantiles_ms(merged)
        if paths:
            entry["by_path"] = paths
        out[metric] = entry
    return out


def _quantiles_ms(h: Hist) -> dict:
    return {
        "count": h.count,
        "mean_ms": (h.sum / h.count) * 1e3 if h.count else 0.0,
        "p50_ms": h.percentile(0.50) * 1e3,
        "p95_ms": h.percentile(0.95) * 1e3,
        "p99_ms": h.percentile(0.99) * 1e3,
    }


# ------------------------------------------------------- process singleton

_SLO: SLORegistry | None = None
_SLO_LOCK = lockdep_lock("telemetry.slo_init")


def maybe_slo() -> SLORegistry | None:
    """The process-wide SLO registry, or None when disabled — the engine
    stores the result once so its hot path pays one attribute load."""
    global _SLO
    if not metrics_enabled():
        return None
    if _SLO is None:
        with _SLO_LOCK:
            if _SLO is None:
                _SLO = SLORegistry()
    return _SLO


# ----------------------------------------------------------- compile counter

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileCounter:
    """XLA backend compiles of this process and their seconds, by jitted
    function. JAX reports the backend-compile duration around
    `compile_or_get_cached`, so a hit in the persistent cache fires it too;
    the cache's own retrieval event comes first on the same thread and
    marks that one as not a compile. An in-memory jit-cache hit fires
    nothing. Unlike the count of new cache files this sees compiles under
    the persistent cache's 1 s threshold."""

    MAX_NAMES = 64   # jitted functions are a few dozen; the rest pool

    def __init__(self):
        self.counts: dict[str, float] = {
            "xla_compiles_total": 0, "xla_compile_ms_total": 0.0}
        self._cached = threading.local()
        # compiles come from the load thread and the engine thread; the
        # listener runs once a compile, never on the request path
        self._lock = lockdep_lock("telemetry.compiles")

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _CACHE_RETRIEVAL:
            self._cached.hit = True
            return
        if event != _BACKEND_COMPILE:
            return
        if getattr(self._cached, "hit", False):
            self._cached.hit = False
            return
        name = str(kw.get("fun_name") or "unnamed")
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]            # JAX says jit(_loop): the function's
        key = f"xla_compiles__{name}"
        with self._lock:
            c = self.counts
            c["xla_compiles_total"] += 1
            c["xla_compile_ms_total"] += duration * 1e3
            if key not in c and len(c) >= self.MAX_NAMES + 2:
                key = "xla_compiles__other"
            c[key] = c.get(key, 0) + 1

    def flat(self) -> dict[str, float]:
        with self._lock:
            return dict(self.counts)


_COMPILES: CompileCounter | None = None
_COMPILES_LOCK = lockdep_lock("telemetry.compiles_init")


def compile_counter() -> CompileCounter:
    """The process-wide compile counter; the first call registers its
    listener with jax.monitoring (a listener cannot be taken back, so there
    is exactly one). Backend processes only: it imports jax."""
    global _COMPILES
    if _COMPILES is None:
        with _COMPILES_LOCK:
            if _COMPILES is None:
                import jax.monitoring

                c = CompileCounter()
                jax.monitoring.register_event_duration_secs_listener(
                    c.on_duration)
                _COMPILES = c
    return _COMPILES


# ----------------------------------------------------------- flight recorder

class FlightRecorder:
    """Bounded rings of recent serving history + auto post-mortem dumps.

    Three rings (deque appends are GIL-atomic; the lock guards only dump
    composition): `requests` — finished request timelines; `ticks` —
    coarse engine-tick summaries; `events` — tripwire / breaker /
    supervision / fatal events. `auto_dump` writes the whole state to
    LOCALAI_FLIGHTREC_DIR (default: the system temp dir), capped so a
    crash loop can't fill the disk."""

    MAX_AUTO_DUMPS = 8

    def __init__(self, requests: int = 256, ticks: int = 256,
                 events: int = 512):
        self.requests: collections.deque = collections.deque(maxlen=requests)
        self.ticks: collections.deque = collections.deque(maxlen=ticks)
        self.events: collections.deque = collections.deque(maxlen=events)
        self._lock = lockdep_lock("telemetry.flightrec")
        self._dumps = 0
        self.last_dump_path = ""

    def record_request(self, timeline: dict):
        self.requests.append(timeline)

    def record_tick(self, summary: dict):
        self.ticks.append(summary)

    def record_event(self, kind: str, **fields):
        e = {"kind": kind, "t_wall": time.time(), **fields}
        if "tick" not in e:
            # stamp the live engine's tick id (ISSUE 13) so breaker/reap/
            # tripwire events correlate with the scheduler tick stream; the
            # import is deferred — sched imports this module
            from localai_tpu.telemetry.sched import current_tick

            tick = current_tick()
            if tick is not None:
                e["tick"] = tick
        self.events.append(e)

    def dump(self) -> dict:
        with self._lock:
            return {
                "pid": os.getpid(),
                "t_wall": time.time(),
                "requests": list(self.requests),
                "ticks": list(self.ticks),
                "events": list(self.events),
                "auto_dumps": self._dumps,
                "last_dump_path": self.last_dump_path,
            }

    def auto_dump(self, reason: str) -> str:
        """Write a post-mortem JSON file; returns its path ("" when the cap
        is hit or the write fails — a dying process must not die harder
        because its black box couldn't be written)."""
        with self._lock:
            if self._dumps >= self.MAX_AUTO_DUMPS:
                return ""
            self._dumps += 1
            n = self._dumps
        d = os.environ.get("LOCALAI_FLIGHTREC_DIR") or tempfile.gettempdir()
        path = os.path.join(
            d, f"localai_flightrec_{os.getpid()}_{n}_{reason}.json")
        payload = self.dump()
        payload["reason"] = reason
        try:
            os.makedirs(d, exist_ok=True)
            with open(path, "w") as fh:
                json.dump(payload, fh, default=str)
        except OSError:
            return ""
        self.last_dump_path = path
        return path


_FLIGHTREC: FlightRecorder | None = None
_FLIGHTREC_LOCK = lockdep_lock("telemetry.flightrec_init")


def flightrec() -> FlightRecorder:
    """The process-wide flight recorder (always available — event recording
    is a deque append on rare paths; request/tick recording is gated by the
    callers on the same enable flag as the histograms)."""
    global _FLIGHTREC
    if _FLIGHTREC is None:
        with _FLIGHTREC_LOCK:
            if _FLIGHTREC is None:
                _FLIGHTREC = FlightRecorder()
    return _FLIGHTREC


def reset_flightrec() -> None:
    """Test hook: fresh recorder (ring contents and the auto-dump cap are
    process-global otherwise)."""
    global _FLIGHTREC
    _FLIGHTREC = None
