"""Low-overhead span tracer — the request-path observability core.

Spans are monotonic-clock intervals with ids/parents, recorded into a
preallocated ring buffer. Writes are lock-free: the ring index comes from
`itertools.count()` (whose `__next__` is atomic under the GIL) and each slot
assignment is a single list store, so the engine loop, gRPC handler threads
and the asyncio HTTP process can all record concurrently without contention.
A full ring overwrites the oldest spans — tracing never blocks or grows.

Everything is opt-in: with `LOCALAI_TRACE` unset the recording calls are
never reached (callers gate on `trace_enabled()` / a cached tracer handle),
so the serving hot path stays untouched.

The export format is Chrome-trace/Perfetto "trace event" JSON (`ph: "X"`
complete events): load the dump at chrome://tracing or ui.perfetto.dev.
Timestamps are perf_counter-based but rebased onto the wall clock at module
import, so spans recorded by different processes (HTTP server + backend
subprocesses) merge into one coherent timeline.

Request-id propagation: `new_request_id()` in the HTTP middleware →
`set_request_id()` contextvar → `current_request_id()` read by the gRPC
client when attaching `x-localai-request-id` metadata → the backend servicer
hands it to the engine via `GenRequest.trace_id` — every layer's spans carry
the same `request_id` arg.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import threading
import time
import uuid

from localai_tpu.testing.lockdep import lockdep_lock

# perf_counter → wall-clock rebasing (one constant per process): Chrome-trace
# `ts` fields from different processes line up on the same timeline
_EPOCH_US = time.time_ns() // 1000 - time.perf_counter_ns() // 1000

_REQUEST_ID: contextvars.ContextVar[str] = contextvars.ContextVar(
    "localai_request_id", default="")
_CURRENT_SPAN: contextvars.ContextVar["OpenSpan | None"] = \
    contextvars.ContextVar("localai_current_span", default=None)

# None = follow the environment; set_trace_enabled() overrides (tests, bench)
_FORCED: bool | None = None


def trace_enabled() -> bool:
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("LOCALAI_TRACE", "") not in ("", "0")


def set_trace_enabled(value: bool | None) -> None:
    """Force tracing on/off in-process (None = back to the env var)."""
    global _FORCED
    _FORCED = value


def new_request_id() -> str:
    return "req-" + uuid.uuid4().hex[:16]


def set_request_id(rid: str):
    """Bind `rid` to the current context; returns the reset token."""
    return _REQUEST_ID.set(rid)


def reset_request_id(token) -> None:
    _REQUEST_ID.reset(token)


def current_request_id() -> str:
    return _REQUEST_ID.get()


class OpenSpan:
    """A begun-but-unfinished span (finish() writes the ring event)."""
    __slots__ = ("sid", "name", "cat", "t0_ns", "parent_id", "args", "tid")

    def __init__(self, sid, name, cat, t0_ns, parent_id, args, tid):
        self.sid = sid
        self.name = name
        self.cat = cat
        self.t0_ns = t0_ns
        self.parent_id = parent_id
        self.args = args
        self.tid = tid


class Tracer:
    """Ring-buffer span recorder; one instance per process (see tracer())."""

    def __init__(self, capacity: int = 16384):
        self.capacity = max(64, capacity)
        self._ring: list[dict | None] = [None] * self.capacity
        self._slot = itertools.count()   # lock-free ring cursor
        self._ids = itertools.count(1)   # span ids (0 = no parent)
        self.pid = os.getpid()

    # ---------------------------------------------------------- recording

    def begin(self, name: str, cat: str = "", parent_id: int | None = None,
              args: dict | None = None) -> OpenSpan:
        if parent_id is None:
            cur = _CURRENT_SPAN.get()
            parent_id = cur.sid if cur is not None else 0
        return OpenSpan(next(self._ids), name, cat, time.perf_counter_ns(),
                        parent_id, dict(args) if args else {},
                        threading.get_native_id())

    def finish(self, span: OpenSpan, **extra) -> None:
        now = time.perf_counter_ns()
        if extra:
            span.args.update(extra)
        self._write(span.name, span.cat, span.t0_ns, now - span.t0_ns,
                    span.sid, span.parent_id, span.args, span.tid)

    def add_complete(self, name: str, t0: float, dur_s: float | None = None,
                     cat: str = "", parent_id: int = 0,
                     args: dict | None = None) -> None:
        """Record a finished interval from a perf_counter() start time."""
        t0_ns = int(t0 * 1e9)
        dur_ns = (time.perf_counter_ns() - t0_ns if dur_s is None
                  else int(dur_s * 1e9))
        self._write(name, cat, t0_ns, dur_ns, next(self._ids), parent_id,
                    dict(args) if args else {}, threading.get_native_id())

    def _write(self, name, cat, t0_ns, dur_ns, sid, parent_id, args, tid):
        args["span_id"] = sid
        if parent_id:
            args["parent_id"] = parent_id
        rid = _REQUEST_ID.get()
        if rid and "request_id" not in args:
            args["request_id"] = rid
        event = {
            "name": name, "cat": cat or "localai", "ph": "X",
            "ts": t0_ns // 1000 + _EPOCH_US,
            "dur": max(dur_ns // 1000, 0),
            "pid": self.pid, "tid": tid, "args": args,
        }
        self._ring[next(self._slot) % self.capacity] = event

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "", **args):
        """Context manager: nested spans parent automatically (contextvar)."""
        s = self.begin(name, cat, args=args)
        token = _CURRENT_SPAN.set(s)
        try:
            yield s
        finally:
            _CURRENT_SPAN.reset(token)
            self.finish(s)

    # ------------------------------------------------------------- export

    def events(self) -> list[dict]:
        """Snapshot the ring as Chrome-trace events, oldest first."""
        out = [e for e in list(self._ring) if e is not None]
        out.sort(key=lambda e: e["ts"])
        return out

    def clear(self) -> None:
        self._ring = [None] * self.capacity


_TRACER: Tracer | None = None
_TRACER_LOCK = lockdep_lock("telemetry.tracer_init")


def tracer() -> Tracer:
    """The process-wide tracer (created on first use)."""
    global _TRACER
    if _TRACER is None:
        with _TRACER_LOCK:
            if _TRACER is None:
                cap = int(os.environ.get("LOCALAI_TRACE_BUFFER", "16384"))
                _TRACER = Tracer(cap)
    return _TRACER


def maybe_tracer() -> Tracer | None:
    """tracer() when tracing is enabled, else None — the cheap gate callers
    cache so a disabled build never constructs or touches the ring."""
    return tracer() if trace_enabled() else None


@contextlib.contextmanager
def span(name: str, cat: str = "", **args):
    """Module-level convenience: no-op when tracing is disabled."""
    t = maybe_tracer()
    if t is None:
        yield None
        return
    with t.span(name, cat, **args) as s:
        yield s


class PhaseClock:
    """The engine thread's wall time, tiled into a fixed set of phases.

    The thread is in exactly one phase at any moment; `switch` closes the
    running phase and opens the next at ONE clock read, so the phases' sum
    is the wall time between any two switches by construction. Each closed
    phase goes to three sinks:

    (a) `metrics["engine_host_ms__<phase>"]` (host work) or
        `metrics["engine_wait_ms__<phase>"]` (waits), cumulative ms — the
        keys exist at 0.0 from construction, so a scrape always finds them;
    (b) a `jax.profiler.TraceAnnotation("engine.<phase>", tick=n)`, so any
        device trace of this process holds the phases on the trace's own
        clock (host tracer level 1); the first annotation of each tick also
        carries `unix_us`, which places ring spans of any process on that
        clock; the next annotation after `note` carries what the caller
        noted (the engine, while `device_trace` runs, at each decode
        dispatch: `rows_active`, `rows_prefill`, `rows_free`, `queued`;
        tools/trace_gaps.py prints them beside each idle gap and program).
        No trace running: about half a microsecond;
    (c) a ring span `engine.<phase>` when LOCALAI_TRACE is on — waits of the
        idle loop and phases under RING_MIN_S stay out of the ring, so an
        idle engine does not wash the request spans out of it.

    Cost with everything off: one clock read and one annotation per switch,
    a handful of switches per engine tick; nothing per token or per step."""

    HOST = ("dispatch", "admit", "emit", "kv")
    WAIT = ("device", "idle")
    RING_MIN_S = 100e-6

    def __init__(self, metrics: dict, tracer: Tracer | None = None):
        self._m = metrics
        self._keys = {p: f"engine_host_ms__{p}" for p in self.HOST}
        self._keys.update({p: f"engine_wait_ms__{p}" for p in self.WAIT})
        for k in self._keys.values():
            metrics[k] = 0.0
        self._tracer = tracer
        from jax.profiler import TraceAnnotation   # engine processes only

        self._annotation = TraceAnnotation
        self.phase = "idle"
        self.tick = 0
        self._stamped = -1       # the last tick whose annotation has unix_us
        self._noted = None       # note(): arguments for the next annotation
        self._open = None        # the running phase's annotation
        self._t0 = time.perf_counter()

    @staticmethod
    def tracing() -> bool:
        """True while `device_trace` traces this process (see `note`)."""
        return _XPROF_ON

    def note(self, **args) -> None:
        """Integers for the next annotation opened, on top of its tick: what
        the caller wants read beside the device's ops in a trace. Worth
        computing only while `tracing()`."""
        self._noted = args

    def switch(self, phase: str, tick: int | None = None) -> str:
        """Close the running phase, open `phase`; returns the one closed."""
        now = time.perf_counter()
        prev, t0 = self.phase, self._t0
        self._m[self._keys[prev]] += (now - t0) * 1e3
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        tr = self._tracer
        if tr is not None and prev != "idle" and now - t0 >= self.RING_MIN_S:
            tr.add_complete("engine." + prev, t0, now - t0, cat="engine",
                            args={"tick": self.tick})
        if tick is not None:
            self.tick = tick
        self.phase, self._t0 = phase, now
        if self.tick != self._stamped:
            self._stamped = self.tick
            a = self._annotation("engine." + phase, tick=self.tick,
                                 unix_us=int(now * 1e6) + _EPOCH_US)
        elif self._noted is not None:
            a = self._annotation("engine." + phase, tick=self.tick,
                                 **self._noted)
            self._noted = None
        else:
            a = self._annotation("engine." + phase, tick=self.tick)
        a.__enter__()
        self._open = a
        return prev

    @contextlib.contextmanager
    def within(self, phase: str):
        """`phase` for the block, then back to the phase it interrupted."""
        prev = self.switch(phase)
        try:
            yield
        finally:
            self.switch(prev)

    def total_ms(self) -> float:
        """Closed phases' sum; the running phase is not in it yet."""
        return sum(self._m[k] for k in self._keys.values())


XPROF_MAX_S = 10.0
_XPROF_LOCK = lockdep_lock("telemetry.xprof")
_XPROF_ON = False     # device_trace is tracing: PhaseClock.tracing()


def device_trace(seconds: float) -> dict:
    """Run the JAX profiler in THIS process for `seconds` (the process that
    holds the chip: only it can trace it) and return {"dir", "seconds",
    "xplane"}; Python tracer off, host tracer at 1, the lowest that keeps
    TraceAnnotation — so the trace holds the device's ops and the engine's
    `engine.<phase>` annotations on one clock (`tools/trace_gaps.py` reduces
    it). One at a time, at most XPROF_MAX_S; anything that goes wrong is
    {"error": ...} — it runs on a handler thread of its own and never
    raises into the serving path."""
    if not 0 < seconds <= XPROF_MAX_S:
        return {"error": f"seconds must be in (0, {XPROF_MAX_S:g}], "
                         f"got {seconds:g}"}
    if not _XPROF_LOCK.acquire(blocking=False):
        return {"error": "a device trace is already running"}
    try:
        import glob
        import tempfile

        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        global _XPROF_ON
        out = tempfile.mkdtemp(prefix="localai_xprof_")
        jax.profiler.start_trace(out, profiler_options=opts)
        _XPROF_ON = True
        try:
            time.sleep(seconds)
        finally:
            _XPROF_ON = False
            jax.profiler.stop_trace()
        return {"dir": out, "seconds": seconds, "pid": os.getpid(),
                "xplane": sorted(glob.glob(os.path.join(
                    out, "plugins", "profile", "*", "*.xplane.pb")))}
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    finally:
        _XPROF_LOCK.release()


def chrome_events() -> list[dict]:
    """This process's recorded spans (empty when tracing never started)."""
    return _TRACER.events() if _TRACER is not None else []


def chrome_trace(events: list[dict],
                 process_names: dict[int, str] | None = None) -> dict:
    """Wrap events into a self-contained Chrome-trace JSON object."""
    meta = []
    for pid, pname in (process_names or {}).items():
        meta.append({"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                     "args": {"name": pname}})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}
