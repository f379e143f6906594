"""Model lifecycle: spawn backend processes, health-poll, load, reap, watchdog.

The reference's L3 (/root/reference/pkg/model): mutex-guarded model map
(loader.go:22-41), spawn on a free localhost port + health poll + LoadModel
RPC (process.go:93-160, initializers.go:50-154), dead-process reap on cache
hit (loader.go:191-225), busy/idle watchdog (watchdog.go:19-49), single-active
-backend serialization (initializers.go:205-226).

Resilience layer (ISSUE 4): loads serialize per MODEL (a 120 s spawn of model
A no longer freezes model B), dead children are detected immediately and
respawned on a fresh port (the free_port TOCTOU race), a per-backend circuit
breaker stops respawn storms, and `supervised()` retries request-time
UNAVAILABLE/dead-backend failures with capped backoff — translating watchdog
reaps and breaker rejections into typed errors the HTTP layer maps to
504/503.
"""
from __future__ import annotations

import collections
import json
import os
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field

import grpc

from localai_tpu.backend.client import BackendClient
from localai_tpu.config import AppConfig, ModelConfig
from localai_tpu.core import resilience
from localai_tpu.core.resilience import (
    BackendUnavailable, CircuitBreaker, DeadlineExceeded, WatchdogReaped,
    backoff,
)
from localai_tpu.testing.lockdep import lockdep_lock


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _terminate(proc: subprocess.Popen, grace: float = 10.0):
    """SIGTERM, then SIGKILL after `grace` (the forced-shutdown escape hatch,
    process.go:29-43) — and wait either way: a chip belongs to one process at
    a time, so a backend is only gone once it has been reaped. A backend
    SIGTERMed mid-LoadModel does not exit on its own (the load runs on a
    worker thread the interpreter joins at exit)."""
    if proc.poll() is not None:
        return
    proc.terminate()
    try:
        proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


class SpawnCrashed(RuntimeError):
    """The backend child exited before ever answering health — either it
    crashed at startup or lost the free_port TOCTOU race (another process
    bound the port between close() and the child's bind). Retriable on a
    fresh port without burning the whole health budget."""


@dataclass
class BackendHandle:
    name: str
    config: ModelConfig
    proc: subprocess.Popen
    client: BackendClient
    port: int
    busy: int = 0                 # in-flight requests
    last_used: float = field(default_factory=time.monotonic)
    busy_since: float = 0.0
    device: dict = field(default_factory=dict)   # the backend's device report
                                  # (Status.device_json) as of its load —
                                  # the control plane's only device facts
    poisoned: str = ""            # terminal reason stamped by the reaper —
                                  # in-flight requests that now fail their
                                  # RPC surface THIS instead of a raw
                                  # severed-channel grpc error
    _lock: threading.Lock = field(
        default_factory=lambda: lockdep_lock("manager.handle"))

    def alive(self) -> bool:
        return self.proc.poll() is None

    def poison(self, reason: str):
        if reason and not self.poisoned:
            self.poisoned = reason

    def mark_busy(self):
        with self._lock:
            if self.busy == 0:
                self.busy_since = time.monotonic()
            self.busy += 1

    def mark_idle(self):
        with self._lock:
            self.busy = max(0, self.busy - 1)
            self.last_used = time.monotonic()


class ModelManager:
    """name → running backend process; the control plane's only way to reach
    model compute."""

    def __init__(self, app: AppConfig):
        self.app = app
        self._models: dict[str, BackendHandle] = {}
        self._lock = lockdep_lock("manager.map")  # guards the maps only —
                                               # never held across
                                               # spawn/health/RPC
        self._model_locks: dict[str, threading.Lock] = {}
        self._breakers: dict[str, CircuitBreaker] = {}
        # supervision telemetry: (model, event) → count, scraped into the
        # localai_backend_supervision_total Prometheus gauge
        self.events: collections.Counter = collections.Counter()
        self._watchdog: threading.Thread | None = None
        self._stop = threading.Event()

    def _model_lock(self, name: str) -> threading.Lock:
        with self._lock:
            lk = self._model_locks.get(name)
            if lk is None:
                lk = self._model_locks[name] = lockdep_lock(
                    "manager.model", per_key=True)
            return lk

    def breaker(self, name: str) -> CircuitBreaker:
        with self._lock:
            br = self._breakers.get(name)
            if br is None:
                br = self._breakers[name] = CircuitBreaker(
                    threshold=getattr(self.app, "breaker_threshold", 3),
                    cooldown=getattr(self.app, "breaker_cooldown", 15.0),
                    name=name)
            return br

    # ------------------------------------------------------------ spawn/load

    def _spawn_once(self, cfg: ModelConfig) -> BackendHandle:
        port = free_port()
        env = dict(os.environ)
        # child must import localai_tpu regardless of the parent's cwd;
        # existing PYTHONPATH entries are the operator's — prepend, never
        # replace
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        parts = [pkg_root] + [
            p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
        # chaos-harness targeting: fault specs may scope to one model name
        # (localai_tpu/testing/faults.py) — stamp the child so they can
        env["LOCALAI_FAULT_MODEL"] = cfg.name
        # preemption grace (ISSUE 19): how long the backend's SIGTERM
        # fast-path lets live slots run before force-freezing them
        grace = getattr(self.app, "preempt_grace", 0.0) or 0.0
        if grace:
            env["LOCALAI_PREEMPT_GRACE"] = str(grace)
        # gallery-installed external backend? its run.sh owns the process
        # (reference initializers.go:50-99 — external backends launch from
        # the backends dir); in-tree roles spawn the python module
        external = None
        if self.app.backends_path:
            from localai_tpu.services.backend_gallery import (
                resolve_backend_dir,
            )

            external = resolve_backend_dir(self.app.backends_path,
                                           cfg.backend)
        if external is not None:
            argv = ["/bin/sh", os.path.join(external, "run.sh"),
                    "--addr", f"127.0.0.1:{port}"]
            cwd = external
        else:
            argv = [sys.executable, "-m", "localai_tpu.backend",
                    "--addr", f"127.0.0.1:{port}", "--backend", cfg.backend]
            # inherit the parent's cwd: a relative --models-path must resolve
            # against the launch dir, not the backends dir
            cwd = None
        proc = subprocess.Popen(
            argv,
            env=env,
            cwd=cwd,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        # tail child output into our log (reference process.go:140-157)
        threading.Thread(target=self._tail, args=(cfg.name, proc),
                         daemon=True).start()
        client = BackendClient(f"127.0.0.1:{port}")
        budget = getattr(self.app, "spawn_timeout", 120.0) or 120.0
        deadline = time.monotonic() + budget
        ready = False
        while time.monotonic() < deadline:
            if client.health(timeout=2.0, wait=True):
                ready = True
                break
            if proc.poll() is not None:
                # dead child: don't sit out the rest of the health budget —
                # either a startup crash or the port TOCTOU race; the caller
                # retries on a fresh port
                client.close()
                raise SpawnCrashed(
                    f"backend for {cfg.name} exited rc={proc.returncode} "
                    f"before becoming healthy (port {port})")
            time.sleep(0.25)
        if not ready:
            client.close()
            # the child may already hold the chip: it must be gone before a
            # respawn can take it
            _terminate(proc)
            raise RuntimeError(
                f"backend for {cfg.name} never became healthy "
                f"within {budget:.0f}s")
        return BackendHandle(name=cfg.name, config=cfg, proc=proc,
                             client=client, port=port)

    def _spawn(self, cfg: ModelConfig) -> BackendHandle:
        """Spawn with fresh-port retries when the child dies before health —
        a crashing backend fails in seconds, not spawn_timeout."""
        retries = max(0, getattr(self.app, "spawn_retries", 2))
        last: Exception | None = None
        for attempt in range(retries + 1):
            try:
                return self._spawn_once(cfg)
            except SpawnCrashed as e:
                last = e
                if attempt < retries:
                    self.events[(cfg.name, "spawn_retry")] += 1
        raise last

    @staticmethod
    def _tail(name: str, proc: subprocess.Popen):
        for line in proc.stdout or []:
            # stderr, not stdout: tools with a machine-readable stdout
            # contract (bench.py's one-JSON-line output) embed the manager
            print(f"[backend:{name}] {line.rstrip()}", file=sys.stderr,
                  flush=True)

    def _load_rpc(self, handle: BackendHandle):
        cfg = self.app
        m = handle.config
        # fields without a proto slot ride the ModelOptions.options JSON
        # blob (the hfapi backend's endpoint override uses the same lane)
        opts = {}
        kv_policy = m.kv_policy
        if not kv_policy and cfg.kv_window:
            # app-wide --kv-window default for models without their own
            # kv_policy (per-model YAML wins)
            kv_policy = (f"sink_window(sinks={cfg.kv_sinks}, "
                         f"window={cfg.kv_window})")
        if kv_policy:
            opts["kv_policy"] = kv_policy
        if m.kv_cold_pages:
            opts["kv_cold_pages"] = m.kv_cold_pages
        kv_host_bytes = m.kv_host_bytes or cfg.kv_host_bytes
        if kv_host_bytes:
            opts["kv_host_bytes"] = kv_host_bytes
        r = handle.client.load_model(
            options=json.dumps(opts) if opts else "",
            model=m.model_dir(cfg.models_path),
            context_size=m.context_size or cfg.context_size,
            parallel=m.parallel or cfg.parallel_requests,
            dtype=m.dtype,
            prefill_buckets=m.prefill_buckets,
            mesh_data=m.mesh.data,
            # per-model YAML mesh wins; else the app-wide --tensor-parallel
            # degree (0 = backend auto-TP over every divisible device)
            mesh_model=m.mesh.model or cfg.tensor_parallel,
            embeddings=m.embeddings or m.backend == "embedding",
            draft_model=(m.draft_model if not m.draft_model
                         or os.path.isabs(m.draft_model)
                         else os.path.join(cfg.models_path, m.draft_model)),
            n_draft=m.n_draft,
            cache_type_key=m.cache_type_k,
            cache_type_value=m.cache_type_v,
            kv_pages=m.kv_pages,
        )
        if not r.success:
            raise RuntimeError(f"LoadModel({m.name}) failed: {r.message}")
        try:
            handle.device = json.loads(
                handle.client.status().device_json or "{}")
        except grpc.RpcError:
            # external (gallery-installed) backends may not implement Status
            handle.device = {}

    # ------------------------------------------------------------ public api

    def load(self, cfg: ModelConfig) -> BackendHandle:
        """Get-or-start the backend for a model config. Health-rechecks cached
        processes and reaps+respawns dead ones (loader.go:191-225).

        Serialization is per model: concurrent loads of the SAME model share
        one spawn; a load of model B proceeds while model A is mid-spawn
        (the seed held one global lock through the whole 120 s health wait).
        The circuit breaker fails fast once a model has proven broken."""
        h = self.get(cfg.name)
        if h is not None and h.alive() and h.client.health(timeout=5.0):
            h.last_used = time.monotonic()
            return h
        br = self.breaker(cfg.name)
        if not br.allow():
            self.events[(cfg.name, "breaker_reject")] += 1
            raise BackendUnavailable(
                f"circuit breaker open for {cfg.name!r} after repeated "
                f"backend failures; next probe in {br.retry_after():.1f}s",
                retry_after=max(br.retry_after(), 0.1))
        with self._model_lock(cfg.name):
            # somebody may have finished the same load while we waited
            h = self.get(cfg.name)
            if h is not None:
                # lint: allow(lock-across-blocking) — the per-MODEL lock is
                # the load-serialization point by design (PR 4): it blocks
                # only same-model loads; the map lock is never held here
                if h.alive() and h.client.health(timeout=5.0):
                    h.last_used = time.monotonic()
                    br.record_success()
                    return h
                # lockdep: allow(lock-blocking) — reap of the dead handle
                # (proc.wait) stays under the per-MODEL lock so the respawn
                # below can't race a half-dead predecessor
                self._reap(h, reason="dead backend found at load")
                self.events[(cfg.name, "reap_dead")] += 1
            if self.app.single_active_backend:
                with self._lock:
                    others = [o for o in self._models.values()
                              if o.name != cfg.name]
                for other in others:
                    # lockdep: allow(lock-blocking) — evicting the previous
                    # backend (proc.wait) must finish before this model's
                    # load proceeds; only same-model loads wait on us
                    self._reap(other, reason="single_active_backend")
            h = None
            try:
                # lockdep: allow(lock-blocking) — spawn + health poll + the
                # load RPC run under the per-MODEL lock on purpose: this IS
                # the load-serialization point (PR 4 moved the blocking off
                # the map lock, not off this one)
                h = self._spawn(cfg)
                # lockdep: allow(lock-blocking) — same: load RPC serialized
                # per model by design
                self._load_rpc(h)
            except Exception:
                br.record_failure()
                if h is not None:
                    # lockdep: allow(lock-blocking) — reaping the failed
                    # spawn (proc.wait) before releasing the load lock keeps
                    # the port/process accounting consistent for the retry
                    self._reap(h, reason="load failed")
                raise
            br.record_success()
            with self._lock:
                self._models[cfg.name] = h
            return h

    def get(self, name: str) -> BackendHandle | None:
        with self._lock:
            return self._models.get(name)

    def loaded(self) -> list[str]:
        with self._lock:
            return sorted(self._models)

    def devices(self) -> dict[str, dict]:
        """model → the device report its backend gave at load."""
        with self._lock:
            return {n: h.device for n, h in sorted(self._models.items())}

    # reap reasons that are routine lifecycle, not failures — they go in the
    # flight-recorder ring but do not trigger a post-mortem dump
    _GRACEFUL_REAPS = ("stopped by request", "drained for shutdown",
                      "server shutdown", "single_active_backend", "preempted")

    def _reap(self, h: BackendHandle, reason: str = ""):
        """Remove (if current) + terminate one backend. Safe to call from any
        thread; never holds the map lock across the process wait."""
        from localai_tpu import telemetry

        rec = telemetry.flightrec()
        rec.record_event("backend_reaped", model=h.name, reason=reason)
        if not reason.startswith(self._GRACEFUL_REAPS):
            rec.auto_dump(f"backend_reaped:{h.name}")
        with self._lock:
            if self._models.get(h.name) is h:
                del self._models[h.name]
        h.poison(reason)
        h.client.close()
        _terminate(h.proc)

    def stop_model(self, name: str) -> bool:
        h = self.get(name)
        if h is None:
            return False
        self._reap(h, reason="stopped by request")
        return True

    def preempt_model(self, name: str, grace: float | None = None) -> bool:
        """Preemption notice (ISSUE 19): SIGTERM the backend so its server
        runs the spill-drain fast-path — live slots freeze into ResumeTokens
        that flush through their open streams — then reap. Unlike
        `drain_model` this does NOT wait for requests to finish: the point
        is to checkpoint them mid-flight."""
        import signal as _signal

        h = self.get(name)
        if h is None:
            return False
        if grace is None:
            grace = getattr(self.app, "preempt_grace", 0.0) or 0.0
        from localai_tpu import telemetry

        telemetry.flightrec().record_event("backend_preempt", model=name,
                                           grace=grace)
        self.events[(name, "preempt")] += 1
        if h.alive():
            h.proc.send_signal(_signal.SIGTERM)
            try:
                # spill-drain budget: the grace window plus headroom for the
                # D2H spills themselves; a wedged child falls through to the
                # reap's terminate/kill escalation
                h.proc.wait(timeout=grace + 30.0)
            except subprocess.TimeoutExpired:
                pass
        self._reap(h, reason="preempted")
        return True

    def drain_model(self, name: str, timeout: float = 30.0) -> bool:
        """Graceful stop: wait for the backend's in-flight requests to finish
        (up to `timeout`), then reap — instead of severing mid-generation."""
        h = self.get(name)
        if h is None:
            return False
        deadline = time.monotonic() + max(timeout, 0.0)
        while h.busy > 0 and time.monotonic() < deadline and h.alive():
            time.sleep(0.05)
        self._reap(h, reason="drained for shutdown")
        return True

    def stop_all(self):
        self._stop.set()
        with self._lock:
            handles = list(self._models.values())
        for h in handles:
            self._reap(h, reason="server shutdown")

    # ------------------------------------------------------------ supervision

    def classify_failure(self, handle: BackendHandle,
                         exc: Exception) -> tuple[bool, Exception]:
        """Turn a request-time failure into (retriable?, translated error).

        Poisoned handle (watchdog/shutdown reap) → the reap reason as a 504,
        never retried: the reaper acted deliberately and a retry would just
        stall again. Dead process → reap + retriable 503 (the next load()
        respawns). Live backend returning UNAVAILABLE → retriable 503.
        Everything else passes through untranslated."""
        code = exc.code() if isinstance(exc, grpc.RpcError) else None
        if handle.poisoned:
            return False, WatchdogReaped(
                f"backend for {handle.name!r} was reaped mid-request "
                f"({handle.poisoned})")
        dead = not handle.alive()
        if not dead and code == grpc.StatusCode.UNAVAILABLE:
            # a severed channel can surface UNAVAILABLE before the child's
            # death is observable (Popen.poll even reports None while
            # another thread holds the wait lock) — give the process table
            # a grace beat before classifying the backend as alive
            deadline = time.monotonic() + 0.5
            while not dead and time.monotonic() < deadline:
                time.sleep(0.05)
                dead = not handle.alive()
        if dead:
            self._reap(handle, reason="died mid-request")
            self.events[(handle.name, "died_midrequest")] += 1
            return True, BackendUnavailable(
                f"backend for {handle.name!r} died mid-request "
                f"(rc={handle.proc.returncode})")
        if code == grpc.StatusCode.UNAVAILABLE:
            self.events[(handle.name, "unavailable_alive")] += 1
            self.breaker(handle.name).record_failure()
            return True, BackendUnavailable(
                f"backend for {handle.name!r} unavailable: "
                f"{exc.details() if hasattr(exc, 'details') else exc}")
        if code == grpc.StatusCode.DEADLINE_EXCEEDED:
            return False, DeadlineExceeded(
                f"backend call for {handle.name!r} exceeded the request "
                f"deadline")
        return False, exc

    def supervised(self, cfg: ModelConfig, op, *, retries: int | None = None):
        """Run `op(handle)` against a live backend, transparently respawning
        and retrying on dead/UNAVAILABLE backends with capped exponential
        backoff — the request-time half of backend supervision. Only safe
        for calls that have produced no client-visible bytes yet (unary RPCs
        and stream OPENS; the HTTP stream bridge enforces the no-bytes rule
        for streams). Busy accounting is owned here: every attempt is
        mark_busy/try/finally mark_idle."""
        if retries is None:
            retries = max(0, getattr(self.app, "retry_budget", 1))
        last: Exception | None = None
        for attempt in range(retries + 1):
            if attempt:
                time.sleep(backoff(attempt))
            rem = resilience.deadline_remaining()
            if rem is not None and rem <= 0:
                # the budget died (possibly mid-retry): a 504 tells the
                # client the truth — their deadline ran out — regardless of
                # what the last backend failure looked like
                raise DeadlineExceeded(
                    "request deadline exhausted before the backend call"
                    + (f" (last failure: {last})" if last else "")) from last
            handle = self.load(cfg)
            handle.mark_busy()
            try:
                return op(handle)
            except grpc.RpcError as e:
                retriable, err = self.classify_failure(handle, e)
                if not retriable or attempt >= retries:
                    raise err from e
                self.events[(cfg.name, "request_retry")] += 1
                last = err
            finally:
                handle.mark_idle()
        raise last  # pragma: no cover - loop always returns or raises

    # ------------------------------------------------------------ watchdog

    def start_watchdog(self, interval: float = 5.0):
        """Kill backends busy or idle past thresholds (watchdog.go:19-49)."""
        if self._watchdog or not (self.app.watchdog_idle_timeout
                                  or self.app.watchdog_busy_timeout):
            return
        self._watchdog = threading.Thread(
            target=self._watchdog_loop, args=(interval,), daemon=True)
        self._watchdog.start()

    def _watchdog_loop(self, interval: float):
        idle_t = self.app.watchdog_idle_timeout
        busy_t = self.app.watchdog_busy_timeout
        while not self._stop.wait(interval):
            now = time.monotonic()
            with self._lock:
                handles = list(self._models.values())
            for h in handles:
                if (busy_t and h.busy > 0
                        and now - h.busy_since > busy_t):
                    print(f"[watchdog] {h.name} busy > {busy_t}s — reaping",
                          flush=True)
                    self.events[(h.name, "watchdog_busy_reap")] += 1
                    # poison BEFORE the channel dies so in-flight requests
                    # fail with the watchdog named, not a raw RpcError
                    self._reap(h, reason=f"busy-watchdog: backend busy "
                                         f"longer than {busy_t:.0f}s")
                elif (idle_t and h.busy == 0
                        and now - h.last_used > idle_t):
                    print(f"[watchdog] {h.name} idle > {idle_t}s — reaping",
                          flush=True)
                    self.events[(h.name, "watchdog_idle_reap")] += 1
                    self._reap(h, reason=f"idle-watchdog: backend idle "
                                         f"longer than {idle_t:.0f}s")
