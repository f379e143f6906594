"""Chaos / resilience suite (ISSUE 4) — the fault-injection harness drives
the full HTTP→gRPC→engine stack through backend kill -9, injected
UNAVAILABLE, slow-start spawns, crash-at-spawn (the free_port TOCTOU shape),
overload shedding, watchdog busy-reaps, and graceful drain, asserting the
specified client-visible outcome for each (VERDICT Weak #7's ask and beyond).

Faults are declared once in LOCALAI_FAULT (localai_tpu/testing/faults.py),
scoped per model name and counted across process boundaries through
LOCALAI_FAULT_DIR, so each scenario is deterministic.
"""
import asyncio
import contextlib
import json
import os
import signal
import threading
import time

import pytest
import requests
import yaml

from fixtures import tiny_checkpoint

pytestmark = pytest.mark.resilience

# The heavyweight end-to-end scenarios (slow-start spawns, crash loops,
# stalled streams, drain waits) additionally carry the `slow` marker: the
# CI `resilience` job and the slow lane run them (`-m resilience` selects
# them regardless), while the tier-1 lane keeps only the cheap pieces —
# the fault sleeps must not eat the tier-1 time budget (ISSUE 4 satellite).

_FAULTS = ",".join([
    "unavailable:0:1:tiny",        # first Predict on tiny aborts UNAVAILABLE
    "slow_start:4::slowpoke",      # every slowpoke spawn stalls 4 s pre-health
    "spawn_crash:::crashy",        # crashy's backend always dies at spawn
    "spawn_crash:0:1:crashy2",     # crashy2 dies once, then spawns fine
    "stall_stream:30:1:staller1",  # first stream wedges 30 s after chunk 1
    "stall_stream:20:1:staller2",  # ditto (overload scenario)
    "stall_stream:30:1:wtiny",     # watchdog-reap scenario
    "stall_stream:1.5:1:dtiny",    # drain scenario: brief mid-stream stall
])


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(scope="module")
def faultenv(tmp_path_factory):
    fault_dir = str(tmp_path_factory.mktemp("faults"))
    old = {k: os.environ.get(k)
           for k in ("LOCALAI_FAULT", "LOCALAI_FAULT_DIR")}
    os.environ["LOCALAI_FAULT"] = _FAULTS
    os.environ["LOCALAI_FAULT_DIR"] = fault_dir
    yield fault_dir
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _write_model(models, name, ckpt, parallel=2):
    (models / f"{name}.yaml").write_text(yaml.safe_dump({
        "name": name,
        "backend": "llm",
        "context_size": 128,
        "parallel": parallel,
        "dtype": "float32",
        "prefill_buckets": [32, 64],
        "parameters": {"model": ckpt, "temperature": 0.0, "max_tokens": 8},
    }))


def _serve(app_cfg, models):
    """Spin up a real API server on a thread; returns (base, manager, api,
    stop)."""
    from aiohttp import web

    from localai_tpu.config import ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    configs = ModelConfigLoader(str(models))
    manager = ModelManager(app_cfg)
    api = API(app_cfg, configs, manager)
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(api.app)
        loop.run_until_complete(runner.setup())
        host, _, port = app_cfg.address.rpartition(":")
        site = web.TCPSite(runner, host, int(port))
        loop.run_until_complete(site.start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    base = f"http://{app_cfg.address}"
    for _ in range(50):
        try:
            requests.get(base + "/healthz", timeout=1)
            break
        except requests.ConnectionError:
            time.sleep(0.1)

    def stop():
        manager.stop_all()
        loop.call_soon_threadsafe(loop.stop)

    return base, manager, api, stop


@pytest.fixture(scope="module")
def stack(tmp_path_factory, faultenv):
    """Main chaos stack: tight resilience knobs, several fault-scoped
    models, real backend subprocesses."""
    from localai_tpu.config import AppConfig

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models")
    for name in ("tiny", "slowpoke", "crashy", "crashy2", "staller1"):
        _write_model(models, name, ckpt)
    _write_model(models, "staller2", ckpt, parallel=1)

    os.environ["JAX_PLATFORMS"] = "cpu"
    app_cfg = AppConfig(
        address=f"127.0.0.1:{_free_port()}", models_path=str(models),
        parallel_requests=2, queue_depth=0, retry_budget=1,
        breaker_threshold=2, breaker_cooldown=2.0,
        spawn_retries=1, spawn_timeout=60.0, drain_timeout=10.0)
    base, manager, api, stop = _serve(app_cfg, models)
    yield base, manager, api
    stop()


def _chat(base, model, n=3, stream=False, timeout=300, headers=None):
    return requests.post(base + "/v1/chat/completions", json={
        "model": model,
        "messages": [{"role": "user", "content": "the quick brown"}],
        "max_tokens": n,
        "stream": stream,
    }, stream=stream, timeout=timeout, headers=headers or {})


def _sse_events(resp):
    """Drain an SSE response into a list of parsed events (+ 'DONE')."""
    events = []
    for line in resp.iter_lines():
        if not line.startswith(b"data: "):
            continue
        payload = line[6:]
        events.append("DONE" if payload == b"[DONE]"
                      else json.loads(payload))
    return events


def _read_until_content(it):
    """Advance an SSE line iterator until a non-empty content delta has
    arrived (i.e. generation bytes have provably reached this client —
    the stall faults wedge the backend right after that first text
    chunk). Returns True when one was seen."""
    for line in it:
        if not line.startswith(b"data: "):
            continue
        payload = line[6:]
        if payload == b"[DONE]":
            return False
        obj = json.loads(payload)
        choices = obj.get("choices") or []
        if choices and choices[0].get("delta", {}).get("content"):
            return True
    return False


# ----------------------------------------------------------- unit pieces


def test_circuit_breaker_transitions():
    from localai_tpu.core.resilience import CircuitBreaker

    t = [0.0]
    br = CircuitBreaker(threshold=2, cooldown=5.0, clock=lambda: t[0])
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert br.allow()
    br.record_failure()
    assert not br.allow() and br.state == "open"
    assert 4.0 < br.retry_after() <= 5.0
    t[0] = 5.1
    assert br.state == "half_open" and br.allow()
    br.record_failure()                      # failed probe → open again
    assert not br.allow()
    t[0] = 10.3
    assert br.allow()
    br.record_success()
    assert br.state == "closed" and br.retry_after() == 0.0


def test_deadline_contextvar_propagates_to_thread():
    from localai_tpu.core import resilience

    assert resilience.deadline_remaining() is None

    async def main():
        tok = resilience.set_deadline(5.0)
        try:
            rem = await asyncio.to_thread(resilience.deadline_remaining)
            assert rem is not None and 4.0 < rem <= 5.0
        finally:
            resilience.reset_deadline(tok)

    asyncio.run(main())
    assert resilience.deadline_remaining() is None


def test_admission_gate_sheds_and_recovers():
    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.core.resilience import RequestShed
    from localai_tpu.server.http import API

    app_cfg = AppConfig(queue_depth=0)
    api = API(app_cfg, None, ModelManager(app_cfg))
    cfg = ModelConfig(name="m", backend="llm", parallel=1)

    async def main():
        # one slot and two permits ahead of it (ISSUE 39): the gate is full
        # with `limit` = 3 requests inside
        assert api._gate(cfg).limit == 3
        async with contextlib.AsyncExitStack() as held:
            for _ in range(3):
                await held.enter_async_context(api._admit(cfg))
            with pytest.raises(RequestShed) as ei:
                async with api._admit(cfg):
                    pass
            assert ei.value.status == 429 and ei.value.model == "m"
            assert ei.value.reason == "queue_full"
        # permits released → admitted again
        async with api._admit(cfg):
            pass

    asyncio.run(main())


def test_admission_gate_bounded_queue():
    """depth=1: with every permit out one waiter queues (and runs once a
    permit comes back), the next is shed."""
    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.core.resilience import RequestShed
    from localai_tpu.server.http import API

    app_cfg = AppConfig(queue_depth=1)
    api = API(app_cfg, None, ModelManager(app_cfg))
    cfg = ModelConfig(name="m", backend="llm", parallel=1)
    order = []

    async def main():
        release = asyncio.Event()

        async def holder():
            async with api._admit(cfg):
                order.append("holder")
                await release.wait()

        async def waiter():
            async with api._admit(cfg):
                order.append("waiter")

        hs = [asyncio.create_task(holder())
              for _ in range(api._gate(cfg).limit)]
        await asyncio.sleep(0.05)
        w = asyncio.create_task(waiter())
        await asyncio.sleep(0.05)          # waiter now queued (depth 1 full)
        with pytest.raises(RequestShed):
            async with api._admit(cfg):
                pass
        release.set()
        await asyncio.gather(w, *hs)

    asyncio.run(main())
    assert order == ["holder"] * 3 + ["waiter"]


def test_federation_breaker_skips_open_worker():
    from localai_tpu.federation import FederatedServer

    srv = FederatedServer(["http://a", "http://b"])
    wa, wb = srv.workers
    for _ in range(3):
        wa.breaker.record_failure()
    assert wa.breaker.state == "open"
    for _ in range(10):
        assert srv.pick() is wb
    for _ in range(3):
        wb.breaker.record_failure()
    assert srv.pick() is not None        # never wedge with zero candidates


# ----------------------------------------------------------- engine-level


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from localai_tpu.engine import (
        Engine, EngineConfig, Tokenizer, load_config, load_params,
    )

    ckpt = tiny_checkpoint(tmp_path_factory, max_position=2048)
    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    eng = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=2048, prefill_buckets=(32,),
        prefill_chunk=32))
    eng.start()
    yield eng, tok
    eng.stop()


def test_engine_evicts_expired_queued_request(engine):
    from localai_tpu.engine import GenRequest

    eng, tok = engine
    rid, out = eng.submit(GenRequest(
        prompt_ids=tok.encode("hello"), max_tokens=8,
        deadline=time.monotonic() - 0.5))
    o = out.get(timeout=30)
    assert o.finished and o.finish_reason == "timeout"
    assert o.generated_tokens == 0


def test_engine_evicts_expired_slot_mid_generation(engine):
    from localai_tpu.engine import GenRequest

    eng, tok = engine
    rid, out = eng.submit(GenRequest(
        prompt_ids=tok.encode("the quick"), max_tokens=100_000,
        ignore_eos=True, deadline=time.monotonic() + 0.4))
    t0 = time.monotonic()
    while True:
        o = out.get(timeout=60)
        if o.finished:
            break
    assert o.finish_reason == "timeout"
    assert time.monotonic() - t0 < 30    # evicted, not run to length
    assert 0 < o.generated_tokens < 100_000


def test_engine_cancel_evicts_slot(engine):
    from localai_tpu.engine import GenRequest

    eng, tok = engine
    rid, out = eng.submit(GenRequest(
        prompt_ids=tok.encode("pack my box"), max_tokens=100_000,
        ignore_eos=True))
    first = out.get(timeout=60)          # generation underway
    assert not first.finished
    eng.cancel(rid)
    while True:
        o = out.get(timeout=60)
        if o.finished:
            break
    assert o.finish_reason == "cancelled"
    assert o.generated_tokens < 100_000
    # bookkeeping drained: a finished/unknown rid cancel is a no-op
    eng.cancel(rid)
    assert rid not in eng._cancelled and rid not in eng._live


# --------------------------------------------------------- chaos: HTTP stack


def test_unavailable_unary_retried_transparently(stack):
    """Injected gRPC UNAVAILABLE on tiny's first Predict: the supervisor
    retries against the live backend and the client sees a clean 200."""
    base, manager, _ = stack
    r = _chat(base, "tiny", n=3)
    assert r.status_code == 200, r.text
    assert r.json()["usage"]["completion_tokens"] == 3
    assert manager.events[("tiny", "request_retry")] >= 1
    assert manager.get("tiny").busy == 0     # try/finally accounting held


@pytest.mark.slow
def test_load_of_b_not_blocked_by_slow_spawn_of_a(stack):
    """Per-model locks: slowpoke's 4 s slow-start spawn must not freeze
    tiny (the seed held ONE global lock through wait_ready)."""
    base, manager, _ = stack
    results = {}

    def spawn_slow():
        results["slow"] = _chat(base, "slowpoke", n=2, timeout=300)

    th = threading.Thread(target=spawn_slow)
    th.start()
    time.sleep(0.5)                       # slowpoke spawn is now in flight
    t0 = time.monotonic()
    r = _chat(base, "tiny", n=2)
    dt = time.monotonic() - t0
    assert r.status_code == 200
    assert dt < 3.0, f"tiny request waited {dt:.1f}s behind slowpoke's spawn"
    th.join(timeout=300)
    assert results["slow"].status_code == 200, results["slow"].text


@pytest.mark.slow
def test_crashing_backend_fails_fast_then_breaker_opens(stack):
    """crashy's backend dies at every spawn: the dead child is detected in
    seconds (not the 120 s health budget), the spawn retries on a fresh
    port, and after breaker_threshold failed loads the circuit opens —
    requests fail fast with 503 + Retry-After."""
    base, manager, _ = stack
    t0 = time.monotonic()
    r1 = _chat(base, "crashy", n=2, timeout=120)
    first_dt = time.monotonic() - t0
    assert r1.status_code == 500
    assert first_dt < 30, f"dead-child spawn burned {first_dt:.0f}s"
    assert manager.events[("crashy", "spawn_retry")] >= 1
    r2 = _chat(base, "crashy", n=2, timeout=120)
    assert r2.status_code == 500
    # breaker open (threshold 2) → instant 503, no spawn attempt
    t0 = time.monotonic()
    r3 = _chat(base, "crashy", n=2, timeout=30)
    assert r3.status_code == 503, r3.text
    assert time.monotonic() - t0 < 1.0
    assert "Retry-After" in r3.headers
    assert "circuit breaker" in r3.json()["error"]["message"]
    assert manager.events[("crashy", "breaker_reject")] >= 1


@pytest.mark.slow
def test_spawn_crash_once_recovers_on_fresh_port(stack):
    """The free_port TOCTOU shape: crashy2's child dies once (shared-count
    fault), the manager respawns on a new port within the same load() and
    the request succeeds."""
    base, manager, _ = stack
    r = _chat(base, "crashy2", n=3, timeout=300)
    assert r.status_code == 200, r.text
    assert manager.events[("crashy2", "spawn_retry")] == 1


@pytest.mark.slow
def test_kill9_midstream_clean_sse_error_then_respawn(stack):
    """VERDICT Weak #7: kill -9 mid-PredictStream → the client sees a clean
    terminal SSE error event (not a hung connection), the handle is reaped,
    and the next request respawns and succeeds."""
    base, manager, _ = stack
    r = _chat(base, "staller1", n=24, stream=True, timeout=(30, 60))
    it = r.iter_lines()
    assert _read_until_content(it)       # bytes streamed; backend now wedged
    h = manager.get("staller1")
    assert h is not None
    os.kill(h.proc.pid, signal.SIGKILL)
    h.proc.wait(timeout=10)
    tail = []
    for line in it:                      # stream MUST terminate cleanly
        if line.startswith(b"data: "):
            payload = line[6:]
            tail.append("DONE" if payload == b"[DONE]"
                        else json.loads(payload))
    assert tail and tail[-1] == "DONE", f"no clean terminal event: {tail}"
    errors = [e for e in tail if isinstance(e, dict) and "error" in e]
    assert errors, f"expected a terminal SSE error event, got {tail}"
    assert errors[-1]["error"]["code"] in (502, 503)
    # reaped on classification…
    assert manager.get("staller1") is None or \
        manager.get("staller1").proc.pid != h.proc.pid, \
        f"events={dict(manager.events)}"
    # …and the follow-up request respawns a fresh backend and completes
    r2 = _chat(base, "staller1", n=3, timeout=300)
    assert r2.status_code == 200, r2.text
    h2 = manager.get("staller1")
    assert h2 is not None and h2.proc.pid != h.proc.pid


@pytest.mark.slow
def test_overload_sheds_429_with_retry_after(stack):
    """staller2 (parallel=1, queue_depth=0): one wedged stream holds the
    slot; the next request is shed fast with 429 + Retry-After, and the
    shed shows up in localai_shed_total."""
    base, manager, _ = stack
    r1 = _chat(base, "staller2", n=16, stream=True, timeout=(30, 60))
    it = r1.iter_lines()
    assert _read_until_content(it)       # stream is live → slot held
    try:
        t0 = time.monotonic()
        r2 = _chat(base, "staller2", n=2, timeout=30)
        assert r2.status_code == 429, r2.text
        assert time.monotonic() - t0 < 1.0, "shed must fail FAST"
        assert "Retry-After" in r2.headers
        assert r2.json()["error"]["type"] == "overloaded_error"
        m = requests.get(base + "/metrics", timeout=30)
        assert b'localai_shed_total' in m.content
        assert b'model="staller2",reason="queue_full"' in m.content
    finally:
        r1.close()                       # cancels the wedged stream


def test_deadline_header_maps_to_504(stack):
    """X-Request-Timeout lowers the request budget; an impossible budget
    surfaces as 504 timeout_error — whether the RPC dies with gRPC
    DEADLINE_EXCEEDED or the budget evaporates during a supervised retry
    (e.g. tiny's injected-UNAVAILABLE fault, if still unconsumed)."""
    base, _, _ = stack
    # warm spawn so the deadline clock measures the RPC, not the load
    assert _chat(base, "tiny", n=2, timeout=300).status_code == 200
    r = _chat(base, "tiny", n=64, timeout=60,
              headers={"X-Request-Timeout": "0.02"})
    assert r.status_code == 504, r.text
    assert r.json()["error"]["type"] == "timeout_error"


# --------------------------------------------------- watchdog busy-reap 504


@pytest.fixture(scope="module")
def wd_stack(tmp_path_factory, faultenv):
    from localai_tpu.config import AppConfig

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models-wd")
    _write_model(models, "wtiny", ckpt)
    os.environ["JAX_PLATFORMS"] = "cpu"
    app_cfg = AppConfig(
        address=f"127.0.0.1:{_free_port()}", models_path=str(models),
        parallel_requests=2, watchdog_busy_timeout=1.5,
        spawn_timeout=60.0, retry_budget=1)
    base, manager, api, stop = _serve(app_cfg, models)
    manager.start_watchdog(interval=0.3)
    yield base, manager
    stop()


@pytest.mark.slow
def test_watchdog_busy_reap_names_watchdog_in_504(wd_stack):
    """A busy-watchdog reap must fail the in-flight stream with an explicit
    watchdog-named error event — not a raw severed-channel RpcError."""
    base, manager = wd_stack
    r = _chat(base, "wtiny", n=24, stream=True, timeout=(30, 60))
    events = _sse_events(r)              # wedged stream → watchdog reaps
    assert events and events[-1] == "DONE"
    errors = [e for e in events if isinstance(e, dict) and "error" in e]
    assert errors, f"no terminal error event: {events}"
    err = errors[-1]["error"]
    assert err["code"] == 504
    assert "watchdog" in err["message"].lower()
    assert manager.events[("wtiny", "watchdog_busy_reap")] >= 1


# --------------------------------------------------------- graceful drain


@pytest.fixture(scope="module")
def drain_stack(tmp_path_factory, faultenv):
    from localai_tpu.config import AppConfig

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models-drain")
    _write_model(models, "dtiny", ckpt)
    os.environ["JAX_PLATFORMS"] = "cpu"
    app_cfg = AppConfig(
        address=f"127.0.0.1:{_free_port()}", models_path=str(models),
        parallel_requests=2, drain_timeout=15.0, spawn_timeout=60.0)
    base, manager, api, stop = _serve(app_cfg, models)
    yield base, manager, api
    stop()


@pytest.mark.slow
def test_graceful_drain_finishes_inflight_rejects_new(drain_stack):
    """/backend/shutdown (the SIGTERM path drives the same _drain): the
    in-flight stream finishes under the drain deadline while concurrent new
    requests get 503, then every backend is stopped."""
    base, manager, api = drain_stack
    # warm the backend so the drain test measures serving, not spawn
    assert _chat(base, "dtiny", n=2).status_code == 200

    r1 = _chat(base, "dtiny", n=24, stream=True, timeout=(30, 60))
    it = r1.iter_lines()
    assert _read_until_content(it)      # mid-stream (stall holds it ~1.5 s)
    shut = {}

    def shutdown():
        shut["r"] = requests.post(base + "/backend/shutdown", json={},
                                  timeout=60)

    th = threading.Thread(target=shutdown)
    th.start()
    time.sleep(0.4)                      # drain flag is now up
    r2 = _chat(base, "dtiny", n=2, timeout=30)
    assert r2.status_code == 503, r2.text
    assert "Retry-After" in r2.headers

    tail = []
    for line in it:                      # in-flight stream runs to completion
        if line.startswith(b"data: "):
            payload = line[6:]
            tail.append("DONE" if payload == b"[DONE]"
                        else json.loads(payload))
    assert tail and tail[-1] == "DONE"
    assert not any(isinstance(e, dict) and "error" in e for e in tail), \
        f"drain severed the in-flight stream: {tail}"
    finals = [e for e in tail if isinstance(e, dict) and e.get("choices")
              and e["choices"][0].get("finish_reason")]
    assert finals, "stream ended without finish_reason"

    th.join(timeout=60)
    assert shut["r"].status_code == 200 and shut["r"].json()["success"]
    assert manager.loaded() == []        # backends stopped after the drain
    # the server stays up but sheds everything while draining
    r3 = _chat(base, "dtiny", n=2, timeout=30)
    assert r3.status_code == 503
