"""Telemetry subsystem tests (ISSUE 2, ISSUE 24): tracer units, the engine
thread's phases and each request's TTFT stages, the per-dispatch and compile
counters, trace integrity under concurrent multi-slot serving through the
full HTTP→gRPC→engine stack, the device-trace endpoint, and the
disabled-path overhead guard.
"""
import json
import os
import threading
import time

import pytest
import requests
import yaml

from fixtures import tiny_checkpoint


# ------------------------------------------------------------------ units


def test_tracer_spans_parents_and_reparse():
    from localai_tpu.telemetry import Tracer, chrome_trace

    tr = Tracer(capacity=128)
    with tr.span("outer", kind="test") as outer:
        with tr.span("inner"):
            pass
    tr.add_complete("standalone", time.perf_counter() - 0.001)
    events = tr.events()
    assert {e["name"] for e in events} == {"inner", "outer", "standalone"}
    by_id = {e["args"]["span_id"]: e for e in events}
    inner = next(e for e in events if e["name"] == "inner")
    # parent resolves to the outer span
    assert by_id[inner["args"]["parent_id"]]["name"] == "outer"
    assert outer.sid == inner["args"]["parent_id"]
    # chrome-trace export re-parses and keeps every event well-formed
    dump = json.dumps(chrome_trace(events, {os.getpid(): "test"}))
    back = json.loads(dump)
    assert back["displayTimeUnit"] == "ms"
    for e in back["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["ts"] > 0 and e["pid"] and e["tid"]


def test_tracer_ring_wraps_without_growing():
    from localai_tpu.telemetry import Tracer

    tr = Tracer(capacity=64)
    t0 = time.perf_counter()
    for i in range(500):
        tr.add_complete(f"s{i}", t0, dur_s=0.0)
    events = tr.events()
    assert len(events) == 64
    names = {e["name"] for e in events}
    # exactly the newest 64 survive the wrap
    assert names == {f"s{i}" for i in range(436, 500)}


def test_tracer_concurrent_writers():
    from localai_tpu.telemetry import Tracer

    tr = Tracer(capacity=4096)

    def writer(k):
        t0 = time.perf_counter()
        for i in range(200):
            tr.add_complete(f"w{k}-{i}", t0, dur_s=0.0)

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    events = tr.events()
    assert len(events) == 1600
    # span ids stay unique across racing writers (the count() atomicity)
    ids = [e["args"]["span_id"] for e in events]
    assert len(set(ids)) == len(ids)


# ------------------------------------------------- engine instrumentation


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_checkpoint(tmp_path_factory)


def _engine(ckpt, **ec_kw):
    from localai_tpu.engine import (
        Engine, EngineConfig, Tokenizer, load_config, load_params,
    )

    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    return Engine(cfg, params, tok, EngineConfig(
        max_slots=4, max_context=128, prefill_buckets=(32, 64),
        prefill_chunk=64, **ec_kw)), tok


def _run(eng, tok, n_req=4, max_tokens=8):
    from localai_tpu.engine import GenRequest

    outs = [eng.submit(GenRequest(
        prompt_ids=tok.encode(f"request number {i} says"),
        max_tokens=max_tokens, ignore_eos=True))[1] for i in range(n_req)]
    while eng.step():
        pass
    finished = 0
    for q in outs:
        while not q.empty():
            if q.get_nowait().finished:
                finished += 1
    return finished


def test_engine_stage_spans_and_profile(ckpt):
    from localai_tpu import telemetry

    telemetry.set_trace_enabled(True)
    tracer = telemetry.tracer()
    tracer.clear()
    try:
        eng, tok = _engine(ckpt)
        assert eng._tracer is not None
        finished = _run(eng, tok, n_req=4)
        assert finished == 4
        names = {e["name"] for e in tracer.events()}
        # the engine thread's phases: admission, the build + enqueue of a
        # decode, the wait for its results, and the emit after the fetch
        assert {"engine.admit", "engine.dispatch", "engine.device",
                "engine.emit"} <= names
        assert "engine.idle" not in names      # idle waits stay out
        # each request's TTFT stages, under its request id
        for stage in ("queue_wait", "admit_to_join", "join_to_first"):
            got = [e for e in tracer.events()
                   if e["name"] == "engine.stage." + stage]
            assert len(got) == 4
            assert len({e["args"]["request_id"] for e in got}) == 4
        # one engine.request span per request, all closed, with ttft args
        reqs = [e for e in tracer.events() if e["name"] == "engine.request"]
        assert len(reqs) == 4
        for r in reqs:
            assert r["args"]["generated"] > 0
            assert r["args"]["ttft_ms"] is not None
            assert r["args"]["request_id"].startswith("rid-")
    finally:
        telemetry.set_trace_enabled(None)
        tracer.clear()


def test_tracing_disabled_is_inert_and_cheap(ckpt):
    """The overhead guard: with the ring off the engine must hold no tracer,
    construct none, record nothing, and its step loop must stay within noise
    of itself — what is left on the hot path is the phase clock: a clock
    read and an annotation per phase switch, a handful per tick."""
    from localai_tpu import telemetry
    from localai_tpu.telemetry import trace as trace_mod

    telemetry.set_trace_enabled(False)
    made = trace_mod._TRACER
    try:
        eng, tok = _engine(ckpt)
        assert eng._tracer is None and eng._phases._tracer is None
        assert not hasattr(eng, "_prof")
        assert trace_mod._TRACER is made      # nothing constructed
        before = len(telemetry.chrome_events())
        _run(eng, tok, n_req=2, max_tokens=16)
        assert len(telemetry.chrome_events()) == before   # nothing recorded

        def timed():
            t0 = time.perf_counter()
            _run(eng, tok, n_req=2, max_tokens=32)
            return time.perf_counter() - t0

        timed()                      # warm
        disabled = min(timed() for _ in range(3))
        # enable spans (no fences) on the SAME engine: the recording path
        # itself must be cheap relative to a device dispatch
        eng._tracer = eng._phases._tracer = telemetry.tracer()
        eng._tracer.clear()
        enabled = min(timed() for _ in range(3))
        eng._tracer.clear()
        assert enabled < disabled * 2.0, (
            f"span recording too expensive: {enabled:.3f}s vs "
            f"{disabled:.3f}s disabled")
    finally:
        telemetry.set_trace_enabled(None)


# ------------------------------------- full-stack concurrent trace integrity


@pytest.fixture(scope="module")
def traced_stack(tmp_path_factory):
    """HTTP server + real backend subprocess with LOCALAI_TRACE on:
    the end-to-end path the /debug endpoints and request-id propagation
    need. Mirrors test_http_api's stack fixture."""
    import asyncio

    from aiohttp import web

    from localai_tpu.config import AppConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models-traced")
    (models / "tiny.yaml").write_text(yaml.safe_dump({
        "name": "tiny",
        "backend": "llm",
        "context_size": 128,
        "parallel": 4,
        "dtype": "float32",
        "prefill_buckets": [32, 64],
        "parameters": {"model": ckpt, "temperature": 0.0, "max_tokens": 8},
    }))

    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    os.environ["JAX_PLATFORMS"] = "cpu"
    old_trace = os.environ.get("LOCALAI_TRACE")
    os.environ["LOCALAI_TRACE"] = "1"    # backend subprocess inherits
    app_cfg = AppConfig(address=f"127.0.0.1:{port}", models_path=str(models),
                        parallel_requests=4)
    configs = ModelConfigLoader(str(models))
    manager = ModelManager(app_cfg)
    api = API(app_cfg, configs, manager)

    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(api.app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{port}"
    for _ in range(50):
        try:
            requests.get(base + "/healthz", timeout=1)
            break
        except requests.ConnectionError:
            time.sleep(0.1)
    yield base, manager
    manager.stop_all()
    loop.call_soon_threadsafe(loop.stop)
    if old_trace is None:
        os.environ.pop("LOCALAI_TRACE", None)
    else:
        os.environ["LOCALAI_TRACE"] = old_trace


def _warm(base):
    """Ensure the backend is loaded and has served at least one request
    (tests in this module must not depend on execution order)."""
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "warm up"}],
        "max_tokens": 4,
    }, timeout=300)
    assert r.status_code == 200, r.text


def test_concurrent_trace_integrity_http_grpc_engine(traced_stack):
    """N parallel chat requests: request ids round-trip HTTP→gRPC→engine,
    every exported span is closed (complete events only), parents resolve
    within their process, and the merged Chrome trace re-parses."""
    base, _ = traced_stack
    n = 4
    rids = [f"it-req-{i}" for i in range(n)]
    results = {}

    def fire(rid):
        r = requests.post(base + "/v1/chat/completions", json={
            "model": "tiny",
            "messages": [{"role": "user", "content": f"hello from {rid}"}],
            "max_tokens": 6,
        }, headers={"X-Request-Id": rid}, timeout=300)
        results[rid] = r

    threads = [threading.Thread(target=fire, args=(rid,)) for rid in rids]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for rid, r in results.items():
        assert r.status_code == 200, r.text
        # the middleware echoes the propagated id back
        assert r.headers.get("X-Request-Id") == rid

    # the engine loop closes a request's span just after the final chunk is
    # streamed — give it a beat before snapshotting
    time.sleep(0.5)
    trace = requests.get(base + "/debug/trace", timeout=60).json()
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "no spans exported"
    assert json.loads(json.dumps(trace))   # re-parses

    # request ids round-tripped into every layer's spans
    for layer in ("http /v1/chat/completions", "rpc.Predict",
                  "grpc.Predict", "engine.request"):
        seen = {e["args"].get("request_id") for e in spans
                if e["name"] == layer}
        assert set(rids) <= seen, f"{layer}: {seen}"

    # every span closed with a sane interval
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] > 0

    # parents resolve within their process
    by_proc = {}
    for e in spans:
        by_proc.setdefault(e["pid"], set()).add(e["args"]["span_id"])
    for e in spans:
        parent = e["args"].get("parent_id")
        if parent:
            assert parent in by_proc[e["pid"]], e

    # engine.request nests under its grpc.Predict span (trace_parent link)
    grpc_ids = {e["args"]["span_id"] for e in spans
                if e["name"] == "grpc.Predict"}
    engine_reqs = [e for e in spans if e["name"] == "engine.request"
                   and e["args"].get("request_id") in rids]
    assert engine_reqs
    assert all(e["args"].get("parent_id") in grpc_ids for e in engine_reqs)

    # the engine's phases and the requests' stages made it across the
    # process boundary, and so did the HTTP process's wait at the gate
    names = {e["name"] for e in spans}
    assert {"engine.admit", "engine.emit",
            "engine.stage.join_to_first"} <= names
    assert "http.gate_wait" in names


def test_gate_wait_and_counters_in_backend_monitor_and_metrics(traced_stack):
    """The HTTP process's wait at the admission gate is one histogram per
    model, merged into the model's metrics under the flat keys the backend's
    use; the always-on engine counters ride the same map; no prof_* key."""
    base, _ = traced_stack
    _warm(base)
    mon = requests.get(base + "/backend/monitor", timeout=60).json()
    m = mon["tiny"]["metrics"]
    assert m["hist_gate_wait__all__count"] >= 1      # a wait of 0 counts
    assert m["hist_gate_wait__all__sum"] >= 0
    # the gate's stream counters ride the same map (no stream has run here)
    assert m["hist_stream_start__all__count"] >= 0
    assert m["streams_open"] == 0
    # one observation per request that passed the gate, as for the engine's
    # stages (every request here got its first token)
    for stage in ("queue_wait", "admit_to_join", "join_to_first"):
        assert m[f"hist_{stage}__all__count"] == \
            m["hist_gate_wait__all__count"], stage
    for k in ("decode_dispatches_consumed", "decode_steps_consumed",
              "requests_admitted", "xla_compiles_total",
              "xla_compile_ms_total", "engine_host_ms__dispatch",
              "engine_host_ms__admit", "engine_host_ms__emit",
              "engine_host_ms__kv", "engine_wait_ms__device",
              "engine_wait_ms__idle", "chunk_ctx_tokens__attended",
              "chunk_ctx_tokens__capacity"):
        assert k in m, k
    assert m["requests_admitted"] >= 1 and m["xla_compiles_total"] >= 1
    assert any(k.startswith("xla_compiles__") for k in m)
    assert not any(k.startswith("prof_") for k in m)

    prom = requests.get(base + "/metrics", timeout=60).text
    assert 'localai_request_gate_wait_seconds_count{model="tiny"' in prom
    assert 'localai_request_stream_start_seconds_count{model="tiny"' in prom
    assert 'localai_streams_open{model="tiny"} 0.0' in prom
    assert 'localai_request_join_to_first_seconds_bucket' in prom
    assert 'localai_engine_phase_seconds_total{kind="wait",model="tiny",' \
           'phase="device"}' in prom
    assert 'localai_xla_compiles_total{model="tiny"}' in prom
    assert 'localai_chunk_ctx_tokens_total{model="tiny",rows="capacity"}' \
        in prom
    assert "localai_engine_stage_" not in prom

    # /backend/monitor's two RPCs leave a ring span on each side: what the
    # caller's holds beyond the handler's is the wait for a handler thread
    trace = requests.get(base + "/debug/trace", timeout=60).json()
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"rpc.Status", "rpc.GetMetrics", "grpc.Status",
            "grpc.GetMetrics"} <= names


def test_debug_xprof_takes_a_trace_and_refuses_long_and_concurrent(
        traced_stack):
    """GET /debug/xprof: the backend profiles itself and names the directory;
    over 10 s is refused, a second call while one runs is refused, and the
    server goes on serving. /debug/profile is gone."""
    base, _ = traced_stack
    _warm(base)
    assert requests.get(base + "/debug/profile", timeout=60).status_code == 404
    r = requests.get(base + "/debug/xprof?model=tiny&seconds=11", timeout=60)
    assert r.status_code == 400 and "seconds" in r.json()["error"]
    r = requests.get(base + "/debug/xprof?model=nope&seconds=1", timeout=60)
    assert r.status_code == 404

    replies = []

    def take():
        replies.append(requests.get(
            base + "/debug/xprof?model=tiny&seconds=1", timeout=300))

    threads = [threading.Thread(target=take) for _ in range(2)]
    threads[0].start()
    time.sleep(0.3)                  # the first is inside its traced second
    threads[1].start()
    _warm(base)                      # serving goes on meanwhile
    [t.join() for t in threads]
    codes = sorted(r.status_code for r in replies)
    assert codes == [200, 409], [r.text for r in replies]
    ok = next(r.json() for r in replies if r.status_code == 200)
    assert ok["model"] == "tiny" and ok["seconds"] == 1.0
    assert ok["xplane"] and all(os.path.exists(f) for f in ok["xplane"])
    refused = next(r.json() for r in replies if r.status_code == 409)
    assert "already running" in refused["error"]


def test_util_trace_cli(traced_stack, tmp_path, capsys):
    """`local-ai util trace <addr>` writes a Chrome-trace file and prints
    the engine thread's phase table."""
    from localai_tpu.cli import main as cli_main

    base, _ = traced_stack
    _warm(base)
    out = tmp_path / "trace.json"
    rc = cli_main(["util", "trace", base, "--out", str(out)])
    assert rc == 0
    dump = json.loads(out.read_text())
    assert any(e.get("ph") == "X" for e in dump["traceEvents"])
    printed = capsys.readouterr().out
    assert "events" in printed
    assert "admit" in printed and "engine thread" in printed


# ------------------------------------------------ a permit's life (ISSUE 37)

ROW_STATES = ("live", "spent", "prefill", "free_queued", "free_starved")


def _metrics(base) -> dict:
    return requests.get(base + "/backend/monitor",
                        timeout=60).json()["tiny"]["metrics"]


def _hist(m: dict, name: str, field: str) -> float:
    """A histogram's count or sum over every decode path."""
    return sum(v for k, v in m.items()
               if k.startswith(f"hist_{name}__") and k.endswith("__" + field))


def _stream_chat(base, rid: str, max_tokens: int, cut_after: int = 0) -> int:
    """Stream one chat completion; with `cut_after`, close the connection
    after that many data lines (what a client that goes away does)."""
    n = 0
    with requests.post(base + "/v1/chat/completions", json={
            "model": "tiny", "stream": True, "ignore_eos": True,
            "messages": [{"role": "user", "content": f"hello from {rid}"}],
            "max_tokens": max_tokens},
            headers={"X-Request-Id": rid}, stream=True, timeout=300) as r:
        assert r.status_code == 200, r.text
        for line in r.iter_lines():
            if line.startswith(b"data: "):
                n += 1
                if cut_after and n >= cut_after:
                    break
    return n


def test_a_finished_streams_tail_in_monitor_metrics_and_the_ring(
        traced_stack):
    """The three spans of a request's tail are observed once for a stream
    that runs to its end, ride /backend/monitor and /metrics beside
    hist_gate_wait, and go into the ring by request id; with the head's
    stages they stay under the permit's whole life."""
    base, _ = traced_stack
    _warm(base)
    before = _metrics(base)
    assert _stream_chat(base, "it-tail-1", max_tokens=6) > 6
    # the client has `[DONE]` before the loop has released the permit (and
    # before the backend's handler thread has resumed after its last reply)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        m = _metrics(base)
        if all(_hist(m, name, "count") > _hist(before, name, "count")
               for name in ("permit_hold", "finish_to_reply")):
            break
        time.sleep(0.05)
    new = {name: (_hist(m, name, "count") - _hist(before, name, "count"),
                  _hist(m, name, "sum") - _hist(before, name, "sum"))
           for name in ("gate_wait", "stream_start", "e2e", "finish_to_reply",
                        "reply_to_release", "permit_hold")}
    for name, (count, total) in new.items():
        assert count == 1 and total >= 0, (name, count, total)
    assert any(k.startswith("hist_finish_to_reply__") and "__all__" not in k
               for k in m)                     # by the decode path, as e2e
    # one request: e2e runs from the engine's queue to its finish decision
    # (queue_wait + admit_to_join + the engine's time to the finish), so
    # the stages are disjoint and all inside the permit's life
    stages = sum(new[n][1] for n in ("stream_start", "e2e",
                                     "reply_to_release"))
    assert new["permit_hold"][1] >= stages
    # finish_to_reply ends when the backend's handler resumes after its last
    # reply, which on a loaded machine is after the HTTP side has read it:
    # it may reach a little into the tail, or past the release
    stages += new["finish_to_reply"][1]
    assert new["permit_hold"][1] + 0.5 >= stages
    assert new["permit_hold"][1] - stages < 5.0     # the two crossings

    prom = requests.get(base + "/metrics", timeout=60).text
    for name in ("finish_to_reply", "reply_to_release", "permit_hold"):
        assert f'localai_request_{name}_seconds_count{{model="tiny"' in prom
    slo = requests.get(base + "/debug/slo", timeout=60).json()
    for name in ("gate_wait", "finish_to_reply", "permit_hold"):
        assert slo["models"]["tiny"][name]["count"] >= 1, name

    trace = requests.get(base + "/debug/trace", timeout=60).json()
    mine = {e["name"] for e in trace["traceEvents"]
            if e.get("args", {}).get("request_id") == "it-tail-1"}
    assert {"engine.stage.queue_wait", "engine.stage.join_to_first",
            "engine.stage.finish_to_reply", "http.stage.reply_to_release",
            "http.stage.permit_hold"} <= mine


def test_a_stream_the_client_cuts_observes_no_tail(traced_stack):
    base, _ = traced_stack
    _warm(base)
    before = _metrics(base)
    assert _stream_chat(base, "it-cut-1", max_tokens=100, cut_after=3) == 3
    # the engine ends the request as cancelled at its next token
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        m = _metrics(base)
        if m["streams_open"] == 0 and (
                m["requests_completed"] > before["requests_completed"]):
            break
        time.sleep(0.1)
    assert _hist(m, "gate_wait", "count") == \
        _hist(before, "gate_wait", "count") + 1
    for name in ("finish_to_reply", "reply_to_release", "permit_hold"):
        assert _hist(m, name, "count") == _hist(before, name, "count"), name


def test_row_states_ride_the_monitor_and_tile_the_steps(traced_stack):
    base, _ = traced_stack
    _warm(base)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:       # every dispatch consumed
        m = _metrics(base)
        if m["decode_dispatches_consumed"] == m["decode_dispatches"]:
            break
        time.sleep(0.1)
    rows = {s: m[f"decode_row_steps__{s}"] for s in ROW_STATES}
    assert sum(rows.values()) == 4 * m["decode_steps_consumed"] > 0, rows
    assert rows["live"] == m["tokens_generated"]
