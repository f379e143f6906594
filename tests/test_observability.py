"""Serving SLO layer tests (ISSUE 11): streaming-histogram math against a
numpy reference, the flat GetMetrics round-trip, Prometheus exposition
format, flight-recorder rings + auto-dump on an injected engine crash, the
disabled-path gate, and the per-request timings surface end to end.

Cheap units run in tier-1; everything that drives an engine or the HTTP
stack carries `slow`.
"""
import glob
import json
import math
import os
import threading
import time

import numpy as np
import pytest
import requests
import yaml

from fixtures import tiny_checkpoint

from localai_tpu.telemetry.metrics import (
    FlightRecorder, Hist, SLORegistry, parse_flat, snapshot_from_hists,
)
from localai_tpu.telemetry.metrics import BUCKETS_S


# ------------------------------------------------------------------ units


def _ref_edge(samples, q):
    """The bucket upper bound Hist.percentile must report: the edge of the
    first bucket whose cumulative count reaches q*n (numpy reference)."""
    edges = np.asarray(BUCKETS_S)
    idx = np.searchsorted(edges, samples, side="left")   # first ub >= v
    counts = np.bincount(idx, minlength=len(edges))
    target = q * len(samples)
    acc = 0
    for i, n in enumerate(counts):
        acc += n
        if acc >= target and n:
            return edges[i] if math.isfinite(edges[i]) else edges[i - 1]
    return edges[-2]


def test_hist_percentile_matches_numpy_reference():
    rng = np.random.default_rng(11)
    # log-uniform over the interesting range, plus exact-edge values (the
    # `v <= ub` boundary) and overflow samples for the open-ended bucket
    samples = list(np.exp(rng.uniform(np.log(60e-6), np.log(4.0), 500)))
    samples += [1e-3, 20e-3, 1.0] * 5 + [7.5, 11.0]
    h = Hist()
    for v in samples:
        h.observe(v)
    assert h.count == len(samples)
    assert abs(h.sum - sum(samples)) < 1e-9 * len(samples)
    for q in (0.5, 0.9, 0.95, 0.99):
        got = h.percentile(q)
        assert got == _ref_edge(samples, q), q
        # the reported edge brackets the true quantile from above (or is
        # the honest floor for overflow samples)
        true = float(np.quantile(samples, q))
        if true <= BUCKETS_S[-2]:
            assert got >= true * 0.999
    # coarse but bounded: one bucket of slack around the true p50
    assert h.percentile(0.5) <= BUCKETS_S[-2]


def test_hist_open_bucket_reports_last_finite_edge():
    h = Hist()
    for _ in range(10):
        h.observe(100.0)          # everything in the +inf bucket
    assert h.percentile(0.5) == BUCKETS_S[-2]
    assert h.percentile(0.99) == BUCKETS_S[-2]


def test_hist_weighted_observe_equals_repeats():
    a, b = Hist(), Hist()
    for v in (0.8e-3, 3e-3, 40e-3, 0.3):
        a.observe(v, n=5)
        for _ in range(5):
            b.observe(v)
    assert a.counts == b.counts
    assert a.count == b.count == 20
    assert abs(a.sum - b.sum) < 1e-12
    for q in (0.5, 0.95):
        assert a.percentile(q) == b.percentile(q)


def test_registry_flat_parse_roundtrip():
    reg = SLORegistry()
    rng = np.random.default_rng(7)
    for path in ("loop", "spec"):
        for v in rng.uniform(1e-3, 0.5, 40):
            reg.observe("ttft", path, float(v))
            reg.observe("e2e", path, float(v) * 4)
    reg.observe("tpot", "loop", 2e-3, n=64)
    flat = reg.flat()
    # headline keys the satellite requires, straight from the histogram
    assert flat["ttft_ms_p50"] == reg.merged("ttft").percentile(0.5) * 1e3
    assert flat["ttft_ms_p95"] == reg.merged("ttft").percentile(0.95) * 1e3
    back = parse_flat(flat)
    assert set(back) == {("ttft", "loop"), ("ttft", "spec"),
                         ("e2e", "loop"), ("e2e", "spec"),
                         ("tpot", "loop")}
    for key, h in reg._hists.items():
        assert back[key].counts == h.counts, key
        assert back[key].count == h.count
        assert abs(back[key].sum - h.sum) < 1e-9
    # the scrape-side snapshot equals the in-process one
    assert snapshot_from_hists(back) == reg.snapshot()


def test_snapshot_shape_and_by_path():
    reg = SLORegistry()
    reg.observe("ttft", "loop", 5e-3)
    reg.observe("ttft", "spec", 50e-3)
    snap = reg.snapshot()
    e = snap["ttft"]
    assert e["count"] == 2 and e["mean_ms"] > 0
    assert set(e["by_path"]) == {"loop", "spec"}
    assert e["by_path"]["spec"]["p50_ms"] >= e["by_path"]["loop"]["p50_ms"]
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        assert k in e
    assert "tpot" not in snap     # no samples → no entry
    reg.reset()
    assert reg.snapshot() == {}


def test_prometheus_exposition_format():
    """_SLOCollector must emit a well-formed histogram: cumulative monotone
    buckets ending at le="+Inf" == _count, and a consistent _sum."""
    from localai_tpu.server import http

    if not http._HAVE_PROM:
        pytest.skip("prometheus_client not available")
    from prometheus_client import generate_latest

    reg = SLORegistry()
    rng = np.random.default_rng(3)
    for v in rng.uniform(1e-3, 2.0, 100):
        reg.observe("ttft", "loop", float(v))
    http._SLO_SCRAPE["obs-test"] = parse_flat(reg.flat())
    try:
        text = generate_latest().decode()
    finally:
        http._SLO_SCRAPE.pop("obs-test", None)
    lines = [ln for ln in text.splitlines()
             if ln.startswith("localai_request_ttft_seconds")
             and 'model="obs-test"' in ln]
    assert lines, text[:2000]
    buckets, count, total = [], None, None
    for ln in lines:
        name, val = ln.rsplit(" ", 1)
        if "_bucket{" in name:
            le = name.split('le="')[1].split('"')[0]
            buckets.append((le, float(val)))
        elif name.startswith("localai_request_ttft_seconds_count"):
            count = float(val)
        elif name.startswith("localai_request_ttft_seconds_sum"):
            total = float(val)
    assert count == 100 and total == pytest.approx(reg.merged("ttft").sum)
    # every edge present, cumulative and monotone, +Inf last and == count
    assert [b[0] for b in buckets][-1] == "+Inf"
    assert len(buckets) == len(BUCKETS_S)
    vals = [b[1] for b in buckets]
    assert vals == sorted(vals)
    assert vals[-1] == count


def test_flightrec_rings_wrap_and_auto_dump(tmp_path, monkeypatch):
    rec = FlightRecorder(requests=8, ticks=4, events=4)
    for i in range(20):
        rec.record_request({"request_id": f"r{i}"})
        rec.record_tick({"tick": i})
        rec.record_event("tripwire", n=i)
    assert len(rec.requests) == 8 and len(rec.ticks) == 4
    assert [r["request_id"] for r in rec.requests] == \
        [f"r{i}" for i in range(12, 20)]         # newest survive the wrap
    assert all("t_wall" in e for e in rec.events)

    monkeypatch.setenv("LOCALAI_FLIGHTREC_DIR", str(tmp_path))
    path = rec.auto_dump("tripwire:test")
    assert path and os.path.exists(path)
    dump = json.loads(open(path).read())
    assert dump["reason"] == "tripwire:test"
    assert dump["requests"][-1]["request_id"] == "r19"
    assert dump["events"][-1]["kind"] == "tripwire"
    # the cap: a crash loop cannot fill the disk
    paths = {path}
    for _ in range(FlightRecorder.MAX_AUTO_DUMPS + 4):
        p = rec.auto_dump("again")
        if p:
            paths.add(p)
    assert len(paths) == FlightRecorder.MAX_AUTO_DUMPS
    assert rec.auto_dump("capped") == ""


def test_metrics_enable_gate():
    from localai_tpu import telemetry

    try:
        telemetry.set_metrics_enabled(False)
        assert telemetry.metrics_enabled() is False
        assert telemetry.maybe_slo() is None
        telemetry.set_metrics_enabled(True)
        reg = telemetry.maybe_slo()
        assert isinstance(reg, SLORegistry)
        # forcing the gate again resets the singleton (fresh registry)
        telemetry.set_metrics_enabled(True)
        assert telemetry.maybe_slo() is not reg
    finally:
        telemetry.set_metrics_enabled(None)


def test_tripwire_trip_records_event_and_dumps(tmp_path, monkeypatch):
    """dispatch_budget leaves a black-box record when it trips."""
    from localai_tpu import telemetry
    from localai_tpu.testing.tripwires import dispatch_budget

    class _FakeEngine:
        metrics = {"decode_dispatches": 0, "tokens_generated": 0}

    monkeypatch.setenv("LOCALAI_FLIGHTREC_DIR", str(tmp_path))
    telemetry.reset_flightrec()
    try:
        eng = _FakeEngine()
        with pytest.raises(AssertionError, match="dispatch budget"):
            with dispatch_budget(eng, max_per_128_tokens=1.0):
                eng.metrics["decode_dispatches"] += 50
                eng.metrics["tokens_generated"] += 16
        rec = telemetry.flightrec()
        trips = [e for e in rec.events if e["kind"] == "tripwire"]
        assert trips and trips[-1]["guard"] == "dispatch_budget"
        assert trips[-1]["dispatches"] == 50
        dumps = glob.glob(str(tmp_path / "*tripwire*"))
        assert dumps
        assert json.loads(open(dumps[0]).read())["reason"].startswith(
            "tripwire:")
    finally:
        telemetry.reset_flightrec()


# ------------------------------------------------- engine-driving (slow)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_checkpoint(tmp_path_factory)


def _engine(ckpt, draft=False, **ec_kw):
    from localai_tpu.engine import (
        Engine, EngineConfig, Tokenizer, load_config, load_params,
    )

    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    return Engine(cfg, params, tok, EngineConfig(
        max_slots=4, max_context=128, prefill_buckets=(32, 64),
        prefill_chunk=64, **ec_kw),
        draft=(cfg, params) if draft else None), tok


def _run_collect(eng, tok, n_req=4, max_tokens=8):
    """Drive the engine to completion, returning each request's final
    (terminal) StepOutput."""
    from localai_tpu.engine import GenRequest

    outs = [eng.submit(GenRequest(
        prompt_ids=tok.encode(f"request number {i} says"),
        max_tokens=max_tokens, ignore_eos=True))[1] for i in range(n_req)]
    while eng.step():
        pass
    finals = []
    for q in outs:
        while not q.empty():
            o = q.get_nowait()
            if o.finished:
                finals.append(o)
    return finals


@pytest.mark.parametrize("path, kw", [
    ("loop", dict(decode_loop=8)),
    ("dense", dict(decode_loop=0, decode_block=1)),
    ("spec", dict(draft=True, gamma=2)),
])
def test_tokens_by_path_sums_to_tokens_generated(ckpt, path, kw):
    """Every token `_emit` commits is put down to the path that served it:
    the `tokens_by_path__*` counters are exactly the three labels the
    engine has, they sum to `tokens_generated`, and an engine of one path
    puts every token under its own label."""
    eng, tok = _engine(ckpt, **kw)
    finals = _run_collect(eng, tok, n_req=3, max_tokens=7)
    assert len(finals) == 3
    m = eng.metrics
    by_path = {k[len("tokens_by_path__"):]: v for k, v in m.items()
               if k.startswith("tokens_by_path__")}
    assert set(by_path) == {"loop", "dense", "spec"}
    assert sum(by_path.values()) == m["tokens_generated"] == 21
    assert by_path[path] == 21, by_path
    for o in finals:
        if o.timings is not None:
            assert o.timings["path"] == path


@pytest.mark.slow
def test_engine_timeline_integrity_concurrent(ckpt):
    """4 concurrent streams: every terminal StepOutput carries a complete
    phase timeline, the registry counts match, and the flight recorder's
    request ring holds every timeline."""
    from localai_tpu import telemetry

    telemetry.set_metrics_enabled(True)   # fresh registry
    telemetry.reset_flightrec()
    try:
        eng, tok = _engine(ckpt)
        assert eng._slo is not None and eng._flightrec is not None
        n, max_tokens = 4, 8
        finals = _run_collect(eng, tok, n_req=n, max_tokens=max_tokens)
        assert len(finals) == n
        for o in finals:
            t = o.timings
            assert t is not None, o
            assert t["request_id"].startswith("rid-")
            assert t["path"] in ("loop", "dense", "spec")
            assert t["generated_tokens"] == max_tokens
            assert t["dispatches"] >= 1
            assert t["kv_policy"]
            assert t["queue_wait_ms"] >= 0
            assert t["ttft_ms"] is not None and t["ttft_ms"] > 0
            assert t["e2e_ms"] >= t["ttft_ms"]
            assert t["finish_reason"] == "length"
        reg = eng._slo
        assert reg.merged("ttft").count == n
        assert reg.merged("e2e").count == n
        assert reg.merged("queue_wait").count == n
        # TPOT is token-weighted and burst-amortized: never more samples
        # than post-first tokens (tail tokens of a final burst may share
        # the finishing host arrival and go unobserved)
        assert reg.merged("tpot").count <= n * (max_tokens - 1)
        flat = reg.flat()
        assert flat["ttft_ms_p50"] > 0 and flat["ttft_ms_p95"] > 0
        rec = telemetry.flightrec()
        ring_ids = {r["request_id"] for r in rec.requests}
        assert {t["request_id"] for t in
                (o.timings for o in finals)} <= ring_ids
    finally:
        telemetry.set_metrics_enabled(None)
        telemetry.reset_flightrec()


@pytest.mark.slow
def test_engine_metrics_disabled_no_timings(ckpt):
    """LOCALAI_METRICS=0: the engine holds no registry/recorder and the
    outputs carry no timelines — the hot path pays one None-check."""
    from localai_tpu import telemetry

    telemetry.set_metrics_enabled(False)
    telemetry.reset_flightrec()
    try:
        eng, tok = _engine(ckpt)
        assert eng._slo is None and eng._flightrec is None
        finals = _run_collect(eng, tok, n_req=2, max_tokens=8)
        assert len(finals) == 2
        assert all(o.timings is None for o in finals)
        assert len(telemetry.flightrec().requests) == 0

        # overhead guard (PR 2 precedent): recording on the SAME engine must
        # stay within noise of disabled — the per-token cost is a few dict
        # increments, nowhere near a device dispatch
        def timed():
            t0 = time.perf_counter()
            _run_collect(eng, tok, n_req=2, max_tokens=32)
            return time.perf_counter() - t0

        timed()                      # warm
        disabled = min(timed() for _ in range(3))
        telemetry.set_metrics_enabled(True)
        eng._slo = telemetry.maybe_slo()
        eng._flightrec = telemetry.flightrec()
        enabled = min(timed() for _ in range(3))
        assert eng._slo.merged("ttft").count >= 2   # it did record
        assert enabled < disabled * 2.0, (
            f"SLO recording too expensive: {enabled:.3f}s vs "
            f"{disabled:.3f}s disabled")
    finally:
        telemetry.set_metrics_enabled(None)
        telemetry.reset_flightrec()


@pytest.mark.slow
def test_engine_crash_auto_dumps_flightrec(ckpt, tmp_path, monkeypatch):
    """Injected fatal step (LOCALAI_FAULT=engine_crash) while a request is
    mid-generation: the dying request gets a terminal 'error' chunk WITH its
    timeline, and the flight recorder auto-dumps a post-mortem containing
    that timeline + the engine_fatal event."""
    from localai_tpu import telemetry
    from localai_tpu.engine import GenRequest
    from localai_tpu.testing import faults

    telemetry.set_metrics_enabled(True)
    telemetry.reset_flightrec()
    monkeypatch.setenv("LOCALAI_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.delenv("LOCALAI_FAULT_DIR", raising=False)
    faults._local_counts.pop("engine_crash", None)
    # small fused blocks so one step cannot finish the whole request (the
    # default single-dispatch loop would emit all 64 tokens at once and the
    # crash would find nothing in flight)
    eng, tok = _engine(ckpt, max_restarts=0, decode_loop=4, decode_block=2)
    try:
        rid, q = eng.submit(GenRequest(
            prompt_ids=tok.encode("doomed request says"),
            max_tokens=64, ignore_eos=True))
        # step synchronously until the request is mid-generation (started
        # timeline, not finished), THEN arm the fault and hand the engine
        # to the serving loop: its next step() crashes deterministically
        first = None
        for _ in range(500):
            eng.step()
            if not q.empty():
                first = q.get_nowait()
                break
        assert first is not None and not first.finished
        monkeypatch.setenv("LOCALAI_FAULT", "engine_crash::1")
        eng.start()
        terminal = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            o = q.get(timeout=60)
            if o.finished:
                terminal = o
                break
        assert terminal is not None
        assert terminal.finish_reason == "error"
        assert terminal.timings is not None
        assert terminal.timings["finish_reason"] == "error"
        # the terminal chunk is enqueued before _loop writes the black box —
        # give the dying loop a beat
        dumps = []
        while not dumps and time.monotonic() < deadline:
            dumps = glob.glob(str(tmp_path / "*engine_fatal*.json"))
            time.sleep(0.05)
        assert dumps, os.listdir(tmp_path)
        dump = json.loads(open(dumps[0]).read())
        assert dump["reason"] == "engine_fatal"
        assert any(e["kind"] == "engine_fatal" for e in dump["events"])
        # the black box holds the dying request's timeline
        assert any(r.get("finish_reason") == "error"
                   for r in dump["requests"]), dump["requests"]
    finally:
        monkeypatch.delenv("LOCALAI_FAULT", raising=False)
        eng.stop()
        telemetry.set_metrics_enabled(None)
        telemetry.reset_flightrec()


# --------------------------------------------- HTTP stack surfaces (slow)


@pytest.fixture(scope="module")
def obs_stack(tmp_path_factory):
    """HTTP server + real backend subprocess with metrics at their default
    (ON) and trace/profile untouched — the SLO surfaces must work without
    any opt-in env."""
    import asyncio
    import socket

    from aiohttp import web

    from localai_tpu.config import AppConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models-obs")
    (models / "tiny.yaml").write_text(yaml.safe_dump({
        "name": "tiny",
        "backend": "llm",
        "context_size": 128,
        "parallel": 4,
        "dtype": "float32",
        "prefill_buckets": [32, 64],
        "parameters": {"model": ckpt, "temperature": 0.0, "max_tokens": 8},
    }))

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    os.environ["JAX_PLATFORMS"] = "cpu"
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


@pytest.mark.slow
def test_sse_timings_and_slo_surfaces(obs_stack):
    """One streamed chat: the final usage chunk carries the llama.cpp-style
    `timings` block, and all three export surfaces agree — /debug/slo,
    /debug/flightrec, and the /metrics histogram series."""
    base, _ = obs_stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny", "stream": True,
        "messages": [{"role": "user", "content": "stream please"}],
        "max_tokens": 6,
    }, stream=True, timeout=300)
    assert r.status_code == 200, r.text
    timings = None
    for line in r.iter_lines():
        if not line or not line.startswith(b"data: "):
            continue
        payload = line[6:]
        if payload == b"[DONE]":
            break
        chunk = json.loads(payload)
        if "timings" in chunk:
            timings = chunk["timings"]
    assert timings is not None, "no timings block in the SSE stream"
    assert timings["path"] in ("loop", "dense", "spec")
    assert timings["ttft_ms"] > 0
    assert timings["e2e_ms"] >= timings["ttft_ms"]
    assert timings["generated_tokens"] >= 1

    slo = requests.get(base + "/debug/slo", timeout=60).json()
    assert slo["metrics_enabled"] is True
    assert slo["bucket_edges_s"] == [b for b in BUCKETS_S
                                     if b != float("inf")]
    tiny = slo["models"]["tiny"]
    assert tiny["ttft"]["count"] >= 1
    assert tiny["e2e"]["p50_ms"] > 0

    rec = requests.get(base + "/debug/flightrec", timeout=60).json()
    reqs = rec["models"]["tiny"]["requests"]
    assert reqs and any(t["generated_tokens"] >= 1 for t in reqs)
    assert "events" in rec["server"]

    m = requests.get(base + "/metrics", timeout=60).text
    assert "localai_request_ttft_seconds_bucket" in m
    assert 'le="+Inf"' in m
    assert "localai_request_e2e_seconds_count" in m
    # the mis-typed supervision gauge is now a counter
    assert "# TYPE localai_backend_supervision_total counter" in m


@pytest.mark.slow
def test_getmetrics_histogram_keys(obs_stack):
    """The backend's GetMetrics map carries the flat hist_* keys plus the
    histogram-backed ttft_ms_p50/p95 (and the legacy ttft_ms_last)."""
    base, manager = obs_stack
    # ensure at least one request has been served
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "warm"}],
        "max_tokens": 4,
    }, timeout=300)
    assert r.status_code == 200, r.text
    h = manager.get("tiny")
    m = h.client.metrics()
    assert any(k.startswith("hist_ttft__") for k in m), sorted(m)[:40]
    assert m["ttft_ms_p50"] > 0 and m["ttft_ms_p95"] >= m["ttft_ms_p50"]
    assert "ttft_ms_last" in m          # kept for one release
    hists = parse_flat(m)
    snap = snapshot_from_hists(hists)
    assert snap["ttft"]["count"] >= 1
