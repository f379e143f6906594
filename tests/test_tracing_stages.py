"""Where a first token's time goes, seen from inside (ISSUE 24): the engine
thread's phases tile its wall time, a request's TTFT stages share their
boundaries, the per-dispatch counters are credited together, XLA compiles
are counted, and a profiler trace of the process holds the phases on its own
clock. Tiny engine on the CPU: counts and identities, never a speed.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from fixtures import tiny_checkpoint

from localai_tpu import telemetry


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
    kw = dict(max_slots=4, max_context=128, prefill_buckets=(32, 64),
              prefill_chunk=64)
    kw.update(ec_kw)
    return Engine(cfg, params, tok, EngineConfig(**kw)), tok


def _submit(eng, tok, i, max_tokens=8):
    from localai_tpu.engine import GenRequest

    return eng.submit(GenRequest(
        prompt_ids=tok.encode(f"request number {i} says"),
        max_tokens=max_tokens, ignore_eos=True))[1]


def _final(q):
    """Drain a request's queue; the finished StepOutput."""
    last = None
    while not q.empty():
        last = q.get_nowait()
    assert last is not None and last.finished
    return last


PHASE_KEYS = ([f"engine_host_ms__{p}" for p in telemetry.PhaseClock.HOST]
              + [f"engine_wait_ms__{p}" for p in telemetry.PhaseClock.WAIT])


# ------------------------------------------------------------- phase clock


def test_phase_clock_switch_within_and_ring_rules():
    """One phase at a time; `within` hands back what it interrupted; the
    ring takes no idle wait and nothing under RING_MIN_S."""
    tr = telemetry.Tracer(64)
    m: dict = {}
    pc = telemetry.PhaseClock(m, tr)
    pc.RING_MIN_S = 1e-3       # a busy machine can hold a thread 100 us
    assert sorted(m) == sorted(PHASE_KEYS) and not any(m.values())
    assert pc.switch("dispatch", tick=7) == "idle"
    with pc.within("admit"):
        assert pc.phase == "admit"
        time.sleep(0.002)
    assert pc.phase == "dispatch" and pc.tick == 7
    pc.switch("idle")
    time.sleep(0.002)
    pc.switch("dispatch", tick=8)
    names = [e["name"] for e in tr.events()]
    assert names == ["engine.admit"]          # idle and the short ones: out
    assert tr.events()[0]["args"]["tick"] == 7
    assert m["engine_host_ms__admit"] >= 2.0 <= m["engine_wait_ms__idle"]
    assert m["engine_host_ms__kv"] == 0.0


def test_counters_and_phase_keys_exist_at_zero_from_engine_start(ckpt):
    """A reader that finds no key reports nothing, so every key a per-layer
    metric reads is there from the engine's start, at 0."""
    eng, _ = _engine(ckpt)
    for k in PHASE_KEYS + ["decode_dispatches_consumed",
                           "decode_steps_consumed", "requests_admitted"]:
        assert eng.metrics[k] == 0, k
    assert not any(k.startswith("prof_") for k in eng.metrics)


def test_phases_tile_the_loops_wall_time(ckpt):
    """The sum over phases is the loop thread's wall time (within 1%; by
    construction exactly, one clock read closes a phase and opens the
    next)."""
    eng, tok = _engine(ckpt)
    pc = eng._phases
    t_from, base = pc._t0, pc.total_ms()
    eng.start()
    try:
        qs = [_submit(eng, tok, i, max_tokens=12) for i in range(5)]
        deadline = time.monotonic() + 120
        while (eng.metrics["requests_completed"] < 5
               and time.monotonic() < deadline):
            time.sleep(0.01)
        time.sleep(0.12)                     # a few idle waits of the loop
    finally:
        eng.stop()
    for q in qs:
        _final(q)
    wall_ms = (pc._t0 - t_from) * 1e3        # up to the last switch
    assert wall_ms > 100
    assert abs((pc.total_ms() - base) - wall_ms) <= 0.01 * wall_ms
    m = eng.metrics
    for k in ("engine_host_ms__dispatch", "engine_host_ms__admit",
              "engine_host_ms__emit", "engine_wait_ms__device",
              "engine_wait_ms__idle"):
        assert m[k] > 0, k
    # today's host_sync_wait_ms stays, and is the `device` phase
    assert m["host_sync_wait_ms"] == pytest.approx(
        m["engine_wait_ms__device"], rel=1e-6)


# ------------------------------------------------------------ TTFT stages


def test_stage_histograms_once_per_request_and_sum_to_ttft(ckpt):
    """Scripted two-dispatch run: a second request arrives while the first
    one's loop is in flight. Every stage is observed once per request and
    mean ttft = queue_wait + admit_to_join + join_to_first."""
    slo = telemetry.maybe_slo()
    assert slo is not None
    slo.reset()
    eng, tok = _engine(ckpt, decode_loop=8)
    q1 = _submit(eng, tok, 1, max_tokens=24)
    eng.step()                    # admits request 1 (idle engine)
    eng.step()                    # its first loop is dispatched, in flight
    assert eng._pending is not None
    q2 = _submit(eng, tok, 2, max_tokens=24)
    while eng.step():
        pass
    outs = [_final(q1), _final(q2)]
    hists = {n: slo.merged(n) for n in (
        "ttft", "queue_wait", "admit_to_join", "join_to_first")}
    for name, h in hists.items():
        assert h.count == 2, name
    parts = sum(hists[n].sum for n in (
        "queue_wait", "admit_to_join", "join_to_first"))
    assert parts == pytest.approx(hists["ttft"].sum, rel=1e-9, abs=1e-9)
    assert eng.metrics["decode_dispatches_consumed"] >= 2
    # the same three in each request's own timeline (flight recorder, the
    # final reply's `timings`)
    for o in outs:
        t = o.timings
        assert t["ttft_ms"] == pytest.approx(
            t["queue_wait_ms"] + t["admit_to_join_ms"]
            + t["join_to_first_ms"], rel=1e-9)
        assert "prefill_ms" not in t
    # the enqueue of a prefill is not a prefill time: that histogram is gone
    assert not any(k.startswith("hist_prefill") for k in slo.flat())


def test_admit_to_join_near_zero_idle_and_positive_behind_a_loop(ckpt):
    """On an idle engine the admission joins the very next dispatch; behind
    a loop in flight the host first waits that loop out (fetch.wait), so the
    stage holds at least that wait."""
    eng, tok = _engine(ckpt, decode_loop=16)
    q0 = _submit(eng, tok, 0, max_tokens=4)          # compiles everything
    while eng.step():
        pass
    _final(q0)
    q1 = _submit(eng, tok, 1, max_tokens=40)
    eng.step()
    eng.step()
    assert eng._pending is not None                   # loop 1 in flight
    waited = eng.metrics["engine_wait_ms__device"]
    q2 = _submit(eng, tok, 2, max_tokens=40)
    eng.step()                    # dispatch loop 2, admit 2, wait out loop 1
    waited = eng.metrics["engine_wait_ms__device"] - waited
    while eng.step():
        pass
    idle, behind = _final(q1).timings, _final(q2).timings
    assert 0 <= idle["admit_to_join_ms"] < 250
    assert waited > 0
    assert behind["admit_to_join_ms"] >= waited
    assert behind["join_to_first_ms"] > 0


# ------------------------------------------------------ dispatch counters


def test_dispatch_counters_credited_together_and_survive_warmup(ckpt):
    eng, tok = _engine(ckpt, decode_loop=8)
    eng.warmup()
    m = eng.metrics
    # warmup dispatches are consumed by nobody and credited to nothing
    assert (m["decode_dispatches"], m["decode_dispatches_consumed"],
            m["decode_steps_consumed"], m["requests_admitted"]) == (0, 0, 0, 0)
    qs = [_submit(eng, tok, i, max_tokens=20) for i in range(3)]
    seen = (0, 0)
    while True:
        busy = eng.step()
        now = (m["decode_dispatches_consumed"], m["decode_steps_consumed"])
        # together: never one without the other
        assert (now[0] > seen[0]) == (now[1] > seen[1])
        seen = now
        if not busy:
            break
    for q in qs:
        _final(q)
    assert m["requests_admitted"] == 3
    # drained: every dispatch was consumed, with the steps the device ran
    assert m["decode_dispatches_consumed"] == m["decode_dispatches"] > 0
    assert m["decode_steps_consumed"] == m["decode_steps_dispatched"] > 0
    assert m["tokens_generated"] == 60


# --------------------------------------------------------- compile counter


def test_compile_counter_new_shape_counts_repeat_and_cache_hit_do_not():
    import jax
    import jax.numpy as jnp

    from localai_tpu.telemetry import metrics as tm

    fresh = telemetry.CompileCounter()
    assert fresh.flat() == {"xla_compiles_total": 0,
                            "xla_compile_ms_total": 0.0}
    # a hit in the persistent cache fires the backend-compile event too,
    # after the cache's own retrieval event: not a compile
    fresh.on_duration(tm._CACHE_RETRIEVAL, 0.01)
    fresh.on_duration(tm._BACKEND_COMPILE, 0.02, fun_name="hit")
    assert fresh.flat()["xla_compiles_total"] == 0
    fresh.on_duration(tm._BACKEND_COMPILE, 0.02, fun_name="miss")
    assert fresh.flat() == {"xla_compiles_total": 1,
                            "xla_compile_ms_total": pytest.approx(20.0),
                            "xla_compiles__miss": 1}

    c = telemetry.compile_counter()
    assert telemetry.compile_counter() is c      # one listener a process

    def issue24_forced_shape(x):
        return jnp.cumsum(x * 3 + 1)

    f = jax.jit(issue24_forced_shape)
    a, b = np.ones((7, 3), np.float32), np.ones((9, 3), np.float32)
    before = c.flat()
    f(a).block_until_ready()
    mid = c.flat()
    assert mid["xla_compiles_total"] == before["xla_compiles_total"] + 1
    assert mid["xla_compile_ms_total"] > before["xla_compile_ms_total"]
    assert mid.get("xla_compiles__issue24_forced_shape", 0) == \
        before.get("xla_compiles__issue24_forced_shape", 0) + 1
    f(a).block_until_ready()                     # a repeat compiles nothing
    assert c.flat() == mid
    f(b).block_until_ready()                     # a new shape does
    assert c.flat()["xla_compiles_total"] == mid["xla_compiles_total"] + 1


# ------------------------------------------- the profiler's trace and clock


def test_device_trace_refuses_over_ten_seconds_and_a_second_call():
    from localai_tpu.telemetry import trace as tt

    assert "error" in telemetry.device_trace(10.5)
    assert "error" in telemetry.device_trace(0)
    assert tt._XPROF_LOCK.acquire(blocking=False)
    try:
        out = telemetry.device_trace(0.1)
    finally:
        tt._XPROF_LOCK.release()
    assert out == {"error": "a device trace is already running"}


def test_profiler_trace_holds_engine_annotations_with_tick(ckpt):
    """A 0.2 s CPU profiler trace of this process (the code path of GET
    /debug/xprof) holds the engine's phases with `tick`, one per tick with
    `unix_us` near the wall clock."""
    from jax.profiler import ProfileData

    eng, tok = _engine(ckpt)
    for _ in range(2):            # every shape the traffic below dispatches
        q0 = _submit(eng, tok, 0, max_tokens=6)
        while eng.step():
            pass
        _final(q0)
    eng.start()
    try:
        stop = threading.Event()

        def traffic():                       # one request at a time
            while not stop.is_set():
                q = _submit(eng, tok, 0, max_tokens=6)
                while not q.get(timeout=60).finished:
                    pass

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        out = telemetry.device_trace(0.2)
        stop.set()
        t.join(30)
    finally:
        eng.stop()
    assert "error" not in out, out
    assert out["xplane"] and os.path.isdir(out["dir"])
    found = {}
    for plane in ProfileData.from_file(out["xplane"][0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"engine.dispatch", "engine.admit", "engine.device",
            "engine.emit"} <= set(found)
    stats = [s for v in found.values() for s in v]
    assert all("tick" in s for s in stats)
    stamped = [s for s in stats if "unix_us" in s]
    assert stamped
    assert abs(stamped[0]["unix_us"] / 1e6 - time.time()) < 600
    # a dispatch enqueued while device_trace ran says what the engine held
    held = [s for s in stats if "rows_active" in s]
    assert held and all(
        s["rows_active"] + s["rows_prefill"] + s["rows_free"] == 4
        and s["queued"] >= 0 for s in held)
    # at most one stamp a tick
    assert len(stamped) == len({s["tick"] for s in stamped})


def test_trace_gaps_on_the_recorded_fixture():
    """tools/trace_gaps.py on tests/data/trace_gaps_small.json; expected
    numbers worked out by hand (ns in the file). Ops 1000-2000, a while
    3000-8000 holding 4000 ns of body ops, 8500-9000: busy 6500 of 8000,
    gaps 2000-3000 and 8000-8500."""
    from tools import trace_gaps

    here = os.path.dirname(os.path.abspath(__file__))
    facts = trace_gaps.reduce(trace_gaps.load(
        os.path.join(here, "data", "trace_gaps_small.json")))
    ns = 1e-9
    assert facts["window_s"] == facts["device_s"] == pytest.approx(8000 * ns)
    assert facts["device_busy_s"] == pytest.approx(6500 * ns)
    assert facts["busy_s"] == pytest.approx(6500 * ns)
    assert facts["idle_share"] == pytest.approx(1500 / 8000)
    want_phase = {"dispatch": (200, 0), "admit": (1000, 200),
                  "device": (5900, 900), "emit": (300, 300),
                  "idle": (600, 100)}
    for phase, (t, idle) in want_phase.items():
        assert facts["phases"][phase]["s"] == pytest.approx(t * ns), phase
        assert facts["phases"][phase]["device_idle_s"] == \
            pytest.approx(idle * ns, abs=1e-15), phase
    assert facts["idle_outside_any_phase_s"] == 0
    assert facts["phases_sum_s"] == facts["phases_span_s"] == \
        pytest.approx(8200 * ns)
    assert [(g["phase"], round(g["ms"] * 1e6)) for g in facts["gaps"]] == [
        ("device", 1000), ("emit", 500)]
    assert facts["host_under"] == {
        "admit": [["PjitFunction(_admit_many)", pytest.approx(800 * ns)],
                  ["TransferToDevice", pytest.approx(40 * ns)]],
        "device": [["PjRtBuffer::Await", pytest.approx(5700 * ns)]]}
    want_scope = {"attention": 2000, "experts/expert_einsums": 2000,
                  "experts/router": 1000, "unscoped": 1000, "lm_head": 500}
    assert {k: round(v / ns) for k, v in facts["scope_s"].items()} == \
        want_scope
    assert sum(facts["scope_s"].values()) == pytest.approx(facts["busy_s"])
    assert facts["unix_offset_us"] == pytest.approx(1000000000.1)
    assert facts["ticks"] == 1
    assert "experts/router" in trace_gaps.render(facts)
    json.dumps(facts)


# ------------------------------------------------------------------- hists


def test_gate_wait_hist_flat_keys_round_trip():
    h = telemetry.Hist()
    h.observe(0.0)                 # a wait of 0 is an observation too
    h.observe(1.5)
    flat = h.flat("gate_wait")
    assert flat["hist_gate_wait__all__count"] == 2
    assert flat["hist_gate_wait__all__sum"] == 1.5
    back = telemetry.parse_flat(flat)[("gate_wait", "all")]
    assert (back.count, back.sum, back.counts) == (2, 1.5, h.counts)
    snap = telemetry.snapshot_from_hists({("gate_wait", "all"): back})
    assert snap["gate_wait"]["count"] == 2
    # an empty histogram still carries count and sum, at 0
    assert telemetry.Hist().flat("gate_wait") == {
        "hist_gate_wait__all__count": 0.0, "hist_gate_wait__all__sum": 0.0}
