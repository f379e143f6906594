"""Scheduler X-ray (ISSUE 13): per-tick pack ledger, fallback reason codes,
and cost-analysis rooflines.

Cheap taxonomy / ledger / roofline / benchdiff units run in tier-1; the
engine-driving scenario streams (grammar overflow, pending admission, KV
demotion) are slow-marked. The load-bearing contract tested
here: every reason code an engine site emits is REGISTERED (unregistered is
a hard ValueError), every registered code has a site that emits it, and the
dispatch-category counters sum exactly to `decode_dispatches`.
"""
import json
import time

import numpy as np
import pytest

from localai_tpu.telemetry import sched as S

pytestmark = pytest.mark.tripwire

TINY = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=2, head_dim=16,
            max_position=8192, dtype="float32")


@pytest.fixture(scope="module")
def tiny_parts():
    import jax

    from localai_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(**TINY)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(tiny_parts, **kw):
    from localai_tpu.engine.engine import Engine, EngineConfig

    cfg, params = tiny_parts
    return Engine(cfg, params, None, EngineConfig(**kw))


def _req(n=8, max_tokens=8, seed=3, **kw):
    from localai_tpu.engine.engine import GenRequest
    from localai_tpu.ops.sampling import SamplingParams

    rng = np.random.default_rng(seed)
    return GenRequest(rng.integers(1, 90, n).tolist(),
                      SamplingParams(temperature=0.0),
                      max_tokens=max_tokens, ignore_eos=True, **kw)


def _drain(eng, steps=3000):
    for _ in range(steps):
        if not eng.step():
            break


# ------------------------------------------------------------ the taxonomy


def test_unregistered_reason_code_hard_fails():
    led = S.TickLedger()
    with pytest.raises(ValueError, match="unregistered"):
        led.reason("made_up_code")
    # the failure leaves no counter behind
    assert "made_up_code" not in led.counters


def test_registry_shape_is_contractual():
    cats = {"dispatch", "demotion", "admission", "kv"}
    for code, (cat, desc) in S.REASON_CODES.items():
        assert cat in cats, code
        assert desc and code == code.lower()
    assert set(S.DISPATCH_CODES) == {
        c for c, (cat, _) in S.REASON_CODES.items() if cat == "dispatch"}
    assert "loop_native" in S.DISPATCH_CODES
    assert S.reason_category("kv_eviction") == "kv"


def test_every_reason_code_has_an_emitter():
    """A registered code that no site of the program names is a series a
    dashboard waits on for ever: each key of REASON_CODES occurs as a
    string literal in `localai_tpu/` outside the registry's own file (the
    dispatch codes through `_loop_block_reason`'s returns)."""
    import ast
    import pathlib

    import localai_tpu

    root = pathlib.Path(localai_tpu.__file__).parent
    literals = set()
    for path in root.rglob("*.py"):
        if path.name == "sched.py" and path.parent.name == "telemetry":
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                literals.add(node.value)
    assert not set(S.REASON_CODES) - literals


def test_sched_gate_and_per_engine_ledgers():
    try:
        S.set_sched_enabled(False)
        assert S.maybe_ledger() is None
        S.set_sched_enabled(True)
        a, b = S.maybe_ledger(), S.maybe_ledger()
        assert a is not None and b is not None and a is not b
    finally:
        S.set_sched_enabled(None)


# ------------------------------------------------------------------ ledger


def test_ledger_flat_snapshot_roundtrip():
    led = S.TickLedger()
    led.begin(1)
    led.reason("pending_admission")
    led.reason("kv_eviction", kind="ring_overwrite")
    led.pack("loop", decode_rows=3, prefill_tokens=16, pad_rows=5,
             rows_used=24, packed=19)
    rec = led.commit(active_slots=3)
    assert rec["tick"] == 1 and rec["active_slots"] == 3
    assert rec["packs"][0]["variant"] == "loop"
    assert json.loads(json.dumps(rec))  # tick records are JSON-clean

    flat = led.flat()
    assert flat["sched_ticks_total"] == 1.0
    assert flat["sched_reason__pending_admission"] == 1.0
    assert flat["sched_variant__loop"] == 1.0
    assert flat["sched_pack__prefill_tokens"] == 16.0
    assert flat["sched_pack__packed"] == 19.0
    assert flat["sched_pad_rows_frac"] == pytest.approx(5 / 24)

    snap = led.snapshot()
    assert snap["reason_counters"]["kv_eviction"] == 1
    assert snap["recent_ticks"][-1]["tick"] == 1

    led.rooflines["loop"] = S.roofline_entry(1e6, 1e6, 1e9, 1e9)
    led.reset()
    # reset drops the stream but keeps the (expensive) cached rooflines
    assert led.n_ticks == 0 and not led.counters
    assert "loop" in led.rooflines
    assert "sched_roofline__loop__flops" in led.flat()


def test_tick_rings_wrap():
    led = S.TickLedger(ring=8)
    for i in range(20):
        led.begin(i)
        led.commit()
    assert led.n_ticks == 20 and len(led.ticks) == 8
    assert [r["tick"] for r in led.ticks] == list(range(12, 20))

    from localai_tpu.telemetry.metrics import FlightRecorder

    rec = FlightRecorder(ticks=4)
    for i in range(10):
        rec.record_tick({"tick": i})
    assert [r["tick"] for r in rec.ticks] == [6, 7, 8, 9]


def test_flightrec_events_stamp_current_tick():
    from localai_tpu.telemetry.metrics import FlightRecorder

    rec = FlightRecorder()
    try:
        S.set_current_tick(41)
        rec.record_event("tripwire", detail="x")
        S.set_current_tick(None)
        rec.record_event("breaker_open")
        rec.record_event("explicit", tick=7)
    finally:
        S.set_current_tick(None)
    evs = list(rec.events)
    assert evs[0]["tick"] == 41
    assert "tick" not in evs[1]
    assert evs[2]["tick"] == 7


# --------------------------------------------------------------- rooflines


def test_roofline_entry_attribution():
    # 1 GFLOP against 1 KB on a (1 TF/s, 1 GB/s) device: compute-bound
    e = S.roofline_entry(1e9, 1e3, 1e12, 1e9)
    assert e["bound"] == "compute" and e["mfu"] == pytest.approx(1.0)
    # 1 KFLOP against 1 GB: bandwidth-bound, ceiling well under 1
    e = S.roofline_entry(1e3, 1e9, 1e12, 1e9)
    assert e["bound"] == "bandwidth" and e["mfu"] < 1e-6
    assert e["t_roofline_us"] == pytest.approx(e["t_memory_us"])



def test_unknown_device_kind_gives_no_peak():
    """One chip table keyed by device_kind, no default and no CPU row: a
    device that is not in it gets cost COUNTS and no time/bound/mfu figure
    (a defaulted peak is how a CPU run once reported an `mfu`)."""
    from localai_tpu.system.capabilities import CHIPS

    assert CHIPS["TPU v5 lite"].bf16_flops == 197e12
    assert CHIPS["TPU v5 lite"].hbm_bytes_per_s == 819e9
    for kind in ("cpu", "", "TPU v9", "tpu v5 lite"):
        assert CHIPS.get(kind) is None
    e = S.roofline_entry(1e9, 1e3)
    assert e["cost_flops"] == 1e9 and e["cost_bytes"] == 1e3
    assert not {"mfu", "bound", "t_roofline_us"} & set(e)
    led = S.TickLedger()
    led.rooflines["decode"] = e
    flat = led.flat()
    assert flat["sched_roofline__decode__flops"] == 1e9
    assert "sched_roofline__decode__mfu" not in flat


# --------------------------------------------------------------- benchdiff


def _bench_json(tmp_path, name, **fields):
    base = {"metric": "decode tok/s/chip (llama-tiny f32, paged ...)",
            "value": 100.0, "unit": "tok/s"}
    base.update(fields)
    p = tmp_path / name
    p.write_text(json.dumps(base))
    return str(p)


def test_benchdiff_gates_ratios_not_throughput(tmp_path):
    from tools import benchdiff

    old = _bench_json(tmp_path, "old.json", paged_over_dense=1.2,
                      compile_count_delta=0)
    # halved raw tok/s is box noise — NOT a regression on its own
    ok = _bench_json(tmp_path, "ok.json", value=55.0,
                     paged_over_dense=1.18, compile_count_delta=0)
    assert benchdiff.main([old, ok]) == 0
    # a collapsed ratio metric IS a regression
    bad = _bench_json(tmp_path, "bad.json", value=100.0,
                      paged_over_dense=0.6, compile_count_delta=0)
    assert benchdiff.main([old, bad]) == 1
    # counter invariants regress on ANY growth (new mid-stream compiles)
    grew = _bench_json(tmp_path, "grew.json", paged_over_dense=1.2,
                       compile_count_delta=2)
    assert benchdiff.main([old, grew]) == 1
    # raw-throughput collapse past the floor fails even with ratios intact
    dead = _bench_json(tmp_path, "dead.json", value=10.0,
                       paged_over_dense=1.2, compile_count_delta=0)
    assert benchdiff.main([old, dead]) == 1
    assert benchdiff.main([str(tmp_path / "missing.json"), ok]) == 2


def test_benchdiff_picks_latest_two_from_runs_dir(tmp_path):
    import os

    from tools import benchdiff

    for i, stamp in enumerate(["2026-01-01", "2026-01-02", "2026-01-03"]):
        p = _bench_json(tmp_path, f"bench_{i}.json", recorded_at=stamp)
        os.utime(p, (1000 + i, 1000 + i))
    prev, latest = benchdiff.latest_two(str(tmp_path))
    assert prev.endswith("bench_1.json") and latest.endswith("bench_2.json")
    assert benchdiff.main(["--runs-dir", str(tmp_path)]) == 0


# ------------------------------------------------- engine scenario streams


@pytest.mark.slow
def test_dispatch_codes_sum_to_dense_dispatches(tiny_parts):
    """The exactness invariant: over a stream with queued admissions,
    EVERY decode dispatch emits exactly one dispatch-category code — the
    counters sum to decode_dispatches, and the pending_admission scenario
    (more requests than slots) appears by name."""
    eng = _engine(tiny_parts, max_slots=2, max_context=128,
                  prefill_buckets=(16,), prompt_cache=False,
                  decode_loop=4)
    assert eng._sched is not None
    # staggered budgets + a 4-step loop window: the short request frees its
    # slot at a loop boundary while its neighbour still decodes, so the
    # next dispatch sees free-slot + queued request simultaneously
    # (_dispatch runs before _prefill_tick each tick) and must fall back
    # dense with the pending_admission code
    for i in range(5):   # 5 requests through 2 slots → queued admissions
        eng.submit(_req(seed=i, max_tokens=4 if i % 2 == 0 else 20))
    _drain(eng)
    sched = eng._sched
    dense = eng.metrics["decode_dispatches"]
    code_sum = sum(sched.counters.get(c, 0) for c in S.DISPATCH_CODES)
    assert dense > 0 and code_sum == dense, dict(sched.counters)
    assert sched.counters.get("pending_admission", 0) > 0
    # ledger <-> metrics cross-checks on the same stream
    assert sched.n_ticks > 0
    assert sched.n_dispatches == sum(sched.variants.values())
    assert sum(v for k, v in eng.metrics.items()
               if k.startswith("tokens_by_path__")) == \
        eng.metrics["tokens_generated"]
    flat = sched.flat()
    assert flat["sched_ticks_total"] == float(sched.n_ticks)
    # tick records reached the flight recorder ring with full pack detail
    if eng._flightrec is not None:
        recs = [r for r in eng._flightrec.ticks if "packs" in r]
        assert recs and any(r["packs"] for r in recs)


@pytest.mark.slow
def test_kv_policy_demotion_reason_matches_metric(tiny_parts):
    """A full-attention request too big for the compact windowed pool is
    demoted at admission: the engine metric and the reason-code counter
    move in lockstep."""
    eng = _engine(tiny_parts, max_slots=1, max_context=4096,
                  prefill_buckets=(16,), kv_pages=24,
                  kv_policy="sink_window(sinks=256, window=512)")
    eng.submit(_req(n=39, max_tokens=3900, kv_policy="full"))
    for _ in range(30):
        eng.step()
    assert eng.metrics["kv_policy_demotions"] >= 1
    assert eng._sched.counters.get("kv_policy_demotion", 0) == \
        eng.metrics["kv_policy_demotions"]


@pytest.mark.slow
def test_grammar_overflow_reason_and_hostonly_dispatches(tmp_path_factory):
    """A 1-state table cap overflows on any real grammar: the admission
    emits grammar_table_overflow, and every dense dispatch while that slot
    lives carries the grammar_hostonly dispatch code."""
    from fixtures import tiny_checkpoint
    from localai_tpu.engine import (
        Engine, EngineConfig, GenRequest, Tokenizer, load_config,
        load_params,
    )
    from localai_tpu.functions.grammars import json_schema_grammar
    from localai_tpu.ops.sampling import SamplingParams

    ckpt = tiny_checkpoint(tmp_path_factory)
    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    eng = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prompt_cache=False, grammar_table_states=1))
    schema = {"type": "object",
              "properties": {"a": {"type": "integer"}}, "required": ["a"]}
    eng.submit(GenRequest(tok.encode("emit json:"),
                          SamplingParams(temperature=0.0), max_tokens=12,
                          grammar=json_schema_grammar(schema)))
    _drain(eng)
    sched = eng._sched
    assert sched.counters.get("grammar_table_overflow", 0) >= 1
    assert sched.counters.get("grammar_hostonly", 0) > 0
    assert eng.metrics.get("grammar_table_overflows", 0) >= 1


@pytest.mark.slow
def test_rooflines_cost_variants_without_new_compiles(tiny_parts):
    """engine.rooflines() AOT-costs every dispatched variant (real XLA
    cost_analysis FLOPs/bytes) and must not add jit-cache compiles — the
    compile-count tripwire quantity stays frozen."""
    from localai_tpu.testing.tripwires import decode_compile_count

    eng = _engine(tiny_parts, max_slots=2, max_context=128,
                  prefill_buckets=(16,), prompt_cache=False)
    for i in range(2):
        eng.submit(_req(seed=20 + i))
    _drain(eng)
    before = decode_compile_count(eng)
    roofs = eng.rooflines(force=True)
    assert roofs, "no variant was costed"
    for name, e in roofs.items():
        assert e["cost_flops"] > 0 and e["cost_bytes"] > 0, name
        # the CPU harness is not in the chip table: counts, no mfu
        assert "mfu" not in e and "bound" not in e
    assert decode_compile_count(eng) == before
    # costed variant names match the dispatched-variant ledger names
    assert set(roofs) <= set(eng._sched.variants) | set(roofs)
    snap = eng.sched_snapshot()
    assert snap["rooflines"] and snap["recent_ticks"]
    assert set(snap["rooflines"]) == set(roofs)
