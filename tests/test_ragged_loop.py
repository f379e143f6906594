"""Fused multi-step ragged ticks (ISSUE 16).

Tier-1 (cheap units): the decode-token-aware dispatch budget has teeth in
BOTH directions, and `_ragged_loop_fn` rides the compile-count guard's attr
list.

Slow (engine-driving, per PR 8/10 precedent): exact token parity fused vs
single-step ragged across greedy + sampled + grammar tenants with
admissions landing mid-decode, a same-tick admission forcing the
prefill early exit, and the zero-recompile guard across two mixed streams.
"""
import numpy as np
import pytest

from fixtures import tiny_checkpoint
from localai_tpu.engine import (
    Engine, EngineConfig, GenRequest, Tokenizer, load_config, load_params,
)
from localai_tpu.ops.sampling import SamplingParams

pytestmark = pytest.mark.ragged


# -------------------------------------------------- dispatch-budget teeth

class _StubEngine:
    """dispatch_budget only reads engine.metrics — a dict stands in."""

    def __init__(self, **metrics):
        self.metrics = dict(
            decode_dispatches=0, tokens_generated=0,
            ragged_dispatches=0, ragged_prefill_tokens=0,
            spec_ragged_dispatches=0)
        self.metrics.update(metrics)


def _flightrec_sandbox(monkeypatch, tmp_path):
    from localai_tpu import telemetry

    monkeypatch.setenv("LOCALAI_FLIGHTREC_DIR", str(tmp_path))
    telemetry.reset_flightrec()


def test_dispatch_budget_trips_on_single_step_ragged(monkeypatch, tmp_path):
    """Teeth, trip direction: the blanket ragged exemption is GONE — a
    decode-heavy single-step ragged stream (~1 dispatch per generated
    token, no prefill credit) blows a 3/128 budget."""
    from localai_tpu import telemetry
    from localai_tpu.testing.tripwires import dispatch_budget

    _flightrec_sandbox(monkeypatch, tmp_path)
    try:
        eng = _StubEngine()
        with pytest.raises(AssertionError, match="dispatch budget"):
            with dispatch_budget(eng, max_per_128_tokens=3.0):
                eng.metrics["decode_dispatches"] += 128
                eng.metrics["ragged_dispatches"] += 128
                eng.metrics["tokens_generated"] += 128
    finally:
        telemetry.reset_flightrec()


def test_dispatch_budget_passes_fused_and_prefill_credit():
    """Teeth, pass direction: a fused multi-step stream (few dispatches,
    many tokens) and a prefill-heavy pack stream (`ragged_prefill_tokens`
    earns credit) both clear the same budget the single-step stream
    trips."""
    from localai_tpu.testing.tripwires import dispatch_budget

    eng = _StubEngine()
    with dispatch_budget(eng, max_per_128_tokens=3.0):
        # fused: 128 tokens over 3 dispatches (~16 steps/dispatch + ticks)
        eng.metrics["decode_dispatches"] += 3
        eng.metrics["ragged_dispatches"] += 3
        eng.metrics["tokens_generated"] += 128
    eng = _StubEngine()
    with dispatch_budget(eng, max_per_128_tokens=3.0):
        # admission burst: 3 dispatches packing 128 prefill tokens and
        # generating nothing yet — budget comes from the packed tokens
        eng.metrics["decode_dispatches"] += 3
        eng.metrics["ragged_dispatches"] += 3
        eng.metrics["ragged_prefill_tokens"] += 128


def test_dispatch_budget_spec_ragged_stays_exempt(monkeypatch, tmp_path):
    """Spec-as-ragged keeps the exemption (gamma-fused by construction,
    gated by acceptance telemetry): the same dispatch count that trips as
    plain ragged passes when attributed to spec_ragged_dispatches."""
    from localai_tpu import telemetry
    from localai_tpu.testing.tripwires import dispatch_budget

    eng = _StubEngine()
    with dispatch_budget(eng, max_per_128_tokens=3.0):
        eng.metrics["decode_dispatches"] += 64
        eng.metrics["ragged_dispatches"] += 64
        eng.metrics["spec_ragged_dispatches"] += 64
        eng.metrics["tokens_generated"] += 128
    _flightrec_sandbox(monkeypatch, tmp_path)
    try:
        eng = _StubEngine()
        with pytest.raises(AssertionError, match="dispatch budget"):
            with dispatch_budget(eng, max_per_128_tokens=3.0):
                eng.metrics["decode_dispatches"] += 64
                eng.metrics["ragged_dispatches"] += 64
                eng.metrics["tokens_generated"] += 128
    finally:
        telemetry.reset_flightrec()


def test_ragged_loop_fn_rides_compile_count_guard():
    from localai_tpu.testing.tripwires import DECODE_FN_ATTRS

    assert "_ragged_loop_fn" in DECODE_FN_ATTRS


# --------------------------------------------- engine parity (slow tier)

@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    ckpt = tiny_checkpoint(tmp_path_factory)
    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    return cfg, params, tok


def _ec(loop_steps, **kw):
    return EngineConfig(max_slots=4, max_context=128,
                        prefill_buckets=(16, 64), prefill_chunk=16,
                        kv_pages=10, prompt_cache=False,
                        ragged_token_budget=64,
                        ragged_loop_steps=loop_steps, **kw)


def _mixed_reqs(cfg, n_tok=10):
    rng = np.random.default_rng(0)
    lens = (5, 12, 33, 7, 21, 3)
    sps = [SamplingParams(temperature=0.0),
           SamplingParams(temperature=0.8, seed=11),
           SamplingParams(temperature=0.7, top_p=0.9, seed=3),
           SamplingParams(temperature=0.0),
           SamplingParams(temperature=1.0, top_k=5, seed=7),
           SamplingParams(temperature=0.0)]
    return [GenRequest(rng.integers(5, cfg.vocab_size, n).tolist(), sp,
                       max_tokens=n_tok, ignore_eos=True)
            for n, sp in zip(lens, sps)]


def _run_stream(cfg, params, tok, loop_steps):
    """The test_ragged mixed stream with admissions landing mid-decode —
    exactly the trace where same-tick admission forces the fused loop's
    prefill early exit."""
    eng = Engine(cfg, params, tok, _ec(loop_steps))
    reqs = _mixed_reqs(cfg)
    outs = [eng.submit(r) for r in reqs[:3]]
    for _ in range(3):
        eng.step()
    outs += [eng.submit(r) for r in reqs[3:]]
    for _ in range(500):
        if not eng.step():
            break
    toks = []
    for _, q in outs:
        seq = []
        while not q.empty():
            o = q.get_nowait()
            if o.token_id >= 0:
                seq.append(o.token_id)
        toks.append(seq)
    return toks, dict(eng.metrics)


@pytest.mark.slow
def test_fused_parity_and_early_exit(loaded):
    """Acceptance: the fused multi-step engine emits token streams
    IDENTICAL to single-step ragged (greedy + seeded top-p/top-k, mixed
    lengths, mid-decode admissions), while spending strictly fewer decode
    dispatches — and the mid-loop admissions force prefill early exits."""
    cfg, params, tok = loaded
    single, m1 = _run_stream(cfg, params, tok, loop_steps=0)
    fused, mf = _run_stream(cfg, params, tok, loop_steps=16)
    assert all(len(s) == 10 for s in single)
    assert single == fused
    # the dispatch boundary actually amortized
    assert mf["decode_dispatches"] < m1["decode_dispatches"], (mf, m1)
    assert mf["decode_steps_dispatched"] / mf["decode_dispatches"] > \
        m1["decode_steps_dispatched"] / m1["decode_dispatches"]
    # exit-reason taxonomy populated: finishes always, prefill exits from
    # the mid-decode admissions (the queue was non-empty at dispatch time)
    exits = {k: v for k, v in mf.items()
             if k.startswith("rloop_exit_") and v > 0}
    assert exits.get("rloop_exit_finish", 0) > 0, mf
    assert exits.get("rloop_exit_prefill", 0) > 0, mf
    assert m1.get("rloop_exit_finish", 0) == 0  # single-step never loops


@pytest.mark.slow
def test_fused_grammar_parity(loaded):
    """Grammar-table slots ride the fused loop (device mask gather +
    state advance per iteration) and match single-step ragged exactly,
    greedy and sampled."""
    from localai_tpu.functions.grammars import json_schema_grammar

    cfg, params, tok = loaded
    schema = {"type": "object",
              "properties": {"a": {"type": "integer"},
                             "b": {"type": "string"}},
              "required": ["a", "b"]}

    def reqs():
        g1 = GenRequest(tok.encode("emit json:"),
                        SamplingParams(temperature=0.0, seed=5),
                        max_tokens=24,
                        grammar=json_schema_grammar(schema))
        g2 = GenRequest(tok.encode("emit json:"),
                        SamplingParams(temperature=0.9, seed=9),
                        max_tokens=24,
                        grammar=json_schema_grammar(schema))
        p = GenRequest(tok.encode("the quick brown fox"),
                       SamplingParams(temperature=0.0),
                       max_tokens=10, ignore_eos=True)
        return [g1, p, g2]

    def drain(loop_steps):
        eng = Engine(cfg, params, tok, _ec(loop_steps))
        outs = [eng.submit(r) for r in reqs()]
        for _ in range(500):
            if not eng.step():
                break
        res = []
        for _, q in outs:
            ids, fin = [], None
            while not q.empty():
                o = q.get_nowait()
                if o.token_id >= 0:
                    ids.append(o.token_id)
                if o.finished:
                    fin = o.finish_reason
            res.append((ids, fin))
        return res, dict(eng.metrics)

    a, m1 = drain(0)
    b, mf = drain(16)
    assert a == b, (a, b)
    assert sum(v for k, v in mf.items()
               if k.startswith("rloop_exit_")) > 0, mf


@pytest.mark.slow
def test_fused_zero_recompiles_two_streams(loaded):
    """Compile-count guard over the fused program: after warmup, TWO mixed
    streams with mid-loop admissions add zero XLA compilations and the
    `_ragged_loop_fn` jit cache stays at its warm size."""
    from localai_tpu.testing.tripwires import (
        CompileCounter, decode_cache_sizes, decode_compile_count,
    )

    cfg, params, tok = loaded
    eng = Engine(cfg, params, tok, _ec(16))
    assert eng._ragged_loop_fn is not None
    eng.warmup()

    def stream():
        reqs = _mixed_reqs(cfg, n_tok=8)
        outs = [eng.submit(r) for r in reqs[:3]]
        for _ in range(2):
            eng.step()
        outs += [eng.submit(r) for r in reqs[3:]]
        for _ in range(500):
            if not eng.step():
                break
        return outs

    stream()  # warm stream: host-side admission programs (_install_row
    #           etc.) compile on first use, same as the soup precedent
    warm = decode_compile_count(eng)
    sizes = decode_cache_sizes(eng)
    assert sizes.get("_ragged_loop_fn", 0) >= 1, sizes
    with CompileCounter() as cc:
        stream()
        stream()
    assert cc.total == 0, cc.counts
    assert decode_compile_count(eng) == warm, decode_cache_sizes(eng)
    assert eng.metrics["tokens_by_path__rloop"] + \
        eng.metrics["tokens_by_path__ragged"] > 0
