"""A model with linear-attention layers beside softmax ones (Solar-Open2's
shape at a tiny size): the served path (models/llama.py over kv.StateKV,
ops/kda.py, the routed expert layer, the engine's programs) against the
plain float32 reference localai_tpu/testing/reference_linear.py.

Logits are compared where the served path shows them: at a prompt's end
after single-shot prefill and after chunked prefill over four chunks with a
padded last one, after decode steps with an inactive row beside the live
ones, and after steps inside the fused loop. float32 at REL_F32 (the bf16
run fails it), int8 weights + int8 KV at REL_INT8 / REL_INT8_MEDIAN. Every
planted fault fails REL_F32 by far.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import Engine, EngineConfig
from localai_tpu.engine.loader import load_config, load_params
from localai_tpu.models import llama
from localai_tpu.models.llama import (
    FULL, LINEAR, PeriodKV, decode_step, extend, init_kv_cache, init_params,
    prefill, rope_tables,
)
from localai_tpu.ops.sampling import SamplingParams, sampler_row
from localai_tpu.testing import reference_linear as ref

# the served float32 path against the reference: rounding only (measured
# 2e-6..8e-6 here). One product in bfloat16 reads 1e-2.
REL_F32 = 1e-4
# int8 weights are shared with the reference (it gets the dequantised
# values); what is left is bf16 activations and int8 KV at a hidden size of
# 64, where one rounding is a large share of a logit and a near tie in the
# router (top-4 of 16 on random weights) swaps an expert: measured 0.12-0.35
# over three seeds of seven positions; every planted fault reads above 0.6
# in float32 and the published widths read 0.05 on the chip (PERF.md)
REL_INT8_MEDIAN, REL_INT8 = 0.35, 0.6
CHUNK = 32

HF = dict(
    model_type="solar_open2", hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, head_dim=16, num_key_value_heads=2,
    vocab_size=128, intermediate_size=128, moe_intermediate_size=32,
    rms_norm_eps=1e-5, max_position_embeddings=4096,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    gqa_layers=[0, 4], use_rope=False, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True, n_routed_experts=8,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=1,
    num_experts_per_tok=4, first_k_dense_replace=0,
    tie_word_embeddings=False,
    localai_expert_share=dict(router_experts=16, first_expert=4),
    localai_synthetic=True)


def _dir(tmp_path, **over):
    d = tmp_path / "ckpt"
    d.mkdir(exist_ok=True)
    (d / "config.json").write_text(json.dumps({**HF, **over}))
    return str(d)


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    cfg = load_config(_dir(tmp_path_factory.mktemp("solar")),
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    rcfg = ref.RefConfig.from_hf(HF)
    return cfg, params, rcfg, ref.from_served(params, cfg.layer_types)


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(8, HF["vocab_size"], size=n)


# ----------------------------------------------------------- the forwards

def _serve(cfg, params, ids, *, dtype=jnp.float32, cache_type="",
           drop_tail=False, extra_steps=()):
    """The row `ids` through chunked prefill (slot 1 of 3, its state planted
    NaN: admission resets it), then teacher-forced decode steps over
    `extra_steps` with slot 0 inactive and holding NaN. Returns the logits
    at the prompt's end and after each step."""
    kc, vc = init_kv_cache(cfg, 3, 256, dtype, cache_type=cache_type,
                           prefill_chunk=CHUNK)
    nan = lambda c: PeriodKV(tuple(  # noqa: E731
        s.at[:, :2].set(jnp.nan) if k == LINEAR else s
        for s, k in zip(c.slots, cfg.period)))
    kc, vc = nan(kc), nan(vc)
    cos, sin = rope_tables(cfg, 256)
    slot = jnp.array([1])
    n = len(ids)
    mid = jax.jit(lambda p, t, s, kc, vc: extend(
        p, cfg, t, s, cos, sin, kc, vc, slot_map=slot, with_logits=False,
        full_window=True))
    last = jax.jit(lambda p, t, s, kc, vc, lp: extend(
        p, cfg, t, s, cos, sin, kc, vc, slot_map=slot, last_pos=lp))
    step = jax.jit(lambda p, t, l, kc, vc, a: decode_step(
        p, cfg, t, l, cos, sin, kc, vc, active=a))
    for pos in range(0, n, CHUNK):
        part = ids[pos:pos + CHUNK]
        buf = np.zeros((1, CHUNK), np.int32)
        buf[0, :len(part)] = part
        if pos + CHUNK >= n:
            logits, kc, vc = last(params, jnp.asarray(buf), jnp.array([pos]),
                                  kc, vc, jnp.array([len(part) - 1]))
        else:
            _, kc, vc = mid(params, jnp.asarray(buf), jnp.array([pos]),
                            kc, vc)
        if drop_tail:       # the planted fault: the conv tail is not carried
            vc = PeriodKV(tuple(
                jnp.zeros_like(s) if k == LINEAR else s
                for s, k in zip(vc.slots, cfg.period)))
    out = [logits[0]]
    lengths = jnp.array([0, n, 0])
    active = jnp.array([False, True, False])
    for t in extra_steps:
        lg, kc, vc = step(params, jnp.array([0, t, 0]), lengths, kc, vc,
                          active)
        lengths = lengths + active
        out.append(lg[1])
    # the inactive row's state is still the NaN it was given
    for s, k in zip(kc.slots, cfg.period):
        if k == LINEAR:
            assert bool(jnp.isnan(s[:, 0]).all())
            assert not bool(jnp.isnan(s[:, 1]).any())
    return out


def test_chunked_prefill_and_decode_match_the_reference(model):
    cfg, params, rcfg, rparams = model
    ids, more = _ids(110), _ids(6, seed=1)        # 32 + 32 + 32 + 14 (padded)
    got = _serve(cfg, params, ids, extra_steps=more)
    want = ref.logits(rparams, rcfg, np.concatenate([ids, more]))
    for i, g in enumerate(got):
        assert _rel(g, want[len(ids) - 1 + i]) < REL_F32, i


def test_single_shot_prefill_matches_the_reference(model):
    cfg, params, rcfg, rparams = model
    kc, vc = init_kv_cache(cfg, 3, 256, jnp.float32, prefill_chunk=CHUNK)
    cos, sin = rope_tables(cfg, 256)
    rows = [_ids(40, seed=2), _ids(64, seed=3)]
    buf = np.zeros((2, 64), np.int32)
    for i, r in enumerate(rows):
        buf[i, :len(r)] = r
    logits, kc, vc = prefill(params, cfg, jnp.asarray(buf),
                             jnp.array([40, 64]), cos, sin, kc, vc,
                             jnp.array([2, 0]))
    for i, r in enumerate(rows):
        want = ref.logits(rparams, rcfg, r)
        assert _rel(logits[i], want[-1]) < REL_F32
    # and forward_train (no cache at all) over the same rows
    full = llama.forward_train(params, cfg, jnp.asarray(rows[1][None]))
    assert _rel(full[0], ref.logits(rparams, rcfg, rows[1])) < REL_F32


def test_bfloat16_fails_the_float32_tolerance(model):
    cfg, params, rcfg, rparams = model
    cfg16 = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
        and a.ndim > 1 else a, params)
    ids = _ids(110)
    got = _serve(cfg16, p16, ids, dtype=jnp.bfloat16)
    want = ref.logits(ref.from_served(p16, cfg.layer_types), rcfg, ids)
    assert _rel(got[0], want[-1]) > 10 * REL_F32


def test_int8_weights_and_int8_kv(tmp_path, monkeypatch):
    monkeypatch.setenv("LOCALAI_ALLOW_SYNTHETIC", "1")
    d = _dir(tmp_path)
    cfg = load_config(d, dtype="int8")
    params = load_params(d, cfg, dtype="int8")
    assert params["layers"][LINEAR]["wq"]["q"].dtype == jnp.int8
    assert params["layers"][LINEAR]["A_log"].dtype == jnp.float32
    ids, more = _ids(110), _ids(4, seed=1)
    got = _serve(cfg, params, ids, dtype=jnp.bfloat16, cache_type="int8",
                 extra_steps=more)
    rp = ref.from_served(params, cfg.layer_types)
    want = ref.logits(rp, ref.RefConfig.from_hf(HF),
                      np.concatenate([ids, more]))
    rels = [_rel(g, want[len(ids) - 1 + i]) for i, g in enumerate(got)]
    assert max(rels) < REL_INT8 and np.median(rels) < REL_INT8_MEDIAN, rels


def _without(params, kind, *names):
    layers = dict(params["layers"])
    layers[kind] = {k: v for k, v in layers[kind].items() if k not in names}
    return dict(params, layers=layers)


FAULTS = {
    # name -> (config change, params change, kwargs of _serve)
    "decay_gate_off": ({}, lambda p: dict(p, layers=dict(
        p["layers"], linear=dict(p["layers"][LINEAR], A_log=jnp.full_like(
            p["layers"][LINEAR]["A_log"], -1e9)))), {}),
    "beta_not_doubled": (dict(linear_neg_eigval=False), None, {}),
    "conv_tail_not_carried": ({}, None, dict(drop_tail=True)),
    "gqa_gate_off": ({}, lambda p: _without(p, FULL, "w_agate"), {}),
    "rope_applied": (dict(use_rope=True), None, {}),
    "shared_expert_left_out": ({}, lambda p: _without(
        _without(p, FULL, "ws_gate", "ws_up", "ws_down"), LINEAR,
        "ws_gate", "ws_up", "ws_down"), {}),
    "first_expert_off_by_one": (dict(first_expert=5), None, {}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails(model, fault):
    cfg, params, rcfg, rparams = model
    over, change, kw = FAULTS[fault]
    ids = _ids(110)
    got = _serve(dataclasses.replace(cfg, **over),
                 change(params) if change else params, ids, **kw)
    want = ref.logits(rparams, rcfg, ids)
    assert _rel(got[0], want[-1]) > 100 * REL_F32


# ------------------------------------------------- the engine's programs

@pytest.fixture()
def engine(model, monkeypatch):
    monkeypatch.setenv("LOCALAI_NO_PREWARM", "1")
    cfg, params, _, _ = model
    return Engine(cfg, params, None, EngineConfig(
        max_slots=3, max_context=256, prefill_buckets=(CHUNK,),
        prefill_chunk=CHUNK, decode_loop=8))


def test_the_engines_programs_match_the_reference(model, engine):
    """Chunked prefill, a bucket admission half way, single steps with an
    inactive row and the fused loop, as the engine dispatches them; slot 1
    serves another tenant first (its state must be reset)."""
    cfg, params, rcfg, rparams = model
    eng = engine
    greedy = sampler_row(SamplingParams(temperature=0.0), cfg.vocab_size,
                         fallback_seed=1, include_bias=False)
    rows = {0: [int(t) for t in _ids(110)],
            1: [int(t) for t in _ids(20, seed=5)]}
    seen = []

    def note(row):
        seen.append((row, len(rows[row]) - 1,
                     np.asarray(eng._last_logits[row], np.float32)))

    def chunk(row, ids, pos):
        part = ids[pos:pos + CHUNK]
        buf = np.zeros((1, CHUNK), np.int32)
        buf[0, :len(part)] = part
        if pos + CHUNK >= len(ids):
            eng._dev_extend_final(buf, pos, len(part), row, greedy, None)
        else:
            eng._dev_extend_mid(buf, pos, row)

    first = [int(t) for t in _ids(50, seed=9)]
    for pos in (0, CHUNK):
        chunk(1, first, pos)
    for n, pos in enumerate(range(0, 110, CHUNK)):
        chunk(0, rows[0], pos)
        if n == 1:
            buf = np.zeros((1, CHUNK), np.int32)
            buf[0, :20] = rows[1]
            eng._dev_admit(buf, 20, 1, greedy, None)
            note(1)
        elif n == 2:
            active = np.array([False, True, False])
            tokens, _ = eng._dev_decode(active).wait()
            rows[1].append(int(tokens[1]))
            note(1)
    note(0)
    for _ in range(3):
        tokens, _ = eng._dev_decode(np.array([True, True, False])).wait()
        for r in (0, 1):
            rows[r].append(int(tokens[r]))
            note(r)
    active = np.array([True, True, False])
    toks, _, n_out, _ = eng._dev_decode_loop(
        active, np.array([8, 8, 0], np.int32), np.zeros((3,), bool)).wait()
    for r in (0, 1):
        assert int(n_out[r]) == 8
        rows[r].extend(int(t) for t in np.asarray(toks)[:8, r])
        note(r)
    want = {r: np.asarray(ref.logits(rparams, rcfg, np.asarray(rows[r])))
            for r in rows}
    assert len(seen) == 11
    for row, pos, got in seen:
        assert _rel(got, want[row][pos]) < REL_F32, (row, pos)


def test_gauges_and_counters(engine):
    m = engine.metrics
    assert m["layers__linear"] == 6 and m["layers__full"] == 2
    # state 4 x 16 x 16 float32 + conv tail 3 x 192 float32, 3 slots, 6 layers
    assert m["kv_bytes__linear"] == 6 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert m["kv_bytes__full"] == 2 * 3 * 2 * 2 * 256 * 16 * 4
    assert "decode_ctx_tokens__full" not in m
    engine._slots[1] = type("S", (), dict(
        request_id=7, prompt_len=10, generated=2, prefilled=True))()
    engine._credit_consumed(3, [(1, 7)])
    per_token = 2 * 2 * 16 * 4
    assert m["decode_cache_bytes__full"] == (13 + 14 + 15) * per_token * 2
    assert m["decode_cache_bytes__linear"] == (
        2 * 3 * 6 * (4 * 16 * 16 * 4 + 3 * 192 * 4))
    engine._slots[1] = None


# ------------------------------------------------ refused, each by name

@pytest.mark.parametrize("over, match", [
    # leading dense layers are computed beside window and full layers, not
    # in a model whose weights are stacked by kind
    (dict(first_k_dense_replace=1), "leading dense layers"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(gqa_layers=[0, 1, 5]), "not periodic"),
    (dict(gqa_layers=[]), "gqa_layers"),
    (dict(linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                  num_heads=4, num_kv_heads=2)),
     "num_kv_heads"),
    (dict(localai_expert_share=dict(router_experts=4, first_expert=0)),
     "cannot have"),
    (dict(localai_expert_share=dict(router_experts=16, first_expert=12)),
     "not among the router"),
])
def test_load_config_refuses_what_it_cannot_honour(tmp_path, over, match):
    with pytest.raises(ValueError, match=match):
        load_config(_dir(tmp_path, **over))


def test_load_config_reads_the_keys(tmp_path):
    cfg = load_config(_dir(tmp_path))
    assert cfg.period == (FULL, LINEAR, LINEAR, LINEAR)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) == (8, 16, 4)
    assert cfg.shared_expert_width == 32 and cfg.routed_scale == 1.0
    assert (cfg.linear_heads, cfg.linear_head_dim, cfg.linear_conv,
            cfg.linear_gate_rank) == (4, 16, 4, 16)
    assert cfg.linear_neg_eigval and cfg.attn_gate and not cfg.use_rope
    two = load_config(_dir(tmp_path, n_shared_experts=2))
    assert two.shared_expert_width == 64
    # a model_type alone names the architecture; an unknown one is refused
    with pytest.raises(ValueError, match="unsupported architecture"):
        load_config(_dir(tmp_path, architectures=["SolarOpen3ForCausalLM"]))


def test_only_synthetic_weights_can_be_loaded(tmp_path, monkeypatch):
    monkeypatch.delenv("LOCALAI_ALLOW_SYNTHETIC", raising=False)
    d = _dir(tmp_path)
    with pytest.raises(ValueError, match="no checkpoint"):
        load_params(d, load_config(d))


def test_synthetic_decay_is_never_zero_or_one(tmp_path, monkeypatch):
    """A_log and dt_bias by the family's initialisation: a token's decay
    spans about 0.1..0.999, so a decay left out or misapplied shows."""
    monkeypatch.setenv("LOCALAI_ALLOW_SYNTHETIC", "1")
    d = _dir(tmp_path)
    cfg = load_config(d, dtype="int8")
    lin = load_params(d, cfg, dtype="int8")["layers"][LINEAR]
    a = jnp.exp(lin["A_log"])
    assert float(a.min()) >= 1.0 and float(a.max()) <= 16.0
    dt = jax.nn.softplus(lin["dt_bias"])
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    alpha = jnp.exp(-a[:, :, None] * dt.reshape(a.shape[0], a.shape[1], -1))
    assert 0.15 < float(alpha.min()) and float(alpha.max()) < 0.9995
    assert float(jnp.median(alpha)) > 0.9


@pytest.mark.parametrize("over, match", [
    (dict(kv_pages=8), "linear-attention layers.*paged KV"),
    (dict(kv_pages=8, kv_host_bytes=1 << 20), "paged KV"),
    (dict(kv_host_bytes=1 << 20), "requires paged KV"),
    (dict(kv_policy="sink_window(sinks=0, window=64)"), "requires paged KV"),
])
def test_the_engine_refuses_at_load(model, monkeypatch, over, match):
    monkeypatch.setenv("LOCALAI_NO_PREWARM", "1")
    cfg, params, _, _ = model
    with pytest.raises(ValueError, match=match):
        Engine(cfg, params, None, EngineConfig(
            max_slots=2, max_context=128, prefill_buckets=(32,), **over))


def test_the_engine_refuses_a_draft_model(model, monkeypatch):
    monkeypatch.setenv("LOCALAI_NO_PREWARM", "1")
    cfg, params, _, _ = model
    with pytest.raises(ValueError, match="linear-attention layers.*draft"):
        Engine(cfg, params, None, EngineConfig(
            max_slots=2, max_context=128, prefill_buckets=(32,)),
            draft=(cfg, params))


def test_the_engine_refuses_at_submit(engine, tmp_path):
    from localai_tpu.engine import GenRequest

    greedy = SamplingParams(temperature=0.0)
    with pytest.raises(ValueError, match="context_shift"):
        engine.submit(GenRequest([9, 10, 11], greedy, max_tokens=4,
                                 context_shift=True))
    with pytest.raises(ValueError, match="prompt_cache_path"):
        engine.submit(GenRequest([9, 10, 11], greedy, max_tokens=4,
                                 prompt_cache_path=str(tmp_path / "p.npz")))


def test_a_slot_lends_no_prefix_and_starts_from_zero(model, engine):
    """Two requests with the same 40-token prefix through one slot: the
    second reuses nothing (the state at the prefix's end is not held),
    starts from a zero state and gives the reference's tokens."""
    from localai_tpu.engine import GenRequest

    cfg, params, rcfg, rparams = model
    eng = engine
    prefix = [int(t) for t in _ids(40, seed=21)]
    greedy = SamplingParams(temperature=0.0)
    out = []
    for tail in ([50, 51, 52], [60, 61]):
        ids = prefix + tail
        toks = [o.token_id for o in eng.generate(GenRequest(
            ids, greedy, max_tokens=5, ignore_eos=True)) if o.token_id >= 0]
        want = np.asarray(ref.logits(rparams, rcfg, np.asarray(ids + toks)))
        assert toks == list(np.argmax(want[len(ids) - 1:-1], axis=-1))
        out.append(toks)
    assert eng.metrics["prompt_cache_hits"] == 0
    assert eng.metrics["prompt_tokens_reused"] == 0
    assert eng.metrics["prompt_tokens_processed"] == 43 + 42
    assert eng.metrics["decode_cache_bytes__linear"] > 0


# ------------------------------------- the chunk's kernel against its twin

def _chunk_inputs(b, s, h, seed, alpha=(0.15, 0.9995), state=True):
    """q, k unit vectors a head (q scaled) as StateKV._qkv makes them, a
    token's decay between `alpha`'s ends (what
    test_synthetic_decay_is_never_zero_or_one allows), beta in (0, 2)."""
    d = 128
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = jnp.log(jax.random.uniform(ks[3], (b, s, h, d), minval=alpha[0],
                                   maxval=alpha[1]))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d))
    return q, k, v, g, beta, s0 if state else jnp.zeros_like(s0)


CHUNK_CASES = {
    # name -> (rows, tokens, heads, n_valid, kwargs of _chunk_inputs)
    "[1, 512]": (1, 512, 2, None, {}),
    "[4, 512]": (4, 512, 2, None, {}),
    "[8, 256]": (8, 256, 2, None, {}),
    "a length 64 does not divide": (2, 200, 2, None, {}),
    "rows that end early": (3, 192, 2, [37, 192, 64], {}),
    "two blocks of heads": (1, 128, 16, [100], {}),
    "from a zero state": (2, 128, 2, None, dict(state=False)),
    "decay at its slow end": (1, 256, 2, None, dict(alpha=(0.999, 0.9995))),
    "decay at its fast end": (1, 256, 2, [250], dict(alpha=(0.15, 0.16))),
    "q and k made unit vectors in the kernel": (2, 128, 2, [128, 90], {}),
    "planted: the triangle not strict": (1, 128, 2, None, {}),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_the_chunk_kernel_against_its_twin(monkeypatch, case):
    """ops/pallas/kda.py:kda_chunk in the interpreter: float32 agreement
    with ops/kda.py:kda_chunk and with the token-by-token scan, over the
    real tokens' outputs and the state."""
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    from localai_tpu.ops import kda
    from localai_tpu.ops.pallas import kda as kernels

    b, s, h, n, kw = CHUNK_CASES[case]
    planted = case.startswith("planted")
    if planted:
        monkeypatch.setattr(kernels, "_below", lambda row, col: row >= col)
    args = _chunk_inputs(b, s, h, seed=len(case), **kw)
    n = None if n is None else jnp.asarray(n)
    given = args
    if case.startswith("q and k"):      # as the convolution's SiLU left them
        given = (args[0] * 37.0, args[1] * 0.2) + args[2:]
        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731 (StateKV._qkv's)
            jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
        args = (unit(given[0]) * 128 ** -0.5, unit(given[1])) + args[2:]
    # (the jitted wrapper would hand the planted case a cached program)
    o, state = kernels.kda_chunk.__wrapped__(
        *given, n_valid=n, unit_qk=given is not args)
    twin_o, twin_state = kda.kda_chunk(*args, n_valid=n)
    live = (jnp.ones((b, s), bool) if n is None
            else jnp.arange(s)[None, :] < n[:, None])
    q, k, v, g, beta, s0 = args
    scan_o, scan_state = kda.kda_recurrent(
        q, k, v, jnp.where(live[..., None, None], g, 0.0),
        jnp.where(live[..., None], beta, 0.0), s0)
    cut = lambda a: jnp.where(live[..., None, None], a, 0.0)  # noqa: E731
    rels = [_rel(cut(o), cut(twin_o)), _rel(state, twin_state),
            _rel(cut(o), cut(scan_o)), _rel(state, scan_state)]
    if planted:
        assert min(rels) > 100 * REL_F32, rels
    else:
        assert max(rels) < REL_F32, rels


def test_the_chunk_kernel_refuses_a_shape_it_cannot_tile(monkeypatch):
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    from localai_tpu.ops.pallas.kda import kda_chunk

    z = jnp.zeros
    with pytest.raises(ValueError, match="do not tile"):
        kda_chunk(z((1, 64, 4, 16)), z((1, 64, 4, 16)), z((1, 64, 4, 128)),
                  z((1, 64, 4, 16)), z((1, 64, 4)), z((1, 4, 16, 128)))


def _state_tokens_through_the_engine(d):
    """Two prompts (80 tokens: three chunks, the last padded; 20: one
    admission bucket) through an engine whose linear heads are 128 wide
    (what the kernels tile): the counters and the greedy tokens."""
    from localai_tpu.engine import GenRequest

    hf = dict(HF, linear_attn_config=dict(HF["linear_attn_config"],
                                          head_dim=128))
    cfg = load_config(_dir(d, **hf), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=256, prefill_buckets=(CHUNK,),
        prefill_chunk=CHUNK))
    greedy = SamplingParams(temperature=0.0)
    toks = [[o.token_id for o in eng.generate(GenRequest(
        [int(t) for t in _ids(n, seed=n)], greedy, max_tokens=3,
        ignore_eos=True)) if o.token_id >= 0] for n in (80, 20)]
    return eng.metrics, toks


@pytest.mark.parametrize("tier", ["xla", "pallas-interpret"])
def test_the_engine_counts_the_tokens_the_chunk_kernel_served(
        tier, monkeypatch, tmp_path):
    """`chunk_state_tokens__seen` is a prompt's real tokens x the linear
    layers, whatever serves them; `__kernel` the same where
    kv.StateKV._mix takes the kernel and 0 on its twin; and the kernel's
    engine picks the twin's tokens."""
    monkeypatch.setenv("LOCALAI_NO_PREWARM", "1")
    m, toks = _state_tokens_through_the_engine(tmp_path)
    if tier != "xla":
        monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
        twin = toks
        m, toks = _state_tokens_through_the_engine(tmp_path)
        assert toks == twin and [len(t) for t in toks] == [3, 3]
    assert m["chunk_state_tokens__seen"] == (80 + 20) * 6
    assert m["chunk_state_tokens__kernel"] == (
        0 if tier == "xla" else (80 + 20) * 6)
