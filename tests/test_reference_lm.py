"""The served model against the plain float32 reference
(localai_tpu/testing/reference_lm.py): logits, not tokens, on seeded random
weights at small sizes.

Two shapes run the same tests: a Mellum2-shaped one (three window layers then
a full one, twice; the window layers on a ring cache and plain RoPE, the full
layers on YaRN; 8 experts top-2 of their own width) and a Mixtral-shaped one
(every layer full, 4 experts top-2), which gives the one-kind path the same
reference.

Tolerances (absolute, on logits whose largest magnitude is 3 to 5):
- F32_TOL 2e-4, float32 weights and cache: both sides compute in float32 and
  differ only in the order of their sums; measured 3e-6 to 6e-6 here. The
  same comparison with bfloat16 weights and cache measures 0.05 to 0.2
  (test_bf16_fails_the_float32_tolerance holds it above the tolerance).
- int8 weights (the reference is given the dequantised values, so the
  weights add under 0.01) and int8 KV: the cache rounds K and V to 8 bits a
  token and head and the attention reads them as bfloat16, and on random
  weights that noise now and then swaps a token's second expert for its
  third (a near tie in the router), which moves that position's logits by
  about 1 wherever it happens: over 260 steps the largest error measures
  0.1 to 2.2, so a largest error decides nothing. What is held instead:
  Q8_MEDIAN_TOL 0.4 on the median over positions of the largest error
  (measured 0.02 to 0.17; logits of an unrelated position differ by 2 to 4,
  so a ring row read as the wrong position or a mask off by one fails it),
  and at most Q8_FLIPS 15% of positions off by more than 1.0 (measured 0 to
  4%). The sharp check of the ring under int8 is RING_TOL 0.02 against the
  SAME program over caches long enough never to wrap: there the int8 values
  are the same ones in other rows, and only the order of the bfloat16 sums
  (and the kernel's blocks) differs (measured 0.004 to 0.012). It holds at
  EVERY position but one where the two runs' routers provably chose
  differently on a tie: the first layer of that decode step whose top-k
  experts differ has its k-th and (k+1)-th probabilities within TIE 1e-3 in
  both runs (_router_spy). The order of the sums moves a probability by
  3e-5 to 7e-5, and of the 4160 router decisions of the 260 steps one or
  two lie closer than that: with the prompt's experts in the routed form
  (PR 32) step 252's layer 5 reads 0.13667198 / 0.13666086 on the ring and
  0.13668582 / 0.13671905 flat, step 204's layer 6 0.170904 / 0.17088193
  and 0.17079337 / 0.17085955, and their logits lie 0.22 and 0.39 apart
  (at most 1% of the positions may be such ties). A choice that differs by
  more than a tie is not excused, so a ring read at the wrong place, which
  moves every position after the wrap, still fails.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    FULL, WINDOW, LlamaConfig, PeriodKV, decode_step, extend, forward_train,
    init_kv_cache, init_params, prefill, ring_len, rope_tables,
)
from localai_tpu.ops.rope import RopeConfig
from localai_tpu.testing import reference_lm as ref

F32_TOL = 2e-4
Q8_MEDIAN_TOL, Q8_FLIPS = 0.4, 0.15
RING_TOL, TIE = 0.02, 1e-3
YARN_FACTOR = 1.2772588722239782     # Mellum2's published attention_factor

SHAPES = {
    "mellum": dict(
        vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, max_position=1024,
        rms_eps=1e-6, rope_base=500000.0, rope_scaling="yarn",
        rope_scale_factor=16.0, rope_original_max_position=16,
        rope_attn_factor=YARN_FACTOR, sliding_window=8, num_experts=8,
        experts_per_tok=2, moe_intermediate_size=32,
        layer_types=(WINDOW, WINDOW, WINDOW, FULL) * 2,
        window_rope=RopeConfig(head_dim=16, base=500000.0,
                               original_max_position=8192)),
    "mixtral": dict(
        vocab_size=96, hidden_size=64, intermediate_size=32, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=16, max_position=1024,
        rms_eps=1e-5, rope_base=1e6, num_experts=4, experts_per_tok=2),
}
# the same shapes as the keys of a published config.json, for the reference
HF = {
    "mellum": dict(
        vocab_size=96, hidden_size=64, intermediate_size=48,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=1024, rms_norm_eps=1e-6,
        sliding_window=8, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, norm_topk_prob=True,
        layer_types=["sliding_attention"] * 3 + ["full_attention"]
        + ["sliding_attention"] * 3 + ["full_attention"],
        mlp_layer_types=["sparse"] * 8,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 16, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": YARN_FACTOR},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        model_type="mellum", tie_word_embeddings=False),
    "mixtral": dict(
        vocab_size=96, hidden_size=64, intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=1024, rms_norm_eps=1e-5, rope_theta=1e6,
        num_local_experts=4, num_experts_per_tok=2, sliding_window=None,
        architectures=["MixtralForCausalLM"], tie_word_embeddings=False),
}


def _config(shape: str, dtype="float32", **over) -> LlamaConfig:
    return LlamaConfig(**{**SHAPES[shape], "dtype": dtype, **over})


def _ids(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, 96, size=n)


@pytest.fixture(scope="module", params=list(SHAPES))
def shape(request):
    return request.param


@pytest.fixture(scope="module")
def model(shape):
    cfg = _config(shape)
    params = init_params(cfg, jax.random.PRNGKey(1))
    return shape, cfg, params, ref.RefConfig.from_hf(HF[shape])


def _serve(cfg, params, ids, *, prompt, short, steps, chunk, context,
           cache_type="", ring_for_chunk=None):
    """The served path over one long row and one short row, as the engine
    drives it: the long row's prompt through `extend` a chunk at a time (the
    last chunk padded), the short row's through single-shot `prefill`, then
    `steps` decode steps of both rows in one dispatch each. Returns the
    logits at each row's last prompt position and at every step, and the
    caches. `ring_for_chunk`: size the rings for a larger chunk than is
    sent (the whole context: rings that never wrap)."""
    kc, vc = init_kv_cache(cfg, 2, context, cache_type=cache_type,
                           prefill_chunk=ring_for_chunk or chunk)
    cos, sin = rope_tables(cfg, context)
    pos = 0
    while pos < prompt:
        part = ids[pos:min(pos + chunk, prompt)]
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :len(part)] = part
        final = pos + chunk >= prompt
        long_logits, kc, vc = extend(
            params, cfg, jnp.asarray(buf), jnp.array([pos]), cos, sin, kc,
            vc, slot_map=jnp.array([0]), with_logits=final,
            last_pos=jnp.array([len(part) - 1]) if final else None)
        pos += chunk
    buf = np.zeros((1, 2 * chunk), np.int32)
    buf[0, :short] = ids[:short]
    short_logits, kc, vc = prefill(
        params, cfg, jnp.asarray(buf), jnp.array([short]), cos, sin, kc, vc,
        jnp.array([1]))
    out = [(np.asarray(long_logits)[0], np.asarray(short_logits)[0])]
    lengths = jnp.array([prompt, short], jnp.int32)
    step = jax.jit(lambda tok, n, kc, vc: decode_step(
        params, cfg, tok, n, cos, sin, kc, vc,
        active=jnp.array([True, True])))
    for i in range(steps):
        tok = jnp.array([ids[prompt + i], ids[short + i]], jnp.int32)
        logits, kc, vc = step(tok, lengths, kc, vc)
        lengths = lengths + 1
        out.append((np.asarray(logits)[0], np.asarray(logits)[1]))
    return out, kc, vc


def _router_spy(monkeypatch):
    """Records, for every expert layer of every decode step traced from now
    on (the dense form, _moe_mlp), the router's k + 1 largest probabilities
    and their experts, [rows, 1, k + 1] each, in the order they ran."""
    from localai_tpu.models import llama

    seen, served = [], llama._moe_mlp

    def spy(x, lp, cfg):
        k = cfg.experts_per_tok
        probs = jax.nn.softmax(
            x.astype(jnp.float32) @ lp["moe_gate"].astype(jnp.float32), -1)
        jax.debug.callback(
            lambda p, e: seen.append((np.asarray(p), np.asarray(e))),
            *jax.lax.top_k(probs, k + 1), ordered=True)
        return served(x, lp, cfg)

    monkeypatch.setattr(llama, "_moe_mlp", spy)
    return seen


def _tied(ring, flat, layers: int, k: int):
    """[steps, rows] bool: the decode steps at which the two runs' routers
    chose different experts ON A TIE: at the first layer whose top-k sets
    differ, both runs' k-th and (k+1)-th probabilities lie within TIE (the
    layers after it compute on what that choice changed)."""
    steps, rows = len(ring) // layers, ring[0][0].shape[0]
    tied = np.zeros((steps, rows), bool)
    for s in range(steps):
        for r in range(rows):
            for (p, e), (q, f) in zip(ring[s * layers:(s + 1) * layers],
                                      flat[s * layers:(s + 1) * layers]):
                if set(e[r, 0, :k]) != set(f[r, 0, :k]):
                    tied[s, r] = max(p[r, 0, k - 1] - p[r, 0, k],
                                     q[r, 0, k - 1] - q[r, 0, k]) < TIE
                    break
    return tied


def _errors(out, want, prompt, short):
    """Largest |served - reference| at every position of both rows."""
    return np.array([[np.abs(a - want[prompt - 1 + i]).max(),
                      np.abs(b - want[short - 1 + i]).max()]
                     for i, (a, b) in enumerate(out)])


def _worst(out, want, prompt, short):
    return _errors(out, want, prompt, short).max()


def test_full_forward_matches_reference(model):
    _, cfg, params, rcfg = model
    ids = _ids(90)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    got = np.asarray(forward_train(params, cfg, jnp.asarray(ids[None])))[0]
    assert np.abs(got - want).max() < F32_TOL


def test_reference_blocks_agree(model):
    """Scoring a block of queries at a time changes the reference's memory,
    not its answer."""
    _, _, params, rcfg = model
    ids = _ids(40)
    rp = ref.from_served(params)
    whole = np.asarray(ref.logits(rp, rcfg, ids))
    blocks = np.asarray(ref.logits(rp, rcfg, ids, block=16))
    assert np.abs(whole - blocks).max() < 1e-5


def test_prefill_one_dispatch_short_and_long_rows(model):
    """A batch that holds one row shorter than the window and one several
    windows long (and longer than a ring), prefilled in one dispatch: both
    rows' logits, and the next decode step's over what was cached."""
    _, cfg, params, rcfg = model
    ids = _ids(60, seed=2)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    kc, vc = init_kv_cache(cfg, 2, 128, prefill_chunk=8)
    cos, sin = rope_tables(cfg, 128)
    buf = np.zeros((2, 48), np.int32)
    buf[0, :5], buf[1, :41] = ids[:5], ids[:41]
    lengths = jnp.array([5, 41], jnp.int32)
    logits, kc, vc = prefill(params, cfg, jnp.asarray(buf), lengths, cos,
                             sin, kc, vc, jnp.arange(2))
    logits = np.asarray(logits)
    assert np.abs(logits[0] - want[4]).max() < F32_TOL
    assert np.abs(logits[1] - want[40]).max() < F32_TOL
    nxt, _, _ = decode_step(params, cfg, jnp.array([ids[5], ids[41]]),
                            lengths, cos, sin, kc, vc,
                            active=jnp.array([True, True]))
    nxt = np.asarray(nxt)
    assert np.abs(nxt[0] - want[5]).max() < F32_TOL
    assert np.abs(nxt[1] - want[41]).max() < F32_TOL


def test_chunked_prefill_and_decode_through_wraps(model):
    """float32 weights and cache: a prompt of more than 3 rings through
    8-token chunks, then 36 decode steps (two more wraps of the 16-token
    ring), beside a 5-token row in the same dispatches: the reference's full
    forward pass at every position."""
    shape, cfg, params, rcfg = model
    prompt, short, steps = 61, 5, 36
    ids = _ids(prompt + steps + 1, seed=3)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    out, kc, _ = _serve(cfg, params, ids, prompt=prompt, short=short,
                        steps=steps, chunk=8, context=128)
    assert _worst(out, want, prompt, short) < F32_TOL
    if shape == "mellum":
        # the window layers' cache never exceeds window + chunk; the full
        # layers' is the context
        assert isinstance(kc, PeriodKV)
        assert [s.shape for s in kc.slots] == (
            [(2, 2, 2, 8 + 8, 16)] * 3 + [(2, 2, 2, 128, 16)])
        assert prompt > 3 * 16 and steps > 2 * 16
    else:
        assert kc.shape == (3, 2, 2, 128, 16)


def test_bf16_fails_the_float32_tolerance(model):
    """The float32 comparison is tight enough to tell a lower precision: the
    same run with bfloat16 weights, activations and cache is refused by
    F32_TOL (and by a wide margin)."""
    _, cfg, params, rcfg = model
    prompt, short, steps = 61, 5, 12
    ids = _ids(prompt + steps + 1, seed=3)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    low = dataclasses.replace(cfg, dtype="bfloat16")
    low_params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16)
        if a.dtype == jnp.float32 and a.ndim > 1 else a, params)
    out, _, _ = _serve(low, low_params, ids, prompt=prompt, short=short,
                       steps=steps, chunk=8, context=128)
    assert _worst(out, want, prompt, short) > 50 * F32_TOL


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
def test_int8_weights_and_int8_kv_through_wraps(model, kernels, monkeypatch):
    """int8 weights (the reference computes with their dequantised values)
    and int8 KV, whose rings are a 128-token scale tile: window 64, chunks
    of 64, a prompt of 420 tokens (more than 3 rings), 260 decode steps (two
    more wraps): against the reference, and against the same program over
    rings that never wrap. `pallas` runs the kernels the chip runs
    (ragged_decode_q8's ring mask, flash_prefill) in the interpreter, for
    fewer steps."""
    from localai_tpu.ops.quant import quantize_params

    shape, cfg, params, rcfg = model
    if kernels == "pallas":
        monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    window = 64 if shape == "mellum" else None
    cfg = dataclasses.replace(cfg, sliding_window=window)
    rcfg = dataclasses.replace(rcfg, sliding_window=window)
    qparams = quantize_params(params)
    prompt, short = 420, 37
    steps = 260 if kernels == "xla" else 6
    ids = _ids(prompt + steps + 1, seed=4)
    want = np.asarray(ref.logits(ref.from_served(qparams), rcfg, ids))
    run = dict(prompt=prompt, short=short, steps=steps, chunk=64,
               context=768, cache_type="int8")
    seen = _router_spy(monkeypatch)
    out, kc, _ = _serve(cfg, qparams, ids, **run)
    errors = _errors(out, want, prompt, short)
    assert np.median(errors, axis=0).max() < Q8_MEDIAN_TOL
    assert (errors > 1.0).mean() <= Q8_FLIPS
    if shape == "mellum":
        assert [s.q.shape[3] for s in kc.slots] == [128, 128, 128, 768]
        assert ring_len(cfg, 768, 64, "int8") == 128 == 64 + 64
        jax.effects_barrier()
        ring_seen = list(seen)
        seen.clear()
        flat, kf, _ = _serve(cfg, qparams, ids, ring_for_chunk=768, **run)
        jax.effects_barrier()
        assert [s.q.shape[3] for s in kf.slots] == [768] * 4
        assert len(ring_seen) == len(seen) == steps * cfg.num_layers
        # index 0 is the prompts' last position (no decode step: no excuse)
        tied = np.concatenate([np.zeros((1, 2), bool), _tied(
            ring_seen, seen, cfg.num_layers, cfg.experts_per_tok)])
        apart = np.array([[np.abs(a - c).max(), np.abs(b - d).max()]
                          for (a, b), (c, d) in zip(out, flat)])
        assert tied.mean() <= 0.01
        assert np.where(tied, 0, apart).sum(1).max() < RING_TOL


def test_ring_decode_kernels_match_the_masked_reference(monkeypatch):
    """ragged_decode / ragged_decode_q8 with ring=True against the XLA mask
    (models/llama._decode_dq): rows before the first wrap, on it, and
    several wraps in."""
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    from localai_tpu.models.llama import _decode_dq
    from localai_tpu.ops.kvcache import QuantKV, quantize_tokens
    from localai_tpu.ops.pallas import ragged_decode, ragged_decode_q8

    b, h, kvh, d, ring, window = 5, 4, 2, 16, 256, 100
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, kvh, ring, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, kvh, ring, d), jnp.float32)
    lengths = jnp.array([1, 60, 256, 257, 1000], jnp.int32)
    want = _decode_dq(q, k, v, lengths, sliding_window=window, ring=True)
    got = ragged_decode(q, k, v, lengths, sliding_window=window, ring=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    # and not what a cache in position order would give
    flat = ragged_decode(q, k, v, jnp.minimum(lengths, ring),
                         sliding_window=window)
    assert np.abs(np.asarray(flat) - np.asarray(want))[3:].max() > 1e-2

    def q8(x):
        xq, s = quantize_tokens(x)
        return QuantKV(xq, s.reshape(b, kvh, ring // 128, 128))

    kq, vq = q8(k), q8(v)
    want = _decode_dq(q, kq, vq, lengths, sliding_window=window, ring=True)
    got = ragged_decode_q8(q, kq.q, kq.s, vq.q, vq.s, lengths,
                           sliding_window=window, ring=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-2
    with pytest.raises(ValueError, match="ring=True"):
        ragged_decode(q, k, v, lengths, ring=True)


def test_inactive_rows_leave_a_ring_alone():
    """A decode step with a row inactive (a slot in the middle of its
    chunked prefill while the others decode) must not write that row's
    ring: a ring has no spare row."""
    cfg = _config("mellum")
    params = init_params(cfg, jax.random.PRNGKey(1))
    kc, vc = init_kv_cache(cfg, 2, 128, prefill_chunk=8)
    kc = jax.tree_util.tree_map(lambda a: a + 1.0, kc)
    cos, sin = rope_tables(cfg, 128)
    _, kc2, _ = decode_step(
        params, cfg, jnp.array([3, 4]), jnp.array([40, 40]), cos, sin, kc,
        vc, active=jnp.array([True, False]))
    for before, after in zip(kc.slots[:3], kc2.slots[:3]):
        assert np.array_equal(np.asarray(before[:, 1]),
                              np.asarray(after[:, 1]))
        assert not np.array_equal(np.asarray(before[:, 0]),
                                  np.asarray(after[:, 0]))


def test_extend_refuses_a_chunk_the_ring_cannot_take():
    cfg = _config("mellum")
    params = init_params(cfg, jax.random.PRNGKey(1))
    kc, vc = init_kv_cache(cfg, 1, 128, prefill_chunk=8)
    cos, sin = rope_tables(cfg, 128)
    with pytest.raises(ValueError, match="ring of 16 tokens"):
        extend(params, cfg, jnp.zeros((1, 16), jnp.int32), jnp.array([0]),
               cos, sin, kc, vc)


def test_period_is_the_scan_body():
    cfg = _config("mellum")
    assert cfg.period == (WINDOW, WINDOW, WINDOW, FULL)
    assert _config("mixtral").period is None
    odd = _config("mellum", layer_types=(WINDOW,) * 7 + (FULL,))
    assert len(odd.period) == 8
    with pytest.raises(ValueError, match="one kind of layer"):
        _config("mellum", layer_types=(FULL,) * 8)
    with pytest.raises(ValueError, match="sliding_window"):
        _config("mellum", sliding_window=None)
    with pytest.raises(ValueError, match="prefill_chunk"):
        init_kv_cache(cfg, 1, 64)


# ------------------------------------------------------------ the engine

def _engine(cfg, params, **kw):
    from localai_tpu.engine import Engine, EngineConfig

    base = dict(max_slots=1, max_context=128, prefill_buckets=(8,),
                prefill_chunk=8, decode_block=4, decode_loop=4)
    draft = kw.pop("draft", None)
    return Engine(cfg, params, None, EngineConfig(**{**base, **kw}),
                  draft=draft)


def _generate(eng, prompt, n=10):
    from localai_tpu.engine.engine import GenRequest, SamplingParams

    _, q = eng.submit(GenRequest(
        prompt_ids=[int(t) for t in prompt], max_tokens=n, ignore_eos=True,
        params=SamplingParams(temperature=0.0)))
    out = []
    while True:
        o = q.get(timeout=120)
        out.append((o.token_id, o.logprob))
        if o.finished:
            return out


def _same(a, b):
    assert [t for t, _ in a] == [t for t, _ in b]
    assert np.allclose([p for _, p in a], [p for _, p in b], atol=1e-4)


@pytest.mark.parametrize("second", ["shared_prefix", "no_shared_prefix",
                                    "extends_the_first"])
def test_second_request_in_a_slot_with_a_wrapped_ring(second):
    """One slot; the first request leaves its window layers' rings wrapped
    (50 + 10 tokens over 16-token rings). A second request, whether it
    shares a prefix with the first or not, answers as it does in a fresh
    engine: a prefix is reused only where every layer still holds it
    (`extends_the_first`: all but the ring's last chunk is gone, but the
    window behind the shared prefix's end is there), else recomputed."""
    cfg = _config("mellum")
    params = init_params(cfg, jax.random.PRNGKey(1))
    first = _ids(50, seed=5)
    prompt = {"shared_prefix": np.concatenate([first[:30], _ids(9, 6)]),
              "no_shared_prefix": _ids(33, seed=7),
              "extends_the_first": np.concatenate([first, _ids(7, 8)])}
    fresh = _engine(cfg, params)
    fresh.start()
    try:
        want = _generate(fresh, prompt[second])
    finally:
        fresh.stop()
    eng = _engine(cfg, params)
    eng.start()
    try:
        _generate(eng, first)
        got = _generate(eng, prompt[second])
        reused = eng.metrics["prompt_tokens_reused"]
    finally:
        eng.stop()
    _same(got, want)
    # `extends_the_first` could lend 50 tokens were the device known to have
    # stopped where the host did; the engine allows for two dispatches in
    # flight, so here it recomputes too. What is never done is to reuse
    # what a ring has lost:
    assert reused == 0


def test_engine_reuses_a_prefix_the_rings_still_hold():
    """Rings sized by a 64-token chunk (72 tokens) with short dispatches:
    a follow-up that extends the first conversation reuses it."""
    cfg = _config("mellum")
    params = init_params(cfg, jax.random.PRNGKey(1))
    first = _ids(90, seed=5)
    follow = np.concatenate([first, _ids(6, 9)])
    kw = dict(prefill_buckets=(64,), prefill_chunk=64, max_context=256)
    fresh = _engine(cfg, params, **kw)
    fresh.start()
    try:
        want = _generate(fresh, follow)
    finally:
        fresh.stop()
    eng = _engine(cfg, params, **kw)
    eng.start()
    try:
        _generate(eng, first)
        got = _generate(eng, follow)
        assert eng.metrics["prompt_tokens_reused"] == 90
        # one full and one window layer's context over the 20 decode steps
        m = eng.metrics
        assert m["decode_ctx_tokens__window"] == 20 * 8
        assert m["decode_ctx_tokens__full"] == sum(
            n + g for n in (90, 96) for g in range(1, 11))
        assert m["layers__window"] == 6 and m["layers__full"] == 2
        assert m["kv_bytes__window"] == 2 * 6 * 1 * 2 * 72 * 16 * 4
        assert m["kv_bytes__full"] == 2 * 2 * 1 * 2 * 256 * 16 * 4
    finally:
        eng.stop()
    _same(got, want)


REFUSALS = {
    "paged": (dict(kv_pages=8), "paged KV"),
    "kv_policy": (dict(kv_pages=8,
                       kv_policy="sink_window(sinks=0, window=64)"),
                  "kv_policy windows"),
    "host_tier": (dict(kv_pages=8, kv_host_bytes=1 << 20), "host KV tier"),
    "small_chunk": (dict(decode_block=16), "prefill_chunk >= decode_block"),
}


@pytest.mark.parametrize("what", list(REFUSALS))
def test_engine_refuses_what_cannot_take_two_caches(what):
    cfg = _config("mellum")
    params = init_params(cfg, jax.random.PRNGKey(1))
    kw, named = REFUSALS[what]
    with pytest.raises(ValueError, match=named):
        _engine(cfg, params, **kw)


def test_engine_refuses_a_draft_model_and_context_shift():
    from localai_tpu.engine.engine import GenRequest, SamplingParams

    cfg = _config("mellum")
    params = init_params(cfg, jax.random.PRNGKey(1))
    small = _config("mixtral")
    with pytest.raises(ValueError, match="speculative decoding"):
        _engine(cfg, params, draft=(small, None))
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="context_shift"):
        eng.submit(GenRequest(prompt_ids=[1, 2, 3], max_tokens=2,
                              context_shift=True,
                              params=SamplingParams(temperature=0.0)))


@pytest.mark.parametrize("what", ["cache_shift_paged", "cache_shift"])
def test_model_functions_refuse_two_caches(what):
    from localai_tpu.models import llama

    cfg = _config("mellum")
    with pytest.raises(NotImplementedError, match="window and full layers"):
        if what == "cache_shift_paged":
            llama.cache_shift_paged(cfg, None, None, keep_blocks=1,
                                    discard_blocks=1)
        else:
            llama.cache_shift(cfg, None, None, None, 0, keep=1, discard=1)


# ------------------------------------------------- config.json and RoPE

def test_yarn_table_against_numbers_worked_by_hand():
    """Mellum2's full-attention RoPE (theta 500000, head_dim 128, factor 16,
    original 8192, beta 32/1, attention_factor 1.2772588722239782), from the
    formula, by hand:
    pair(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): pair(32) = 18.08,
    pair(1) = 34.98, so low = 18, high = 35 and the ramp is (i - 18) / 17;
    pair i turns at 500000^(-i/64) rad a token (ln 500000 = 13.122363);
    i = 0:  ramp 0: 1.0 (kept);
    i = 18: ramp 0: e^(-3.690665) = 0.0249554 (kept);
    i = 26: ramp 8/17: f = e^(-5.330960) = 0.00483942, and
            f (1 - 8/17) + f / 16 (8/17) = 0.00270438;
    i = 35: ramp 1: e^(-7.176292) / 16 = 0.000764497 / 16 = 4.77811e-5;
    i = 63: ramp 1: e^(-12.917326) / 16 = 2.45514e-6 / 16 = 1.53446e-7;
    cos and sin are multiplied by 1.2772588722239782 (given, used verbatim;
    0.1 ln 16 + 1 is the same number)."""
    import math

    from localai_tpu.ops.rope import rope_freqs, rope_table

    hand = {0: 1.0, 18: 0.0249554, 26: 0.00270438, 35: 4.77811e-5,
            63: 1.53446e-7}
    assert abs(0.1 * math.log(16) + 1.0 - YARN_FACTOR) < 1e-12
    served = RopeConfig(head_dim=128, base=500000.0, scaling="yarn",
                        scale_factor=16.0, original_max_position=8192,
                        beta_fast=32.0, beta_slow=1.0,
                        attn_factor=YARN_FACTOR)
    plain = ref.RefRope(theta=500000.0, kind="yarn", factor=16.0,
                        original_max_position=8192,
                        attention_factor=YARN_FACTOR)
    for freq, scale in (rope_freqs(served),
                        ref.rope_frequencies(plain, 128)):
        freq = np.asarray(freq, np.float64)
        assert scale == YARN_FACTOR
        for i, value in hand.items():
            assert abs(freq[i] / value - 1) < 2e-5, (i, freq[i], value)
    cos, sin = rope_table(served, 8192)
    p, i = 5000, 26
    assert abs(float(cos[p, i]) - YARN_FACTOR
               * math.cos(p * hand[i])) < 2e-3
    assert abs(float(sin[p, 0]) - YARN_FACTOR * math.sin(p * 1.0)) < 2e-3


def _write_config(tmp_path, hf):
    import json

    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def test_load_config_on_the_published_keys(tmp_path):
    """Mellum2's config.json as published (the catalog's row), to the
    letter."""
    import json
    import os

    from localai_tpu.engine.loader import load_config

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", "mellum2-12b-a2.5b-d16.json")
    notes = ("source", "reduced", "published", "assumed", "deployment",
             "serving", "rehearsal")      # benchmark/harness/server.py
    with open(path) as f:
        doc = json.load(f)
    cut = {k: v for k, v in doc.items() if k not in notes}
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "mlp_layer_types"]
    hf = dict(cut)
    hf["layer_types"] = (["sliding_attention"] * 3 + ["full_attention"]) * 7
    hf["mlp_layer_types"] = ["sparse"] * 28
    hf["num_hidden_layers"] = 28
    cfg = load_config(_write_config(tmp_path, hf))
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (28, 2304, 32, 4, 128, 98304)
    assert cfg.period == (WINDOW, WINDOW, WINDOW, FULL)
    assert cfg.sliding_window == 1024 and cfg.max_position == 131072
    assert (cfg.num_experts, cfg.experts_per_tok, cfg.expert_width,
            cfg.intermediate_size) == (64, 8, 896, 7168)
    assert cfg.rms_eps == 1e-6 and not cfg.tie_embeddings
    assert not cfg.qkv_bias
    full, window = cfg.rope_of(FULL), cfg.rope_of(WINDOW)
    assert (full.scaling, full.base, full.scale_factor,
            full.original_max_position, full.beta_fast, full.beta_slow,
            full.attn_factor) == ("yarn", 500000, 16, 8192, 32, 1,
                                  YARN_FACTOR)
    assert (window.scaling, window.base) == ("none", 500000)
    # the benchmark's cut of it: 16 layers, four whole periods
    served = load_config(_write_config(tmp_path, cut))
    assert served.num_layers == 16 and served.period == cfg.period
    assert dataclasses.replace(served, num_layers=28,
                               layer_types=cfg.layer_types) == cfg


@pytest.mark.parametrize("key,value,named", [
    ("mlp_layer_types", ["sparse"] * 7 + ["dense"], "mlp_layer_types"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("layer_types", ["sliding_attention"] * 7 + ["chunked_attention"],
     "layer_types"),
    ("architectures", ["BrumbyForCausalLM"], "unsupported architecture"),
])
def test_load_config_refusals(tmp_path, key, value, named):
    from localai_tpu.engine.loader import load_config

    hf = dict(HF["mellum"], **{key: value})
    with pytest.raises(ValueError, match=named):
        load_config(_write_config(tmp_path, hf))


@pytest.mark.parametrize("kinds,window", [
    (["full_attention"] * 8, None), (["sliding_attention"] * 8, 8)])
def test_load_config_one_kind_of_layer_is_the_one_kind_path(
        tmp_path, kinds, window):
    from localai_tpu.engine.loader import load_config

    cfg = load_config(_write_config(
        tmp_path, dict(HF["mellum"], layer_types=kinds)))
    assert cfg.layer_types is None and cfg.sliding_window == window
    # every layer windowed: Mistral's path, rotating as the window layers do
    assert cfg.rope.scaling == ("none" if window else "yarn")


def test_load_config_dense_mlp_layer_types(tmp_path):
    from localai_tpu.engine.loader import load_config

    cfg = load_config(_write_config(
        tmp_path, dict(HF["mellum"], mlp_layer_types=["dense"] * 8)))
    assert cfg.num_experts == 0


def test_loaded_config_and_reference_config_agree(shape, tmp_path):
    """The served config read from the HF keys is the one the tests above
    build by hand, and RefConfig.from_hf reads the same keys."""
    from localai_tpu.engine.loader import load_config

    cfg = load_config(_write_config(tmp_path, HF[shape]), dtype="float32")
    assert cfg == _config(shape)
    rcfg = ref.RefConfig.from_hf(HF[shape])
    assert rcfg.layer_types == (cfg.layer_types
                                or (FULL,) * cfg.num_layers)
    assert rcfg.num_experts == cfg.num_experts


def test_synthetic_params_size_experts_by_the_expert_width():
    from localai_tpu.engine.loader import _synthetic_params

    cfg = _config("mellum", dtype="bfloat16")
    for qbits in (None, 8):
        layers = _synthetic_params(cfg, dtype=jnp.bfloat16,
                                   qbits=qbits)["layers"]
        w1 = layers["moe_w1"]["q"] if qbits else layers["moe_w1"]
        assert w1.shape == (8, 8, 64, 32)


# ------------------------------------------------------- spans and scopes

def test_attention_is_traced_by_layer_kind():
    """A mixed model's decode step names its attention attention/window and
    attention/full in the ops' metadata, and tools/trace_gaps.py reads the
    kind; a one-kind model's stays `attention`."""
    from tools.trace_gaps import scope_of

    def scopes(shape):
        cfg = _config(shape)
        params = init_params(cfg, jax.random.PRNGKey(1))
        kc, vc = init_kv_cache(cfg, 1, 64, prefill_chunk=8)
        cos, sin = rope_tables(cfg, 64)
        text = jax.jit(lambda kc, vc: decode_step(
            params, cfg, jnp.array([3]), jnp.array([9]), cos, sin, kc, vc)
        ).lower(kc, vc).as_text(debug_info=True)
        import re

        return {scope_of("", {"tf_op": m})
                for m in re.findall(r'loc\("(attention/[^"]*)"', text)}

    assert scopes("mellum") == {"attention/window", "attention/full"}
    assert scopes("mixtral") == {"attention"}
    assert scope_of("", {"tf_op": "jit(_loop)/while/body/attention/full/"
                                  "jit(ragged_decode_q8)/x"}) \
        == "attention/full"
    assert scope_of("", {"tf_op": "jit(_loop)/experts/router/top_k"}) \
        == "experts/router"


@pytest.mark.parametrize("name,program", [("mellum", "_decode"),
                                          ("mixtral", None)])
def test_a_mixed_models_single_decode_steps_have_a_name(name, program):
    """jax.jit calls a functools.partial's program jit__unknown. A mixed
    model's single decode steps are jit__decode (the class `decode` of
    benchmark/programs/ finds them in a trace); a one-kind model keeps the
    programs, and the compile-cache keys, it had."""
    cfg = _config(name)
    eng = _engine(cfg, init_params(cfg, jax.random.PRNGKey(1)))
    for fn in (eng._decode_nomask_fn, eng._decode_fast_fn):
        assert getattr(fn.__wrapped__, "__name__", None) == program
    assert getattr(eng._decode_block_fn.__wrapped__, "__name__", None) == (
        program and "_decode_block")


@pytest.mark.parametrize("name,steps", [("mellum", 4), ("mixtral", 16)])
def test_a_mixed_models_fused_loops_are_a_block_long(name, steps):
    """A loop's tokens reach the streams when it ends. A model with window
    and full layers runs loops of decode_block steps inside the decode_loop
    program (a budget of that many tokens a row, no other program) and gives
    the tokens the long loop gives; a one-kind model's loops stay decode_loop
    steps."""
    cfg = _config(name)
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = _engine(cfg, params, decode_block=4, decode_loop=16)
    assert eng._loop_steps == steps
    ran = []
    consume = eng._consume_loop

    def spy(pend):
        before = eng.metrics["decode_steps_consumed"]
        consume(pend)
        ran.append(eng.metrics["decode_steps_consumed"] - before)

    eng._consume_loop = spy
    whole = _engine(cfg, params, decode_block=16, decode_loop=16,
                    prefill_buckets=(16,), prefill_chunk=16)
    assert whole._loop_steps == 16
    eng.start()
    whole.start()
    try:
        out = _generate(eng, _ids(12), n=40)
        want = _generate(whole, _ids(12), n=40)
    finally:
        eng.stop()
        whole.stop()
    assert len(out) == 40 and ran and max(ran) == steps
    _same(out, want)
