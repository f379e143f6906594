"""Int8 KV cache (reference CacheTypeKey/Value, backend.proto:257-258) and
fused decode blocks: parity against the dense/bf16 paths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    LlamaConfig, cache_shift, decode_step, extend, init_kv_cache, prefill,
)
from localai_tpu.ops.kvcache import (
    QuantKV, dequant, init_quant, is_quant_kind, quantize_tokens,
)
from localai_tpu.ops.rope import rope_table

CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                  max_position=256, dtype="float32")


def _params(cfg=CFG, seed=0):
    from localai_tpu.models.llama import init_params

    return init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)


def test_quantize_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 2, 16))
    q, s = quantize_tokens(x)
    assert q.dtype == jnp.int8
    back = q.astype(jnp.float32) * s[..., None]
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=float(np.abs(x).max()) / 100)


def test_is_quant_kind():
    assert is_quant_kind("int8") and is_quant_kind("q8_0")
    assert not is_quant_kind("") and not is_quant_kind("bf16")


def test_init_kv_cache_int8_layout():
    kc, vc = init_kv_cache(CFG, 2, 200, cache_type="int8")
    assert isinstance(kc, QuantKV)
    # token axis padded to the 128 scale tile
    assert kc.shape == (2, 2, 2, 256, 16)
    assert kc.q.dtype == jnp.int8
    assert kc.s.shape == (2, 2, 2, 2, 128)
    # dense bytes would be 4x (f32) the int8 payload
    assert kc.q.nbytes == np.prod(kc.shape)


def _run_decode(cache_type, n_steps=6):
    params = _params()
    B, T = 2, 128
    kc, vc = init_kv_cache(CFG, B, T, cache_type=cache_type)
    cos, sin = rope_table(CFG.rope, T)
    tokens = jnp.array([[1, 2, 3, 4, 0, 0], [5, 6, 7, 0, 0, 0]], jnp.int32)
    lengths = jnp.array([4, 3], jnp.int32)
    logits, kc, vc = prefill(params, CFG, tokens, lengths, cos, sin, kc, vc,
                             jnp.arange(B))
    outs = [logits]
    toks = jnp.argmax(logits, -1)
    for _ in range(n_steps):
        logits, kc, vc = decode_step(params, CFG, toks, lengths, cos, sin,
                                     kc, vc)
        lengths = lengths + 1
        toks = jnp.argmax(logits, -1)
        outs.append(logits)
    return [np.asarray(o) for o in outs]


def test_decode_parity_int8_vs_dense():
    dense = _run_decode("")
    quant = _run_decode("int8")
    for d, q in zip(dense, quant):
        # int8 cache error is small relative to the logit scale
        assert np.max(np.abs(d - q)) < 0.05 * max(np.max(np.abs(d)), 1.0)


def test_extend_parity_int8_vs_dense():
    params = _params()
    B, T, S = 2, 128, 4
    cos, sin = rope_table(CFG.rope, T)
    tokens = jnp.array([[9, 8, 7, 6], [1, 2, 3, 4]], jnp.int32)
    start = jnp.array([0, 0], jnp.int32)
    outs = {}
    for kind in ("", "int8"):
        kc, vc = init_kv_cache(CFG, B, T, cache_type=kind)
        logits, _, _ = extend(params, CFG, tokens, start, cos, sin, kc, vc)
        outs[kind] = np.asarray(logits)
    assert np.max(np.abs(outs[""] - outs["int8"])) < 0.05 * np.max(
        np.abs(outs[""]) + 1.0)


@pytest.mark.parametrize("block_k", [None, 128, 256])
def test_ragged_decode_q8_matches_xla_on_same_values(block_k):
    """block_k: the tokens a grid step moves (None: from the shapes, 256
    here), as many scale rows of [T // 128, 128] a step."""
    from localai_tpu.ops.attention import mha_decode
    from localai_tpu.ops.pallas import ragged_decode_q8

    B, H, KVH, D, T = 2, 4, 2, 64, 256
    q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, D), jnp.float32)
    kd = jax.random.normal(jax.random.PRNGKey(1), (B, KVH, T, D))
    vd = jax.random.normal(jax.random.PRNGKey(2), (B, KVH, T, D))
    kc = init_quant((B, KVH, T, D))
    kq, ks = quantize_tokens(kd)
    vq, vs = quantize_tokens(vd)
    kc = QuantKV(kq, ks.reshape(B, KVH, T // 128, 128))
    vc = QuantKV(vq, vs.reshape(B, KVH, T // 128, 128))
    lengths = jnp.array([200, 77], jnp.int32)
    out = ragged_decode_q8(q, kc.q, kc.s, vc.q, vc.s, lengths,
                           block_k=block_k)
    ref = mha_decode(q.astype(jnp.float32),
                     dequant(kc, jnp.float32), dequant(vc, jnp.float32),
                     lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_cache_shift_quant_parity():
    B, T = 1, 128
    cfg = CFG
    kd = jax.random.normal(jax.random.PRNGKey(3),
                           (cfg.num_layers, B, cfg.num_kv_heads, T,
                            cfg.head_dim))
    vd = jax.random.normal(jax.random.PRNGKey(4), kd.shape)
    lengths = jnp.array([100], jnp.int32)
    kq, ks = quantize_tokens(kd)
    vq, vs = quantize_tokens(vd)
    kcq = QuantKV(kq, ks.reshape(*ks.shape[:-1], T // 128, 128))
    vcq = QuantKV(vq, vs.reshape(*vs.shape[:-1], T // 128, 128))

    kd2, vd2, l2 = cache_shift(cfg, kd, vd, lengths, 0, keep=4, discard=32)
    kq2, vq2, lq2 = cache_shift(cfg, kcq, vcq, lengths, 0, keep=4, discard=32)
    assert int(l2[0]) == int(lq2[0]) == 68
    scale = float(np.max(np.abs(np.asarray(kd2)))) or 1.0
    n = 68
    np.testing.assert_allclose(
        np.asarray(dequant(kq2, jnp.float32))[:, :, :, :n],
        np.asarray(kd2)[:, :, :, :n], atol=0.05 * scale)
    np.testing.assert_allclose(
        np.asarray(dequant(vq2, jnp.float32))[:, :, :, :n],
        np.asarray(vd2)[:, :, :, :n], atol=0.05 * scale)


# --------------------------------------------------------------- engine level

def _collect(out_q):
    texts, toks = [], []
    while True:
        o = out_q.get(timeout=60)
        toks.append(o.token_id)
        if o.finished:
            return toks, o


def _engine(cache_type="", decode_block=1, **kw):
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams

    cfg = dataclasses.replace(CFG, dtype="float32")
    params = _params(cfg)
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prefill_chunk=16, cache_type=cache_type, decode_block=decode_block,
        **kw))
    return eng, GenRequest, SamplingParams


def test_engine_int8_cache_serves():
    eng, GenRequest, SamplingParams = _engine(cache_type="int8")
    eng.start()
    try:
        _, q = eng.submit(GenRequest(
            prompt_ids=[1, 2, 3], max_tokens=8, ignore_eos=True,
            params=SamplingParams(temperature=0.0, seed=7)))
        toks, last = _collect(q)
        assert len(toks) == 8 and last.finish_reason == "length"
    finally:
        eng.stop()


def test_engine_decode_block_parity():
    """Fused-block dispatch must emit the exact same tokens as single steps
    (per-slot RNG streams are independent of dispatch grouping)."""
    results = []
    for block in (1, 4):
        eng, GenRequest, SamplingParams = _engine(decode_block=block)
        eng.start()
        try:
            _, q = eng.submit(GenRequest(
                prompt_ids=[5, 6, 7, 8], max_tokens=12, ignore_eos=True,
                params=SamplingParams(temperature=0.8, top_k=20, seed=3)))
            toks, _ = _collect(q)
            results.append(toks)
        finally:
            eng.stop()
    assert results[0] == results[1]


@pytest.mark.parametrize("block", [1, 4])
def test_engine_int8_block_combined(block):
    eng, GenRequest, SamplingParams = _engine(cache_type="int8",
                                              decode_block=block)
    eng.start()
    try:
        qs = [eng.submit(GenRequest(
            prompt_ids=[i + 1, i + 2], max_tokens=6, ignore_eos=True,
            params=SamplingParams(temperature=0.5, seed=i)))[1]
            for i in range(2)]
        for q in qs:
            toks, last = _collect(q)
            assert len(toks) == 6
    finally:
        eng.stop()


def test_fast_topk_sampler_parity():
    """Sort-free decode sampling (sampling_topk_width): greedy rows match the
    full path exactly, logprobs are full-vocab exact, and stochastic draws
    stay inside the top-k set."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.ops.sampling import (
        SamplerState, SamplingParams, sample, sampler_row,
    )

    B, V = 4, 512
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, V)) * 3.0
    st = SamplerState.init(B, V)
    rows = [sampler_row(SamplingParams(temperature=0.0, seed=1), V, 1),
            sampler_row(SamplingParams(temperature=0.8, top_k=20, seed=2),
                        V, 2),
            sampler_row(SamplingParams(temperature=1.2, top_k=5, top_p=0.9,
                                       seed=3), V, 3),
            sampler_row(SamplingParams(temperature=0.0, seed=4), V, 4)]
    import dataclasses as dc

    fields = {}
    for f in dc.fields(SamplerState):
        cur = getattr(st, f.name)
        if f.name == "token_counts":
            fields[f.name] = cur
        else:
            fields[f.name] = jnp.stack([r[f.name] for r in rows])
    st = SamplerState(**fields)

    t_full, _, lp_full = sample(logits, st)
    t_fast, _, lp_fast = sample(logits, st, topk_width=64)
    # greedy rows (0 and 3) must match exactly, incl. logprob
    for i in (0, 3):
        assert int(t_full[i]) == int(t_fast[i]) == int(jnp.argmax(logits[i]))
        assert abs(float(lp_full[i]) - float(lp_fast[i])) < 1e-4
    # stochastic rows: drawn token must be inside the row's top-k set
    for i, k in ((1, 20), (2, 5)):
        topk = set(np.asarray(jax.lax.top_k(logits[i], k)[1]).tolist())
        assert int(t_fast[i]) in topk
