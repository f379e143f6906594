"""Pallas kernel parity vs the reference attention ops (interpreter mode on
CPU; same code compiles for the MXU on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops.attention import mha_decode, mha_prefill
from localai_tpu.ops.pallas import flash_prefill, ragged_decode


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.mark.parametrize("H,KVH", [(4, 4), (4, 2), (8, 1)])
def test_flash_prefill_matches_reference(H, KVH):
    B, S, D = 2, 64, 16
    q = _rand(0, (B, S, H, D))
    k = _rand(1, (B, S, KVH, D))
    v = _rand(2, (B, S, KVH, D))
    lengths = jnp.array([S, 37], jnp.int32)
    ref = mha_prefill(q, k, v, lengths)
    out = flash_prefill(q, k, v, lengths, block_q=16, block_k=16)
    # compare only valid rows (padded rows are garbage in both)
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(np.asarray(out[b, :n]),
                                   np.asarray(ref[b, :n]),
                                   rtol=2e-5, atol=2e-5)


def test_flash_prefill_sliding_window():
    B, S, H, D = 1, 48, 2, 8
    q, k, v = _rand(3, (B, S, H, D)), _rand(4, (B, S, H, D)), _rand(5, (B, S, H, D))
    lengths = jnp.array([S], jnp.int32)
    ref = mha_prefill(q, k, v, lengths, sliding_window=8)
    out = flash_prefill(q, k, v, lengths, sliding_window=8,
                        block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [None, 0, 2])
@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_ragged_decode_matches_reference(H, KVH, layer):
    """layer: the caches are a [3, ...] stack and the kernel reads that
    layer of it; the reference is given stack[layer]."""
    B, T, D = 3, 64, 16
    lead = () if layer is None else (3,)
    q = _rand(6, (B, 1, H, D))
    kc = _rand(7, (*lead, B, KVH, T, D))
    vc = _rand(8, (*lead, B, KVH, T, D))
    lengths = jnp.array([5, 64, 23], jnp.int32)
    ref = (mha_decode(q, kc, vc, lengths) if layer is None
           else mha_decode(q, kc[layer], vc[layer], lengths))
    out = ragged_decode(q, kc, vc, lengths, block_k=16,
                        layer=None if layer is None else jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("ring", [False, True], ids=["dense", "ring"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "q8"])
def test_ragged_decode_reads_its_layer_of_a_stack(quant, ring, layer):
    """ragged_decode / ragged_decode_q8 over a [L, B, KVH, T, D] stack with a
    traced layer index, full caches and rings (rows before the first wrap,
    on it and several wraps in), against the XLA twin on stack[layer] and
    on the stack with the same index."""
    from localai_tpu.models.llama import _decode_dq
    from localai_tpu.ops.kvcache import QuantKV, quantize_tokens
    from localai_tpu.ops.pallas import ragged_decode_q8

    L, B, H, KVH, D, T, window = 3, 4, 4, 2, 16, 256, 100
    q = _rand(30, (B, 1, H, D))
    k = _rand(31, (L, B, KVH, T, D))
    v = _rand(32, (L, B, KVH, T, D))
    lengths = jnp.array([1, 60, 256, 1000 if ring else 201], jnp.int32)
    kw = dict(sliding_window=window, ring=True) if ring else {}
    if quant:
        def q8(x):
            xq, s = quantize_tokens(x)
            return QuantKV(xq, s.reshape(L, B, KVH, T // 128, 128))

        k, v = q8(k), q8(v)
        run = jax.jit(lambda i: ragged_decode_q8(
            q, k.q, k.s, v.q, v.s, lengths, layer=i, **kw))
    else:
        run = jax.jit(lambda i: ragged_decode(q, k, v, lengths, layer=i,
                                              **kw))
    got = np.asarray(run(jnp.int32(layer)))
    want = np.asarray(_decode_dq(q, k[layer], v[layer], lengths, **kw))
    tol = 2e-2 if quant else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    twin = np.asarray(_decode_dq(q, k, v, lengths, layer=layer, **kw))
    np.testing.assert_array_equal(twin, want)
    # and not another layer's
    other = np.asarray(_decode_dq(q, k[1], v[1], lengths, **kw))
    assert np.abs(got - other).max() > 0.05


def test_ragged_decode_layer_goes_with_a_stack():
    q = _rand(33, (2, 1, 4, 16))
    kc = _rand(34, (2, 2, 32, 16))
    lengths = jnp.array([5, 9], jnp.int32)
    with pytest.raises(ValueError, match="cache stack"):
        ragged_decode(q, kc, kc, lengths, layer=0)
    with pytest.raises(ValueError, match="cache stack"):
        ragged_decode(q, kc[None], kc[None], lengths)


def test_ragged_decode_sliding_window():
    B, T, H, D = 2, 32, 2, 8
    q = _rand(9, (B, 1, H, D))
    kc = _rand(10, (B, H, T, D))
    vc = _rand(11, (B, H, T, D))
    lengths = jnp.array([30, 12], jnp.int32)
    ref = mha_decode(q, kc, vc, lengths, sliding_window=8)
    out = ragged_decode(q, kc, vc, lengths, sliding_window=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_prefill_partial_blocks():
    """S not a multiple of block_k: pl.ds clamps, so the kernel must pad K/V
    (round-4 review finding — silently wrong keys in the final block)."""
    B, S, H, D = 1, 192, 4, 16
    q, k, v = _rand(20, (B, S, H, D)), _rand(21, (B, S, H, D)), _rand(22, (B, S, H, D))
    lengths = jnp.array([137], jnp.int32)
    ref = mha_prefill(q, k, v, lengths)
    out = flash_prefill(q, k, v, lengths, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out[0, :137]), np.asarray(ref[0, :137]),
                               rtol=2e-5, atol=2e-5)


def test_ragged_decode_partial_final_block():
    """T not a multiple of block_k: padded tail rows are undefined and must
    not poison the accumulator (round-4 review finding — NaN logits)."""
    B, T, H, KVH, D = 2, 40, 4, 2, 16
    q = _rand(23, (B, 1, H, D))
    kc = _rand(24, (B, KVH, T, D))
    vc = _rand(25, (B, KVH, T, D))
    for lens in ([40, 7], [39, 16], [33, 40]):
        lengths = jnp.array(lens, jnp.int32)
        ref = mha_decode(q, kc, vc, lengths)
        out = ragged_decode(q, kc, vc, lengths, block_k=16)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_model_end_to_end_with_pallas(monkeypatch):
    """Whole model through the Pallas kernels (interpret mode): cached decode
    must equal the XLA-path full forward."""
    from localai_tpu.models.llama import (
        LlamaConfig, forward_train, init_kv_cache, init_params, prefill,
        decode_step,
    )
    from localai_tpu.ops.rope import rope_table

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                      max_position=64, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 6), 0, 128)
    ref = np.asarray(forward_train(params, cfg, tokens))  # XLA path

    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    cos, sin = rope_table(cfg.rope, 32)
    kc, vc = init_kv_cache(cfg, 2, 32)
    lengths = jnp.array([6], jnp.int32)
    logits, kc, vc = prefill(params, cfg, tokens, lengths, cos, sin, kc, vc,
                             jnp.array([0], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[0]), ref[0, -1],
                               rtol=2e-4, atol=2e-4)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    slot_tokens = jnp.zeros((2,), jnp.int32).at[0].set(nxt[0])
    slot_lengths = jnp.zeros((2,), jnp.int32).at[0].set(6)
    dlogits, _, _ = decode_step(params, cfg, slot_tokens, slot_lengths,
                                cos, sin, kc, vc)
    seq = jnp.concatenate([tokens, nxt[None]], axis=1)
    monkeypatch.delenv("LOCALAI_FORCE_PALLAS")
    full = np.asarray(forward_train(params, cfg, seq))
    np.testing.assert_allclose(np.asarray(dlogits[0]), full[0, -1],
                               rtol=2e-4, atol=2e-4)


def test_bf16_io_f32_accumulate():
    B, S, H, D = 1, 32, 2, 16
    q = _rand(12, (B, S, H, D)).astype(jnp.bfloat16)
    k = _rand(13, (B, S, H, D)).astype(jnp.bfloat16)
    v = _rand(14, (B, S, H, D)).astype(jnp.bfloat16)
    lengths = jnp.array([S], jnp.int32)
    out = flash_prefill(q, k, v, lengths, block_q=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    ref = mha_prefill(q.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32), lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
