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


# ------------------------------------------- the dense grid (PR 30)
# A grid step moves block_k tokens of every KV head of a row; block_k comes
# from the shapes; a row of length 0 is not decoding.

def _dense_case(quant, form, G, T, B=8, KVH=2, D=16, seed=40):
    """q, K, V (QuantKV where quant), the kernel's and the twin's keywords
    for a dense / stacked / ring / window cache of T rows."""
    from localai_tpu.ops.kvcache import QuantKV, quantize_tokens

    lead = (3,) if form.startswith("layer") else ()
    q = _rand(seed, (B, 1, KVH * G, D))
    k = _rand(seed + 1, (*lead, B, KVH, T, D))
    v = _rand(seed + 2, (*lead, B, KVH, T, D))
    if quant:
        def q8(x):
            xq, s = quantize_tokens(x)
            return QuantKV(xq, s.reshape(*lead, B, KVH, T // 128, 128))

        k, v = q8(k), q8(v)
    kw = {"ring": dict(sliding_window=T - 200, ring=True),
          "window": dict(sliding_window=300)}.get(form, {})
    if lead:
        kw["layer"] = int(form[-1])
    return q, k, v, kw


def _run(q, k, v, lengths, layer=None, **kw):
    from localai_tpu.ops.pallas import ragged_decode_q8

    if layer is not None:
        kw["layer"] = jnp.int32(layer)
    lengths = jnp.asarray(lengths, jnp.int32)
    if hasattr(k, "s"):
        return np.asarray(ragged_decode_q8(q, k.q, k.s, v.q, v.s, lengths,
                                           **kw))
    return np.asarray(ragged_decode(q, k, v, lengths, **kw))


def _edges(T, bk, ring):
    """A row each at the edges of a scale tile, of a block, and of the cache
    (a ring's rows count every token so far: past T, and wraps in)."""
    return [1, 127, 128, 129, bk - 1, bk, bk + 1,
            *((T + 1, 3 * T + 77) if ring else (T,))][-8:]


# (6, 4608): 48 query heads over 8 KV heads, a ring of 4096 + 512 (PR 35):
# the first group that is no power of two
@pytest.mark.parametrize("G,T", [(1, 640), (4, 1536), (8, 2048), (6, 4608)])
@pytest.mark.parametrize("form", ["dense", "layer0", "layer2", "ring",
                                  "window"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "q8"])
def test_dense_grid_matches_the_xla_twin(quant, form, G, T):
    from localai_tpu.models.llama import _decode_dq
    from localai_tpu.ops.pallas.flash_attention import _dense_block_k

    q, k, v, kw = _dense_case(quant, form, G, T)
    bk = _dense_block_k(T, 2, 16, 1 if quant else 4)
    assert bk == {640: 128, 1536: 512, 2048: 1024, 4608: 512}[T]
    lengths = _edges(T, bk, form == "ring")
    got = _run(q, k, v, lengths, **kw)
    want = np.asarray(_decode_dq(q, k, v, jnp.asarray(lengths, jnp.int32),
                                 **kw))
    tol = 2e-2 if quant else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("block_k", [128, 256, 512, 1024])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "q8"])
def test_dense_grid_gives_the_same_at_any_block(quant, block_k):
    """block_k is the kernel's own choice: whatever it is, the rows come out
    as the twin's (a step applies block_k // 128 scale rows of a q8 cache;
    the ring mask and the clamp past a row's end go by positions)."""
    from localai_tpu.models.llama import _decode_dq

    for form in ("layer2", "ring"):
        q, k, v, kw = _dense_case(quant, form, 4, 2048, B=4)
        lengths = jnp.array([block_k - 1, block_k + 1, 2048,
                             5000 if form == "ring" else 1], jnp.int32)
        got = _run(q, k, v, lengths, block_k=block_k, **kw)
        want = np.asarray(_decode_dq(q, k, v, lengths, **kw))
        tol = 2e-2 if quant else 2e-5
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("form", ["dense", "layer2", "ring", "window"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "q8"])
def test_a_row_that_is_not_decoding_costs_nothing(quant, form):
    """Length 0: zeros out and nothing of the row is read — its K and V can
    hold NaN (f32) or anything (q8) and no row's output moves by a bit,
    wherever the dead rows lie: first, last, between, all."""
    import dataclasses

    T = 1536
    q, k, v, kw = _dense_case(quant, form, 4, T, B=6)
    full = np.array([700, 40, 513, 1536, 5, 1200])
    if form == "ring":
        full[3] = 4000
    for dead in ([0], [5], [1, 2], [0, 2, 3, 5], list(range(6))):
        lengths = full.copy()
        lengths[dead] = 0
        rows = (slice(None),) * (k.ndim - 4) + (np.array(dead),)
        if quant:
            def junk(c):
                return dataclasses.replace(
                    c, q=c.q.at[rows].set(127), s=c.s.at[rows].set(jnp.nan))
        else:
            def junk(c):
                return c.at[rows].set(jnp.nan)
        got = _run(q, junk(k), junk(v), lengths, **kw)
        assert np.isfinite(got).all()
        assert not got[dead].any()
        alive = np.setdiff1d(np.arange(6), dead)
        np.testing.assert_array_equal(got[alive],
                                      _run(q, k, v, full, **kw)[alive])


@pytest.mark.parametrize("form", ["dense", "ring"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "q8"])
def test_a_rows_output_does_not_depend_on_the_other_rows(quant, form):
    """Where the grid's steps point past a row's end is decided by the rows
    after it (the next row's first block is fetched early): the row's own
    numbers must not move with them."""
    q, k, v, kw = _dense_case(quant, form, 4, 1536, B=4)
    base = _run(q, k, v, [300, 900, 513, 1536], **kw)
    for others in ([300, 0, 0, 0], [300, 1, 1536, 0], [300, 1536, 0, 1],
                   [300, 512, 512, 512]):
        np.testing.assert_array_equal(_run(q, k, v, others, **kw)[0], base[0])
    for others in ([0, 0, 513, 0], [1536, 1536, 513, 1536], [0, 1, 513, 7]):
        np.testing.assert_array_equal(_run(q, k, v, others, **kw)[2], base[2])


@pytest.mark.parametrize("t,kvh,d,itemsize,want", [
    (640, 8, 128, 1, 128),      # no block of the ladder divides T
    (1536, 8, 128, 1, 512),     # Mixtral's cache, Mellum2's rings
    (8192, 4, 128, 1, 1024),    # Mellum2's full layers
    (2048, 8, 128, 1, 1024),    # the dense 7 B shape: 4 MiB in flight
    (1536, 8, 128, 2, 512),     # bf16 KV
    (2048, 8, 128, 2, 512),     # 1024 would pass the budget
    (2048, 32, 128, 1, 256),    # MHA
    (2048, 32, 256, 4, 128),    # past the budget at any block: 128
    (64, 2, 16, 4, 64),         # a cache shorter than a block: whole
])
def test_dense_block_comes_from_the_shapes(t, kvh, d, itemsize, want):
    from localai_tpu.ops.pallas.flash_attention import _dense_block_k

    assert _dense_block_k(t, kvh, d, itemsize) == want


@pytest.mark.parametrize("lengths,window,want", [
    # lo, hi, where the other steps point: (row, block)
    ([300, 900, 513, 1536], None,
     [(0, 0, 1, 0), (0, 1, 2, 0), (0, 1, 3, 0), (0, 2, 3, 2)]),
    # rows that do not decode point at the next row that does; after the
    # last one, at the block already in hand
    ([0, 0, 600, 0, 100, 0], None,
     [(0, -1, 2, 0), (0, -1, 2, 0), (0, 1, 4, 0), (0, -1, 4, 0),
      (0, 0, 4, 0), (0, -1, 4, 0)]),
    ([0, 0], None, [(0, -1, 0, 0), (0, -1, 0, 0)]),
    # a window's rows start at the block the window starts in
    ([1500, 100, 1100], 300,
     [(2, 2, 1, 0), (0, 0, 2, 1), (1, 2, 2, 2)]),
])
def test_fetch_plan(lengths, window, want):
    from localai_tpu.ops.pallas.flash_attention import _fetch_plan

    plan = _fetch_plan(jnp.asarray(lengths, jnp.int32), 512, 3, window,
                       False)
    assert [tuple(int(x) for x in col) for col in np.asarray(plan).T] == want


@pytest.mark.parametrize("kind", ["", "int8"])
def test_decode_step_tells_the_kernel_which_rows_decode(monkeypatch, kind):
    """decode_step(active=...) through the Pallas kernels: the rows that
    decode read as on the XLA path, whatever the others' lengths say."""
    from localai_tpu.models.llama import (
        LlamaConfig, decode_step, init_kv_cache, init_params,
    )
    from localai_tpu.ops.rope import rope_table

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                      max_position=256, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    cos, sin = rope_table(cfg.rope, 256)
    kc, vc = init_kv_cache(cfg, 4, 256, cache_type=kind)
    fill = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 2, 256, 8))
    if kind:
        from localai_tpu.ops.kvcache import requantize
        kc, vc = requantize(kc, fill), requantize(vc, -fill)
    else:
        kc, vc = fill, -fill
    tokens = jnp.array([5, 6, 7, 8], jnp.int32)
    lengths = jnp.array([100, 200, 129, 17], jnp.int32)
    active = jnp.array([True, False, True, False])
    want = np.asarray(decode_step(params, cfg, tokens, lengths, cos, sin,
                                  kc, vc, active)[0])
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    got = np.asarray(decode_step(params, cfg, tokens, lengths, cos, sin,
                                 kc, vc, active)[0])
    assert np.isfinite(got).all()
    tol = 2e-2 if kind else 2e-4
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=tol, atol=tol)
    # and with every row decoding, as before
    every = np.asarray(decode_step(params, cfg, tokens, lengths, cos, sin,
                                   kc, vc)[0])
    np.testing.assert_allclose(every[[0, 2]], got[[0, 2]], rtol=tol, atol=tol)
