"""Real-TPU lowering tests: kernels that pass in interpreter mode can still
die in Mosaic lowering on hardware, and only a chip run can tell.

One lowering-and-parity test per `pallas_call` in `localai_tpu/ops/pallas/`,
each against its XLA twin, at the Llama-8B head geometry (32/8/128) and one
D=64 geometry; then the engine programs that compose them (dense, paged,
int8-paged) for a few ticks.

Skipped on the CPU harness; run on a machine with a TPU attached:
`LOCALAI_TPU_TESTS=1 python -m pytest tests/test_tpu_real.py`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="requires a real TPU (LOCALAI_TPU_TESTS=1)",
)

GEOMS = [(32, 8, 128), (8, 4, 64)]          # (H, KVH, D)
BS = 128                                     # ops.paged.BLOCK


def _bf16(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.bfloat16)


def _close(out, ref, tol=3e-2):
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def _quant_pool(key, nb, kvh, d):
    """int8 pool [NB, KVH, BS, D] + scales [NB, KVH, 1, BS] (ops/paged.py)."""
    from localai_tpu.ops.kvcache import quantize_tokens

    dense = jax.random.normal(jax.random.PRNGKey(key), (nb, kvh, BS, d))
    q, s = quantize_tokens(dense)            # [NB,KVH,BS,D] i8, [NB,KVH,BS]
    return q, s.reshape(nb, kvh, 1, BS)


# ------------------------------------------------- flash_attention.py

@pytest.mark.parametrize("H,KVH,D", GEOMS + [(8, 8, 128)])
@pytest.mark.parametrize("S", [256, 512])   # 512 = the largest prefill chunk
def test_flash_prefill(H, KVH, D, S):
    from localai_tpu.ops.attention import mha_prefill
    from localai_tpu.ops.pallas import flash_prefill

    B = 2
    q, k, v = _bf16(0, (B, S, H, D)), _bf16(1, (B, S, KVH, D)), \
        _bf16(2, (B, S, KVH, D))
    lengths = jnp.array([S, 100], jnp.int32)
    out = flash_prefill(q, k, v, lengths)
    ref = mha_prefill(q, k, v, lengths)
    for b in range(B):
        n = int(lengths[b])
        _close(out[b, :n], ref[b, :n])


# layer: the caches are a [3, ...] stack and the kernel reads that layer of it
# through its index maps (what a decode step's layer scan hands it); the
# reference is given stack[layer]
LAYERS = [None, 0, 2]


def _lead(layer):
    return () if layer is None else (3,)


def _of(layer, *stacks):
    return stacks if layer is None else tuple(a[layer] for a in stacks)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("H,KVH,D", GEOMS + [(8, 8, 128)])
def test_ragged_decode_dense(H, KVH, D, layer):
    from localai_tpu.ops.attention import mha_decode
    from localai_tpu.ops.pallas import ragged_decode

    B, T = 4, 1024
    q = _bf16(3, (B, 1, H, D))
    kc = _bf16(4, (*_lead(layer), B, KVH, T, D))
    vc = _bf16(5, (*_lead(layer), B, KVH, T, D))
    lengths = jnp.array([1, 100, 777, T], jnp.int32)
    _close(ragged_decode(q, kc, vc, lengths, layer=layer),
           mha_decode(q, *_of(layer, kc, vc), lengths))


def _table(B, maxb):
    """Distinct physical blocks per slot, shuffled so the table matters."""
    perm = np.random.default_rng(0).permutation(B * maxb) + 1   # 0 = trash
    return jnp.asarray(perm.reshape(B, maxb), jnp.int32)


@pytest.mark.parametrize("H,KVH,D", GEOMS)
def test_ragged_decode_paged(H, KVH, D):
    from localai_tpu.ops.attention import mha_decode
    from localai_tpu.ops.paged import paged_view
    from localai_tpu.ops.pallas import ragged_decode

    B, maxb = 4, 4
    table = _table(B, maxb)
    nb = B * maxb + 1
    q = _bf16(6, (B, 1, H, D))
    kp, vp = _bf16(7, (nb, KVH, BS, D)), _bf16(8, (nb, KVH, BS, D))
    lengths = jnp.array([1, 100, 300, maxb * BS], jnp.int32)
    out = ragged_decode(q, kp, vp, lengths, table=table)
    ref = mha_decode(q, paged_view(kp, table), paged_view(vp, table), lengths)
    _close(out, ref)


def _quant(key, shape):
    """A QuantKV of logical shape [..., T, D] from random values."""
    from localai_tpu.ops.kvcache import QuantKV, quantize_tokens

    xq, s = quantize_tokens(jax.random.normal(jax.random.PRNGKey(key), shape))
    *lead, t, _ = shape
    return QuantKV(xq, s.reshape(*lead, t // 128, 128))


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("H,KVH,D", GEOMS)
def test_ragged_decode_q8_dense(H, KVH, D, layer):
    from localai_tpu.ops.attention import mha_decode
    from localai_tpu.ops.kvcache import dequant
    from localai_tpu.ops.pallas import ragged_decode_q8

    B, T = 4, 1024
    q = _bf16(9, (B, 1, H, D))
    kc = _quant(10, (*_lead(layer), B, KVH, T, D))
    vc = _quant(11, (*_lead(layer), B, KVH, T, D))
    lengths = jnp.array([1, 100, 777, T], jnp.int32)
    out = ragged_decode_q8(q, kc.q, kc.s, vc.q, vc.s, lengths, layer=layer)
    k1, v1 = _of(layer, kc, vc)
    _close(out, mha_decode(q, dequant(k1), dequant(v1), lengths))


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("H,KVH,D", GEOMS + [(32, 4, 128)])
def test_ragged_decode_ring(H, KVH, D, layer):
    """ring=True (a window layer's cache: row p mod T holds position p) in
    both dtypes, at Mellum2's ring (1024 + 512) and head geometry, against
    the XLA mask."""
    from localai_tpu.models.llama import _decode_dq
    from localai_tpu.ops.pallas import ragged_decode, ragged_decode_q8

    B, T, W = 4, 1536, 1024
    q = _bf16(31, (B, 1, H, D))
    k = _bf16(32, (*_lead(layer), B, KVH, T, D))
    v = _bf16(33, (*_lead(layer), B, KVH, T, D))
    lengths = jnp.array([1, 1200, 1537, 7000], jnp.int32)
    _close(ragged_decode(q, k, v, lengths, sliding_window=W, ring=True,
                         layer=layer),
           _decode_dq(q, *_of(layer, k, v), lengths, sliding_window=W,
                      ring=True))
    kc = _quant(34, (*_lead(layer), B, KVH, T, D))
    vc = _quant(35, (*_lead(layer), B, KVH, T, D))
    _close(ragged_decode_q8(q, kc.q, kc.s, vc.q, vc.s, lengths,
                            sliding_window=W, ring=True, layer=layer),
           _decode_dq(q, *_of(layer, kc, vc), lengths, sliding_window=W,
                      ring=True))


# the cells' own shapes (PR 30): 32 rows, every KV head of a row a grid step,
# block_k from the shapes (512 of Mixtral's 1536 and of a ring, 1024 of
# Mellum2's 8192), a third of the rows not decoding (length 0)
CELL_SHAPES = {
    "mixtral": (8, 4, 1536, None, 1536),
    "mellum2-full": (4, 8, 8192, None, 7680),
    "mellum2-ring": (4, 8, 1536, 1024, 6000),
    # 48 query heads over 8 KV heads: a group of 6, the first that is no
    # power of two (PR 35); a ring of 4096 + 512 tokens, rows of 16384
    "trinity-full": (8, 6, 16384, None, 16383),
    "trinity-ring": (8, 6, 4608, 4096, 16000),
}


@pytest.mark.parametrize("quant", [True, False], ids=["q8", "bf16"])
@pytest.mark.parametrize("shape", list(CELL_SHAPES))
def test_ragged_decode_at_the_cells_shapes(shape, quant):
    from localai_tpu.models.llama import _decode_dq
    from localai_tpu.ops.pallas import ragged_decode, ragged_decode_q8

    KVH, G, T, W, longest = CELL_SHAPES[shape]
    B, D, L, layer = 32, 128, 2, 1
    rng = np.random.default_rng(30)
    lengths = rng.integers(1, longest + 1, B)
    lengths[:8] = [1, 127, 128, 129, 511, 512, 513, longest]
    active = np.ones(B, bool)
    active[rng.permutation(B)[:B // 3]] = False
    active[[0, 7]] = True
    kw = dict(sliding_window=W, ring=True) if W else {}
    q = _bf16(40, (B, 1, KVH * G, D))
    if quant:
        k, v = _quant(41, (L, B, KVH, T, D)), _quant(42, (L, B, KVH, T, D))
    else:
        k, v = _bf16(41, (L, B, KVH, T, D)), _bf16(42, (L, B, KVH, T, D))
    want = _decode_dq(q, k[layer], v[layer], jnp.asarray(lengths, jnp.int32),
                      **kw)
    lens = jnp.asarray(np.where(active, lengths, 0), jnp.int32)
    if quant:
        got = ragged_decode_q8(q, k.q, k.s, v.q, v.s, lens,
                               layer=jnp.int32(layer), **kw)
    else:
        got = ragged_decode(q, k, v, lens, layer=jnp.int32(layer), **kw)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    assert not got[~active].any()
    _close(got[active], np.asarray(want, np.float32)[active])


@pytest.mark.parametrize("quant", [True, False], ids=["q8", "bf16"])
@pytest.mark.parametrize("shape", ["mixtral", "mellum2-full", "trinity-full"])
def test_chunk_attention_at_the_cells_shapes(shape, quant):
    """A 512-token chunk of one gathered row over the cell's full-length
    stack (XLA, PR 36): the blocks up to the context the chunk has against
    the whole row, rows past it NaN (an int8 row's scale)."""
    from localai_tpu.models import kv
    from localai_tpu.ops.kvcache import QuantKV

    KVH, G, T, _, _ = CELL_SHAPES[shape]
    B, D, L, S = 4, 128, 2, 512
    q = _bf16(50, (1, S, KVH * G, D))
    rows = jnp.array([B - 1])
    past = jnp.arange(T)
    for at in (0, T // 2 - 100, T - S):
        start = jnp.array([at], jnp.int32)
        dead = past >= at + S
        if quant:
            k, v = _quant(51, (L, B, KVH, T, D)), _quant(52, (L, B, KVH, T, D))
            bad = [QuantKV(c.q, jnp.where(dead.reshape(T // 128, 128),
                                          jnp.nan, c.s)) for c in (k, v)]
        else:
            k, v = _bf16(51, (L, B, KVH, T, D)), _bf16(52, (L, B, KVH, T, D))
            bad = [jnp.where(dead[:, None], jnp.nan, c) for c in (k, v)]

        def attend(form, k, v):
            return jax.jit(lambda k, v, q, start: form(
                kv.DenseKV(k, v, None, layer=jnp.int32(1)), q,
                start[:, None] + jnp.arange(S)[None, :], start, rows,
                True))(k, v, q, start)

        got = np.asarray(attend(kv.DenseKV.attend_window, *bad), np.float32)
        assert np.isfinite(got).all()
        _close(got, attend(kv.NoKV.attend_window, k, v))


@pytest.mark.parametrize("H,KVH,D", GEOMS)
def test_ragged_decode_q8_paged(H, KVH, D):
    from localai_tpu.ops.attention import mha_decode
    from localai_tpu.ops.kvcache import QuantKV, dequant
    from localai_tpu.ops.paged import paged_view
    from localai_tpu.ops.pallas import ragged_decode_q8

    B, maxb = 4, 4
    table = _table(B, maxb)
    nb = B * maxb + 1
    q = _bf16(12, (B, 1, H, D))
    kq, ks = _quant_pool(13, nb, KVH, D)
    vq, vs = _quant_pool(14, nb, KVH, D)
    lengths = jnp.array([1, 100, 300, maxb * BS], jnp.int32)
    out = ragged_decode_q8(q, kq, ks, vq, vs, lengths, table=table)
    kv = paged_view(QuantKV(kq, ks), table)
    vv = paged_view(QuantKV(vq, vs), table)
    _close(out, mha_decode(q, dequant(kv), dequant(vv), lengths))


# --------------------------------------------------- paged_scatter.py

def _scatter_case(B=16, maxb=4):
    table = _table(B, maxb)
    # every in-block row offset class: 0, mid-tile, tile edges, last row
    positions = jnp.asarray(
        [0, 1, 7, 8, 15, 16, 31, 32, 33, 127, 128, 129, 255, 300, 383, 511],
        jnp.int32)[:B]
    active = jnp.asarray([True] * (B - 2) + [False, False])
    return table, positions, active


@pytest.mark.parametrize("H,KVH,D", GEOMS)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_scatter_append(H, KVH, D, dtype):
    from localai_tpu.ops.pallas import paged_scatter_append
    from localai_tpu.ops.pallas.paged_scatter import _targets

    B, maxb = 16, 4
    table, positions, active = _scatter_case(B, maxb)
    nb = B * maxb + 1
    kp = _bf16(15, (nb, KVH, BS, D)).astype(dtype)
    vp = _bf16(16, (nb, KVH, BS, D)).astype(dtype)
    kn, vn = _bf16(17, (B, KVH, D)), _bf16(18, (B, KVH, D))
    pb, off = _targets(positions, table, active)
    want_k = np.array(kp, np.float32)
    want_v = np.array(vp, np.float32)
    for b in range(B):
        want_k[int(pb[b]), :, int(off[b])] = np.asarray(
            kn[b].astype(dtype), np.float32)
        want_v[int(pb[b]), :, int(off[b])] = np.asarray(
            vn[b].astype(dtype), np.float32)
    ko, vo = jax.jit(paged_scatter_append)(kp, vp, kn, vn, positions, table,
                                           active)
    np.testing.assert_array_equal(np.asarray(ko, np.float32), want_k)
    np.testing.assert_array_equal(np.asarray(vo, np.float32), want_v)


@pytest.mark.parametrize("H,KVH,D", GEOMS)
def test_paged_scatter_append_q8(H, KVH, D):
    from localai_tpu.ops.kvcache import quantize_tokens
    from localai_tpu.ops.pallas import paged_scatter_append_q8
    from localai_tpu.ops.pallas.paged_scatter import _targets

    B, maxb = 16, 4
    table, positions, active = _scatter_case(B, maxb)
    nb = B * maxb + 1
    kq, ks = _quant_pool(19, nb, KVH, D)
    vq, vs = _quant_pool(20, nb, KVH, D)
    kn, vn = _bf16(21, (B, KVH, D)), _bf16(22, (B, KVH, D))
    pb, off = _targets(positions, table, active)
    want = [np.array(x) for x in (kq, ks, vq, vs)]
    knq, kns = quantize_tokens(kn)
    vnq, vns = quantize_tokens(vn)
    for b in range(B):
        p, o = int(pb[b]), int(off[b])
        want[0][p, :, o] = np.asarray(knq[b])
        want[1][p, :, 0, o] = np.asarray(kns[b])
        want[2][p, :, o] = np.asarray(vnq[b])
        want[3][p, :, 0, o] = np.asarray(vns[b])
    got = jax.jit(paged_scatter_append_q8)(kq, ks, vq, vs, kn, vn, positions,
                                           table, active)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


# ------------------------------------------------------------- kda.py

def _kda_inputs(b, s, h, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, d), minval=-7.0,
                                    maxval=0.5))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, d, d))


@pytest.mark.parametrize("live", ["all", "most", "one", "none"])
@pytest.mark.parametrize("layer", [0, 1])
def test_kda_decode_at_the_cells_shape(live, layer):
    """ops/pallas/kda.py at the new cell's shape (32 slots, 64 heads x 128 x
    128 float32, a stack of two layers): Mosaic lowers it, a live row's
    state and output are the XLA twin's, a row that is not decoding keeps
    the NaN its state holds, and the other layer is untouched."""
    from localai_tpu.ops.kda import kda_step
    from localai_tpu.ops.pallas.kda import kda_decode

    b, h, d = 32, 64, 128
    q, k, v, g, beta, _ = _kda_inputs(b, 1, h, d, seed=3)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    stack = jax.random.normal(jax.random.PRNGKey(4), (2, b, h, d, d))
    mask = np.zeros((b,), bool)
    mask[{"all": slice(None), "most": slice(3, 30), "one": slice(17, 18),
          "none": slice(0, 0)}[live]] = True
    planted = stack.at[layer].set(jnp.where(
        jnp.asarray(mask)[:, None, None, None], stack[layer], jnp.nan))
    o, out = kda_decode(q, k, v, g, beta, planted, layer, jnp.asarray(mask))
    want_o, want_s = kda_step(q, k, v, g, beta, stack[layer])
    _close(o[mask], want_o[mask], tol=1e-3)
    _close(out[layer][mask], want_s[mask], tol=1e-3)
    assert bool((o[~mask] == 0).all())
    assert bool(jnp.isnan(out[layer][~mask]).all())
    assert bool((out[1 - layer] == stack[1 - layer]).all())


def test_kda_chunk_at_the_cells_shape():
    """ops/kda.py's chunkwise form (XLA, float32 products at HIGHEST) over
    one 512-token chunk of 64 heads against the token-by-token scan."""
    from localai_tpu.ops.kda import kda_chunk, kda_recurrent

    q, k, v, g, beta, state = _kda_inputs(1, 512, 64, 128, seed=5)
    o, s = jax.jit(kda_chunk)(q, k, v, g, beta, state)
    want_o, want_s = jax.jit(kda_recurrent)(q, k, v, g, beta, state)
    _close(o, want_o, tol=1e-3)
    _close(s, want_s, tol=1e-3)


# the routed expert layer at the cells' shapes: (hidden, experts, expert
# width, top-k, [batch, sequence]): a chunk of Mixtral's and of Mellum2's,
# and _admit_many's four prompts at once
ROUTED_SHAPES = {
    "mixtral-8x7b, a 512-token chunk": (4096, 8, 14336, 2, (1, 512)),
    "mixtral-8x7b, four 512-token prompts": (4096, 8, 14336, 2, (4, 512)),
    "mellum2-12b-a2.5b, a 512-token chunk": (2304, 64, 896, 8, (1, 512)),
    "mellum2-12b-a2.5b, a 64-token chunk": (2304, 64, 896, 8, (1, 64)),
}


@pytest.mark.parametrize("shape", list(ROUTED_SHAPES))
def test_routed_experts_at_the_cells_shapes(shape):
    """models/llama._moe_routed (the grouped product kernel over int8
    experts in a two-layer stack, bf16 activations, as served) against the
    dense form _moe_mlp, the layer these models ran before."""
    from localai_tpu.models.llama import (
        LlamaConfig, _InStack, _moe_mlp, _moe_routed,
    )

    hidden, experts, width, k, (b, s) = ROUTED_SHAPES[shape]
    cfg = LlamaConfig(hidden_size=hidden, num_experts=experts,
                      experts_per_tok=k, moe_intermediate_size=width)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    qw = lambda kk, shape: {  # noqa: E731
        "q": jax.random.randint(kk, shape, -127, 128, jnp.int8),
        "s": jnp.full(shape[:-2] + (1, shape[-1]), shape[-2] ** -0.5 / 73,
                      jnp.float32)}
    gate = jax.random.normal(ks[0], (hidden, experts)) * 0.02
    stacks = {"moe_w1": qw(ks[1], (2, experts, hidden, width)),
              "moe_w3": qw(ks[2], (2, experts, hidden, width)),
              "moe_w2": qw(ks[3], (2, experts, width, hidden))}
    x = jax.random.normal(ks[4], (b, s, hidden), jnp.bfloat16)

    def routed(x, gate, stacks):
        return _moe_routed(x, {"moe_gate": gate, **{
            n: _InStack(w, 1) for n, w in stacks.items()}}, cfg)

    def dense(x, gate, stacks):
        return _moe_mlp(x, {"moe_gate": gate, **jax.tree_util.tree_map(
            lambda a: a[1], stacks)}, cfg)

    got = jax.jit(routed)(x, gate, stacks).astype(jnp.float32)
    want = jax.jit(dense)(x, gate, stacks).astype(jnp.float32)
    assert bool(jnp.isfinite(got).all())
    # bf16 activations either way; the two sum a token's experts in
    # different orders
    assert float(jnp.abs(got - want).max()) < 0.03 * float(
        jnp.abs(want).max())


# ------------------------------------------------------------- engine

def _tiny_cfg(H, KVH, D):
    from localai_tpu.models.llama import LlamaConfig

    return LlamaConfig(vocab_size=256, hidden_size=H * D // 4,
                       intermediate_size=512, num_layers=2, num_heads=H,
                       num_kv_heads=KVH, head_dim=D, max_position=512)


@pytest.mark.parametrize("rows", [(1, 512), (32, 1)],
                         ids=["a 512-token chunk", "a decode step's 32 rows"])
def test_routed_share_at_trinitys_widths(rows):
    """models/llama._moe_routed as Trinity's cell serves it (experts of
    3072 x 3072 int8 in a two-layer stack, 32 held of the 256 a sigmoid
    router with a selection bias scores, top-4, route_scale, a shared
    expert; bf16 activations), the grouped product kernel against the
    masked twin. If the compile hangs in warm-up as PR 32's first kernel
    did at 1024-row tiles (vmem_limit_bytes; PERF.md section 6), this is
    the shape that brings it back."""
    from localai_tpu.models.llama import LlamaConfig, _InStack, _moe_routed

    hidden, held, routers, b_s = 3072, 32, 256, rows
    cfg = LlamaConfig(hidden_size=hidden, num_experts=held, experts_per_tok=4,
                      moe_intermediate_size=hidden, router_experts=routers,
                      shared_expert_width=hidden, routed_scale=2.448,
                      router_sigmoid=True, router_bias=True)
    ks = jax.random.split(jax.random.PRNGKey(35), 9)
    qw = lambda kk, shape: {  # noqa: E731
        "q": jax.random.randint(kk, shape, -127, 128, jnp.int8),
        "s": jnp.full(shape[:-2] + (1, shape[-1]), shape[-2] ** -0.5 / 73,
                      jnp.float32)}
    rest = {"moe_gate": jax.random.normal(ks[0], (hidden, routers))
            * hidden ** -0.5,
            "moe_bias": 0.02 * jax.random.normal(ks[1], (routers,)),
            "ws_gate": qw(ks[2], (hidden, hidden)),
            "ws_up": qw(ks[3], (hidden, hidden)),
            "ws_down": qw(ks[4], (hidden, hidden))}
    stacks = {"moe_w1": qw(ks[5], (2, held, hidden, hidden)),
              "moe_w3": qw(ks[6], (2, held, hidden, hidden)),
              "moe_w2": qw(ks[7], (2, held, hidden, hidden))}
    x = jax.random.normal(ks[8], (*b_s, hidden), jnp.bfloat16)

    def layer(grouped):
        return jax.jit(lambda x, rest, stacks: _moe_routed(
            x, {**rest, **{n: _InStack(w, 1) for n, w in stacks.items()}},
            cfg, grouped=grouped))

    got = layer(True)(x, rest, stacks).astype(jnp.float32)
    want = layer(False)(x, rest, stacks).astype(jnp.float32)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 0.03 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("H,KVH,D", GEOMS)
def test_model_prefill_and_decode_step(H, KVH, D):
    """decode_step through the Pallas selector compiles and runs."""
    from localai_tpu.models.llama import (
        decode_step, init_kv_cache, init_params, prefill,
    )
    from localai_tpu.ops.rope import rope_table

    cfg = _tiny_cfg(H, KVH, D)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cos, sin = rope_table(cfg.rope, 256)
    kc, vc = init_kv_cache(cfg, 2, 256)
    tokens = jnp.array([[1, 2, 3, 4]], jnp.int32)
    logits, kc, vc = prefill(params, cfg, tokens, jnp.array([4], jnp.int32),
                             cos, sin, kc, vc, jnp.array([0], jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()
    dlogits, _, _ = decode_step(params, cfg, jnp.array([5, 0], jnp.int32),
                                jnp.array([4, 0], jnp.int32),
                                cos, sin, kc, vc)
    assert np.isfinite(np.asarray(dlogits[0])).all()


ENGINES = {
    "dense": dict(),
    "dense-int8kv": dict(cache_type="int8"),
    "paged": dict(kv_pages=24),
    "paged-int8kv": dict(kv_pages=24, cache_type="int8"),
}


@pytest.mark.parametrize("H,KVH,D", GEOMS)
@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_engine_runs_on_tpu(H, KVH, D, kind):
    """The serving programs (admission, chunked prefill, the fused decode
    while-loop with donated caches) compile and run on the
    chip for a few ticks, on the Pallas tier, with exact token counts."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams
    from localai_tpu.models.llama import init_params

    cfg = _tiny_cfg(H, KVH, D)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=4, max_context=384, prefill_buckets=(16, 64),
        prefill_chunk=64, decode_block=8, **ENGINES[kind]))
    tiers = eng.kernel_tiers()
    assert tiers["prefill_attention"] == tiers["decode_attention"] == "pallas"
    assert tiers["chunk_attention"] == (
        "xla" if "kv_pages" in ENGINES[kind] else "xla-blocks")
    if "kv_pages" in ENGINES[kind]:
        assert tiers["decode_kv_write"] == "pallas"
    eng.warmup()
    eng.start()
    try:
        # a short prompt, one past the 64-token chunk, and a second wave
        # that admits while the first decodes
        prompts = [[1, 2, 3], list(range(1, 151)), [7] * 20, [9] * 70]
        want = [24, 12, 40, 8]
        qs = [eng.submit(GenRequest(
            prompt_ids=p, max_tokens=n, ignore_eos=True,
            params=SamplingParams(temperature=0.0 if i % 2 else 0.8,
                                  seed=i + 1)))[1]
            for i, (p, n) in enumerate(zip(prompts, want))]
        for q, n in zip(qs, want):
            ids = []
            while True:
                o = q.get(timeout=300)
                if o.token_id >= 0:
                    ids.append(o.token_id)
                if o.finished:
                    break
            assert o.finish_reason == "length", (kind, eng.last_error)
            assert len(ids) == n
            assert all(0 <= t < cfg.vocab_size for t in ids)
        if kind.startswith("ragged"):
            assert eng.metrics["ragged_dispatches"] > 0
    finally:
        eng.stop()
