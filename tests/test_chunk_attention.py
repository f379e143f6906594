"""A chunk's attention over a dense, full-length cache (kv.DenseKV.
attend_window -> ops/attention.mha_extend_blocks) against the whole-row
reference it replaced in the served programs (kv.NoKV.attend_window ->
mha_extend): the same inputs, float32 products (tests/conftest.py), so the two
agree to rounding. The grid is the ways a served program calls it — a float
or an int8 stack, the layer named or sliced out, one gathered row or every
slot, `start` at 0, at a block's edge, one short of it and at T - S, rows of
one program at different starts, a speculative-verification window, a window
on a full-length cache — and what proves the bound: every cache row past
start + S poisoned with NaN, and the output finite and equal. PR 46: a
LATENT layer's chunk (kv.LatentKV.attend_window) in its kernel
(ops/pallas/mla.py: mla_chunk, the interpreter here) against its twin, the
XLA block loop, and against mha_extend over the whole row expanded."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import kv
from localai_tpu.ops.kvcache import QuantKV, quantize_tokens

L, LAYER, SLOTS, H, KVH, D = 2, 1, 4, 4, 2, 16
T, S, BLOCK = 1536, 64, kv.CHUNK_BLOCK          # three blocks
STARTS = {
    "0": [0],
    "a block's edge": [BLOCK],
    "one short of it": [BLOCK - 1],
    "ends on an edge": [2 * BLOCK - S],
    "T - S": [T - S - 3],
    "rows differ": [3, 2 * BLOCK - 1, T - S - 3],
}


def _stack(seed, t, quant):
    x = jax.random.normal(jax.random.PRNGKey(seed), (L, SLOTS, KVH, t, D))
    if not quant:
        return x
    q, s = quantize_tokens(x)
    return QuantKV(q, s.reshape(L, SLOTS, KVH, t // 128, 128))


def _both(starts, *, quant=False, layer=True, gathered=True, s=S, t=T,
          window=None, poison=False):
    """(reference, served) for rows that begin at `starts`: gathered, the
    rows are the LAST slots in reverse; not, row i is slot i, `starts` dealt
    round the slots (a slot further on, a token later)."""
    k, v = _stack(1, t, quant), _stack(2, t, quant)
    if not gathered:
        starts = [starts[i % len(starts)] + i for i in range(SLOTS)]
    b = len(starts)
    start = jnp.asarray(starts, jnp.int32)
    positions = start[:, None] + jnp.arange(s)[None, :]
    rows = jnp.arange(SLOTS - 1, SLOTS - 1 - b, -1)
    q = jax.random.normal(jax.random.PRNGKey(3), (b, s, H, D))

    def view(k, v):
        if layer:
            return kv.DenseKV(k, v, window, layer=jnp.int32(LAYER))
        return kv.DenseKV(k[LAYER], v[LAYER], window)

    ref = kv.NoKV.attend_window(view(k, v), q, positions, start, rows,
                                gathered)
    if poison:
        # NaN in every row past start + S of every slot a query row reads
        slots = np.asarray(rows) if gathered else np.arange(b)
        past = np.zeros((SLOTS, t), bool)
        for slot, at in zip(slots, starts):
            past[slot, at + s:] = True

        def spoil(c):
            if quant:
                bad = jnp.asarray(past.reshape(SLOTS, 1, t // 128, 128))
                return QuantKV(c.q, jnp.where(bad[None], jnp.nan, c.s))
            return jnp.where(jnp.asarray(past)[None, :, None, :, None],
                             jnp.nan, c)

        k, v = spoil(k), spoil(v)
    out = jax.jit(lambda k, v, q, start, rows: view(k, v).attend_window(
        q, start[:, None] + jnp.arange(s)[None, :], start, rows, gathered))(
            k, v, q, start, rows)
    return np.asarray(ref), np.asarray(out)


def _same(ref, out):
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("gathered", [True, False], ids=["rows", "slots"])
@pytest.mark.parametrize("layer", [True, False], ids=["stack", "one-layer"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("starts", STARTS.values(), ids=list(STARTS))
def test_blocks_equal_the_whole_row(starts, quant, layer, gathered):
    _same(*_both(starts, quant=quant, layer=layer, gathered=gathered))


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_a_verify_window_of_every_slot(quant):
    """extend as speculative verification calls it: a few tokens, row i is
    slot i, each at its own length; the bound is the longest row's."""
    _same(*_both([5, BLOCK - 3, 2 * BLOCK + 40, 17], quant=quant, s=5,
                 gathered=False))
    _same(*_both([5, BLOCK - 3, 2 * BLOCK + 40, 17], quant=quant, s=5,
                 gathered=False, poison=True))


@pytest.mark.parametrize("starts",
                         [[0], [BLOCK + 100], [BLOCK - 30, T - S - 3]],
                         ids=["0", "past a block", "rows differ"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_a_window_on_a_full_length_cache(starts, quant):
    """A one-kind model's sliding window over a dense cache: the same mask,
    and blocks wholly before min(start) - window are skipped."""
    _same(*_both(starts, quant=quant, window=200))
    _same(*_both(starts, quant=quant, window=200, poison=True))


@pytest.mark.parametrize("t,starts", [(128, [0, 61]), (640, [0, 573]),
                                      (1000, [933, 500])],
                         ids=["T under a block", "T = 640", "T = 1000"])
def test_a_row_the_block_does_not_divide(t, starts):
    """The last block is moved back inside the row and the rows it shares
    with the one before count once (int8 where T is whole scale tiles)."""
    for quant in {False, t % 128 == 0}:
        _same(*_both(starts, quant=quant, t=t))


@pytest.mark.parametrize("gathered", [True, False], ids=["rows", "slots"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("starts", [[0], [BLOCK - S], [BLOCK - 1, 100]],
                         ids=["0", "ends on an edge", "rows differ"])
def test_nothing_past_the_bound_is_read(starts, quant, gathered):
    """Every cache row past start + S is NaN (an int8 row's scale): were a
    block past the bound fetched, or a row past its query row's newest
    position multiplied, the output would not be finite."""
    _same(*_both(starts, quant=quant, gathered=gathered, poison=True))


def test_the_trip_count_is_traced():
    """One program a shape: `start` changes and nothing compiles again."""
    k, v = _stack(1, T, False), _stack(2, T, False)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, S, H, D))
    calls = []

    @jax.jit
    def attend(start):
        calls.append(1)
        view = kv.DenseKV(k, v, None, layer=jnp.int32(LAYER))
        return view.attend_window(
            q, start[:, None] + jnp.arange(S)[None, :], start,
            jnp.array([2]), True)

    outs = [attend(jnp.array([at], jnp.int32)) for at in (0, 700, T - S)]
    assert len(calls) == 1 and not np.allclose(outs[0], outs[1])


def test_the_host_counts_the_rows_the_device_visits():
    assert kv.chunk_rows(16384, None, 3072, 512) == 3584
    assert kv.chunk_rows(16384, None, 0, 512) == 512
    assert kv.chunk_rows(16384, None, 16384 - 512, 512) == 16384
    assert kv.chunk_rows(1536, None, 511, 64) == 1024
    assert kv.chunk_rows(1536, 200, 1000, 64) == 1024     # blocks 1 and 2
    assert kv.chunk_rows(1000, None, 936, 64) == 1000     # the moved block
    assert kv.chunk_rows(128, None, 0, 64) == 128


def test_the_engine_counts_a_chunks_context():
    """A 200-token prompt past a 64-token bucket is four chunks (from 0, 64,
    128 and 192), each inside the first block of a 1536-row cache; a pool
    reads the row whole."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams
    from localai_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                      max_position=T, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    for pool, attended in (({}, 4 * BLOCK), (dict(kv_pages=16), 4 * T)):
        eng = Engine(cfg, params, None, EngineConfig(
            max_slots=2, max_context=T, prefill_buckets=(64,),
            prefill_chunk=64, **pool))
        m = eng.metrics
        assert m["chunk_ctx_tokens__attended"] == 0
        assert eng.kernel_tiers()["chunk_attention"] == (
            "xla" if pool else "xla-blocks")
        eng.start()
        try:
            _, q = eng.submit(GenRequest(
                prompt_ids=list(range(1, 101)) * 2, max_tokens=2,
                ignore_eos=True,
                params=SamplingParams(temperature=0.0, seed=1)))
            while not q.get(timeout=300).finished:
                pass
        finally:
            eng.stop()
        assert (m["chunk_ctx_tokens__attended"],
                m["chunk_ctx_tokens__capacity"]) == (attended, 4 * T)


@pytest.mark.parametrize("cache_type", ["", "int8"], ids=["float", "int8"])
def test_chunks_under_a_mesh_match_unmeshed(cache_type):
    """A prompt past its bucket under a 2 x 4 mesh (the KV heads sharded on
    'model': the blocks are cut out of a sharded stack) picks the tokens
    the engine picks without a mesh."""
    from localai_tpu.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.models.llama import LlamaConfig, init_params, param_specs
    from localai_tpu.ops.sampling import SamplingParams
    from localai_tpu.parallel.mesh import MeshConfig, build_mesh, shard_params

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=4, head_dim=16,
                      max_position=1280, dtype="float32")
    ps = init_params(cfg, jax.random.PRNGKey(3))
    mesh = build_mesh(MeshConfig(data=2, model=4))

    def run(mesh_arg):
        eng = Engine(cfg, ps if mesh_arg is None else
                     shard_params(ps, param_specs(cfg), mesh_arg), None,
                     EngineConfig(max_slots=2, max_context=1280,
                                  prefill_buckets=(64,), prefill_chunk=64,
                                  cache_type=cache_type, mesh=mesh_arg))
        ids = [o.token_id for o in eng.generate(GenRequest(
            prompt_ids=[(7 * i) % 250 + 1 for i in range(600)],
            params=SamplingParams(temperature=0.0), max_tokens=6,
            ignore_eos=True))]
        assert eng.metrics["chunk_ctx_tokens__attended"] == (
            8 * BLOCK + 2 * 2 * BLOCK)      # 8 chunks in block 0, 2 past it
        return ids

    assert run(None) == run(mesh)


# ------------------------------------------- a latent layer's chunk (PR 46)

LH, LR, LP, LN, LV = 4, 64, 16, 32, 32      # heads, rank, rope, nope, vdim
LATENT_CASES = {
    # name: (T, starts, gathered, what lies past each row's newest position)
    "a context that ends inside a block": (T, [BLOCK + 100], True, None),
    "start differs by row, gathered": (
        T, [3, 2 * BLOCK - 1, T - S - 3], True, None),
    "start differs by row, every slot": (
        T, [3, 2 * BLOCK - 1, T - S - 3, BLOCK], False, None),
    "512 does not divide T": (1000, [936, 500], True, None),
    "a padded chunk runs past the end of such a T": (
        1000, [960, 400], True, None),
    "an earlier tenant's inf and nan": (
        T, [BLOCK - S, 2 * BLOCK + 7], True, (jnp.inf, jnp.nan)),
    "position 0 of the first block": (T, [0], True, jnp.nan),
}


def _latent(t, starts, gathered, past, dtype, quant):
    """(the kernel, the XLA loop, mha_extend over the expanded row) for a
    latent layer's chunk of S tokens a row."""
    import dataclasses

    from localai_tpu.ops import mla
    from localai_tpu.ops.attention import mha_extend
    from localai_tpu.ops.pallas.mla import mla_chunk
    from localai_tpu.ops.quant import quantize

    width = kv.latent_row_width(LR, LP)
    ks = jax.random.split(jax.random.PRNGKey(46), 3)
    cache = jnp.concatenate([
        jax.random.normal(ks[0], (L, SLOTS, t, LR + LP)),
        jnp.zeros((L, SLOTS, t, width - LR - LP))], -1).astype(dtype)
    w = quantize(jax.random.normal(ks[1], (LR, LH * (LN + LV))) * LR ** -0.5)
    if not quant:
        w = (w["q"] * w["s"]).astype(dtype)
    b = len(starts)
    start = jnp.asarray(starts, jnp.int32)
    positions = start[:, None] + jnp.arange(S)[None, :]
    rows = jnp.arange(SLOTS - 1, SLOTS - 1 - b, -1)
    slots = np.asarray(rows) if gathered else np.arange(b)
    q = jax.random.normal(ks[2], (b, S, LH, LN + LP)).astype(dtype)
    view = kv.LatentKV(cache, None, layer=jnp.int32(LAYER), heads=LH,
                       nope=LN, rope=LP, rank=LR, vdim=LV, w_kvb=w)
    kx, vx = view._expand(cache[LAYER][slots])
    vx = jnp.pad(vx, ((0, 0),) * 3 + ((0, LN + LP - LV),))
    whole = mha_extend(q, kx, vx, positions, scale=view.scale)[..., :LV]
    if past is not None:
        bad = np.zeros((SLOTS, t), bool)
        for i, (slot, at) in enumerate(zip(slots, starts)):
            bad[slot, at + S:] = True
        fill = jnp.asarray(np.resize(np.asarray(past, np.float32), t))
        cache = jnp.where(jnp.asarray(bad)[None, :, :, None],
                          fill[None, None, :, None].astype(dtype), cache)
        view = dataclasses.replace(view, k=cache)
    loop = jax.jit(lambda c, q, start, rows: dataclasses.replace(
        view, k=c).attend_window_xla(
            q, start[:, None] + jnp.arange(S)[None, :], start, rows,
            gathered))(cache, q, start, rows)
    kernel = mla_chunk(q, cache, w, start,
                       rows if gathered else jnp.arange(b), view.layer,
                       rank=LR, nope=LN, scale=view.scale,
                       block=min(BLOCK, t))
    return [np.asarray(a, np.float32) for a in (kernel, loop, whole)]


@pytest.mark.parametrize("precision", ["float32", "bfloat16-int8"])
@pytest.mark.parametrize("case", LATENT_CASES.values(),
                         ids=list(LATENT_CASES))
def test_a_latent_chunks_kernel_equals_its_twin_and_the_whole_row(
        case, precision):
    """float32: to rounding. As served (a bfloat16 cache, int8 W_kvb): the
    median relative error under tools/reference_check.py's latent limit,
    against the loop and against the whole row alike, and the largest
    within a few bfloat16 steps of the outputs' scale."""
    from tools.reference_check import MEDIAN_REL_LATENT

    served = precision != "float32"
    kernel, loop, whole = _latent(
        *case, jnp.bfloat16 if served else jnp.float32, served)
    assert np.isfinite(kernel).all()
    for ref in (loop, whole):
        if not served:
            np.testing.assert_allclose(kernel, ref, rtol=2e-5, atol=2e-5)
            continue
        err = np.abs(kernel - ref)
        assert np.median(err / np.maximum(np.abs(ref), 1e-3)) < (
            MEDIAN_REL_LATENT)
        assert err.max() < 0.05


def test_a_latent_view_takes_the_kernel_where_decode_does(monkeypatch):
    """LatentKV.attend_window chooses by kv._pallas_attention, as its
    decode: the kernel when forced here (the interpreter), else the twin;
    the two agree."""
    import dataclasses

    from localai_tpu.ops.pallas import mla as pallas_mla

    width = kv.latent_row_width(LR, LP)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    cache = jnp.pad(jax.random.normal(ks[0], (L, SLOTS, T, LR + LP)),
                    ((0, 0),) * 3 + ((0, width - LR - LP),))
    view = kv.LatentKV(
        cache, None, layer=jnp.int32(LAYER), heads=LH, nope=LN, rope=LP,
        rank=LR, vdim=LV,
        w_kvb=jax.random.normal(ks[1], (LR, LH * (LN + LV))) * LR ** -0.5)
    q = jax.random.normal(ks[2], (1, S, LH, LN + LP))
    start = jnp.asarray([BLOCK + 9], jnp.int32)
    args = (q, start[:, None] + jnp.arange(S)[None, :], start,
            jnp.asarray([2]), True)
    calls = []
    real = pallas_mla.mla_chunk
    monkeypatch.setattr(pallas_mla, "mla_chunk",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    twin = view.attend_window(*args)
    assert not calls
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    served = dataclasses.replace(view).attend_window(*args)
    assert calls == [1]
    np.testing.assert_allclose(served, twin, rtol=2e-5, atol=2e-5)
    # and the kernel runs under a scope tools/trace_gaps.py reads as a part
    from tools.trace_gaps import scope_of

    text = jax.jit(lambda c: dataclasses.replace(view, k=c).attend_window(
        *args)).lower(cache).as_text(debug_info=True)
    assert "chunk_kernel" in text
    assert scope_of("", {"tf_op": "jit(_extend_mid)/while/body/attention/"
                         "latent/attention/chunk_kernel/jit(mla_chunk)/x"}) \
        == "attention/latent/chunk_kernel"
    assert scope_of("", {"tf_op": "jit(mla_chunk)/x"}) \
        == "attention/latent/chunk_kernel"


def _small_pangu_through_the_engine(workdir):
    """(kernel_tiers' chunk_attention, the metrics, the tokens picked) of a
    small openPangu through the engine's own loop: a 40-token prompt past a
    16-token bucket, chunks from 0, 16 and 32 inside the first block of a
    1024-row cache."""
    import json
    import os

    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams
    from localai_tpu.engine.loader import load_config
    from localai_tpu.models.llama import init_params

    with open(os.path.join(workdir, "config.json"), "w") as f:
        json.dump(dict(
            model_type="pangu_ultra_moe", vocab_size=96, hidden_size=48,
            intermediate_size=64, moe_intermediate_size=24,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=40,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            max_position_embeddings=1024, rms_norm_eps=1e-5,
            rope_theta=25600000, first_k_dense_replace=1,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            sandwich_norm=True, num_nextn_predict_layers=1,
            attention_bias=False, hidden_act="silu",
            tie_word_embeddings=False), f)
    cfg = load_config(workdir, dtype="float32")
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(1)), None,
                 EngineConfig(max_slots=2, max_context=1024,
                              prefill_buckets=(16,), prefill_chunk=16))
    assert eng.metrics["chunk_latent_rows__kernel"] == 0
    eng.start()
    try:
        _, q = eng.submit(GenRequest(
            prompt_ids=[(5 * i) % 90 + 1 for i in range(40)], max_tokens=4,
            ignore_eos=True, params=SamplingParams(temperature=0.0, seed=1)))
        ids = [q.get(timeout=300)]
        while not ids[-1].finished:
            ids.append(q.get(timeout=300))
    finally:
        eng.stop()
    return (eng.kernel_tiers()["chunk_attention"], eng.metrics,
            [o.token_id for o in ids])


@pytest.mark.parametrize("tier", ["xla-blocks", "pallas-interpret"])
def test_the_engine_counts_the_rows_the_kernel_expanded(tier, monkeypatch,
                                                        tmp_path):
    """`chunk_latent_rows__kernel` is the rows expanded where the chunk's
    program holds the kernel and 0 on its twin; the tier's name does not
    move the rows a chunk is counted to visit (one block of 512 a chunk,
    not the row's 1024); and the kernel's engine picks the twin's tokens."""
    twin = _small_pangu_through_the_engine(str(tmp_path))
    if tier != "xla-blocks":
        monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
        served = _small_pangu_through_the_engine(str(tmp_path))
        assert served[2] == twin[2] and len(twin[2]) == 4
    name, m, _ = twin if tier == "xla-blocks" else served
    assert name == tier
    assert (m["chunk_ctx_tokens__attended"],
            m["chunk_ctx_tokens__capacity"]) == (3 * BLOCK, 3 * 1024)
    assert m["chunk_latent_rows__expanded"] == 3 * BLOCK
    assert m["chunk_latent_rows__kernel"] == (
        0 if tier == "xla-blocks" else 3 * BLOCK)


def test_the_bench_rehearses(tmp_path):
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "chunk_attention_bench.py")
    spec = importlib.util.spec_from_file_location("chunk_attention_bench",
                                                  path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = tmp_path / "bench.json"
    assert bench.main(["--cpu-rehearsal", "--models", "mixtral", "--blocks",
                       "256,1024", "--out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert {r["call"] for r in rows} == {"chunk", "verify"}
    assert all(r["whole_row_ms"] is None and r["blocks_1024_diff"] < 0.02
               for r in rows)                   # a CPU run times nothing
    assert kv.CHUNK_BLOCK == BLOCK
