"""Which form a call takes through an expert layer (models/llama.expert_form)
and that the two forms are one layer: the routed form (_moe_routed: each
expert's pairs in tiles of their own) equals the dense form (_moe_mlp) on a model that
holds every expert; a prompt's tokens take the routed form with the experts
left in their stack, a decode step's rows the dense form, unchanged; the
engine counts expert tokens by the same rule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import llama
from localai_tpu.models.llama import (
    DENSE, FULL, ROUTED, WINDOW, LlamaConfig, _moe_mlp, _moe_routed,
    decode_step, expert_form, extend, init_kv_cache, init_params,
    rope_tables,
)
from localai_tpu.ops.quant import quantize
from test_moe_share import _shapes

H, WIDTH = 32, 16


def _cfg(experts, k, **over):
    return LlamaConfig(**{**dict(
        vocab_size=64, hidden_size=H, intermediate_size=64, num_layers=4,
        num_heads=4, num_kv_heads=2, head_dim=8, max_position=1024,
        num_experts=experts, experts_per_tok=k, moe_intermediate_size=WIDTH,
        dtype="float32"), **over})


def _layer(experts, int8, seed=0):
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) * s[-2] ** -0.5, jnp.float32)
    lp = {"moe_gate": w(H, experts), "moe_w1": w(experts, H, WIDTH),
          "moe_w3": w(experts, H, WIDTH), "moe_w2": w(experts, WIDTH, H)}
    if int8:
        lp = {k: quantize(v) if k.startswith("moe_w") else v
              for k, v in lp.items()}
    return lp


def _tokens(n, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (1, n, H)), jnp.float32)


@pytest.mark.parametrize("tokens", [1, 32, 64, 512])
@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("experts,k", [(8, 2), (64, 8)],
                         ids=["top2of8", "top8of64"])
def test_routed_equals_dense_where_every_expert_is_held(experts, k, int8,
                                                        tokens):
    lp, x = _layer(experts, int8), _tokens(tokens)
    a = _moe_routed(x, lp, _cfg(experts, k))
    b = _moe_mlp(x, lp, _cfg(experts, k))
    assert float(jnp.abs(a - b).max()) < 1e-5


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("experts,k", [(8, 2), (64, 8)],
                         ids=["top2of8", "top8of64"])
def test_one_expert_gets_every_token_and_one_gets_none(experts, k, int8,
                                                       kernel, monkeypatch):
    """Every token chooses expert 1 (its group is the whole batch, several
    tiles) and none chooses expert 2 (no tile, not read); in XLA and through
    the Pallas grouped product (the interpreter here)."""
    monkeypatch.setenv(
        "LOCALAI_FORCE_PALLAS" if kernel else "LOCALAI_NO_PALLAS", "1")
    lp = _layer(experts, int8, seed=2)
    x = _tokens(96, seed=3).at[..., 0].set(8.0)
    gate = lp["moe_gate"].at[0].set(0.0)
    lp["moe_gate"] = gate.at[0, 1].set(4.0).at[0, 2].set(-4.0)
    chosen = jax.lax.top_k(x[0] @ lp["moe_gate"], k)[1]
    assert bool((chosen == 1).any(-1).all()) and not bool((chosen == 2).any())
    a = _moe_routed(x, lp, _cfg(experts, k))
    b = _moe_mlp(x, lp, _cfg(experts, k))
    assert float(jnp.abs(a - b).max()) < 1e-5


@pytest.mark.parametrize("bad", [float("nan"), float("inf")],
                         ids=["nan", "inf"])
@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_a_row_that_is_not_finite_stays_alone(kernel, bad, monkeypatch):
    """A slot that is not decoding may hold NaN or inf (nothing reads its
    output); the 0/1 products that fill the tiles and bring the results
    back hand none of it to the other rows, and the row itself comes back
    not finite, as it would from a gather: nothing is masked to 0."""
    monkeypatch.setenv(
        "LOCALAI_FORCE_PALLAS" if kernel else "LOCALAI_NO_PALLAS", "1")
    lp, x = _layer(8, True), _tokens(32)
    got = _moe_routed(x.at[0, 5, 3].set(bad), lp, _cfg(8, 2))
    want = _moe_mlp(x, lp, _cfg(8, 2))
    others = jnp.arange(32) != 5
    assert float(jnp.abs(got - want)[0, others].max()) < 1e-5
    assert not bool(jnp.isfinite(got[0, 5]).any())


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_an_expert_that_overflows_loses_its_own_tokens_only(kernel,
                                                            monkeypatch):
    """Finite tokens, one expert whose down product overflows: the tokens
    that chose it come back not finite, the others as the dense form has
    them."""
    monkeypatch.setenv(
        "LOCALAI_FORCE_PALLAS" if kernel else "LOCALAI_NO_PALLAS", "1")
    lp, x = _layer(8, False), _tokens(32)
    want = _moe_mlp(x, lp, _cfg(8, 2))
    lp["moe_w2"] = lp["moe_w2"].at[3].set(jnp.inf)
    chosen = jax.lax.top_k(x[0] @ lp["moe_gate"], 2)[1]
    hit = (chosen == 3).any(-1)
    assert 0 < int(hit.sum()) < 32
    got = _moe_routed(x, lp, _cfg(8, 2))
    assert not bool(jnp.isfinite(got[0, hit]).all(-1).any())
    assert float(jnp.abs(got - want)[0, ~hit].max()) < 1e-5


class _Mesh:
    def __init__(self, **shape):
        self.shape = shape


@pytest.mark.parametrize("tokens,mesh,share,want", [
    (32, None, False, DENSE),                   # a decode step's 32 rows
    (1, None, False, DENSE),
    (64, None, False, ROUTED),                  # every prefill bucket
    (256, None, False, ROUTED),
    (512, None, False, ROUTED),
    (4 * 512, None, False, ROUTED),             # _admit_many's [4, 512]
    (512, _Mesh(data=1, model=2), False, DENSE),  # experts sharded
    (512, _Mesh(data=2, model=1), False, ROUTED),
    (32, None, True, ROUTED),                   # a share: routed only
    (32, _Mesh(data=1, model=2), True, ROUTED),
])
def test_the_rule_by_shape_mesh_and_share(tokens, mesh, share, want):
    cfg = _cfg(8, 2)
    if share:
        cfg = _cfg(8, 2, router_experts=32, first_expert=8,
                   layer_types=(FULL, llama.LINEAR) * 2, linear_heads=2,
                   linear_head_dim=16, linear_gate_rank=16)
    assert expert_form(cfg, tokens, mesh) == want
    if tokens == 512 and mesh is None:
        assert expert_form(cfg, tokens, mesh, in_stack=False) == (
            ROUTED if share else DENSE)


@pytest.mark.parametrize("tokens,in_stack", [
    (32, False), (64, False), (512, False), (64, True)])
def test_mlp_takes_the_form_the_rule_names(tokens, in_stack):
    """Experts handed in their stack (_scan_layers does it where the rule
    says ROUTED: a forward over a cache, by the call's shape): routed; a
    layer's own arrays (a decode step, no cache, a pipeline stage): the
    dense form at any shape."""
    cfg, lp = _cfg(8, 2), _layer(8, False)
    if in_stack:
        lp = {k: llama._InStack(v[None], 0) if k.startswith("moe_w") else v
              for k, v in lp.items()}
    values = _shapes(jax.make_jaxpr(
        lambda x: llama._mlp(x, lp, cfg))(_tokens(tokens)))[0]
    dense = (1, tokens, 8, WIDTH) in values
    assert dense == (expert_form(cfg, tokens, None, in_stack) == DENSE)
    assert dense == (not in_stack)


MODELS = {
    # one kind of layer (Mixtral): the scan's xs; window and full layers
    # (Mellum2): a period of kinds, weights sliced where they are used
    "one-kind": dict(experts=8, k=2),
    "window-full": dict(experts=16, k=4, sliding_window=16,
                        layer_types=(WINDOW, WINDOW, WINDOW, FULL)),
}


def _model(name, int8=False):
    over = dict(MODELS[name])
    cfg = _cfg(over.pop("experts"), over.pop("k"), **over)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    if int8:
        from localai_tpu.ops.quant import quantize_params

        params = quantize_params(params)
    slots = 3
    kc, vc = init_kv_cache(cfg, slots, 128, jnp.float32, prefill_chunk=64)
    return cfg, params, kc, vc, rope_tables(cfg, 128)


def _extend(cfg, cos, sin, tokens=64):
    return lambda p, kc, vc: extend(
        p, cfg, jnp.zeros((1, tokens), jnp.int32), jnp.array([0]), cos, sin,
        kc, vc, slot_map=jnp.array([1]), with_logits=False, full_window=True)


@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
@pytest.mark.parametrize("name", list(MODELS))
def test_extend_leaves_the_experts_in_their_stack(name, int8):
    """A prompt chunk's program holds no [tokens, E, width] value (the dense
    dispatch), no scan operand the size of an expert stack, and no value the
    size of one layer's experts (a slice or a copy of them)."""
    cfg, params, kc, vc, (cos, sin) = _model(name, int8)
    values, operands = _shapes(jax.make_jaxpr(_extend(cfg, cos, sin))(
        params, kc, vc))
    e, tokens = cfg.num_experts, 64
    assert (tokens, e, WIDTH) not in values
    assert (1, tokens, e, WIDTH) not in values
    for inner, outer in ((H, WIDTH), (WIDTH, H)):
        assert (e, inner, outer) not in values
        for shape in operands:
            assert shape[-3:] != (e, inner, outer), shape
    # the detector fires on the dense form of the same program
    orig = llama.expert_form
    llama.expert_form = lambda *a, **k: DENSE
    try:
        values, operands = _shapes(jax.make_jaxpr(_extend(cfg, cos, sin))(
            params, kc, vc))
    finally:
        llama.expert_form = orig
    assert (1, tokens, e, WIDTH) in values
    assert any(s[-3:] == (e, H, WIDTH) for s in operands | values)


@pytest.mark.parametrize("name", list(MODELS))
def test_a_decode_step_is_the_dense_program_unchanged(name, monkeypatch):
    """32 rows: the jaxpr is the one the dense form alone gives."""
    cfg, params, _, _, (cos, sin) = _model(name)
    kc, vc = init_kv_cache(cfg, 32, 128, jnp.float32, prefill_chunk=64)

    def step(p, kc, vc):
        return decode_step(p, cfg, jnp.zeros((32,), jnp.int32),
                           jnp.full((32,), 5), cos, sin, kc, vc)

    served = str(jax.make_jaxpr(step)(params, kc, vc))
    monkeypatch.setattr(llama, "expert_form", lambda *a, **k: DENSE)
    assert served == str(jax.make_jaxpr(step)(params, kc, vc))


@pytest.mark.parametrize("name", list(MODELS))
def test_a_prompt_chunk_computes_what_the_dense_form_computes(name,
                                                              monkeypatch):
    """The whole model over a 64-token chunk after a 64-token chunk: the
    caches and the logits of the routed program equal the dense one's."""
    cfg, params, kc, vc, (cos, sin) = _model(name)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 64, (1, 64)),
                      jnp.int32)

    def run():
        k1, v1 = _extend(cfg, cos, sin)(params, kc, vc)[1:]
        return extend(params, cfg, ids, jnp.array([64]), cos, sin, k1, v1,
                      slot_map=jnp.array([1]))

    got = run()
    monkeypatch.setattr(llama, "expert_form", lambda *a, **k: DENSE)
    want = run()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(a - b).max()) < 1e-4


@pytest.mark.parametrize("name", list(MODELS))
def test_a_forward_with_no_cache_is_the_dense_program_unchanged(
        name, monkeypatch):
    """forward_train over 64 tokens (no cache: not a served program, and
    what train.py differentiates): the jaxpr is the dense form's."""
    cfg, params, _, _, _ = _model(name)
    ids = jnp.zeros((1, 64), jnp.int32)
    run = lambda p: llama.forward_train(p, cfg, ids)  # noqa: E731
    served = str(jax.make_jaxpr(run)(params))
    monkeypatch.setattr(llama, "expert_form", lambda *a, **k: DENSE)
    assert served == str(jax.make_jaxpr(run)(params))


@pytest.mark.parametrize("name", list(MODELS))
def test_a_train_step_has_a_gradient_through_the_experts(name):
    """train.causal_lm_loss over 2 x 65 tokens of an MoE model: a finite
    gradient that reaches every expert matrix and the router."""
    from localai_tpu.train import causal_lm_loss

    cfg, params, _, _, _ = _model(name)
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 64, (2, 65)),
                      jnp.int32)
    loss, grads = jax.jit(jax.value_and_grad(causal_lm_loss),
                          static_argnums=1)(params, cfg, ids)
    assert bool(jnp.isfinite(loss))
    for key in ("moe_gate", "moe_w1", "moe_w2", "moe_w3"):
        g = grads["layers"][key]
        assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0


def test_the_engine_counts_expert_tokens_by_the_same_rule():
    """At 0 from the engine's start; a 70-token prompt through a 64-token
    bucket is two routed calls (64 + 6 tokens x layers), its decode steps
    are dense."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams

    cfg = _cfg(8, 2, vocab_size=128)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(64,),
        prefill_chunk=64))
    m = eng.metrics
    assert (m["expert_tokens__routed"], m["expert_tokens__dense"]) == (0, 0)
    eng.start()
    try:
        _, q = eng.submit(GenRequest(
            prompt_ids=list(range(1, 71)), max_tokens=6, ignore_eos=True,
            params=SamplingParams(temperature=0.0, seed=1)))
        while not q.get(timeout=300).finished:
            pass
    finally:
        eng.stop()
    assert m["expert_tokens__routed"] == 70 * cfg.num_layers
    # two routed calls' expert layers; on a CPU the XLA loop serves them,
    # whose buffer is the static worst case: none bounded
    assert m["expert_tile_calls__seen"] == 2 * cfg.num_layers
    assert m["expert_tile_calls__bounded"] == 0
    # the first token comes from the prompt's logits, the other five from
    # decode steps (one more may have been dispatched before the stop)
    assert m["expert_tokens__dense"] in (5 * cfg.num_layers,
                                         6 * cfg.num_layers)
    dense_cfg = _cfg(0, 2)
    eng = Engine(dense_cfg, init_params(dense_cfg, jax.random.PRNGKey(0),
                                        dtype=jnp.float32), None,
                 EngineConfig(max_slots=2, max_context=64,
                              prefill_buckets=(16,), prefill_chunk=16))
    eng._credit_experts(64, 64)
    assert eng.metrics["expert_tokens__routed"] == 0


@pytest.mark.parametrize("env,bounded", [
    (None, 0), ("LOCALAI_NO_PALLAS", 0), ("LOCALAI_FORCE_PALLAS", 1)],
    ids=["a CPU", "XLA asked for", "the kernel (the interpreter)"])
def test_the_engine_counts_the_calls_whose_grid_ends_at_the_tiles_in_use(
        env, bounded, monkeypatch):
    """`expert_tile_calls__seen`: the expert layers of each call that takes
    the routed form; `__bounded`: the same where the grouped product kernel
    serves them (_grouped_experts' own rule). A decode step's rows (the
    dense form) credit neither."""
    from localai_tpu.engine import Engine, EngineConfig

    if env:
        monkeypatch.setenv(env, "1")
    cfg = _cfg(8, 2, vocab_size=128)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(0),
                                  dtype=jnp.float32), None,
                 EngineConfig(max_slots=2, max_context=128,
                              prefill_buckets=(64,), prefill_chunk=64))
    m = eng.metrics
    assert (m["expert_tile_calls__seen"],
            m["expert_tile_calls__bounded"]) == (0, 0)
    eng._credit_experts(64, 50)
    eng._credit_experts(2, 2)
    eng._credit_experts(128, 128)
    assert m["expert_tile_calls__seen"] == 2 * cfg.num_layers
    assert m["expert_tile_calls__bounded"] == 2 * cfg.num_layers * bounded


@pytest.mark.parametrize("model", ["mellum2", "solar", "trinity",
                                   "openpangu", "nemotron"])
def test_the_layer_bench_rehearses(tmp_path, model):
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "moe_layer_bench.py")
    spec = importlib.util.spec_from_file_location("moe_layer_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = tmp_path / "bench.json"
    assert bench.main(["--cpu-rehearsal", "--models", model,
                       "--out", str(out)]) == 0
    rows = json.loads(out.read_text())["rows"]
    assert {r["form"] for r in rows} >= {ROUTED, DENSE}
    assert all(r["ms"] is None for r in rows)       # a CPU run times nothing
    alone = [r for r in rows if r["form"].startswith("the products alone")]
    assert len(alone) == 2                          # one a call shape
    for r in alone:
        # the grid ends at the tiles in use, and the kernel is its twin
        assert r["grid"][0] == "used"
        assert r["static_tiles"] > r["tiles_in_use"]
        assert set(r["grid_steps"].values()) == {max(r["tiles_in_use"], 1)}
        assert max(r["differs_by"].values()) < 0.02
