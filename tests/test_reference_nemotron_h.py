"""A small Nemotron-H (model_type nemotron_h) as served against the plain
float32 reference (localai_tpu/testing/reference_nemotron_h.py): logits on
seeded random weights, never sampled tokens.

The shape: two periods `*EMEMEM` of one part a layer: 4 query heads over 2
KV heads of 16 without a position encoding; Mamba-2 layers of 8 heads x 8
channels, 2 groups, state 16, 4 taps with a bias, chunks of 8 tokens; expert
layers of relu^2 experts of width 24 in a latent of 32, a sigmoid router 16
wide with a selection bias, top-5, routed_scaling_factor 5, a relu^2 shared
expert of 40 over the hidden 48; held whole, or as the share [4, 12) of the
16. The norms' gains are drawn, not ones.

F32_TOL 2e-4 (float32 weights and cache; the two sides differ in the order
of their sums, and the chunked scan in the order of its products: measured
5e-6). A planted fault, given to the reference, must read above FAULT 0.01.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    EXPERTS, FULL, SSM, LlamaConfig, PeriodKV, decode_step, extend,
    forward_train, init_kv_cache, init_params, prefill, rope_tables,
)
from localai_tpu.testing import reference_nemotron_h as ref

F32_TOL, FAULT = 2e-4, 0.01
HF = dict(
    model_type="nemotron_h", vocab_size=96, hidden_size=48,
    intermediate_size=24, moe_intermediate_size=24, num_hidden_layers=14,
    hybrid_override_pattern="*EMEMEM*EMEMEM", num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8, expand=2,
    max_position_embeddings=1024, norm_eps=1e-5, layer_norm_epsilon=1e-5,
    n_routed_experts=16, num_experts_per_tok=5, n_shared_experts=1,
    moe_shared_expert_intermediate_size=40, moe_latent_size=32,
    norm_topk_prob=True, n_group=1, topk_group=1, routed_scaling_factor=5.0,
    mlp_hidden_act="relu2", mamba_hidden_act="silu", use_conv_bias=True,
    mamba_proj_bias=False, use_bias=False, mlp_bias=False,
    attention_bias=False, num_nextn_predict_layers=0, rope_theta=10000,
    partial_rotary_factor=1, time_step_min=0.001, time_step_max=0.1,
    tie_word_embeddings=False)
SHARE = dict(HF, n_routed_experts=8,
             localai_expert_share={"router_experts": 16, "first_expert": 4})
KINDS = (FULL, EXPERTS, SSM, EXPERTS, SSM, EXPERTS, SSM)


def _write(tmp_path, hf):
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def _ids(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, 96, size=n)


@pytest.fixture(scope="module", params=["whole", "share"])
def model(request, tmp_path_factory):
    from localai_tpu.engine.loader import load_config

    hf = HF if request.param == "whole" else SHARE
    cfg = load_config(_write(tmp_path_factory.mktemp("nemotron"), hf),
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(1))
    # gains that are not ones: a norm left out then moves every logit
    rng = np.random.default_rng(7)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1 + 0.3 * rng.standard_normal(a.shape)).astype(
            a.dtype) if path[-1].key.endswith("norm") else a, params)
    rcfg = ref.RefConfig.from_hf(hf)
    return cfg, params, rcfg, ref.from_served(params, rcfg.pattern)


def test_load_config_reads_the_architecture(model):
    cfg, params, rcfg, _ = model
    assert cfg.layer_types == KINDS * 2 and cfg.period == KINDS
    assert cfg.cache_kinds == (FULL, SSM, SSM, SSM)
    assert cfg.cache_places == (0, None, 1, None, 2, None, 3)
    assert cfg.split_layers and cfg.stacked_by_kind and cfg.drawn_by_leaf
    assert cfg.expert_layers == 6 and cfg.layers_of(SSM) == 6
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk) == (8, 8, 2, 16, 4, 8)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4, 2, 16)
    assert not cfg.use_rope and not cfg.rotates(FULL)
    assert rope_tables(cfg, 32) == ({}, {})
    assert (cfg.expert_act, cfg.moe_latent, cfg.shared_expert_width,
            cfg.routed_scale, cfg.experts_per_tok) == ("relu2", 32, 40, 5.0, 5)
    assert cfg.router_sigmoid and cfg.router_bias
    assert (cfg.num_experts, cfg.router_experts or cfg.num_experts,
            cfg.first_expert) == (rcfg.num_experts, 16, rcfg.first_expert)
    layers = params["layers"]
    assert set(layers) == {FULL, SSM, EXPERTS}
    assert set(layers[FULL]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(layers[SSM]) == {"attn_norm", "w_in", "conv", "conv_bias",
                                "dt_bias", "A_log", "D", "ssm_norm", "w_out"}
    assert set(layers[EXPERTS]) == {
        "mlp_norm", "moe_gate", "moe_bias", "moe_w1", "moe_w2", "w_lat_in",
        "w_lat_out", "ws_up", "ws_down"}
    assert layers[SSM]["w_in"].shape == (6, 48, 64 + 64 + 2 * 2 * 16 + 8)
    assert layers[SSM]["conv"].shape == (6, 128, 4)
    assert layers[EXPERTS]["moe_w1"].shape == (6, rcfg.num_experts, 32, 24)
    assert layers[EXPERTS]["moe_gate"].shape == (6, 48, 16)
    assert layers[EXPERTS]["ws_up"].shape == (6, 48, 40)
    assert layers[FULL]["wk"].shape == (2, 48, 32)


@pytest.mark.parametrize("change,named", [
    (dict(hybrid_override_pattern="*EMEM-M*EMEMEM"), "dense MLP layer"),
    (dict(hybrid_override_pattern="*EMEMEM"), "hybrid_override_pattern"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
    (dict(use_bias=True), "use_bias"),
    (dict(n_group=2), "n_group"), (dict(topk_group=4), "topk_group"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(use_conv_bias=False), "use_conv_bias"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
])
def test_load_config_refuses_what_the_layers_cannot_honour(tmp_path, change,
                                                           named):
    from localai_tpu.engine.loader import load_config

    with pytest.raises(ValueError, match=named):
        load_config(_write(tmp_path, dict(HF, **change)))


def test_only_synthetic_weights_can_be_loaded(tmp_path, monkeypatch):
    from localai_tpu.engine.loader import load_config, load_params

    monkeypatch.delenv("LOCALAI_ALLOW_SYNTHETIC", raising=False)
    d = _write(tmp_path, HF)
    with pytest.raises(ValueError, match="no checkpoint"):
        load_params(d, load_config(d))


def test_synthetic_int8_weights_and_their_special_draws(tmp_path,
                                                        monkeypatch):
    """The loader's synthetic int8 draw: matrices {q, s}, the router, its
    bias and the state-space layer's small leaves float32 or the compute
    type, A in 1..16, softplus(dt_bias) in 1e-3..1e-1, D not all ones."""
    from localai_tpu.engine.loader import load_config, load_params

    monkeypatch.setenv("LOCALAI_ALLOW_SYNTHETIC", "1")
    d = _write(tmp_path, dict(SHARE, localai_synthetic=True))
    cfg = load_config(d, dtype="int8")
    layers = load_params(d, cfg, dtype="int8")["layers"]
    for kind, name in ((SSM, "w_in"), (SSM, "w_out"), (EXPERTS, "moe_w1"),
                       (EXPERTS, "w_lat_in"), (EXPERTS, "ws_down"),
                       (FULL, "wq")):
        assert layers[kind][name]["q"].dtype == jnp.int8
    assert "moe_w3" not in layers[EXPERTS] and "ws_gate" not in layers[EXPERTS]
    assert layers[EXPERTS]["moe_gate"].dtype == jnp.float32
    a = jnp.exp(layers[SSM]["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    dt = jax.nn.softplus(layers[SSM]["dt_bias"])
    assert 0.9e-3 < float(dt.min()) and float(dt.max()) < 0.11
    d_skip = np.asarray(layers[SSM]["D"])
    assert 0.5 <= d_skip.min() and d_skip.max() <= 1.5 and d_skip.std() > 0.1
    assert float(jnp.abs(layers[SSM]["conv_bias"].astype(jnp.float32)).max())


@pytest.mark.parametrize("option,named", [
    (dict(kv_pages=8), "state-space layers.*paged KV"),
    ("draft", "state-space layers.*speculative"), ("mesh", "mesh"),
    ("context_shift", "context_shift"),
    ("prompt_cache_path", "prompt_cache_path"),
])
def test_the_engine_refuses_what_rests_on_another_cache(model, option, named,
                                                        tmp_path):
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest

    cfg, params, _, _ = model
    ec = dict(max_slots=2, max_context=64, prefill_buckets=(16,),
              prefill_chunk=16)
    with pytest.raises(ValueError, match=named):
        if option == "draft":
            Engine(cfg, params, None, EngineConfig(**ec),
                   draft=(cfg, params))
        elif option == "mesh":
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                        ("data", "model"))
            Engine(cfg, params, None, EngineConfig(**ec, mesh=mesh))
        elif option == "context_shift":
            Engine(cfg, params, None, EngineConfig(**ec)).submit(GenRequest(
                prompt_ids=[1, 2, 3], max_tokens=2, context_shift=True))
        elif option == "prompt_cache_path":
            Engine(cfg, params, None, EngineConfig(**ec)).submit(GenRequest(
                prompt_ids=[1, 2, 3], max_tokens=2,
                prompt_cache_path=str(tmp_path / "p.npz")))
        else:
            Engine(cfg, params, None, EngineConfig(**ec, **option))


def test_the_config_takes_these_kinds_together_only():
    over = dict(ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
                num_experts=4)
    cfg = LlamaConfig(num_layers=4, layer_types=(SSM, EXPERTS) * 2, **over)
    assert cfg.cache_kinds == (SSM,) and cfg.cache_places == (0, None)
    with pytest.raises(ValueError, match="come together"):
        LlamaConfig(num_layers=2, layer_types=(SSM, FULL), **over)
    with pytest.raises(ValueError, match="come together"):
        LlamaConfig(num_layers=2, layer_types=(SSM, EXPERTS),
                    **dict(over, num_experts=0))
    with pytest.raises(ValueError, match="come together"):
        LlamaConfig(num_layers=2, layer_types=("linear", EXPERTS), **over)
    with pytest.raises(ValueError, match="ssm_heads"):
        LlamaConfig(num_layers=2, layer_types=(SSM, EXPERTS),
                    **dict(over, ssm_groups=3))
    with pytest.raises(ValueError, match="expert_act"):
        LlamaConfig(num_layers=2, layer_types=(SSM, EXPERTS),
                    expert_act="gelu", **over)


def test_full_forward_matches_reference(model):
    cfg, params, rcfg, rp = model
    ids = _ids(90)
    want = np.asarray(ref.logits(rp, rcfg, ids))
    got = np.asarray(forward_train(params, cfg, jnp.asarray(ids[None])))[0]
    assert np.abs(got - want).max() < F32_TOL
    blocks = np.asarray(ref.logits(rp, rcfg, ids, block=16))
    assert np.abs(want - blocks).max() < 1e-5


FAULTS = {
    "bfloat16_state": dict(state_dtype="bfloat16"),
    "relu_for_relu2": dict(squared=False),
    "d_term_dropped": dict(skip_d=False),
    "conv_bias_dropped": dict(conv_bias=False),
    "gate_after_the_norm": dict(gate_before_norm=False),
    "top_k_less_one": dict(experts_per_tok=4),
    "routed_scaling_factor_off": dict(route_scale=1.0),
    "latent_projection_missing": dict(latent_in=False),
    "bias_left_out_of_the_choice": dict(bias_in_choice=False),
    "dt_bias_dropped": dict(dt_bias=False),
    "share_offset": dict(first_expert=2),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_is_seen(model, fault):
    """The served logits are far from the reference given any one fault:
    each mechanism is in the served program, and the comparison sees it."""
    cfg, params, rcfg, rp = model
    if fault == "share_offset" and rcfg.num_experts == 16:
        pytest.skip("the whole router has no offset to get wrong")
    ids = _ids(40, seed=5)
    got = np.asarray(forward_train(params, cfg, jnp.asarray(ids[None])))[0]
    bad = dataclasses.replace(rcfg, **FAULTS[fault])
    want = np.asarray(ref.logits(rp, bad, ids))
    # (a state rounded to bfloat16 moves this small model's logits by 1e-3,
    # two hundred times what the sound paths differ by: a floor of its own)
    floor = 2.5 * F32_TOL if fault == "bfloat16_state" else FAULT
    assert np.abs(got - want).max() > floor


def test_chunked_prefill_and_decode_through_the_state_cache(model):
    """A prompt of 61 tokens through 24-token chunks (three chunks of the
    chunked scan at 8, the last one padded), then 20 decode steps beside a
    5-token row: the reference's full forward at every position."""
    from test_reference_lm import _serve, _worst

    cfg, params, rcfg, rp = model
    prompt, short, steps = 61, 5, 20
    ids = _ids(prompt + steps + 1, seed=3)
    want = np.asarray(ref.logits(rp, rcfg, ids))
    out, kc, vc = _serve(cfg, params, ids, prompt=prompt, short=short,
                         steps=steps, chunk=24, context=128)
    assert _worst(out, want, prompt, short) < F32_TOL
    # a place a MIXER of the period, none for an expert layer: K and V of
    # the attention layer, then three states (float32) and their tails
    assert isinstance(kc, PeriodKV) and len(kc.slots) == 4
    assert [s.shape for s in kc.slots] == [(2, 2, 2, 128, 16)] + [
        (2, 2, 8, 8, 16)] * 3
    assert [s.shape for s in vc.slots] == [(2, 2, 2, 128, 16)] + [
        (2, 2, 3, 128)] * 3
    assert all(s.dtype == jnp.float32 for s in kc.slots[1:])


def _step(cfg, params, cos, sin, kc, vc, tokens, lengths, active):
    logits, kc, vc = decode_step(
        params, cfg, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(lengths, jnp.int32), cos, sin, kc, vc,
        active=jnp.asarray(active))
    return np.asarray(logits), kc, vc


def _chunk(cfg, params, cos, sin, kc, vc, ids, start, slot, size=16):
    buf = np.zeros((1, size), np.int32)
    buf[0, :len(ids)] = ids
    logits, kc, vc = extend(
        params, cfg, jnp.asarray(buf), jnp.array([start]), cos, sin, kc, vc,
        slot_map=jnp.array([slot]), last_pos=jnp.array([len(ids) - 1]))
    return np.asarray(logits)[0], kc, vc


def test_a_late_row_an_inactive_row_and_a_reused_slot(model):
    """Slot 1 decodes while slot 0's prompt arrives in chunks (an inactive
    row of the decode step: its state and tail stay as the chunks left
    them), slot 0 joins late, and slot 1 is then given to another tenant
    whose first chunk starts at 0: the state is reset on the device,
    whatever the last tenant left. Every logit is the reference's."""
    cfg, params, rcfg, rp = model
    a, b, c = _ids(60, seed=21), _ids(50, seed=22), _ids(40, seed=23)
    want = {n: np.asarray(ref.logits(rp, rcfg, ids))
            for n, ids in (("a", a), ("b", b), ("c", c))}
    kc, vc = init_kv_cache(cfg, 2, 64, prefill_chunk=16)
    cos, sin = rope_tables(cfg, 64)
    worst = 0.0

    def see(got, name, pos):
        nonlocal worst
        worst = max(worst, float(np.abs(got - want[name][pos]).max()))

    # tenant b fills slot 1 (30 tokens, the last chunk padded)
    got, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, b[:16], 0, 1)
    got, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, b[16:30], 16, 1)
    see(got, "b", 29)
    # tenant a's first chunk into slot 0, then b decodes beside it
    got, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, a[:16], 0, 0)
    held = [np.asarray(s[:, 0]) for s in kc.slots[1:] + vc.slots[1:]]
    got, kc, vc = _step(cfg, params, cos, sin, kc, vc, [7, b[30]], [16, 30],
                        [False, True])
    see(got[1], "b", 30)
    for was, now in zip(held, kc.slots[1:] + vc.slots[1:]):
        assert np.array_equal(was, np.asarray(now[:, 0]))
    got, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, a[16:25], 16, 0)
    see(got, "a", 24)
    # a joins: both rows decode
    for i in range(6):
        got, kc, vc = _step(cfg, params, cos, sin, kc, vc,
                            [a[25 + i], b[31 + i]], [25 + i, 31 + i],
                            [True, True])
        see(got[0], "a", 25 + i)
        see(got[1], "b", 31 + i)
    # slot 1 goes to tenant c; a decodes on alone meanwhile
    got, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, c[:16], 0, 1)
    got, kc, vc = _step(cfg, params, cos, sin, kc, vc, [a[31], 3], [31, 16],
                        [True, False])
    see(got[0], "a", 31)
    got, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, c[16:21], 16, 1)
    see(got, "c", 20)
    got, kc, vc = _step(cfg, params, cos, sin, kc, vc, [a[32], c[21]],
                        [32, 21], [True, True])
    see(got[0], "a", 32)
    see(got[1], "c", 21)
    assert worst < F32_TOL


def test_single_shot_prefill_of_padded_rows(model):
    """Two prompts of different lengths in one padded prefill call: each
    row's state and tail are those after its own last token."""
    cfg, params, rcfg, rp = model
    a, b = _ids(20, seed=31), _ids(9, seed=32)
    kc, vc = init_kv_cache(cfg, 2, 64, prefill_chunk=16)
    cos, sin = rope_tables(cfg, 64)
    buf = np.zeros((2, 24), np.int32)
    buf[0, :19], buf[1, :8] = a[:19], b[:8]
    logits, kc, vc = prefill(params, cfg, jnp.asarray(buf),
                             jnp.array([19, 8]), cos, sin, kc, vc,
                             jnp.array([1, 0]))
    wa = np.asarray(ref.logits(rp, rcfg, a))
    wb = np.asarray(ref.logits(rp, rcfg, b))
    got = np.asarray(logits)
    assert np.abs(got[0] - wa[18]).max() < F32_TOL
    assert np.abs(got[1] - wb[7]).max() < F32_TOL
    got, kc, vc = _step(cfg, params, cos, sin, kc, vc, [b[8], a[19]],
                        [8, 19], [True, True])
    assert np.abs(got[1] - wa[19]).max() < F32_TOL
    assert np.abs(got[0] - wb[8]).max() < F32_TOL


@pytest.mark.parametrize("s,chunk", [(37, 8), (64, 16), (5, 128), (130, 128)])
def test_the_chunked_form_is_the_recurrence(s, chunk):
    """ops/ssd.ssd_chunk against the token-by-token scan, from a state that
    is not zero, with a ragged last chunk, and with padding past n_valid."""
    from localai_tpu.ops.ssd import ssd_chunk, ssd_recurrent

    k = jax.random.split(jax.random.PRNGKey(s), 7)
    h, p, n, g = 8, 8, 16, 2
    x = jax.random.normal(k[0], (2, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (2, s, h)) - 2)
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    bm, cm = (jax.random.normal(kk, (2, s, g, n)) for kk in k[3:5])
    s0 = jax.random.normal(k[5], (2, h, p, n))
    y, s1 = ssd_chunk(x, dt, a, bm, cm, s0, chunk=chunk)
    wy, ws = ssd_recurrent(x, dt, a, bm, cm, s0)
    assert float(jnp.abs(y - wy).max()) < 1e-4
    assert float(jnp.abs(s1 - ws).max()) < 1e-5
    cut = max(s // 2, 1)
    y, s1 = ssd_chunk(x, dt, a, bm, cm, s0, n_valid=jnp.array([cut, s]),
                      chunk=chunk)
    wy, ws = ssd_recurrent(x[:1, :cut], dt[:1, :cut], a, bm[:1, :cut],
                           cm[:1, :cut], s0[:1])
    assert float(jnp.abs(y[0, :cut] - wy[0]).max()) < 1e-4
    assert float(jnp.abs(s1[0] - ws[0]).max()) < 1e-5


@pytest.mark.parametrize("active", [
    (True, False, True, True), (False,) * 4, (False, True, False, False),
    (True,) * 4])
def test_the_kernel_is_its_xla_twin(active):
    """ssd_decode in the interpreter against ops/ssd.ssd_step: live rows
    updated in place in the layer's place of the stack, a row that is not
    decoding untouched and its output zeros, the other layers untouched."""
    from localai_tpu.ops.pallas.ssd import ssd_decode
    from localai_tpu.ops.ssd import ssd_step

    k = jax.random.split(jax.random.PRNGKey(0), 6)
    nb, h, p, n, g, layers = 4, 64, 8, 128, 4, 3
    x = jax.random.normal(k[0], (nb, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (nb, h)))
    a = -jnp.exp(jax.random.normal(k[2], (h,)))
    bm, cm = (jax.random.normal(kk, (nb, g, n)) for kk in k[3:5])
    state = jax.random.normal(k[5], (layers, nb, h, p, n))
    act = jnp.array(active)
    y, new = ssd_decode(x, dt, a, bm, cm, state, 1, act)
    wy, ws = ssd_step(x, dt, a, bm, cm, state[1])
    wy = jnp.where(act[:, None, None], wy, 0)
    assert float(jnp.abs(y - wy).max()) < 1e-4
    assert float(jnp.abs(new[1] - jnp.where(
        act[:, None, None, None], ws, state[1])).max()) < 1e-5
    assert bool(jnp.array_equal(new[0], state[0]))
    assert bool(jnp.array_equal(new[2], state[2]))
    rows = np.flatnonzero(~np.asarray(active))
    assert bool(jnp.array_equal(new[1][rows], state[1][rows]))


def test_the_kernel_refuses_shapes_it_cannot_tile():
    from localai_tpu.ops.pallas.ssd import ssd_decode

    z = jnp.zeros
    with pytest.raises(ValueError, match="do not tile"):
        ssd_decode(z((2, 8, 8)), z((2, 8)), z((8,)), z((2, 2, 16)),
                   z((2, 2, 16)), z((1, 2, 8, 8, 16)), 0,
                   jnp.ones((2,), bool))


def test_the_served_decode_step_takes_the_kernel(monkeypatch, tmp_path):
    """With the Pallas kernels forced on (the interpreter here), a decode
    step's logits are the XLA twin's (a state of 128 lanes: the kernel's
    tile)."""
    from localai_tpu.engine.loader import load_config

    cfg = load_config(_write(tmp_path, dict(HF, ssm_state_size=128)),
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(2))
    ids = _ids(30, seed=10)
    kc, vc = init_kv_cache(cfg, 2, 64, prefill_chunk=32)
    cos, sin = rope_tables(cfg, 64)
    _, kc, vc = _chunk(cfg, params, cos, sin, kc, vc, ids, 0, 1, size=32)
    args = (jnp.asarray([3, 11]), jnp.array([9, 30]), cos, sin, kc, vc)
    active = jnp.array([False, True])
    twin, tk, tv = decode_step(params, cfg, *args, active=active)
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    assert "ssd_decode" in str(jax.make_jaxpr(
        lambda *a: decode_step(params, cfg, *a, active=active))(*args))
    kernel, kk, kvv = decode_step(params, cfg, *args, active=active)
    assert np.abs(np.asarray(kernel) - np.asarray(twin))[1].max() < 2e-5
    for one, other in zip(tk.slots[1:] + tv.slots[1:],
                          kk.slots[1:] + kvv.slots[1:]):
        assert np.abs(np.asarray(one) - np.asarray(other)).max() < 1e-5


def test_the_engines_programs_match_the_reference(model):
    """The engine's own compiled programs, driven as it drives them: a
    prompt through chunked prefill, a short one through a prefill bucket
    half way, single decode steps (one beside the long row's last chunks,
    with an inactive row in it) and the fused loop."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.ops.sampling import SamplingParams, sampler_row

    cfg, params, rcfg, rp = model
    chunk, B = 16, 3
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=B, max_context=256, prefill_buckets=(16,),
        prefill_chunk=chunk))
    m = eng.metrics
    assert (m["layers__ssm"], m["layers__full"]) == (6, 2)
    assert "layers__experts" not in m
    # a state of 8 x 8 x 16 float32 and a tail of 3 x 128 float32 a row
    assert m["kv_bytes__ssm"] == 6 * B * (8 * 8 * 16 + 3 * 128) * 4
    assert m["kv_bytes__full"] == 2 * B * 2 * 2 * 256 * 16 * 4
    rows = {0: list(_ids(90, seed=11)), 1: list(_ids(12, seed=12))}
    greedy = sampler_row(SamplingParams(temperature=0.0), cfg.vocab_size,
                         fallback_seed=1, include_bias=False)
    served = {0: {}, 1: {}}

    def note(row):
        served[row][len(rows[row]) - 1] = np.asarray(
            eng._last_logits[row], np.float32)

    def decode(active_rows):
        active = np.zeros((B,), bool)
        active[list(active_rows)] = True
        tokens, _ = eng._dev_decode(active).wait()
        for r in active_rows:
            rows[r].append(int(tokens[r]))
            note(r)

    long_ids = list(rows[0])
    for n, pos in enumerate(range(0, 90, chunk)):
        buf = np.zeros((1, chunk), np.int32)
        part = long_ids[pos:pos + chunk]
        buf[0, :len(part)] = part
        if pos + chunk >= 90:
            eng._dev_extend_final(buf, pos, len(part), 0, greedy, None)
            note(0)
        else:
            eng._dev_extend_mid(buf, pos, 0)
        if n == 2:
            ids = np.zeros((1, 16), np.int32)
            ids[0, :12] = rows[1]
            eng._dev_admit(ids, 12, 1, greedy, None)
            note(1)
        elif n > 2 and pos + chunk < 90:
            decode([1])
    assert m["chunk_ctx_tokens__capacity"] == 6 * 256
    for _ in range(4):
        decode([0, 1])
    active = np.array([True, True, False])
    remaining = np.array([8, 8, 0], np.int32)
    toks, _, n_out, _ = eng._dev_decode_loop(
        active, remaining, np.zeros((B,), bool)).wait()
    for r in (0, 1):
        assert int(n_out[r]) == 8
        rows[r].extend(int(t) for t in np.asarray(toks)[:8, r])
        note(r)
    for r in (0, 1):
        want = np.asarray(ref.logits(rp, rcfg, np.asarray(rows[r])))
        assert len(served[r]) >= 6
        for pos, got in served[r].items():
            assert np.abs(got - want[pos]).max() < F32_TOL, (r, pos)
        # greedy: every token picked is the reference's choice
        n = 90 if r == 0 else 12
        assert rows[r][n:] == list(want[n - 1:-1].argmax(-1))


def test_the_engine_counts_this_model_too(model):
    """Requests through the engine's own loop: the state bytes the
    state-space layers read and wrote in decode, the K and V bytes the
    attention layers attended over, the expert tokens (x the 6 expert
    layers, not the 14 layers); a second request with the same prompt
    through the same slot borrows no prefix and gives the same tokens."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams

    cfg, params, _, _ = model
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prefill_chunk=16))
    prompt = [int(t) for t in _ids(30, seed=13)]

    def run():
        _, q = eng.submit(GenRequest(
            prompt_ids=list(prompt), max_tokens=12, ignore_eos=True,
            params=SamplingParams(temperature=0.0, seed=1)))
        out = []
        while True:
            o = q.get(timeout=300)
            out.append(o.token_id)
            if o.finished:
                return out

    eng.start()
    try:
        first = run()
        m = dict(eng.metrics)
        assert run() == first
        after = dict(eng.metrics)
    finally:
        eng.stop()
    assert len(first) == 12
    state = (8 * 8 * 16 + 3 * 128) * 4
    assert m["decode_cache_bytes__ssm"] in (
        n * 2 * state * 6 for n in (11, 12))
    # 11 or 12 steps from 30 tokens on: 31 + 32 + ... tokens of K and V of
    # 2 heads x 16 float32 each, in 2 layers
    assert m["decode_cache_bytes__full"] in (
        sum(range(31, 31 + n)) * 2 * 2 * 16 * 4 * 2 for n in (11, 12))
    assert "decode_cache_bytes__linear" not in m
    assert "decode_ctx_tokens__full" not in m
    assert m["chunk_ctx_tokens__attended"] > 0
    assert m["expert_tokens__routed"] in ((30 + 11) * 6, (30 + 12) * 6)
    assert m["expert_tokens__dense"] == 0
    assert m["decode_row_steps__live"] == m["tokens_generated"] == 12
    assert after["prompt_cache_hits"] == after["prompt_tokens_reused"] == 0
    assert after["prompt_tokens_processed"] == 60


def test_the_parts_are_traced_by_name(model):
    """A decode step and a prompt chunk name a state-space layer's parts and
    the expert layer's latent pair in the ops' metadata, and
    tools/trace_gaps.py reads them."""
    import re

    from tools.trace_gaps import scope_of

    cfg, params, _, _ = model
    kc, vc = init_kv_cache(cfg, 1, 64, prefill_chunk=16)
    cos, sin = rope_tables(cfg, 64)

    def scopes(fn):
        text = jax.jit(fn).lower(kc, vc).as_text(debug_info=True)
        return {scope_of("", {"tf_op": m}) for m in re.findall(
            r'loc\("((?:attention|experts)/[^"]*)"', text)}

    step = scopes(lambda kc, vc: decode_step(
        params, cfg, jnp.array([3]), jnp.array([9]), cos, sin, kc, vc))
    chunk = scopes(lambda kc, vc: extend(
        params, cfg, jnp.zeros((1, 16), jnp.int32), jnp.array([0]), cos, sin,
        kc, vc, slot_map=jnp.array([0]), with_logits=False))
    ssm = {"attention/ssm/" + p for p in ("in_proj", "gated_norm",
                                          "out_proj")}
    both = ssm | {"attention/ssm", "attention/full", "experts/router",
                  "experts/shared", "experts/dispatch",
                  "experts/expert_einsums", "experts/latent_in",
                  "experts/latent_out"}
    assert step >= both | {"attention/ssm/ssd_decode"}
    assert chunk >= both | {"attention/ssm/ssd_chunk"}
    assert "attention/ssm/ssd_chunk" not in step
    assert scope_of("", {"tf_op": "jit(_loop)/while/body/attention/ssm/"
                                  "jit(ssd_decode)/x"}) \
        == "attention/ssm/ssd_decode"
    assert scope_of("", {"tf_op": "jit(ssd_decode)/x"}) \
        == "attention/ssm/ssd_decode"


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/nemotron_h.py is the program's reference from its
    first import on, and imports nothing of the program."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def code(*path):
        with open(os.path.join(root, *path)) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    mine = code("benchmark", "reference", "nemotron_h.py")
    assert mine == code("localai_tpu", "testing", "reference_nemotron_h.py")
    assert "localai_tpu" not in mine
