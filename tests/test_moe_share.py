"""The expert layer that holds a share of the routed experts
(models/llama._moe_routed): the eight shares add up to the whole layer of
the uncut reference, the grouped form equals the masked form on a share,
and the programs of a share-holding model carry neither the dense dispatch
nor a state-sized scan operand."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    FULL, LINEAR, LlamaConfig, _moe_routed, extend, init_kv_cache,
    init_params, rope_tables,
)
from localai_tpu.ops.quant import quantize
from localai_tpu.testing import reference_linear as ref

H, R, HELD, K, WIDTH = 32, 32, 4, 4, 16


def _cfg(first=0, held=HELD, **over):
    return LlamaConfig(**{**dict(
        vocab_size=64, hidden_size=H, intermediate_size=64, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=8, max_position=512,
        num_experts=held, experts_per_tok=K, moe_intermediate_size=WIDTH,
        router_experts=R, first_expert=first, shared_expert_width=WIDTH,
        layer_types=(FULL, LINEAR, LINEAR, LINEAR) * 2, linear_heads=2,
        linear_head_dim=16, linear_gate_rank=16, linear_neg_eigval=True,
        use_rope=False, attn_gate=True, dtype="float32"), **over})


@pytest.fixture(scope="module")
def whole():
    """All 32 experts' weights and a router, one layer."""
    rng = np.random.default_rng(0)
    w = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) * s[-2] ** -0.5, jnp.float32)
    return {"moe_gate": w(H, R), "moe_w1": w(R, H, WIDTH),
            "moe_w3": w(R, H, WIDTH), "moe_w2": w(R, WIDTH, H),
            "ws_gate": w(H, WIDTH), "ws_up": w(H, WIDTH),
            "ws_down": w(WIDTH, H)}


def _share(lp, first, held=HELD, shared=True):
    out = {k: (v[first:first + held] if k.startswith("moe_w") else v)
           for k, v in lp.items()}
    return out if shared else {k: v for k, v in out.items()
                               if not k.startswith("ws_")}


def _x(n=24, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, n // 2, H)), jnp.float32)


def test_the_eight_shares_add_up_to_the_whole_layer(whole):
    """Routed parts of all eight shares + the shared expert counted once =
    the uncut reference's expert layer (router 32 wide, top-4, all held)."""
    x = _x()
    rcfg = ref.RefConfig(
        vocab_size=64, hidden_size=H, num_layers=1, num_heads=4,
        num_kv_heads=2, head_dim=8, rms_eps=1e-5, layer_types=(FULL,),
        linear_heads=2, linear_head_dim=16, num_experts=R, experts_per_tok=K)
    names = {"moe_gate": "router", "moe_w1": "w1", "moe_w2": "w2",
             "moe_w3": "w3"}
    want = ref.experts(x.reshape(-1, H),
                       {names.get(k, k): v for k, v in whole.items()}, rcfg)
    total = jnp.zeros_like(want)
    for n in range(R // HELD):
        part = _moe_routed(x, _share(whole, n * HELD, shared=(n == 0)),
                           _cfg(first=n * HELD))
        total = total + part.reshape(-1, H)
    assert float(jnp.abs(total - want).max()) < 1e-5
    # one share alone is far from the whole: nothing stands in for the rest
    one = _moe_routed(x, _share(whole, 0), _cfg()).reshape(-1, H)
    assert float(jnp.abs(one - want).max()) > 0.05


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "masked"])
def test_the_eight_shares_of_a_sigmoid_router_add_up_too(whole, grouped):
    """The same sum under Trinity's router (PR 35): sigmoid scores, a
    per-expert bias in the choice only, route_scale, against the afmoe
    reference's uncut layer. The bias is large enough to move the choice of
    most tokens; weighing by it, or leaving it out, is far from the sum."""
    from localai_tpu.testing import reference_afmoe as afmoe

    x = _x(seed=4)
    bias = jnp.asarray(np.random.default_rng(5).standard_normal(R) * 0.1,
                       jnp.float32)
    rcfg = afmoe.RefConfig(
        vocab_size=64, hidden_size=H, num_layers=1, num_heads=4,
        num_kv_heads=2, head_dim=8, rms_eps=1e-5, layer_types=(FULL,),
        sliding_window=8, rope_theta=1e4, num_dense_layers=0, num_experts=R,
        experts_per_tok=K, route_scale=2.448)
    names = {"moe_gate": "router", "moe_w1": "w1", "moe_w2": "w2",
             "moe_w3": "w3"}
    rp = {**{names.get(k, k): v for k, v in whole.items()}, "bias": bias}
    want = afmoe.experts(x.reshape(-1, H), rp, rcfg)
    over = dict(router_sigmoid=True, router_bias=True, routed_scale=2.448)
    total = jnp.zeros_like(want)
    for n in range(R // HELD):
        lp = dict(_share(whole, n * HELD, shared=(n == 0)), moe_bias=bias)
        total = total + _moe_routed(x, lp, _cfg(first=n * HELD, **over),
                                    grouped=grouped).reshape(-1, H)
    assert float(jnp.abs(total - want).max()) < 1e-5
    for fault in (dict(bias_in_weights=True), dict(bias_in_choice=False),
                  dict(scoring="softmax"), dict(route_scale=1.0)):
        other = afmoe.experts(x.reshape(-1, H), rp,
                              dataclasses.replace(rcfg, **fault))
        assert float(jnp.abs(total - other).max()) > 0.02, fault


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "masked"])
def test_sixteen_shares_of_a_sigmoid_router_without_bias_add_up(whole,
                                                                grouped):
    """The sum under openPangu-Ultra-MoE's router (PR 40): sigmoid scores,
    NO selection bias, routed_scaling_factor 2.5, two experts a share, the
    shared expert counted once, against the pangu reference's uncut layer."""
    from localai_tpu.testing import reference_pangu as pangu

    x = _x(seed=6)
    rcfg = pangu.RefConfig(
        vocab_size=64, hidden_size=H, num_layers=1, num_heads=4,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, rms_eps=1e-5, rope_theta=1e4, num_dense_layers=0,
        num_experts=R, experts_per_tok=K, route_scale=2.5)
    names = {"moe_gate": "router", "moe_w1": "w1", "moe_w2": "w2",
             "moe_w3": "w3"}
    rp = {names.get(k, k): v for k, v in whole.items()}
    want = pangu.experts(x.reshape(-1, H), rp, rcfg)
    over = dict(router_sigmoid=True, routed_scale=2.5)
    total = jnp.zeros_like(want)
    for n in range(16):
        lp = _share(whole, 2 * n, held=2, shared=(n == 0))
        total = total + _moe_routed(
            x, lp, _cfg(first=2 * n, held=2, **over),
            grouped=grouped).reshape(-1, H)
    assert float(jnp.abs(total - want).max()) < 1e-5
    for fault in (dict(scoring="softmax"), dict(route_scale=1.0)):
        other = pangu.experts(x.reshape(-1, H), rp,
                              dataclasses.replace(rcfg, **fault))
        assert float(jnp.abs(total - other).max()) > 0.02, fault
    # the shared expert counted sixteen times is far from it
    twice = total + 15 * (_moe_routed(x, _share(whole, 0, held=2), _cfg(
        held=2, **over)) - _moe_routed(x, _share(
            whole, 0, held=2, shared=False), _cfg(
                held=2, shared_expert_width=0, **over))).reshape(-1, H)
    assert float(jnp.abs(twice - want).max()) > 0.05


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "masked"])
def test_four_shares_of_latent_relu2_experts_add_up(grouped):
    """The sum under Nemotron-3-Super's expert layer (PR 42): sigmoid scores
    with a selection bias, top-22 of a router 32 wide, x 5, relu^2 experts of
    two matrices in a latent of 24, a relu^2 shared expert over the hidden
    state; four shares of eight experts, each through the latent pair (the
    way out is linear, so the shares' outputs add), the shared expert
    counted once, against the nemotron_h reference's uncut layer."""
    from localai_tpu.testing import reference_nemotron_h as nemo

    lat, k, wide = 24, 22, 40
    rng = np.random.default_rng(8)
    w = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) * s[-2] ** -0.5, jnp.float32)
    whole = {"moe_gate": w(H, R), "moe_w1": w(R, lat, WIDTH),
             "moe_w2": w(R, WIDTH, lat), "w_lat_in": w(H, lat),
             "w_lat_out": w(lat, H), "ws_up": w(H, wide),
             "ws_down": w(wide, H),
             "moe_bias": jnp.asarray(0.05 * rng.standard_normal(R),
                                     jnp.float32)}
    x = _x(seed=9)
    rcfg = nemo.RefConfig(
        vocab_size=64, hidden_size=H, pattern="E", num_heads=4,
        num_kv_heads=2, head_dim=8, ssm_heads=2, ssm_head_dim=8,
        ssm_groups=1, ssm_state=8, rms_eps=1e-5, num_experts=R,
        experts_per_tok=k, route_scale=5.0)
    names = {"moe_gate": "router", "moe_bias": "router_bias",
             "moe_w1": "w1", "moe_w2": "w2"}
    rp = {names.get(n, n): v for n, v in whole.items()}
    want = nemo.experts(x.reshape(-1, H), rp, rcfg)
    over = dict(router_sigmoid=True, router_bias=True, routed_scale=5.0,
                expert_act="relu2", moe_latent=lat, experts_per_tok=k)

    def share(n, shared):
        return _moe_routed(
            x, _share(whole, 8 * n, held=8, shared=shared),
            _cfg(first=8 * n, held=8, **over,
                 shared_expert_width=wide if shared else 0),
            grouped=grouped).reshape(-1, H)

    total = sum(share(n, n == 0) for n in range(4))
    assert float(jnp.abs(total - want).max()) < 2e-5
    for fault in (dict(squared=False), dict(route_scale=1.0),
                  dict(experts_per_tok=k - 1), dict(latent_in=False),
                  dict(bias_in_choice=False)):
        other = nemo.experts(x.reshape(-1, H), rp,
                             dataclasses.replace(rcfg, **fault))
        assert float(jnp.abs(total - other).max()) > 0.02, fault
    # the shared expert counted four times is far from it
    assert float(jnp.abs(sum(share(n, True) for n in range(4))
                         - want).max()) > 0.05


@pytest.mark.parametrize("first", [0, 12, 28])
@pytest.mark.parametrize("int8", [False, True])
def test_grouped_equals_masked_on_a_share(whole, first, int8):
    lp = _share(whole, first)
    if int8:
        lp = {k: quantize(v) if k.startswith(("moe_w", "ws_")) else v
              for k, v in lp.items()}
    x = _x(seed=first)
    a = _moe_routed(x, lp, _cfg(first), grouped=True)
    b = _moe_routed(x, lp, _cfg(first), grouped=False)
    assert float(jnp.abs(a - b).max()) < 1e-5


def test_the_whole_is_a_share_too(whole):
    """Written for any share: all 32 held, first 0, against the reference."""
    x = _x(seed=3)
    got = _moe_routed(x, _share(whole, 0, held=R), _cfg(0, held=R))
    again = _moe_routed(x, _share(whole, 0, held=R), _cfg(0, held=R),
                        grouped=False)
    assert float(jnp.abs(got - again).max()) < 1e-5


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among the router"):
        _cfg(first=30)


def _shapes(jaxpr):
    """(every value's shape; every scan's xs and ys shapes)."""
    values, operands = set(), set()

    def visit(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                if hasattr(v.aval, "shape"):
                    values.add(tuple(v.aval.shape))
            if eqn.primitive.name == "scan":
                nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
                for v in list(eqn.invars[nc + nk:]) + list(eqn.outvars[nk:]):
                    operands.add(tuple(v.aval.shape))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        visit(sub)

    visit(jaxpr.jaxpr)
    return values, operands


def test_extend_has_no_dense_dispatch_and_no_state_sized_scan_operand():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    slots, tokens = 3, 32
    kc, vc = init_kv_cache(cfg, slots, 128, jnp.float32, prefill_chunk=tokens)
    cos, sin = rope_tables(cfg, 128)
    jaxpr = jax.make_jaxpr(lambda p, kc, vc: extend(
        p, cfg, jnp.zeros((1, tokens), jnp.int32), jnp.array([32]), cos, sin,
        kc, vc, slot_map=jnp.array([1]), with_logits=False,
        full_window=True))(params, kc, vc)
    values, operands = _shapes(jaxpr)
    # the dense dispatch's [tokens, experts held, expert width] is not there
    assert (tokens, HELD, WIDTH) not in values
    assert (1, tokens, HELD, WIDTH) not in values
    # the routed one is, a tile of 8 of one expert's pairs at a time
    assert (8, WIDTH) in values
    # no scan takes or gives a state, or a layer's stack of states
    state = (2, 16, 16)
    per_place = cfg.num_layers // len(cfg.period)
    for shape in operands:
        assert shape[-3:] != state or len(shape) < 4, shape
        assert shape != (per_place, slots, *state)
    # the detector fires on the masked form
    dense = jax.make_jaxpr(lambda x, lp: _moe_routed(
        x, lp, cfg, grouped=False))(
        jnp.zeros((1, tokens, H)),
        jax.tree_util.tree_map(lambda a: a[0], params["layers"][FULL]))
    assert (tokens, HELD, WIDTH) in _shapes(dense)[0]
