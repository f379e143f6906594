"""A small Trinity (model_type afmoe) as served against the plain float32
reference (localai_tpu/testing/reference_afmoe.py): logits on seeded random
weights.

The shape: two leading dense layers (a window and a full one, so both kinds
of leading cache place are there), then two periods of three window layers
and a full one; 6 query heads over 1 KV head (a group of 6); q/k RMSNorm,
RoPE on the window layers only, the output gate, sandwich norms, the
embedding's scale; a sigmoid router 16 wide with a selection bias, top-2,
route_scale, a shared expert; held whole, or as the share [4, 12) of the 16.
The norms' gains and the bias are drawn, not ones and small, so that each
mechanism moves the logits by far more than the tolerance.

F32_TOL 2e-4 (float32 weights and cache; the two sides differ in the order
of their sums: measured 2e-6 to 2e-5). A planted fault, given to the
reference, must read above FAULT 0.01 (the least measured 0.05).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    FULL, WINDOW, LlamaConfig, PeriodKV, forward_train, init_params,
)
from localai_tpu.testing import reference_afmoe as ref

F32_TOL, FAULT = 2e-4, 0.01
KINDS = (WINDOW, FULL) + (WINDOW, WINDOW, WINDOW, FULL) * 2
HF = dict(
    model_type="afmoe", vocab_size=96, hidden_size=48, intermediate_size=64,
    moe_intermediate_size=24, num_hidden_layers=10, num_attention_heads=6,
    num_key_value_heads=1, head_dim=16, max_position_embeddings=1024,
    rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None, sliding_window=8,
    layer_types=[{WINDOW: "sliding_attention", FULL: "full_attention"}[k]
                 for k in KINDS],
    num_dense_layers=2, num_experts=16, num_experts_per_tok=2,
    num_shared_experts=1, score_func="sigmoid", route_norm=True,
    route_scale=2.448, n_group=1, topk_group=1, num_expert_groups=1,
    num_limited_groups=1, mup_enabled=True, tie_word_embeddings=False)
SHARE = dict(HF, num_experts=8,
             localai_expert_share={"router_experts": 16, "first_expert": 4})


def _write(tmp_path, hf):
    import json

    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def _ids(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, 96, size=n)


@pytest.fixture(scope="module", params=["whole", "share"])
def model(request, tmp_path_factory):
    from localai_tpu.engine.loader import load_config

    hf = HF if request.param == "whole" else SHARE
    cfg = load_config(_write(tmp_path_factory.mktemp("afmoe"), hf),
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(1))
    # gains that are not ones and a bias that is not small: a norm, or the
    # bias, left out then moves every logit
    rng = np.random.default_rng(7)

    def drawn(path, a):
        name = path[-1].key
        if name.endswith("norm"):
            return a * (1 + 0.3 * rng.standard_normal(a.shape)).astype(a.dtype)
        return a * 5 if name == "moe_bias" else a

    params = jax.tree_util.tree_map_with_path(drawn, params)
    return cfg, params, ref.RefConfig.from_hf(hf)


def test_load_config_reads_the_architecture(model):
    cfg, params, rcfg = model
    assert cfg.period == (WINDOW, WINDOW, WINDOW, FULL)
    assert cfg.cache_kinds == cfg.period + (WINDOW, FULL)
    assert (cfg.leading_dense_layers, cfg.qk_norm, cfg.post_norms,
            cfg.attn_gate, cfg.router_sigmoid, cfg.router_bias) == (
                2, True, True, True, True, True)
    assert cfg.rotates(WINDOW) and not cfg.rotates(FULL)
    assert cfg.embed_scale == pytest.approx(48 ** 0.5)
    assert (cfg.routed_scale, cfg.shared_expert_width) == (2.448, 24)
    assert cfg.num_heads // cfg.num_kv_heads == 6
    assert (cfg.num_experts, cfg.router_experts or cfg.num_experts,
            cfg.first_expert) == (rcfg.num_experts, 16, rcfg.first_expert)
    assert set(params["leading"]) >= {"w_gate", "w_up", "w_down", "q_norm"}
    assert "moe_gate" not in params["leading"]
    assert params["layers"]["moe_bias"].shape == (8, 16)
    assert params["layers"]["moe_w1"].shape[:2] == (8, rcfg.num_experts)


@pytest.mark.parametrize("key,value,named", [
    ("n_group", 2, "n_group"), ("num_limited_groups", 4, "num_limited"),
    ("route_norm", False, "route_norm"), ("score_func", "tanh", "tanh"),
])
def test_load_config_refuses_what_the_layer_cannot_honour(tmp_path, key,
                                                          value, named):
    from localai_tpu.engine.loader import load_config

    with pytest.raises(ValueError, match=named):
        load_config(_write(tmp_path, dict(HF, **{key: value})))


def test_mlp_layer_types_may_say_which_layers_are_dense(tmp_path):
    """Dense entries before the first sparse one are leading dense layers;
    a dense layer after a sparse one is refused by name."""
    from localai_tpu.engine.loader import load_config

    hf = dict(HF, num_dense_layers=0,
              mlp_layer_types=["dense"] * 3 + ["sparse"] * 7)
    assert load_config(_write(tmp_path, hf)).leading_dense_layers == 3
    hf["mlp_layer_types"] = ["dense", "sparse", "dense"] + ["sparse"] * 7
    with pytest.raises(ValueError, match="mlp_layer_types"):
        load_config(_write(tmp_path, hf))


def test_leading_dense_layers_need_layer_types():
    with pytest.raises(ValueError, match="leading dense"):
        LlamaConfig(num_layers=4, num_experts=4, leading_dense_layers=1)


def test_full_forward_matches_reference(model):
    cfg, params, rcfg = model
    ids = _ids(90)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    got = np.asarray(forward_train(params, cfg, jnp.asarray(ids[None])))[0]
    assert np.abs(got - want).max() < F32_TOL
    blocks = np.asarray(ref.logits(ref.from_served(params), rcfg, ids,
                                   block=16))
    assert np.abs(want - blocks).max() < 1e-5


FAULTS = {
    "qk_norm_off": dict(qk_norm=False),
    "gate_off": dict(attn_gate=False),
    "full_layers_rotated": dict(rotating=(WINDOW, FULL)),
    "window_layers_not_rotated": dict(rotating=()),
    "bias_left_out_of_the_choice": dict(bias_in_choice=False),
    "bias_added_to_the_weights": dict(bias_in_weights=True),
    "route_scale_off": dict(route_scale=1.0),
    "post_norms_off": dict(post_norms=False),
    "embed_scale_off": dict(embed_scale=1.0),
    "softmax_for_sigmoid": dict(scoring="softmax"),
    "leading_layer_as_expert_layer": dict(leading_dense=False),
    "window_mask_off": dict(sliding_window=1 << 30),
    "share_offset": dict(first_expert=8),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_is_seen(model, fault):
    """The served logits are far from the reference given any one fault:
    each mechanism is in the served program, and the comparison sees it."""
    cfg, params, rcfg = model
    ids = _ids(40, seed=5)
    got = np.asarray(forward_train(params, cfg, jnp.asarray(ids[None])))[0]
    bad = dataclasses.replace(rcfg, **FAULTS[fault])
    want = np.asarray(ref.logits(ref.from_served(params), bad, ids))
    assert np.abs(got - want).max() > FAULT


def test_chunked_prefill_and_decode_through_wraps(model):
    """A prompt of more than 3 rings through 8-token chunks, then 36 decode
    steps (two more wraps of the 16-token rings, the leading window layer's
    too), beside a 5-token row: the reference at every position."""
    cfg, params, rcfg = model
    prompt, short, steps = 61, 5, 36
    ids = _ids(prompt + steps + 1, seed=3)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    # the drive of tests/test_reference_lm.py: the long row through
    # `extend` a chunk at a time, the short one through `prefill`, then
    # decode steps of both
    from test_reference_lm import _serve, _worst

    out, kc, _ = _serve(cfg, params, ids, prompt=prompt, short=short,
                        steps=steps, chunk=8, context=128)
    assert _worst(out, want, prompt, short) < F32_TOL
    # the period's places hold two layers each, a leading layer's one; a
    # window place is a ring of window + chunk, a full one the context
    assert isinstance(kc, PeriodKV)
    assert [s.shape for s in kc.slots] == (
        [(2, 2, 1, 16, 16)] * 3 + [(2, 2, 1, 128, 16)]
        + [(1, 2, 1, 16, 16), (1, 2, 1, 128, 16)])


def test_the_engines_programs_match_the_reference(model):
    """The engine's own compiled programs, driven as it drives them: a
    prompt through chunked prefill past the rings, a short one through a
    prefill bucket half way, single decode steps (one beside the long
    row's last chunks, with an inactive row in it) and the fused loop."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.ops.sampling import SamplingParams, sampler_row

    cfg, params, rcfg = model
    chunk, B = 16, 3
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=B, max_context=256, prefill_buckets=(16,),
        prefill_chunk=chunk))
    m = eng.metrics
    assert (m["layers__window"], m["layers__full"],
            m["layers__leading_dense"]) == (7, 3, 2)
    ring = (8 + chunk) * 16 * 4 * 2 * B        # K and V, float32, a layer
    assert m["kv_bytes__window"] == 7 * ring
    assert m["kv_bytes__full"] == 3 * 256 * 16 * 4 * 2 * B
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
            # (not after the final chunk: a row that has its first logits
            # is active in every step the engine dispatches from then on)
            decode([1])
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
    rp = ref.from_served(params)
    for r in (0, 1):
        want = np.asarray(ref.logits(rp, rcfg, np.asarray(rows[r])))
        assert len(served[r]) >= 6
        for pos, got in served[r].items():
            assert np.abs(got - want[pos]).max() < F32_TOL, (r, pos)
        # greedy: every token picked is the reference's choice
        n = 90 if r == 0 else 12
        assert rows[r][n:] == list(want[n - 1:-1].argmax(-1))


def test_the_engine_counts_this_model_too(model):
    """A request through the engine's own loop: context tokens by layer kind
    and expert tokens (x the 8 expert layers, not the 10 layers) are
    counted, and a second request gets the same tokens."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest, SamplingParams

    cfg, params, _ = model
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
    finally:
        eng.stop()
    assert len(first) == 12
    assert m["decode_ctx_tokens__full"] > 2 * m["decode_ctx_tokens__window"]
    assert m["decode_ctx_tokens__window"] > 0
    # the prompt's 30 tokens and the 11 (or, with one more step dispatched
    # before the stop, 12) that decode fed back: routed whatever the shape
    assert m["expert_tokens__routed"] in ((30 + 11) * 8, (30 + 12) * 8)
    assert m["expert_tokens__dense"] == 0


def test_a_checkpoints_tensors_load_into_the_same_stacks(model, tmp_path):
    """The served params written out under an afmoe checkpoint's names (as
    engine/loader.py has them: [out, in] matrices, a layer at a time, the
    experts by their number in the router) load back equal, the leading
    layers into their own stack and the share's experts from their place."""
    from fixtures import _write_safetensors
    from localai_tpu.engine.loader import LLAMA_FAMILY, load_params

    cfg, params, rcfg = model
    names = LLAMA_FAMILY["AfmoeForCausalLM"]["tensors"]
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": params["lm_head"].T}
    for first, stack in ((0, params["leading"]), (2, params["layers"])):
        for name, leaf in stack.items():
            for n, t in enumerate(np.asarray(leaf)):
                at = f"model.layers.{first + n}." + names[name]
                if name.startswith("moe_w"):
                    for e, w in enumerate(t):
                        out[at.format(e=rcfg.first_expert + e)] = w.T
                else:
                    out[at] = t.T if t.ndim == 2 else t
    hf = HF if rcfg.num_experts == 16 else SHARE
    _write(tmp_path, hf)
    _write_safetensors(str(tmp_path / "model.safetensors"),
                       {k: np.asarray(v, np.float32) for k, v in out.items()})
    loaded = load_params(str(tmp_path), cfg, dtype="float32")
    assert (jax.tree_util.tree_structure(loaded)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))
