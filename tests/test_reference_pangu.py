"""A small openPangu-Ultra-MoE (model_type pangu_ultra_moe) as served against
the plain float32 reference (localai_tpu/testing/reference_pangu.py): logits
on seeded random weights, never sampled tokens.

The shape: two leading dense layers (each a cache place of its own), then
three expert layers in the layer scan, every layer LATENT: 4 heads of 16 + 8
key columns and 16 value columns over a latent of 32 (a cache row of 40,
padded to 128), the query through a rank of 40; sandwich norms; a sigmoid
router 16 wide WITHOUT a selection bias, top-2, routed_scaling_factor 2.5, a
shared expert; held whole, or as the share [4, 12) of the 16. The norms'
gains are drawn, not ones, so that each mechanism moves the logits by far
more than the tolerance.

F32_TOL 2e-4 (float32 weights and cache; the two sides differ in the order
of their sums, and the absorbed decode path in the order of its products:
measured 2e-6 to 6e-6). A planted fault, given to the reference, must read
above FAULT 0.01 (the least measured 0.8: softmax for sigmoid scores).
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import kv
from localai_tpu.models.llama import (
    LATENT, LlamaConfig, PeriodKV, decode_step, extend, forward_train,
    init_kv_cache, init_params, rope_tables,
)
from localai_tpu.testing import reference_pangu as ref

F32_TOL, FAULT = 2e-4, 0.01
HF = dict(
    model_type="pangu_ultra_moe", vocab_size=96, hidden_size=48,
    intermediate_size=64, moe_intermediate_size=24, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
    q_lora_rank=40, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    max_position_embeddings=1024, rms_norm_eps=1e-5, rope_theta=25600000,
    first_k_dense_replace=2, n_routed_experts=16, num_experts_per_tok=2,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    sandwich_norm=True, num_nextn_predict_layers=1, attention_bias=False,
    hidden_act="silu", tie_word_embeddings=False)
SHARE = dict(HF, n_routed_experts=8,
             localai_expert_share={"router_experts": 16, "first_expert": 4})


def _write(tmp_path, hf):
    (tmp_path / "config.json").write_text(json.dumps(hf))
    return str(tmp_path)


def _ids(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, 96, size=n)


@pytest.fixture(scope="module", params=["whole", "share"])
def model(request, tmp_path_factory):
    from localai_tpu.engine.loader import load_config

    hf = HF if request.param == "whole" else SHARE
    cfg = load_config(_write(tmp_path_factory.mktemp("pangu"), hf),
                      dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(1))
    # gains that are not ones: a norm left out then moves every logit
    rng = np.random.default_rng(7)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1 + 0.3 * rng.standard_normal(a.shape)).astype(
            a.dtype) if path[-1].key.endswith("norm") else a, params)
    return cfg, params, ref.RefConfig.from_hf(hf)


def test_load_config_reads_the_architecture(model):
    cfg, params, rcfg = model
    assert cfg.layer_types == (LATENT,) * 5 and cfg.period == (LATENT,)
    assert cfg.cache_kinds == (LATENT,) * 3
    assert (cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.head_dim) == (
                32, 40, 16, 8, 16, 24)
    assert (cfg.leading_dense_layers, cfg.post_norms, cfg.router_sigmoid,
            cfg.router_bias, cfg.qk_norm, cfg.attn_gate) == (
                2, True, True, False, False, False)
    assert (cfg.routed_scale, cfg.shared_expert_width, cfg.rope_base) == (
        2.5, 24, 25600000)
    assert cfg.rope_of(LATENT).head_dim == 8 and cfg.rotates(LATENT)
    assert (cfg.num_experts, cfg.router_experts or cfg.num_experts,
            cfg.first_expert) == (rcfg.num_experts, 16, rcfg.first_expert)
    assert set(params["leading"]) >= {"w_gate", "wq_a", "wkv_b", "kv_a_norm"}
    assert "moe_gate" not in params["leading"] and "wq" not in params["layers"]
    assert params["layers"]["wkv_a"].shape == (3, 48, 32 + 8)
    assert params["layers"]["wkv_b"].shape == (3, 32, 4 * (16 + 16))
    assert params["layers"]["moe_w1"].shape[:2] == (3, rcfg.num_experts)
    assert "moe_bias" not in params["layers"]


@pytest.mark.parametrize("change,named", [
    (dict(n_group=2), "n_group"), (dict(topk_group=4), "topk_group"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(q_lora_rank=None), "q_lora_rank"),
    (dict(num_key_value_heads=2), "num_key_value_heads"),
])
def test_load_config_refuses_what_the_layer_cannot_honour(tmp_path, change,
                                                          named):
    from localai_tpu.engine.loader import load_config

    with pytest.raises(ValueError, match=named):
        load_config(_write(tmp_path, dict(HF, **change)))


@pytest.mark.parametrize("option,named", [
    (dict(kv_pages=8), "kv_pages"), (dict(cache_type="int8"), "cache_type"),
    ("draft", "speculative"), ("context_shift", "context_shift"),
])
def test_the_engine_refuses_what_rests_on_another_cache(model, option,
                                                        named):
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.engine import GenRequest

    cfg, params, _ = model
    ec = dict(max_slots=2, max_context=64, prefill_buckets=(16,),
              prefill_chunk=16)
    with pytest.raises(ValueError, match=named):
        if option == "draft":
            Engine(cfg, params, None, EngineConfig(**ec),
                   draft=(cfg, params))
        elif option == "context_shift":
            Engine(cfg, params, None, EngineConfig(**ec)).submit(GenRequest(
                prompt_ids=[1, 2, 3], max_tokens=2, context_shift=True))
        else:
            Engine(cfg, params, None, EngineConfig(**ec, **option))


def test_one_kind_of_layer_takes_leading_dense_layers_if_latent():
    """A stack of LATENT layers alone is a layer_types (its cache class is
    reached by kind), with or without leading dense layers; any other kind
    alone still leaves layer_types None, and latent widths need the kind."""
    over = dict(kv_lora_rank=32, q_lora_rank=40, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16)
    cfg = LlamaConfig(num_layers=4, num_experts=4, leading_dense_layers=1,
                      layer_types=(LATENT,) * 4, **over)
    assert cfg.cache_kinds == (LATENT, LATENT) and not cfg.stacked_by_kind
    assert LlamaConfig(num_layers=2, layer_types=(LATENT,) * 2,
                       **over).cache_kinds == (LATENT,)
    with pytest.raises(ValueError, match="one kind"):
        LlamaConfig(num_layers=2, layer_types=("full",) * 2)
    with pytest.raises(ValueError, match="another kind"):
        LlamaConfig(num_layers=2, layer_types=(LATENT, "full"), **over)
    with pytest.raises(ValueError, match="kv_lora_rank"):
        LlamaConfig(num_layers=2, **over)
    with pytest.raises(ValueError, match="q_lora_rank"):
        LlamaConfig(num_layers=2, layer_types=(LATENT,) * 2, kv_lora_rank=8)


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
    "k_pe_not_rotated": dict(rotate_k_pe=False),
    "q_pe_not_rotated": dict(rotate_q_pe=False),
    "kv_a_norm_left_out": dict(kv_a_norm=False),
    "q_a_norm_left_out": dict(q_a_norm=False),
    "scale_of_the_nope_width": dict(scale_width=16),
    "routed_scaling_factor_off": dict(route_scale=1.0),
    "post_norms_off": dict(post_norms=False),
    "values_from_shifted_columns": dict(value_shift=8),
    "softmax_for_sigmoid": dict(scoring="softmax"),
    "leading_layer_as_expert_layer": dict(leading_dense=False),
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


def test_chunked_prefill_and_decode_through_the_latent_cache(
        model, monkeypatch):
    """A prompt of 61 tokens through 24-token chunks over blocks of 16 rows
    (every chunk boundary inside a block, the last chunk over four blocks),
    then 20 decode steps beside a 5-token row: the reference's full forward
    at every position."""
    from test_reference_lm import _serve, _worst

    monkeypatch.setattr(kv, "CHUNK_BLOCK", 16)
    cfg, params, rcfg = model
    prompt, short, steps = 61, 5, 20
    ids = _ids(prompt + steps + 1, seed=3)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    out, kc, vc = _serve(cfg, params, ids, prompt=prompt, short=short,
                         steps=steps, chunk=24, context=128)
    assert _worst(out, want, prompt, short) < F32_TOL
    # one buffer a place, no heads axis, no V: the period's place holds the
    # three scanned layers, a leading layer's place one; a row of 32 + 8
    # padded to the 128-lane tile
    assert isinstance(kc, PeriodKV) and vc.slots == (None,) * 3
    assert [s.shape for s in kc.slots] == [(3, 2, 128, 128)] + [
        (1, 2, 128, 128)] * 2
    assert not np.asarray(kc.slots[0][..., 40:]).any()


def _filled(cfg, params, ids, context=64):
    """A cache of 2 slots whose slot 1 holds `ids` (through one chunk)."""
    kc, vc = init_kv_cache(cfg, 2, context, prefill_chunk=len(ids))
    cos, sin = rope_tables(cfg, context)
    _, kc, vc = extend(params, cfg, jnp.asarray(ids[None]), jnp.array([0]),
                       cos, sin, kc, vc, slot_map=jnp.array([1]),
                       with_logits=False)
    return kc, vc, cos, sin


def test_a_decode_step_with_an_inactive_row(model):
    """Row 0 is not decoding (a chunked prefill is filling its slot): its
    write lands in the last row, which nothing reads, its slot's other rows
    are untouched, and row 1 reads the reference's logits."""
    cfg, params, rcfg = model
    ids = _ids(31, seed=8)
    want = np.asarray(ref.logits(ref.from_served(params), rcfg, ids))
    kc, vc, cos, sin = _filled(cfg, params, ids[:30])
    before = np.asarray(kc.slots[0])
    logits, kc, _ = decode_step(
        params, cfg, jnp.asarray([7, ids[30]]), jnp.array([12, 30]), cos, sin,
        kc, vc, active=jnp.array([False, True]))
    assert np.abs(np.asarray(logits)[1] - want[30]).max() < F32_TOL
    after = np.asarray(kc.slots[0])
    assert np.array_equal(after[:, 0, :63], before[:, 0, :63])
    assert after[:, 0, 63].any() and after[:, 1, 30].any()


def test_absorbed_decode_is_the_expanding_form_on_the_same_cache(model):
    """One token's attention over the same cached rows, both ways: decode's
    absorbed path (the query through W_UK, the latents as keys and values,
    the output through W_UV) and a one-token chunk's expanding path (every
    row through W_kvb, heads of 24 / 16)."""
    cfg, params, _ = model
    ids = _ids(30, seed=9)
    kc, vc, cos, sin = _filled(cfg, params, ids)
    tok, n = jnp.asarray([3, 11]), jnp.array([0, 30])
    absorbed, _, _ = decode_step(params, cfg, tok, n, cos, sin, kc, vc,
                                 active=jnp.array([False, True]))
    expanded, _, _ = extend(params, cfg, tok[1:, None], n[1:], cos, sin, kc,
                            vc, slot_map=jnp.array([1]))
    assert np.abs(np.asarray(absorbed)[1]
                  - np.asarray(expanded)[0, 0]).max() < 2e-5


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.03)])
def test_the_kernel_is_its_xla_twin(dtype, tol):
    """mla_decode in the interpreter against ops/mla.mla_decode_xla: rows of
    lengths inside the first block, across blocks, 0 (not decoding: zeros)
    and the whole cache; a layer of a stack; a partial last block. bfloat16:
    the two round their probabilities to bfloat16 at different maxima."""
    from localai_tpu.ops.mla import mla_decode_xla
    from localai_tpu.ops.pallas.mla import mla_decode

    key = jax.random.split(jax.random.PRNGKey(0), 2)
    for t, block_k in ((320, 128), (200, 128), (64, None)):
        cache = jax.random.normal(key[0], (2, 4, t, 128)).astype(dtype)
        q = jax.random.normal(key[1], (4, 8, 128)).astype(dtype)
        lengths = jnp.array([5, 0, t - 20, t])
        got = mla_decode(q, cache, lengths, 1, rank=96, scale=0.1,
                         block_k=block_k)
        want = mla_decode_xla(q, cache[1], lengths, 96, 0.1)
        assert got.shape == (4, 8, 96) and got.dtype == q.dtype
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.abs(got - want)[[0, 2, 3]].max() < tol
        assert not got[1].any()


def test_the_served_decode_step_takes_the_kernel(model, monkeypatch):
    """With the Pallas kernels forced on (the interpreter here), a decode
    step's logits are the XLA twin's."""
    cfg, params, _ = model
    ids = _ids(30, seed=10)
    kc, vc, cos, sin = _filled(cfg, params, ids)
    args = (jnp.asarray([3, 11]), jnp.array([9, 30]), cos, sin, kc, vc)
    twin, _, _ = decode_step(params, cfg, *args)
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    assert "mla_decode" in str(jax.make_jaxpr(
        lambda *a: decode_step(params, cfg, *a))(*args))
    kernel, _, _ = decode_step(params, cfg, *args)
    assert np.abs(np.asarray(kernel) - np.asarray(twin)).max() < 2e-5


def test_the_engines_programs_match_the_reference(model, monkeypatch):
    """The engine's own compiled programs, driven as it drives them: a
    prompt through chunked prefill over several blocks, a short one through
    a prefill bucket half way, single decode steps (one beside the long
    row's last chunks, with an inactive row in it) and the fused loop."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.ops.sampling import SamplingParams, sampler_row

    monkeypatch.setattr(kv, "CHUNK_BLOCK", 32)
    cfg, params, rcfg = model
    chunk, B = 16, 3
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=B, max_context=256, prefill_buckets=(16,),
        prefill_chunk=chunk))
    m = eng.metrics
    assert (m["layers__latent"], m["layers__leading_dense"]) == (5, 2)
    # one buffer of float32 rows of 128 (32 + 8, padded) a layer
    assert m["kv_bytes__latent"] == 5 * B * 256 * 128 * 4
    assert eng.kernel_tiers()["chunk_attention"] == "xla-blocks"
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
    # six chunks from 0, 16, .. 80 over blocks of 32 rows: 32 + 32 + 64 +
    # 64 + 96 + 96 rows visited, each expanded once, of a capacity of 256
    assert m["chunk_ctx_tokens__attended"] == 384
    assert m["chunk_latent_rows__expanded"] == 384
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
    """A request through the engine's own loop: the context a latent layer
    attended over in decode, the rows its chunks expanded and the expert
    tokens (x the 3 expert layers, not the 5 layers) are counted, and a
    second request (its prefix lent by the slot) gets the same tokens."""
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
    # 11 or 12 steps from 30 tokens on: 31 + 32 + ...
    assert m["decode_ctx_tokens__latent"] in (
        sum(range(31, 42)), sum(range(31, 43)))
    assert "decode_ctx_tokens__full" not in m
    assert m["chunk_latent_rows__expanded"] == m[
        "chunk_ctx_tokens__attended"] > 0
    assert m["expert_tokens__routed"] in ((30 + 11) * 3, (30 + 12) * 3)
    assert m["expert_tokens__dense"] == 0
    assert m["decode_row_steps__live"] == m["tokens_generated"] == 12


def test_a_checkpoints_tensors_load_into_the_same_stacks(model, tmp_path):
    """The served params written out under a pangu_ultra_moe checkpoint's
    names (as engine/loader.py has them: [out, in] matrices, a layer at a
    time, the experts by their number in the router) load back equal, the
    leading layers into their own stack and the share's experts from their
    place."""
    from fixtures import _write_safetensors
    from localai_tpu.engine.loader import LLAMA_FAMILY, load_params

    cfg, params, rcfg = model
    names = LLAMA_FAMILY["PanguUltraMoEForCausalLM"]["tensors"]
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
    _write(tmp_path, dict(hf, architectures=["PanguUltraMoEForCausalLM"]))
    _write_safetensors(str(tmp_path / "model.safetensors"),
                       {k: np.asarray(v, np.float32) for k, v in out.items()})
    loaded = load_params(str(tmp_path), cfg, dtype="float32")
    assert (jax.tree_util.tree_structure(loaded)
            == jax.tree_util.tree_structure(params))
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def test_the_benchmarks_copy_of_the_reference_is_this_one():
    """benchmark/reference/openpangu_ultra_moe.py is the program's reference
    from its first import on, and imports nothing of the program."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def code(*path):
        with open(os.path.join(root, *path)) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    mine = code("benchmark", "reference", "openpangu_ultra_moe.py")
    assert mine == code("localai_tpu", "testing", "reference_pangu.py")
    assert "localai_tpu" not in mine
