"""benchmark/reference/nemotron_h.py: the benchmark's own copy of the plain
reference for the nemotron_h architecture is the program's
(localai_tpu/testing/reference_nemotron_h.py), runs on the configuration's
rehearsal geometry, and honours the share and each of the mechanisms a fault
can leave out."""
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    spec = importlib.util.spec_from_file_location("bench_ref_nemotron", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _hf() -> dict:
    with open(os.path.join(BENCH, "configs",
                           "nemotron3-super-120b-ep4-d22.json")) as f:
        doc = json.load(f)
    hf = {k: v for k, v in doc.items()
          if k not in ("source", "reduced", "published", "assumed",
                       "deployment", "serving", "rehearsal")}
    hf.update(doc["rehearsal"]["geometry"])
    return hf


def _tiny(hf: dict, seed: int) -> dict:
    """Seeded float32 weights in the layout the reference takes."""
    rng = np.random.default_rng(seed)
    h, v = hf["hidden_size"], hf["vocab_size"]
    nh, nkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    mh, p, g, n = (hf["mamba_num_heads"], hf["mamba_head_dim"],
                   hf["n_groups"], hf["ssm_state_size"])
    e, i, lat, wide = (hf["n_routed_experts"], hf["moe_intermediate_size"],
                       hf["moe_latent_size"],
                       hf["moe_shared_expert_intermediate_size"])
    routers = hf["localai_expert_share"]["router_experts"]
    conv = mh * p + 2 * g * n

    def w(*shape):
        return (rng.standard_normal(shape) * shape[-2] ** -0.5).astype(
            np.float32)

    def gain(k):
        return (1 + 0.3 * rng.standard_normal(k)).astype(np.float32)

    def layer(letter: str) -> dict:
        if letter == "M":
            return {"norm": gain(h), "w_in": w(h, mh * p + conv + mh),
                    "conv": w(conv, hf["conv_kernel"]),
                    "conv_bias": (0.25 * rng.standard_normal(conv)).astype(
                        np.float32),
                    "dt_bias": rng.uniform(-6.9, -2.2, mh).astype(np.float32),
                    "A_log": np.log(rng.uniform(1, 16, mh)).astype(np.float32),
                    "D": rng.uniform(0.5, 1.5, mh).astype(np.float32),
                    "ssm_norm": gain(mh * p), "w_out": w(mh * p, h)}
        if letter == "*":
            return {"norm": gain(h), "wq": w(h, nh * d), "wk": w(h, nkv * d),
                    "wv": w(h, nkv * d), "wo": w(nh * d, h)}
        return {"norm": gain(h), "router": w(h, routers),
                "router_bias": (0.05 * rng.standard_normal(routers)).astype(
                    np.float32),
                "w_lat_in": w(h, lat), "w_lat_out": w(lat, h),
                "w1": w(e, lat, i), "w2": w(e, i, lat),
                "ws_up": w(h, wide), "ws_down": w(wide, h)}

    return {"embed": w(v, h), "final_norm": np.ones(h, np.float32),
            "lm_head": w(h, v),
            "layers": [layer(c) for c in hf["hybrid_override_pattern"]]}


def test_the_copy_is_the_programs_reference():
    def code(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    mine = code(os.path.join(BENCH, "reference", "nemotron_h.py"))
    theirs = code(os.path.join(os.path.dirname(BENCH), "localai_tpu",
                               "testing", "reference_nemotron_h.py"))
    assert mine == theirs and "localai_tpu" not in mine


def test_it_runs_on_the_rehearsal_geometry_and_sees_each_mechanism():
    ref = _load(os.path.join(BENCH, "reference", "nemotron_h.py"))
    hf = _hf()
    cfg = ref.RefConfig.from_hf(hf)
    assert (cfg.pattern, cfg.num_experts, cfg.first_expert,
            cfg.experts_per_tok, cfg.route_scale) == (
        "*EMEMEMEMEM*EMEMEMEMEM", 8, 8, 22, 5.0)
    params = _tiny(hf, 0)
    ids = np.random.default_rng(1).integers(0, hf["vocab_size"], size=48)
    want = np.asarray(ref.logits(params, cfg, ids))
    assert want.shape == (48, hf["vocab_size"]) and np.isfinite(want).all()
    blocks = np.asarray(ref.logits(params, cfg, ids, block=16))
    assert np.abs(want - blocks).max() < 1e-4
    # a position's logits depend on nothing after it
    head = np.asarray(ref.logits(params, cfg, ids[:20]))
    assert np.abs(head - want[:20]).max() < 1e-4
    for fault in (dict(squared=False), dict(skip_d=False),
                  dict(conv_bias=False), dict(gate_before_norm=False),
                  dict(experts_per_tok=21), dict(route_scale=1.0),
                  dict(latent_in=False), dict(bias_in_choice=False),
                  dict(dt_bias=False), dict(first_expert=0)):
        other = np.asarray(ref.logits(
            params, dataclasses.replace(cfg, **fault), ids))
        assert np.abs(other - want).max() > 1e-3, fault
    # what a sequence leaves is what the next one can be started from
    left: dict = {}
    ref.hidden_states(params, cfg, ids[:20], left=left)
    assert sorted(left) == [i for i, c in enumerate(cfg.pattern) if c == "M"]
    carried = np.asarray(ref.head(params, cfg, ref.hidden_states(
        params, cfg, ids[20:], carried=left)))
    fresh = np.asarray(ref.logits(params, cfg, ids[20:]))
    assert np.abs(carried - fresh).max() > 1e-3
