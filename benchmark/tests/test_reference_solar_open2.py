"""benchmark/reference/solar_open2.py: the benchmark's own copy of the plain
reference for a model with linear-attention layers is the program's
(localai_tpu/testing/reference_linear.py), runs, and honours the share."""
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    spec = importlib.util.spec_from_file_location("bench_ref_solar", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _tiny(hf: dict, seed: int) -> dict:
    """Seeded float32 weights in the layout the reference takes."""
    rng = np.random.default_rng(seed)
    h, v = hf["hidden_size"], hf["vocab_size"]
    nh, nkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    la = hf["linear_attn_config"]
    c, r = la["num_heads"] * la["head_dim"], la["head_dim"]
    e, i = hf["n_routed_experts"], hf["moe_intermediate_size"]
    routers = hf["localai_expert_share"]["router_experts"]

    def w(*shape):
        return (rng.standard_normal(shape) * shape[-2] ** -0.5).astype(
            np.float32)

    def layer(n: int) -> dict:
        lp = {"attn_norm": np.ones(h, np.float32),
              "mlp_norm": np.ones(h, np.float32), "router": w(h, routers),
              "w1": w(e, h, i), "w3": w(e, h, i), "w2": w(e, i, h),
              "ws_gate": w(h, i), "ws_up": w(h, i), "ws_down": w(i, h)}
        if n in hf["gqa_layers"]:
            lp.update(wq=w(h, nh * d), wk=w(h, nkv * d), wv=w(h, nkv * d),
                      wo=w(nh * d, h), w_agate=w(h, nh * d))
        else:
            lp.update(
                wq=w(h, c), wk=w(h, c), wv=w(h, c), wo=w(c, h),
                w_f1=w(h, r), w_f2=w(r, c) * 0.25, w_g1=w(h, r), w_g2=w(r, c),
                w_b=w(h, la["num_heads"]),
                conv=(rng.standard_normal((3 * c, 4)) * 0.5).astype(
                    np.float32),
                A_log=np.log(rng.uniform(1, 16, la["num_heads"])).astype(
                    np.float32),
                dt_bias=np.full(c, -4.0, np.float32),
                o_norm=np.ones(la["head_dim"], np.float32))
        return lp

    return {"embed": w(v, h), "final_norm": np.ones(h, np.float32),
            "lm_head": w(h, v),
            "layers": [layer(n) for n in range(hf["num_hidden_layers"])]}


def test_the_copy_is_the_programs_reference():
    from localai_tpu.testing import reference_linear as theirs

    mine = _load(os.path.join(BENCH, "reference", "solar_open2.py"))
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-ep8-d8.json")) as f:
        doc = json.load(f)
    hf = dict(doc, **doc["rehearsal"]["geometry"])
    ids = np.random.default_rng(1).integers(0, hf["vocab_size"], size=90)
    params = _tiny(hf, seed=2)
    cfg = mine.RefConfig.from_hf(hf)
    a = np.asarray(mine.logits(params, cfg, ids))
    b = np.asarray(theirs.logits(params, theirs.RefConfig.from_hf(hf), ids))
    assert a.shape == (90, hf["vocab_size"])
    assert np.array_equal(a, b)
    # the share is in force: another chip's experts give other logits, and
    # so does a decay left out
    for fault in (dict(first_expert=0), dict(linear_decay=False)):
        c = np.asarray(mine.logits(
            params, dataclasses.replace(cfg, **fault), ids))
        assert np.abs(a - c).max() > 1e-2

    def code(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    assert code(mine.__file__) == code(theirs.__file__)
    assert "localai_tpu" not in code(mine.__file__)
