"""benchmark/reference/trinity.py: the benchmark's own copy of the plain
reference for the afmoe architecture is the program's
(localai_tpu/testing/reference_afmoe.py), runs, and honours the share, the
leading dense layer and each of the mechanisms a fault can leave out."""
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    spec = importlib.util.spec_from_file_location("bench_ref_trinity", path)
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
    e, i, wide = (hf["num_experts"], hf["moe_intermediate_size"],
                  hf["intermediate_size"])
    routers = hf["localai_expert_share"]["router_experts"]

    def w(*shape):
        return (rng.standard_normal(shape) * shape[-2] ** -0.5).astype(
            np.float32)

    def gain(n):
        return (1 + 0.3 * rng.standard_normal(n)).astype(np.float32)

    def layer(n: int) -> dict:
        lp = {"attn_norm": gain(h), "mlp_norm": gain(h),
              "attn_post_norm": gain(h), "mlp_post_norm": gain(h),
              "q_norm": gain(d), "k_norm": gain(d),
              "wq": w(h, nh * d), "wk": w(h, nkv * d), "wv": w(h, nkv * d),
              "wo": w(nh * d, h), "w_agate": w(h, nh * d)}
        if n < hf["num_dense_layers"]:
            lp.update(w_gate=w(h, wide), w_up=w(h, wide), w_down=w(wide, h))
        else:
            lp.update(router=w(h, routers),
                      bias=(0.1 * rng.standard_normal(routers)).astype(
                          np.float32),
                      w1=w(e, h, i), w3=w(e, h, i), w2=w(e, i, h),
                      ws_gate=w(h, i), ws_up=w(h, i), ws_down=w(i, h))
        return lp

    return {"embed": w(v, h), "final_norm": np.ones(h, np.float32),
            "lm_head": w(h, v),
            "layers": [layer(n) for n in range(hf["num_hidden_layers"])]}


def test_the_copy_is_the_programs_reference():
    from localai_tpu.testing import reference_afmoe as theirs

    mine = _load(os.path.join(BENCH, "reference", "trinity.py"))
    with open(os.path.join(BENCH, "configs",
                           "trinity-large-ep8-d5.json")) as f:
        doc = json.load(f)
    hf = dict(doc, **doc["rehearsal"]["geometry"])
    ids = np.random.default_rng(1).integers(0, hf["vocab_size"], size=150)
    params = _tiny(hf, seed=2)
    cfg = mine.RefConfig.from_hf(hf)
    assert (cfg.num_dense_layers, cfg.num_experts, cfg.first_expert) == (
        1, 8, 8)
    assert cfg.num_heads // cfg.num_kv_heads == 6
    a = np.asarray(mine.logits(params, cfg, ids))
    b = np.asarray(theirs.logits(params, theirs.RefConfig.from_hf(hf), ids))
    assert a.shape == (150, hf["vocab_size"])
    assert np.array_equal(a, b)
    # each mechanism is in force: left out, or another chip's experts, the
    # logits are others
    for fault in (dict(first_expert=0), dict(qk_norm=False),
                  dict(attn_gate=False), dict(post_norms=False),
                  dict(rotating=("window", "full")), dict(rotating=()),
                  dict(scoring="softmax"), dict(bias_in_choice=False),
                  dict(bias_in_weights=True), dict(route_scale=1.0),
                  dict(embed_scale=1.0), dict(leading_dense=False)):
        c = np.asarray(mine.logits(
            params, dataclasses.replace(cfg, **fault), ids))
        assert np.abs(a - c).max() > 1e-2, fault
    # the window (64 here) is in force: a wider one changes nothing before
    # position 64 and everything after
    c = np.asarray(mine.logits(
        params, dataclasses.replace(cfg, sliding_window=4096), ids))
    assert np.abs(a - c)[:64].max() < 1e-5 < np.abs(a - c)[100:].max()

    def code(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    assert code(mine.__file__) == code(theirs.__file__)
    assert "localai_tpu" not in code(mine.__file__)
