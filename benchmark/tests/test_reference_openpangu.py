"""benchmark/reference/openpangu_ultra_moe.py: the benchmark's own copy of
the plain reference for the pangu_ultra_moe architecture is the program's
(localai_tpu/testing/reference_pangu.py), runs, and honours the share, the
leading dense layer and each of the mechanisms a fault can leave out."""
import dataclasses
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    spec = importlib.util.spec_from_file_location("bench_ref_openpangu", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _tiny(hf: dict, seed: int) -> dict:
    """Seeded float32 weights in the layout the reference takes."""
    rng = np.random.default_rng(seed)
    h, v = hf["hidden_size"], hf["vocab_size"]
    nh, r, qr = (hf["num_attention_heads"], hf["kv_lora_rank"],
                 hf["q_lora_rank"])
    n, p, vd = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                hf["v_head_dim"])
    e, i, wide = (hf["n_routed_experts"], hf["moe_intermediate_size"],
                  hf["intermediate_size"])
    routers = hf["localai_expert_share"]["router_experts"]

    def w(*shape):
        return (rng.standard_normal(shape) * shape[-2] ** -0.5).astype(
            np.float32)

    def gain(k):
        return (1 + 0.3 * rng.standard_normal(k)).astype(np.float32)

    def layer(k: int) -> dict:
        lp = {"attn_norm": gain(h), "mlp_norm": gain(h),
              "attn_post_norm": gain(h), "mlp_post_norm": gain(h),
              "wq_a": w(h, qr), "q_a_norm": gain(qr),
              "wq_b": w(qr, nh * (n + p)), "wkv_a": w(h, r + p),
              "kv_a_norm": gain(r), "wkv_b": w(r, nh * (n + vd)),
              "wo": w(nh * vd, h)}
        if k < hf["first_k_dense_replace"]:
            lp.update(w_gate=w(h, wide), w_up=w(h, wide), w_down=w(wide, h))
        else:
            lp.update(router=w(h, routers), w1=w(e, h, i), w3=w(e, h, i),
                      w2=w(e, i, h), ws_gate=w(h, i), ws_up=w(h, i),
                      ws_down=w(i, h))
        return lp

    return {"embed": w(v, h), "final_norm": np.ones(h, np.float32),
            "lm_head": w(h, v),
            "layers": [layer(k) for k in range(hf["num_hidden_layers"])]}


def test_the_copy_is_the_programs_reference():
    from localai_tpu.testing import reference_pangu as theirs

    mine = _load(os.path.join(BENCH, "reference", "openpangu_ultra_moe.py"))
    with open(os.path.join(BENCH, "configs",
                           "openpangu-ultra-moe-ep16-d6.json")) as f:
        doc = json.load(f)
    hf = dict(doc, **doc["rehearsal"]["geometry"])
    ids = np.random.default_rng(1).integers(0, hf["vocab_size"], size=120)
    params = _tiny(hf, seed=2)
    cfg = mine.RefConfig.from_hf(hf)
    assert (cfg.num_dense_layers, cfg.num_experts, cfg.first_expert,
            cfg.route_scale, cfg.post_norms) == (1, 4, 8, 2.5, True)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (64, 32, 16, 32)
    a = np.asarray(mine.logits(params, cfg, ids))
    b = np.asarray(theirs.logits(params, theirs.RefConfig.from_hf(hf), ids))
    assert a.shape == (120, hf["vocab_size"])
    assert np.array_equal(a, b)
    # each mechanism is in force: left out, or another chip's experts, the
    # logits are others
    for fault in (dict(first_expert=0), dict(rotate_k_pe=False),
                  dict(rotate_q_pe=False), dict(kv_a_norm=False),
                  dict(q_a_norm=False), dict(scale_width=32),
                  dict(value_shift=16), dict(post_norms=False),
                  dict(scoring="softmax"), dict(route_scale=1.0),
                  dict(leading_dense=False)):
        c = np.asarray(mine.logits(
            params, dataclasses.replace(cfg, **fault), ids))
        assert np.abs(a - c).max() > 1e-2, fault
    # a block of queries at a time changes the memory, not the answer
    c = np.asarray(mine.logits(params, cfg, ids, block=32))
    assert np.abs(a - c).max() < 1e-5

    def code(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    assert code(mine.__file__) == code(theirs.__file__)
    assert "localai_tpu" not in code(mine.__file__)
