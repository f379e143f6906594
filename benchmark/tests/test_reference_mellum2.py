"""benchmark/reference/mellum2.py, the benchmark's own copy of the plain
reference, against the program's copy (localai_tpu/testing/reference_lm.py)
on seeded tiny weights: the same logits, and the same code below the
docstring, so a change to one is a change to both or a failure here."""
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _tiny(hf, seed):
    """Seeded random weights in the layout both copies take."""
    rng = np.random.default_rng(seed)
    h, v = hf["hidden_size"], hf["vocab_size"]
    nh, nkv, d = (hf["num_attention_heads"], hf["num_key_value_heads"],
                  hf["head_dim"])
    e, width = hf["num_experts"], hf["moe_intermediate_size"]

    def w(*shape):
        return (rng.standard_normal(shape) * shape[-2] ** -0.5).astype(
            np.float32)

    layers = [{"attn_norm": np.ones(h, np.float32),
               "mlp_norm": np.ones(h, np.float32),
               "wq": w(h, nh * d), "wk": w(h, nkv * d), "wv": w(h, nkv * d),
               "wo": w(nh * d, h), "router": w(h, e), "w1": w(e, h, width),
               "w3": w(e, h, width), "w2": w(e, width, h)}
              for _ in range(hf["num_hidden_layers"])]
    return {"embed": w(v, h), "final_norm": np.ones(h, np.float32),
            "lm_head": w(h, v), "layers": layers}


def test_the_copy_is_the_programs_reference():
    from localai_tpu.testing import reference_lm as theirs

    mine = _load(os.path.join(BENCH, "reference", "mellum2.py"))
    with open(os.path.join(BENCH, "configs",
                           "mellum2-12b-a2.5b-d16.json")) as f:
        doc = json.load(f)
    hf = dict(doc, **doc["rehearsal"]["geometry"])
    ids = np.random.default_rng(1).integers(0, hf["vocab_size"], size=150)
    params = _tiny(hf, seed=2)
    a = np.asarray(mine.logits(params, mine.RefConfig.from_hf(hf), ids))
    b = np.asarray(theirs.logits(params, theirs.RefConfig.from_hf(hf), ids))
    assert a.shape == (150, hf["vocab_size"])
    assert np.array_equal(a, b)
    # the window (64 here) is in force: a wider one changes nothing before
    # position 64 and everything after
    wide = dict(hf, sliding_window=4096)
    c = np.asarray(mine.logits(params, mine.RefConfig.from_hf(wide), ids))
    assert np.abs(a - c)[:64].max() < 1e-5 < np.abs(a - c)[100:].max()

    def code(path):
        with open(path) as f:
            text = f.read()
        return text[text.index("from __future__"):]

    assert code(mine.__file__) == code(theirs.__file__)
    assert "localai_tpu" not in code(mine.__file__)
