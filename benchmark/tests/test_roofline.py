"""The bytes-and-operations function for a decode step against numbers
worked out by hand from published shapes: the expert configuration's, and the
dense Mistral-7B's (measured by PR 23, PERF.md section 6; not a cell yet)."""
import json
import os

import pytest

from benchmark.harness import roofline

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
# mistralai/Mistral-7B-Instruct-v0.3, served as the expert configuration is
MISTRAL_7B = dict(hidden_size=4096, intermediate_size=14336,
                  num_hidden_layers=32, num_attention_heads=32,
                  num_key_value_heads=8, head_dim=128, vocab_size=32768,
                  tie_word_embeddings=False,
                  serving=load("mixtral-8x7b-d6")["serving"])


def test_mistral_7b_decode_step():
    c = MISTRAL_7B
    cost = roofline.decode_step_cost(c, c["serving"], batch=32, context=400)
    h, inter, V, L = 4096, 14336, 32768, 32
    attn = 2 * h * h + 2 * h * 1024                     # q, o; k, v
    mlp = 3 * h * inter
    scales = 4 * (h + 1024 + 1024 + h + inter + inter + h)
    weights = L * (attn + mlp + scales) + h * V + 4 * V + (2 * L + 1) * h * 2
    assert cost["weight_bytes"] == pytest.approx(weights)
    assert 7.0e9 < cost["weight_bytes"] < 7.2e9        # "7.1 GB" of the issue
    kv_token = L * 2 * 8 * (128 + 4)                    # 66 KB a token
    assert cost["kv_bytes_per_token"] == kv_token == 67584
    assert cost["kv_bytes"] == pytest.approx(32 * 400 * kv_token)
    ops_tok = 2 * (L * (attn + mlp) + h * V) + L * 4 * 400 * 32 * 128
    assert cost["ops"] == pytest.approx(32 * ops_tok)
    least = roofline.least_step_seconds(cost, PEAKS)
    assert least["bound"] == "bandwidth"
    assert least["seconds"] == pytest.approx(cost["bytes"] / 819e9)
    assert 0.0095 < least["seconds"] < 0.0100


def test_mixtral_d6_decode_step():
    c = load("mixtral-8x7b-d6")
    h, inter, V, L, E, k = 4096, 14336, 32000, 6, 8, 2
    expert = 3 * h * inter + 4 * (2 * inter + h)
    attn = 2 * h * h + 2 * h * 1024 + 4 * (h + 1024 + 1024 + h)
    one = roofline.decode_step_cost(c, c["serving"], batch=1, context=300)
    # one token touches exactly its 2 experts of a layer
    assert one["experts_touched_per_layer"] == pytest.approx(2.0)
    w1 = L * (attn + 2 * expert + h * E * 4) + h * V + 4 * V + (2 * L + 1) * h * 2
    assert one["weight_bytes"] == pytest.approx(w1)
    full = roofline.decode_step_cost(c, c["serving"], batch=32, context=400)
    touched = E * (1 - (1 - k / E) ** 32)
    assert full["experts_touched_per_layer"] == pytest.approx(touched)
    assert 7.99 < touched < 8.0
    assert 8.7e9 < full["weight_bytes"] < 8.9e9        # "8.7 GB of experts"
    assert full["kv_bytes_per_token"] == L * 2 * 8 * (128 + 4) == 12672
    # operations count the 2 experts a token uses, not all 8
    ops_tok = (2 * (L * (2 * h * h + 2 * h * 1024 + k * 3 * h * inter + h * E)
                    + h * V) + L * 4 * 400 * 32 * 128)
    assert full["ops"] == pytest.approx(32 * ops_tok)
    least = roofline.least_step_seconds(full, PEAKS)
    assert least["bound"] == "bandwidth" and 0.0105 < least["seconds"] < 0.0112


def test_compute_bound_when_the_batch_is_huge():
    c = MISTRAL_7B
    cost = roofline.decode_step_cost(c, c["serving"], batch=4096, context=16)
    assert roofline.least_step_seconds(cost, PEAKS)["bound"] == "compute"
