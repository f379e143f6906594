"""Operations and bytes one decode step needs, from the configuration's
shapes alone, and the least time a chip could take for it. Kept with the
benchmark so that no PR that claims a gain can change the yardstick.

One decode step at batch B (live slots) and mean context C (tokens of KV each
live slot attends over) has to:
  read every weight it multiplies with once: the attention projections, the
    MLP (dense), or the router and the experts some token is routed to
    (sparse: with uniform routing B tokens x k of E experts touch
    E * (1 - (1 - k/E)**B) experts of a layer in expectation), and the head;
    int8 weights are one byte an element plus an f32 scale per output channel
  read B * C tokens of K and V in every layer (int8: one byte an element
    plus an f32 scale per token and KV head; else the cache's item size)
  read B rows of the embedding
  do 2 operations per weight element a token multiplies with (k experts of
    the E, not all of them), and 4 * C * heads * head_dim per layer for
    attention (q.k and p.v)
Writes (one token of KV a slot, the logits) are left out: they are under a
thousandth of the reads.
"""
from __future__ import annotations

WEIGHT_BYTES = {"int8": 1.0, "q8": 1.0, "int4": 0.5, "q4": 0.5,
                "bfloat16": 2.0, "float16": 2.0, "float32": 4.0}
KV_BYTES = {"int8": 1.0, "q8_0": 1.0, "q8": 1.0, "": 2.0, "bf16": 2.0,
            "f16": 2.0, "f32": 4.0}


def decode_step_cost(cfg: dict, serving: dict, batch: float,
                     context: float) -> dict:
    h = cfg["hidden_size"]
    L = cfg["num_hidden_layers"]
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nh)
    hd = cfg.get("head_dim") or h // nh
    inter = cfg["intermediate_size"]
    V = cfg["vocab_size"]
    E = cfg.get("num_local_experts", 0) or 0
    k = cfg.get("num_experts_per_tok", 0) or 0
    wb = WEIGHT_BYTES[serving.get("dtype", "bfloat16")]
    quant = wb <= 1.0
    kvb = KV_BYTES[serving.get("cache_type_k", "")]
    act_bytes = 2.0 if serving.get("dtype") != "float32" else 4.0

    def matrix(rows, cols):
        # bytes of one [rows, cols] weight: body + f32 scale per column
        return rows * cols * wb + (cols * 4.0 if quant else 0.0)

    attn_elems = h * nh * hd + 2 * h * nkv * hd + nh * hd * h
    attn_bytes = (matrix(h, nh * hd) + 2 * matrix(h, nkv * hd)
                  + matrix(nh * hd, h))
    if E:
        touched = E * (1.0 - (1.0 - k / E) ** max(batch, 0.0))
        expert_bytes = 2 * matrix(h, inter) + matrix(inter, h)
        mlp_bytes = touched * expert_bytes + h * E * 4.0      # + f32 router
        mlp_elems_per_token = k * 3 * h * inter + h * E
    else:
        touched = 0.0
        mlp_bytes = 2 * matrix(h, inter) + matrix(inter, h)
        mlp_elems_per_token = 3 * h * inter
    head_bytes = matrix(h, V)
    norm_bytes = (2 * L + 1) * h * act_bytes
    weight_bytes = L * (attn_bytes + mlp_bytes) + head_bytes + norm_bytes
    kv_token_bytes = L * 2 * nkv * (hd * kvb + (4.0 if kvb <= 1.0 else 0.0))
    kv_bytes = batch * context * kv_token_bytes
    embed_bytes = batch * h * act_bytes
    ops_per_token = (2.0 * (L * (attn_elems + mlp_elems_per_token) + h * V)
                     + L * 4.0 * context * nh * hd)
    return {
        "batch": batch, "context": context,
        "weight_bytes": weight_bytes, "kv_bytes": kv_bytes,
        "bytes": weight_bytes + kv_bytes + embed_bytes,
        "ops": batch * ops_per_token,
        "kv_bytes_per_token": kv_token_bytes,
        "experts_touched_per_layer": touched,
    }


def least_step_seconds(cost: dict, peaks: dict) -> dict:
    """The roofline: the larger of bytes over bandwidth and operations over
    the matmul peak. Activations are bf16 whatever the weights' storage (an
    int8 weight is dequantised into the bf16 matmul), so the bf16 peak."""
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = cost["ops"] / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_ops),
            "bound": "bandwidth" if t_bytes >= t_ops else "compute",
            "bytes_s": t_bytes, "ops_s": t_ops}
