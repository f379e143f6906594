"""The plain reference for a decoder LM of the `pangu_ultra_moe`
architecture (openPangu-Ultra-MoE): its forward pass in straightforward
jax.numpy, float32, matrix products at "highest" precision. A sibling of
localai_tpu/testing/reference_lm.py, reference_linear.py and
reference_afmoe.py (each held equal, to the letter, to a file of the
benchmark that a later PR may not edit, so none can gain an architecture;
this one is held equal to benchmark/reference/openpangu_ultra_moe.py).

What the served path (models/llama.py: a latent layer's cache of latent
rows, the absorbed decode kernel, the expanding chunk loop, the leading layer
before the layer scan, the routed expert layer, batching, int8) is compared
against, in tests/test_reference_pangu.py on the CPU and in
tools/reference_check.py on the chip. It shares nothing with that path: no
import from localai_tpu.models or localai_tpu.ops, no kernel, no cache, no
batch axis, and NO absorption: every position's keys and values are expanded
from its latent for every head. One sequence goes in, every position's
logits can come out.

Written from the keys of openPangu-Ultra-MoE-718B's published `config.json`
(`model_type: pangu_ultra_moe`) and the family's description, not from the
served code; the modelling code was not at hand, so where a key leaves a
choice the family's convention is taken (the configuration file lists them
under `assumed`). RMSNorm is x / sqrt(mean(x^2) + eps) * w throughout. With
H heads, R = kv_lora_rank, N = qk_nope_head_dim, P = qk_rope_head_dim, V =
v_head_dim:

- x0 = embed[ids];
- every layer: a = RMSNorm_in(x); q = W_qb RMSNorm_qa(W_qa a), H heads of
  N + P columns; [c | k_pe] = W_kva a (R | P); c = RMSNorm_kva(c); RoPE
  (theta, half-split layout: channel i rotates with i + P / 2; no scaling
  factor) on the last P columns of every query head and on the ONE k_pe all
  heads share; [k_nope | v][h] = W_kvb[h] c (N | V); causal softmax of
  (q_nope . k_nope + q_pe . k_pe) / sqrt(N + P) over everything, in float32;
  o = the heads' sums of v, side by side; x = x + RMSNorm_post_attn(o W_o);
  m = RMSNorm_pre_mlp(x); x = x + RMSNorm_post_mlp(MLP(m)) (`sandwich_norm`;
  without it the outputs are added as they are);
- the MLP of the first `first_k_dense_replace` layers: a SwiGLU of
  intermediate_size;
- the MLP of every other layer: s = sigmoid(m W_r) in float32 (no bias, no
  groups); the k experts with the largest s; w = s[chosen] / (sum s[chosen]
  + 1e-20) * routed_scaling_factor; the weighted sum of the chosen experts'
  SwiGLU of moe_intermediate_size, plus a shared SwiGLU (n_shared_experts
  times as wide) added ungated. Where `localai_expert_share` says so the
  layer holds a SHARE of the routed experts, given as (router width = W_r's
  columns, first expert, experts held): router, choice and renormalisation
  over the whole router width, the sum over the chosen experts in [first,
  first + held) only (the other chips of an expert-parallel layout hold the
  rest; nothing stands in for them);
- final RMSNorm, then the untied head. The multi-token-prediction layer
  (`num_nextn_predict_layers`) is not part of the forward pass: a draft head
  for self-speculation, not loaded.

Departures from the description: none in the mathematics. The published
code de-interleaves the P rotated columns before the half-split rotation:
on seeded weights a fixed permutation of W_qb's and W_kva's columns, which
matters for a real checkpoint only and is left out here and in the served
path alike. The experts' sum is taken expert by expert over the tokens that
chose the expert (a token's other experts add exact zeros), so that a block
of positions is one matrix product; the terms summed per token are the same.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RefConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_eps: float
    rope_theta: float
    num_dense_layers: int                 # leading layers with a dense MLP
    num_experts: int                      # routed experts HELD
    experts_per_tok: int
    first_expert: int = 0                 # the share: experts [first, first
    route_scale: float = 1.0              # + num_experts) of the router's
    post_norms: bool = True               # False: outputs added as they are
    # switches tools/reference_check.py and the tests turn to compute the
    # reference GIVEN a fault (what a served path with that fault would read
    # like); a sound reference leaves them alone
    rotate_k_pe: bool = True              # False: the position key as W_kva
    rotate_q_pe: bool = True              # gives it; the queries' columns
    kv_a_norm: bool = True                # False: the latent not normalised
    q_a_norm: bool = True                 # False: nor the query's
    scale_width: int = 0                  # softmax scale width^-1/2; 0: N + P
    value_shift: int = 0                  # values made of columns [shift,
    scoring: str = "sigmoid"              # shift + R) of [c | k_pe]
    leading_dense: bool = True            # False: they run as expert layers

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "RefConfig":
        """From the keys of the published `config.json` (pangu_ultra_moe)."""
        if hf.get("rope_scaling"):
            raise NotImplementedError("this reference rotates by theta alone")
        for name in ("n_group", "topk_group"):
            if (hf.get(name) or 1) != 1:
                raise NotImplementedError(f"{name} other than 1")
        if not hf.get("norm_topk_prob", True):
            raise NotImplementedError("the chosen scores are renormalised")
        heads = hf["num_attention_heads"]
        if hf.get("num_key_value_heads", heads) != heads:
            raise NotImplementedError("every head has keys of its own")
        share = hf.get("localai_expert_share") or {}
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"], num_heads=heads,
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            num_dense_layers=hf.get("first_k_dense_replace", 0),
            num_experts=hf["n_routed_experts"],
            experts_per_tok=hf["num_experts_per_tok"],
            first_expert=share.get("first_expert", 0),
            route_scale=float(hf.get("routed_scaling_factor", 1.0)),
            post_norms=bool(hf.get("sandwich_norm", False)))


# ---------------------------------------------------------------- layers

def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, positions, theta: float):
    """x [S, heads, width] at `positions` [S] -> rotated, float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp: dict, cfg: RefConfig, block: int):
    """Latent self-attention of one sequence x [S, h] (the layer's normed
    input), NOT absorbed: every position's latent is expanded to every
    head's keys and values, a block of queries at a time against them all;
    then W_o."""
    s = x.shape[0]
    nh, r = cfg.num_heads, cfg.kv_lora_rank
    n, v = cfg.qk_nope_head_dim, cfg.v_head_dim
    pos = jnp.arange(s)
    qa = x @ lp["wq_a"]
    if cfg.q_a_norm:
        qa = rms_norm(qa, lp["q_a_norm"], cfg.rms_eps)
    q = (qa @ lp["wq_b"]).reshape(s, nh, -1)
    q_nope, q_pe = q[..., :n], q[..., n:]
    row = x @ lp["wkv_a"]
    c, k_pe = row[:, :r], row[:, None, r:]                  # [S, 1, P]
    if cfg.kv_a_norm:
        c = rms_norm(c, lp["kv_a_norm"], cfg.rms_eps)
    if cfg.rotate_q_pe:
        q_pe = rotate(q_pe, pos, cfg.rope_theta)
    if cfg.rotate_k_pe:
        k_pe = rotate(k_pe, pos, cfg.rope_theta)
    kv = (c @ lp["wkv_b"]).reshape(s, nh, n + v)
    k_nope, val = kv[..., :n], kv[..., n:]
    if cfg.value_shift:
        # (the fault: the values read from the wrong columns of the row)
        cols = jnp.concatenate([c, k_pe[:, 0]], -1)
        cols = cols[:, cfg.value_shift:cfg.value_shift + r]
        val = (cols @ lp["wkv_b"]).reshape(s, nh, n + v)[..., n:]
    width = cfg.scale_width or n + cfg.qk_rope_head_dim
    out = []
    for lo in range(0, s, block):
        see = pos[None, :] <= pos[lo:lo + block, None]
        score = (jnp.einsum("qhd,khd->hqk", q_nope[lo:lo + block], k_nope)
                 + jnp.einsum("qhd,kd->hqk", q_pe[lo:lo + block], k_pe[:, 0])
                 ) / math.sqrt(width)
        prob = jax.nn.softmax(jnp.where(see[None], score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, val).reshape(-1, nh * v))
    return jnp.concatenate(out) @ lp["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, lp: dict, cfg: RefConfig):
    """The router over x [S, h]: (experts [S, k] among the whole router
    width, their weights [S, k]): sigmoid scores, the k largest, the chosen
    scores renormalised and scaled."""
    logit = x @ lp["router"]                                    # [S, R]
    score = (jax.nn.sigmoid(logit) if cfg.scoring == "sigmoid"
             else jax.nn.softmax(logit, axis=-1))
    top_s, top_e = jax.lax.top_k(score, cfg.experts_per_tok)
    return top_e, (top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
                   * cfg.route_scale)


def experts(x, lp: dict, cfg: RefConfig):
    """The expert layer over x [S, h]: the sum over the chosen experts held
    here, each under its weight, and the shared expert."""
    top_e, top_w = route(x, lp, cfg)
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        # this expert's weight per token: its renormalised score where the
        # token chose it, else 0. The e-th expert held is expert
        # first_expert + e of the router's R; the others add nothing here
        w = jnp.where(top_e == cfg.first_expert + e, top_w, 0.0).sum(-1)
        y = y + w[:, None] * swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y + swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def hidden_states(params: dict, cfg: RefConfig, tokens, block: int | None
                  = None, precision: str = "highest"):
    """tokens [S] -> the final norm's output [S, h], float32. `block`: how
    many query positions attention scores at a time (memory only).
    `precision`: of every matrix product; "bfloat16" is the control one
    precision down (tools/reference_check.py), never the reference."""
    with jax.default_matmul_precision(precision):
        tokens = jnp.asarray(tokens)
        block = block or tokens.shape[0]
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        layers: Sequence[dict] = params["layers"]
        for i in range(cfg.num_layers):
            lp = layers[i]
            o = attention(rms_norm(x, lp["attn_norm"], cfg.rms_eps), lp, cfg,
                          block)
            if cfg.post_norms:
                o = rms_norm(o, lp["attn_post_norm"], cfg.rms_eps)
            x = x + o
            m = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
            if i < cfg.num_dense_layers and cfg.leading_dense:
                m = swiglu(m, lp["w_gate"], lp["w_up"], lp["w_down"])
            else:
                # (the fault: a leading layer has no experts of its own and
                # borrows the first expert layer's)
                m = experts(m, lp if "router" in lp
                            else layers[cfg.num_dense_layers], cfg)
            if cfg.post_norms:
                m = rms_norm(m, lp["mlp_post_norm"], cfg.rms_eps)
            x = x + m
        return rms_norm(x, params["final_norm"], cfg.rms_eps)


def head(params: dict, cfg: RefConfig, hidden, precision: str = "highest"):
    """Logits [.., V] of hidden states [.., h]."""
    with jax.default_matmul_precision(precision):
        return hidden @ params["lm_head"]


def logits(params: dict, cfg: RefConfig, tokens, block: int | None = None):
    """tokens [S] -> logits [S, V] float32: position i's row is the
    distribution of token i + 1 given tokens 0..i."""
    return head(params, cfg, hidden_states(params, cfg, tokens, block))


# ------------------------------------- weights, from the served layout

def _dense(leaf):
    """A float32 array from a weight leaf of the served pytree: a plain
    array, or the int8 form {"q", "s"} (value q * s, scale per output
    channel), so that the reference computes with the very values the
    served path dequantises to."""
    if isinstance(leaf, dict):
        return leaf["q"].astype(jnp.float32) * leaf["s"].astype(jnp.float32)
    return jnp.asarray(leaf, jnp.float32)


class _Experts:
    """A layer's routed experts' matrices [E, in, out], made float32 an
    expert at a time."""

    def __init__(self, leaf):
        self._leaf = leaf

    def __getitem__(self, e: int):
        return _dense(jax.tree_util.tree_map(lambda a: a[e], self._leaf))


class _Layers:
    """Layer i's weights, made float32 when asked for (one layer of a large
    model at a time, its experts one at a time). The served stacks are two:
    the leading dense layers', then every other layer's."""

    _NAMES = {"moe_gate": "router", "moe_w1": "w1", "moe_w2": "w2",
              "moe_w3": "w3"}

    def __init__(self, leading: dict | None, stacked: dict):
        self._leading, self._stacked = leading, stacked
        self._lead = (0 if leading is None
                      else jax.tree_util.tree_leaves(leading)[0].shape[0])

    def __getitem__(self, i: int) -> dict:
        stack, n = ((self._leading, i) if i < self._lead
                    else (self._stacked, i - self._lead))
        pick = jax.tree_util.tree_map(lambda a: a[n], stack)
        return {self._NAMES.get(k, k):
                _Experts(v) if k.startswith("moe_w") else _dense(v)
                for k, v in pick.items()}


def from_served(params: dict) -> dict:
    """The served parameter pytree (params["leading"] and params["layers"],
    each stacked on a leading axis, every matrix laid out for x @ W,
    possibly int8) as the reference takes it."""
    return {"embed": _dense(params["embed"]),
            "final_norm": _dense(params["final_norm"]),
            "lm_head": _dense(params["lm_head"]),
            "layers": _Layers(params.get("leading"), params["layers"])}
