"""The benchmark's own copy of the plain reference for a decoder LM of the
`afmoe` architecture (Trinity-Large-Preview; PR 35): its forward pass in
straightforward jax.numpy, float32, matrix products at "highest" precision.
It imports nothing of the program under test, so what decides `correct` one
day cannot drift with the program; a self-check
(benchmark/tests/test_reference_trinity.py) holds it equal to the program's
own copy, localai_tpu/testing/reference_afmoe.py, on seeded tiny weights.
benchmark/run.py does not call it yet: comparing logits inside `correct`
needs an edit there (a `benchmark` issue; PERF.md section 7.3).

Covers the configuration trinity-large-ep8-d5: a leading dense layer before
the expert layers, window layers that rotate beside full layers that do
not, RMSNorm on q and k, a gated attention output, sandwich norms, the
embedding's scale, a sigmoid router with a selection bias over 256 experts
of which 32 are held, a shared expert. The layer's equations are written
out in benchmark/configs/trinity-large-ep8-d5.json under `assumed.layer`.
What follows is the program's copy, to the letter.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

FULL, WINDOW = "full", "window"
_HF_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


@dataclasses.dataclass(frozen=True)
class RefConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rms_eps: float
    layer_types: tuple[str, ...]          # FULL / WINDOW per layer
    sliding_window: int
    rope_theta: float
    num_dense_layers: int                 # leading layers with a dense MLP
    num_experts: int                      # routed experts HELD
    experts_per_tok: int
    first_expert: int = 0                 # the share: experts [first, first
    route_scale: float = 1.0              # + num_experts) of the router's
    embed_scale: float = 1.0
    # switches tools/reference_check.py and the tests turn to compute the
    # reference GIVEN a fault (what a served path with that fault would read
    # like); a sound reference leaves them alone
    rotating: tuple[str, ...] = (WINDOW,)  # the layer kinds under RoPE
    qk_norm: bool = True                  # False: q and k not normalised
    attn_gate: bool = True                # False: the output gate left out
    post_norms: bool = True               # False: outputs added as they are
    scoring: str = "sigmoid"              # "softmax": over the router width
    bias_in_choice: bool = True           # False: the k largest scores
    bias_in_weights: bool = False         # True: s + b weighs as well
    leading_dense: bool = True            # False: they run as expert layers

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "RefConfig":
        """From the keys of the published `config.json` (afmoe)."""
        if hf.get("rope_scaling"):
            raise NotImplementedError("this reference rotates by theta alone")
        for name in ("n_group", "topk_group", "num_expert_groups",
                     "num_limited_groups"):
            if (hf.get(name) or 1) != 1:
                raise NotImplementedError(f"{name} other than 1")
        if hf.get("score_func", "sigmoid") != "sigmoid" or not hf.get(
                "route_norm", True):
            raise NotImplementedError("sigmoid scores, renormalised")
        n_layers = hf["num_hidden_layers"]
        heads = hf["num_attention_heads"]
        kinds = tuple(_HF_KINDS[t] for t in hf["layer_types"])
        if len(kinds) != n_layers:
            raise ValueError("layer_types does not match num_hidden_layers")
        share = hf.get("localai_expert_share") or {}
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=n_layers, num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            rms_eps=hf.get("rms_norm_eps", 1e-5), layer_types=kinds,
            sliding_window=hf["sliding_window"],
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            num_dense_layers=hf.get("num_dense_layers", 0),
            num_experts=hf["num_experts"],
            experts_per_tok=hf["num_experts_per_tok"],
            first_expert=share.get("first_expert", 0),
            route_scale=float(hf.get("route_scale", 1.0)),
            embed_scale=(math.sqrt(hf["hidden_size"])
                         if hf.get("mup_enabled") else 1.0))


# ---------------------------------------------------------------- layers

def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, positions, theta: float):
    """x [S, heads, head_dim] at `positions` [S] -> rotated, float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp: dict, cfg: RefConfig, kind: str, block: int):
    """Self-attention of one sequence x [S, h] (the layer's normed input),
    a block of queries at a time against every key, then the output gate
    and W_o."""
    s = x.shape[0]
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ lp["wq"]).reshape(s, nh, d)
    k = (x @ lp["wk"]).reshape(s, nkv, d)
    v = (x @ lp["wv"]).reshape(s, nkv, d)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
    pos = jnp.arange(s)
    if kind in cfg.rotating:
        q, k = rotate(q, pos, cfg.rope_theta), rotate(k, pos, cfg.rope_theta)
    # query head i reads KV head i // (nh / nkv)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    out = []
    for lo in range(0, s, block):
        qi = pos[lo:lo + block, None]
        see = pos[None, :] <= qi
        if kind == WINDOW:
            see &= qi - pos[None, :] < cfg.sliding_window
        score = jnp.einsum("qhd,khd->hqk", q[lo:lo + block], k) / math.sqrt(d)
        prob = jax.nn.softmax(jnp.where(see[None], score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v).reshape(-1, nh * d))
    o = jnp.concatenate(out)
    if cfg.attn_gate:
        o = o * jax.nn.sigmoid(x @ lp["w_agate"])
    return o @ lp["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, lp: dict, cfg: RefConfig):
    """The router over x [S, h]: (experts [S, k] among the whole router
    width, their weights [S, k]): sigmoid scores, the k largest of score +
    bias, the chosen scores (without the bias) renormalised and scaled."""
    logit = x @ lp["router"]                                    # [S, R]
    score = (jax.nn.sigmoid(logit) if cfg.scoring == "sigmoid"
             else jax.nn.softmax(logit, axis=-1))
    biased = score + lp["bias"]
    _, top_e = jax.lax.top_k(biased if cfg.bias_in_choice else score,
                             cfg.experts_per_tok)
    top_s = jnp.take_along_axis(biased if cfg.bias_in_weights else score,
                                top_e, axis=-1)
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
        x = jnp.asarray(params["embed"], jnp.float32)[tokens] * cfg.embed_scale
        layers: Sequence[dict] = params["layers"]
        for i in range(cfg.num_layers):
            lp = layers[i]
            o = attention(rms_norm(x, lp["attn_norm"], cfg.rms_eps), lp, cfg,
                          cfg.layer_types[i], block)
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
    expert at a time (all 32 of a layer at once are 3.6 GB)."""

    def __init__(self, leaf):
        self._leaf = leaf

    def __getitem__(self, e: int):
        return _dense(jax.tree_util.tree_map(lambda a: a[e], self._leaf))


class _Layers:
    """Layer i's weights, made float32 when asked for (one layer of a large
    model at a time, its experts one at a time). The served stacks are two:
    the leading dense layers', then every other layer's."""

    _NAMES = {"moe_gate": "router", "moe_bias": "bias", "moe_w1": "w1",
              "moe_w2": "w2", "moe_w3": "w3"}

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
