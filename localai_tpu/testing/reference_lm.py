"""The plain reference: a decoder LM's forward pass in straightforward
jax.numpy, float32, matrix products at "highest" precision.

What the served path (models/llama.py: kernels, caches, rings, batching,
int8) is compared against, in tests/test_reference_lm.py on the CPU and in
tools/reference_check.py on the chip. It shares nothing with that path: no
import from localai_tpu.models or localai_tpu.ops, no kernel, no cache, no
batch axis. One sequence goes in, every position's logits can come out.

It covers the Llama family as this repo serves it and is written from the
published descriptions (the HF `config.json` keys and the RoPE papers'
formulae), not from the served code:

- pre-norm residual blocks, RMSNorm (x / sqrt(mean(x^2) + eps) * w);
- grouped-query attention, scores / sqrt(head_dim), softmax in float32;
  per layer a causal mask (`full`) or a causal mask that also hides keys
  `sliding_window` or more positions back (`window`: query i sees key j iff
  j <= i and i - j < sliding_window);
- RoPE in the half-split layout (channel i rotates with i + head_dim/2), one
  parameter set per layer kind: plain (theta), linear, llama3, or YaRN
  (Peng et al. 2023: per-channel blend of interpolated and original
  frequencies between the beta_fast/beta_slow correction dims, and cos/sin
  scaled by `attention_factor`, or 0.1 ln(factor) + 1 where none is given);
- a dense SwiGLU MLP, or sparse experts: router logits h -> E without bias,
  softmax over all E in float32, the top-k probabilities renormalised to sum
  to 1, and each token's output the weighted sum of its k experts' SwiGLU;
- final RMSNorm, then the head (the embedding transposed where tied).

Mixtral is the case "every layer full, 8 experts top-2"; Mellum2 "three
window layers then a full one, each kind its own RoPE, 64 experts top-8".

A model with linear-attention layers (a recurrent state beside the softmax
layers' cache: Solar-Open2) has its reference in the sibling
localai_tpu/testing/reference_linear.py: this file's code is held equal, to
the letter, to benchmark/reference/mellum2.py.

Departures from the published descriptions: none in the mathematics. The
experts' sum is taken expert by expert over the tokens that chose the expert
(a token's other experts add exact zeros), so that a block of positions is
one matrix product; the terms summed per token are the same.
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
class RefRope:
    theta: float = 10000.0
    kind: str = "default"            # default | linear | llama3 | yarn
    factor: float = 1.0
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0

    @classmethod
    def from_hf(cls, rp: dict | None, theta: float, max_position: int):
        rp = rp or {}
        kind = rp.get("rope_type", rp.get("type")) or "default"
        return cls(
            theta=float(rp.get("rope_theta", theta)), kind=kind,
            factor=float(rp.get("factor", 1.0)),
            original_max_position=int(rp.get(
                "original_max_position_embeddings", max_position)),
            beta_fast=float(rp.get("beta_fast", 32.0)),
            beta_slow=float(rp.get("beta_slow", 1.0)),
            attention_factor=rp.get("attention_factor"),
            low_freq_factor=float(rp.get("low_freq_factor", 1.0)),
            high_freq_factor=float(rp.get("high_freq_factor", 4.0)))


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
    sliding_window: int | None
    rope: dict[str, RefRope]              # by layer kind
    num_experts: int = 0                  # 0: dense MLP
    experts_per_tok: int = 0
    tie_embeddings: bool = False

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "RefConfig":
        """From the keys of a published `config.json` (Llama, Mistral,
        Mixtral, Qwen2, Mellum)."""
        n_layers = hf["num_hidden_layers"]
        heads = hf["num_attention_heads"]
        window = hf.get("sliding_window")
        if hf.get("use_sliding_window") is False:
            window = None
        if hf.get("layer_types"):
            kinds = tuple(_HF_KINDS[t] for t in hf["layer_types"])
        else:
            kinds = (WINDOW if window else FULL,) * n_layers
        if len(kinds) != n_layers:
            raise ValueError("layer_types does not match num_hidden_layers")
        max_pos = hf.get("max_position_embeddings", 8192)
        theta = hf.get("rope_theta", 10000.0)
        rp = hf.get("rope_parameters") or hf.get("rope_scaling") or {}
        if any(k in rp for k in _HF_KINDS):
            rope = {kind: RefRope.from_hf(rp.get(name), theta, max_pos)
                    for name, kind in _HF_KINDS.items()}
        else:
            one = RefRope.from_hf(rp, theta, max_pos)
            rope = {FULL: one, WINDOW: one}
        experts = hf.get("num_experts", hf.get("num_local_experts", 0)) or 0
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=n_layers, num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            rms_eps=hf.get("rms_norm_eps", 1e-5), layer_types=kinds,
            sliding_window=window, rope=rope, num_experts=experts,
            experts_per_tok=hf.get("num_experts_per_tok", 2) if experts else 0,
            tie_embeddings=hf.get("tie_word_embeddings", False))


# ------------------------------------------------------------------ RoPE

def rope_frequencies(rope: RefRope, head_dim: int):
    """(angular frequency per channel pair [head_dim/2] float32, the factor
    that multiplies cos and sin)."""
    half = head_dim // 2
    i = jnp.arange(half, dtype=jnp.float32)
    freq = rope.theta ** (-i / half)          # theta^(-2i/d)
    if rope.kind == "default":
        return freq, 1.0
    if rope.kind == "linear":
        return freq / rope.factor, 1.0
    if rope.kind == "llama3":
        # wavelengths longer than original/low_freq_factor are interpolated,
        # shorter than original/high_freq_factor kept, the band between
        # blended linearly in original/wavelength
        orig = rope.original_max_position
        wavelen = 2 * math.pi / freq
        smooth = ((orig / wavelen - rope.low_freq_factor)
                  / (rope.high_freq_factor - rope.low_freq_factor))
        blended = (1 - smooth) * freq / rope.factor + smooth * freq
        out = jnp.where(wavelen > orig / rope.low_freq_factor,
                        freq / rope.factor,
                        jnp.where(wavelen < orig / rope.high_freq_factor,
                                  freq, blended))
        return out, 1.0
    if rope.kind == "yarn":
        # the channel pair at which a full context holds `rotations` turns:
        # d ln(L / (2 pi rotations)) / (2 ln theta)
        def pair_of(rotations):
            return (head_dim * math.log(rope.original_max_position
                                        / (rotations * 2 * math.pi))
                    / (2 * math.log(rope.theta)))

        low = max(math.floor(pair_of(rope.beta_fast)), 0)
        high = min(math.ceil(pair_of(rope.beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001
        # 0 below `low` (fast channels keep their frequency), 1 above `high`
        # (slow channels are interpolated by 1/factor), linear between
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        out = freq / rope.factor * ramp + freq * (1.0 - ramp)
        scale = rope.attention_factor
        if scale is None:
            scale = (0.1 * math.log(rope.factor) + 1.0
                     if rope.factor > 1 else 1.0)
        return out, float(scale)
    raise ValueError(f"unknown rope kind {rope.kind!r}")


def rotate(x, positions, rope: RefRope):
    """x [S, heads, head_dim] at `positions` [S] -> rotated, float32."""
    freq, scale = rope_frequencies(rope, x.shape[-1])
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ---------------------------------------------------------------- layers

def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def attention(x, lp: dict, cfg: RefConfig, kind: str, block: int):
    """Self-attention of one sequence x [S, h], a block of queries at a
    time against every key."""
    s = x.shape[0]
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    pos = jnp.arange(s)
    q = rotate(q.reshape(s, nh, d), pos, cfg.rope[kind])
    k = rotate(k.reshape(s, nkv, d), pos, cfg.rope[kind])
    v = v.reshape(s, nkv, d)
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
    return jnp.concatenate(out) @ lp["wo"]


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts(x, lp: dict, cfg: RefConfig):
    """Sparse experts over x [S, h]: softmax router, top-k, renormalise,
    weighted sum of the chosen experts' SwiGLU."""
    prob = jax.nn.softmax(x @ lp["router"], axis=-1)            # [S, E]
    top_p, top_e = jax.lax.top_k(prob, cfg.experts_per_tok)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        # this expert's weight per token: its renormalised probability
        # where the token chose it, else 0
        w = jnp.where(top_e == e, top_p, 0.0).sum(-1)
        y = y + w[:, None] * swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


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
            x = x + attention(rms_norm(x, lp["attn_norm"], cfg.rms_eps), lp,
                              cfg, cfg.layer_types[i], block)
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
            if cfg.num_experts:
                x = x + experts(h, lp, cfg)
            else:
                x = x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return rms_norm(x, params["final_norm"], cfg.rms_eps)


def head(params: dict, cfg: RefConfig, hidden, precision: str = "highest"):
    """Logits [.., V] of hidden states [.., h]."""
    with jax.default_matmul_precision(precision):
        if cfg.tie_embeddings:
            return hidden @ jnp.asarray(params["embed"], jnp.float32).T
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
    """A layer's expert matrices [E, in, out], one expert made float32 when
    it is indexed (a layer's experts whole are 5.6 GB of float32 at
    Mixtral's widths, beside the served engine)."""

    def __init__(self, leaf):
        self._leaf = leaf

    def __getitem__(self, e: int):
        return _dense(jax.tree_util.tree_map(lambda a: a[e], self._leaf))


class _Layers:
    """Layer i's weights, made float32 when asked for (one layer of a large
    model at a time, and of its experts one at a time)."""

    _NAMES = {"moe_gate": "router", "moe_w1": "w1", "moe_w2": "w2",
              "moe_w3": "w3"}

    def __init__(self, stacked: dict):
        self._stacked = stacked

    def __getitem__(self, i: int) -> dict:
        pick = jax.tree_util.tree_map(lambda a: a[i], self._stacked)
        return {self._NAMES.get(k, k): _Experts(v) if k.startswith("moe_w")
                else _dense(v) for k, v in pick.items()}


def from_served(params: dict) -> dict:
    """The served parameter pytree (layers stacked on a leading axis, every
    matrix laid out for x @ W, possibly int8) as the reference takes it."""
    out = {"embed": _dense(params["embed"]),
           "final_norm": _dense(params["final_norm"]),
           "layers": _Layers(params["layers"])}
    if "lm_head" in params:
        out["lm_head"] = _dense(params["lm_head"])
    return out
