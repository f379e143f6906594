"""The plain reference for a decoder LM of the `nemotron_h` architecture
(NVIDIA-Nemotron-3-Super-120B-A12B): its forward pass in straightforward
jax.numpy, float32, matrix products at "highest" precision. A sibling of
localai_tpu/testing/reference_lm.py, reference_linear.py, reference_afmoe.py
and reference_pangu.py (each held equal, to the letter, to a file of the
benchmark that a later PR may not edit, so none can gain an architecture;
this one is held equal to benchmark/reference/nemotron_h.py).

What the served path (models/llama.py: the period scan over layers stacked
by kind, a state-space layer's chunked form and its decode kernel, the state
cache, the routed latent expert layer, batching, int8) is compared against,
in tests/test_reference_nemotron_h.py on the CPU and in
tools/reference_check.py on the chip. It shares nothing with that path: no
import from localai_tpu.models or localai_tpu.ops, no kernel, no cache, no
batch axis, no chunked scan: the published pattern is walked LAYER BY LAYER
(one mixer a layer) and a state-space layer's recurrence is a plain
`lax.scan` over the tokens. One sequence goes in, every position's logits
can come out.

Written from the keys of the published `config.json` (`model_type:
nemotron_h`) and the family's description, not from the served code; the
modelling code was not at hand, so where a key leaves a choice the family's
convention is taken (the configuration file lists them under `assumed`).
RMSNorm is x / sqrt(mean(x^2) + eps) * w throughout; no matrix has a bias.
`hybrid_override_pattern` has one letter a layer; every layer is
x = x + part(RMSNorm(x)) with its part:

- `M`, Mamba-2 (H heads of P channels, G groups, state N, K taps):
  [z | xBC | dt] = W_in h, widths H P | H P + 2 G N | H; xBC =
  silu(conv1d(xBC)), causal, depthwise, K taps, with a bias, zeros before
  the sequence; split into x [H, P], B [G, N], C [G, N], head j reading group
  j // (H / G); dt = softplus(dt + dt_bias) a head (no clamp), A =
  -exp(A_log) a head; state S [P, N] float32 a head, S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t; y = RMSNorm over each
  group's H P / G channels of (y silu(z)), with a gain; out W_out y.
- `*`, attention: heads of head_dim over grouped KV heads, causal
  softmax(q k^T / sqrt(head_dim)) v, W_o; no position encoding, no q/k norm,
  no gate.
- `E`, latent experts: s = sigmoid(W_r h) in float32; the k largest of
  s + b (a selection bias that chooses and never weighs; no groups); w =
  s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor; u = W_in_lat h
  (into the latent); r = sum_i w_i W2_i relu(W1_i u)^2 over the chosen
  experts; out W_out_lat r + W2_s relu(W1_s h)^2 (the shared expert, over
  the hidden state, added ungated). Where `localai_expert_share` says so the
  layer holds a SHARE of the routed experts, [first, first + held) of the
  router's width: router, choice and renormalisation over the whole width,
  the sum over the chosen experts held only (the other chips of an
  expert-parallel layout hold the rest; nothing stands in for them).
- final RMSNorm, then the untied head. The multi-token-prediction layer
  (`num_nextn_predict_layers`) is not part of the forward pass: a draft head
  for self-speculation, not loaded.

The experts' sum is taken expert by expert over all tokens under the
token's weight for that expert (0 where it did not choose it), so that a
sequence is one matrix product an expert; the terms summed per token are
the same.
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
    pattern: str                          # one of M * E a layer
    num_heads: int
    num_kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    rms_eps: float
    num_experts: int                      # routed experts HELD
    experts_per_tok: int
    first_expert: int = 0                 # the share: experts [first, first
    route_scale: float = 1.0              # + num_experts) of the router's
    # switches tools/reference_check.py and the tests turn to compute the
    # reference GIVEN a fault (what a served path with that fault would read
    # like); a sound reference leaves them alone
    state_dtype: str = "float32"          # "bfloat16": the state rounded
    squared: bool = True                  # False: relu for relu^2
    skip_d: bool = True                   # False: no D x term
    conv_bias: bool = True                # False: the convolution's bias off
    gate_before_norm: bool = True         # False: RMSNorm(y) silu(z)
    latent_in: bool = True                # False: the hidden state's first
    bias_in_choice: bool = True           # columns for W_in_lat h; False:
    dt_bias: bool = True                  # top k by s alone; no dt_bias

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "RefConfig":
        """From the keys of the published `config.json` (nemotron_h)."""
        for name in ("n_group", "topk_group"):
            if (hf.get(name) or 1) != 1:
                raise NotImplementedError(f"{name} other than 1")
        if not hf.get("norm_topk_prob", True):
            raise NotImplementedError("the chosen scores are renormalised")
        for name in ("mamba_proj_bias", "use_bias", "mlp_bias",
                     "attention_bias"):
            if hf.get(name):
                raise NotImplementedError(f"{name}: no matrix has a bias")
        pattern = hf["hybrid_override_pattern"]
        if set(pattern) - set("M*E") or len(pattern) != hf[
                "num_hidden_layers"]:
            raise NotImplementedError(f"pattern {pattern!r}")
        if hf.get("mlp_hidden_act", "relu2") != "relu2":
            raise NotImplementedError("experts other than relu^2")
        share = hf.get("localai_expert_share") or {}
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            pattern=pattern, num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
            ssm_heads=hf["mamba_num_heads"],
            ssm_head_dim=hf["mamba_head_dim"], ssm_groups=hf["n_groups"],
            ssm_state=hf["ssm_state_size"],
            rms_eps=hf.get("norm_eps", hf.get("layer_norm_epsilon", 1e-5)),
            num_experts=hf["n_routed_experts"],
            experts_per_tok=hf["num_experts_per_tok"],
            first_expert=share.get("first_expert", 0),
            route_scale=float(hf.get("routed_scaling_factor", 1.0)))


# ---------------------------------------------------------------- layers

def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def relu2(x, cfg: RefConfig):
    r = jax.nn.relu(x)
    return r * r if cfg.squared else r


def mamba(h, lp: dict, cfg: RefConfig, carried=None):
    """The Mamba-2 mixer over one sequence h [S, hidden] (the layer's
    normed input), the recurrence a token at a time. `carried`: the (state,
    last K-1 inputs of the convolution) to start from where not zeros (what
    another sequence left: a fault, never the model). Returns the layer's
    output and what this sequence leaves."""
    s = h.shape[0]
    nh, p, g, n = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                   cfg.ssm_state)
    inner = nh * p
    zxd = h @ lp["w_in"]
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:-nh], zxd[:, -nh:]
    taps = lp["conv"].shape[-1]
    state0, tail = carried or (jnp.zeros((nh, p, n)),
                               jnp.zeros((taps - 1, xbc.shape[1])))
    padded = jnp.concatenate([tail, xbc])
    conv = sum(padded[i:i + s] * lp["conv"][:, i] for i in range(taps))
    if cfg.conv_bias:
        conv = conv + lp["conv_bias"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, nh, p)
    bm = xbc[:, inner:inner + g * n].reshape(s, g, n)
    cm = xbc[:, inner + g * n:].reshape(s, g, n)
    bm, cm = (jnp.repeat(v, nh // g, axis=1) for v in (bm, cm))  # a head's
    dt = jax.nn.softplus(dt + lp["dt_bias"] if cfg.dt_bias else dt)
    a = -jnp.exp(lp["A_log"])                                    # [H]
    dtype = jnp.dtype(cfg.state_dtype)

    def token(state, xs):
        x_t, b_t, c_t, dt_t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state.astype(jnp.float32)
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = state.astype(dtype)
        y = jnp.einsum("hpn,hn->hp", state.astype(jnp.float32), c_t)
        return state, y

    state, y = jax.lax.scan(token, state0.astype(dtype), (x, bm, cm, dt))
    if cfg.skip_d:
        y = y + lp["D"][:, None] * x
    y, gate = y.reshape(s, g, -1), jax.nn.silu(z).reshape(s, g, -1)

    def norm(v):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + cfg.rms_eps)

    y = norm(y * gate) if cfg.gate_before_norm else norm(y) * gate
    return ((y.reshape(s, inner) * lp["ssm_norm"]) @ lp["w_out"],
            (state.astype(jnp.float32), padded[-(taps - 1):]))


def attention(h, lp: dict, cfg: RefConfig, block: int):
    """Grouped-query causal self-attention of one sequence h [S, hidden],
    a block of queries at a time against every key; no position encoding."""
    s = h.shape[0]
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ lp["wq"]).reshape(s, nh, d)
    k = jnp.repeat((h @ lp["wk"]).reshape(s, nkv, d), nh // nkv, axis=1)
    v = jnp.repeat((h @ lp["wv"]).reshape(s, nkv, d), nh // nkv, axis=1)
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, block):
        see = pos[None, :] <= pos[lo:lo + block, None]
        score = jnp.einsum("qhd,khd->hqk", q[lo:lo + block], k) / math.sqrt(d)
        prob = jax.nn.softmax(jnp.where(see[None], score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v).reshape(-1, nh * d))
    return jnp.concatenate(out) @ lp["wo"]


def route(h, lp: dict, cfg: RefConfig):
    """The router over h [S, hidden]: (experts [S, k] among the whole
    router width, their weights [S, k])."""
    score = jax.nn.sigmoid(h @ lp["router"])                     # [S, R]
    choose = score + lp["router_bias"] if cfg.bias_in_choice else score
    _, top_e = jax.lax.top_k(choose, cfg.experts_per_tok)
    top_s = jnp.take_along_axis(score, top_e, axis=-1)
    return top_e, (top_s / (top_s.sum(-1, keepdims=True) + 1e-20)
                   * cfg.route_scale)


def experts(h, lp: dict, cfg: RefConfig):
    """The latent expert layer over h [S, hidden]."""
    top_e, top_w = route(h, lp, cfg)
    u = (h @ lp["w_lat_in"] if cfg.latent_in
         else h[:, :lp["w_lat_in"].shape[1]])
    r = jnp.zeros_like(u)
    for e in range(cfg.num_experts):
        # this expert's weight per token: its renormalised score where the
        # token chose it, else 0. The e-th expert held is expert
        # first_expert + e of the router's; the others add nothing here
        w = jnp.where(top_e == cfg.first_expert + e, top_w, 0.0).sum(-1)
        r = r + w[:, None] * (relu2(u @ lp["w1"][e], cfg) @ lp["w2"][e])
    return r @ lp["w_lat_out"] + relu2(h @ lp["ws_up"], cfg) @ lp["ws_down"]


def hidden_states(params: dict, cfg: RefConfig, tokens, block: int | None
                  = None, precision: str = "highest", left: dict | None = None,
                  carried: dict | None = None, depth: int | None = None):
    """tokens [S] -> the final norm's output [S, hidden], float32. `block`:
    how many query positions attention scores at a time (memory only).
    `precision`: of every matrix product; "bfloat16" is the control one
    precision down (tools/reference_check.py), never the reference. `left`:
    a dict that receives, by layer index, what each Mamba-2 layer's
    sequence leaves (state, the convolution's last inputs); `carried`: such
    a dict to start from (the fault of a state not reset); `depth`: walk
    only the first `depth` layers (to read a state near the input)."""
    with jax.default_matmul_precision(precision):
        tokens = jnp.asarray(tokens)
        block = block or tokens.shape[0]
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        layers: Sequence[dict] = params["layers"]
        for i, letter in enumerate(cfg.pattern[:depth]):
            lp = layers[i]
            h = rms_norm(x, lp["norm"], cfg.rms_eps)
            if letter == "M":
                out, end = mamba(h, lp, cfg, (carried or {}).get(i))
                x = x + out
                if left is not None:
                    left[i] = end
            elif letter == "*":
                x = x + attention(h, lp, cfg, block)
            else:
                x = x + experts(h, lp, cfg)
        return rms_norm(x, params["final_norm"], cfg.rms_eps)


def head(params: dict, cfg: RefConfig, hidden, precision: str = "highest"):
    """Logits [.., V] of hidden states [.., hidden]."""
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
    model at a time, its experts one at a time). The served stacks are by
    kind: layer i is the n-th of its letter's."""

    _KIND = {"M": "ssm", "*": "full", "E": "experts"}
    _NAMES = {"moe_gate": "router", "moe_bias": "router_bias",
              "moe_w1": "w1", "moe_w2": "w2", "attn_norm": "norm",
              "mlp_norm": "norm"}

    def __init__(self, pattern: str, stacks: dict):
        self._pattern, self._stacks = pattern, stacks

    def __getitem__(self, i: int) -> dict:
        letter = self._pattern[i]
        n = self._pattern[:i].count(letter)
        pick = jax.tree_util.tree_map(lambda a: a[n],
                                      self._stacks[self._KIND[letter]])
        return {self._NAMES.get(k, k):
                _Experts(v) if k.startswith("moe_w") else _dense(v)
                for k, v in pick.items()}


def from_served(params: dict, pattern: str) -> dict:
    """The served parameter pytree (params["layers"][kind], each stacked on
    a leading axis, every matrix laid out for x @ W, possibly int8) as the
    reference takes it, by the published pattern."""
    return {"embed": _dense(params["embed"]),
            "final_norm": _dense(params["final_norm"]),
            "lm_head": _dense(params["lm_head"]),
            "layers": _Layers(pattern, params["layers"])}
