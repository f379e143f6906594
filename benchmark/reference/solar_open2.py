"""The benchmark's own copy of the plain reference for a model with
linear-attention layers (PR 31: Solar-Open2): a decoder LM's forward pass in
straightforward jax.numpy, float32, matrix products at "highest" precision.
It imports nothing of the program under test, so what decides `correct` one
day cannot drift with the program; a self-check
(benchmark/tests/test_reference_solar_open2.py) holds it equal to the
program's own copy, localai_tpu/testing/reference_linear.py, on seeded tiny
weights. benchmark/run.py does not call it yet: comparing logits inside
`correct` needs an edit there (a `benchmark` issue; PERF.md section 7.3).

What follows is the program's copy, to the letter, from its own description
down.

Written from the keys of Solar-Open2's published `config.json`
(`model_type: solar_open2`) and the family's description, not from the
served code. A layer is `x += mixer(norm(x)); x += moe(norm(x))`, RMSNorm
(x / sqrt(mean(x^2) + eps) * w) before each:

- `linear` layers (every layer not in `gqa_layers`), a gated delta rule with
  per-channel decay, TOKEN BY TOKEN exactly as written: q~, k~, v~ = W x; a
  causal depthwise convolution over time (kernel 4, no bias) and SiLU on
  each; q = l2norm(q') d^-1/2, k = l2norm(k') per head (l2norm(a) = a /
  sqrt(sum a^2 + 1e-6)); g = -exp(A_log[h]) softplus(W_f2 W_f1 x + dt_bias)
  per channel; beta = sigmoid(W_b x), doubled under kda_allow_neg_eigval;
  S' = diag(exp g) S, S = S' + beta k (v - S'^T k)^T, o = S^T q, S a
  [d, d] float32 state per head from zero; y = W_o [rmsnorm_head(o) *
  sigmoid(W_g2 W_g1 x)];
- `full` layers (`gqa_layers`): grouped-query attention, scores /
  sqrt(head_dim), causal softmax in float32, NO position encoding
  (`use_rope: false`; a config that asks for RoPE is refused here), and an
  elementwise sigmoid gate W_gate x on attention's output before W_o;
- an expert layer: router logits h -> R without bias, softmax over all R in
  float32, the top-k probabilities renormalised to sum to 1, times
  `routed_scaling_factor`; each token's output the weighted sum of its
  chosen experts' SwiGLU, plus a shared SwiGLU expert added ungated. Where
  `localai_expert_share` says so the layer holds a SHARE of the routed
  experts: router, top-k and renormalisation over all R, the sum over the
  chosen experts in [first, first + held) only (the other chips of an
  expert-parallel layout hold the rest; nothing stands in for them);
- final RMSNorm, then the head.

Departures from the description: none in the mathematics. The experts' sum
is taken expert by expert over the tokens that chose the expert (a token's
other experts add exact zeros), so that a block of positions is one matrix
product; the terms summed per token are the same.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp

FULL, LINEAR = "full", "linear"


@dataclasses.dataclass(frozen=True)
class RefConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rms_eps: float
    layer_types: tuple[str, ...]          # FULL / LINEAR per layer
    linear_heads: int                     # LINEAR layers: heads x head size
    linear_head_dim: int
    num_experts: int                      # routed experts HELD
    experts_per_tok: int
    first_expert: int = 0                 # the share: experts [first, first
    routed_scale: float = 1.0             # + num_experts) of the router's
    linear_beta_scale: float = 1.0        # 2 under kda_allow_neg_eigval
    # switches tools/reference_check.py turns to compute the reference GIVEN
    # a fault (what a served path with that fault would read like); a sound
    # reference leaves them alone
    linear_decay: bool = True             # False: diag(exp g) left out
    attn_gate: bool = True                # False: the GQA output gate left out

    @classmethod
    def from_hf(cls, hf: dict[str, Any]) -> "RefConfig":
        """From the keys of the published `config.json` (solar_open2)."""
        if hf.get("use_rope", True):
            raise NotImplementedError(
                "this reference has no position encoding (use_rope: false)")
        n_layers = hf["num_hidden_layers"]
        heads = hf["num_attention_heads"]
        la = hf["linear_attn_config"]
        share = hf.get("localai_expert_share") or {}
        return cls(
            vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
            num_layers=n_layers, num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
            rms_eps=hf.get("rms_norm_eps", 1e-5),
            layer_types=tuple(FULL if i in hf["gqa_layers"] else LINEAR
                              for i in range(n_layers)),
            linear_heads=la["num_heads"], linear_head_dim=la["head_dim"],
            num_experts=hf["n_routed_experts"],
            experts_per_tok=hf["num_experts_per_tok"],
            first_expert=share.get("first_expert", 0),
            routed_scale=hf.get("routed_scaling_factor", 1.0),
            linear_beta_scale=2.0 if hf.get("kda_allow_neg_eigval") else 1.0)


# ---------------------------------------------------------------- layers

def rms_norm(x, weight, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def attention(x, lp: dict, cfg: RefConfig, block: int):
    """Causal self-attention of one sequence x [S, h] without position
    encoding, a block of queries at a time against every key, then the
    output gate."""
    s = x.shape[0]
    nh, nkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ lp["wq"]).reshape(s, nh, d)
    # query head i reads KV head i // (nh / nkv)
    k = jnp.repeat((x @ lp["wk"]).reshape(s, nkv, d), nh // nkv, axis=1)
    v = jnp.repeat((x @ lp["wv"]).reshape(s, nkv, d), nh // nkv, axis=1)
    pos = jnp.arange(s)
    out = []
    for lo in range(0, s, block):
        see = pos[None, :] <= pos[lo:lo + block, None]
        score = jnp.einsum("qhd,khd->hqk", q[lo:lo + block], k) / math.sqrt(d)
        prob = jax.nn.softmax(jnp.where(see[None], score, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", prob, v).reshape(-1, nh * d))
    out = jnp.concatenate(out)
    if "w_agate" in lp and cfg.attn_gate:
        out = out * jax.nn.sigmoid(x @ lp["w_agate"])
    return out @ lp["wo"]


def linear_attention(x, lp: dict, cfg: RefConfig, carried=None):
    """A gated delta-rule layer over one sequence x [S, h], a token at a
    time: the recurrence as the module's docstring writes it. Returns (y,
    (state, the convolution's last inputs)); `carried`: such a pair to
    start from instead of zeros (a fault tools/reference_check.py plants: a
    slot's state not reset at admission)."""
    s = x.shape[0]
    nh, d = cfg.linear_heads, cfg.linear_head_dim
    taps = lp["conv"].shape[-1]
    c = nh * d
    pre = jnp.concatenate([x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]], -1)
    state0, tail = carried or (jnp.zeros((nh, d, d)),
                               jnp.zeros((taps - 1, 3 * c)))
    padded = jnp.concatenate([tail, pre])
    conv = jax.nn.silu(sum(padded[i:i + s] * lp["conv"][:, i]
                           for i in range(taps)))   # causal, no bias

    def l2norm(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q, k, v = (conv[:, i * c:(i + 1) * c].reshape(s, nh, d) for i in range(3))
    q, k = l2norm(q) * d ** -0.5, l2norm(k)
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        ((x @ lp["w_f1"]) @ lp["w_f2"] + lp["dt_bias"]).reshape(s, nh, d))
    beta = cfg.linear_beta_scale * jax.nn.sigmoid(x @ lp["w_b"])   # [S, nh]
    if not cfg.linear_decay:
        g = jnp.zeros_like(g)

    def token(state, xs):           # state [nh, d, d]
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, :, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k)
        state = state + beta[:, None, None] * k[:, :, None] * (
            v - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    state, o = jax.lax.scan(token, state0, (q, k, v, g, beta))
    o = rms_norm(o, lp["o_norm"], cfg.rms_eps).reshape(s, c)
    y = (o * jax.nn.sigmoid((x @ lp["w_g1"]) @ lp["w_g2"])) @ lp["wo"]
    return y, (state, padded[-(taps - 1):])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def experts(x, lp: dict, cfg: RefConfig):
    """The expert layer over x [S, h]: softmax router over all R experts,
    top-k, renormalise, the weighted sum of the chosen experts held here,
    plus the shared expert."""
    prob = jax.nn.softmax(x @ lp["router"], axis=-1)            # [S, R]
    top_p, top_e = jax.lax.top_k(prob, cfg.experts_per_tok)
    top_p = top_p / top_p.sum(-1, keepdims=True) * cfg.routed_scale
    y = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        # this expert's weight per token: its renormalised probability
        # where the token chose it, else 0. The experts held are
        # first_expert + e of the router's R; the others add nothing here
        w = jnp.where(top_e == cfg.first_expert + e, top_p, 0.0).sum(-1)
        y = y + w[:, None] * swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    if "ws_gate" in lp:
        y = y + swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return y


def hidden_states(params: dict, cfg: RefConfig, tokens, block: int | None
                  = None, precision: str = "highest", carried=None,
                  left: dict | None = None):
    """tokens [S] -> the final norm's output [S, h], float32. `block`: how
    many query positions attention scores at a time (memory only).
    `precision`: of every matrix product; "bfloat16" is the control one
    precision down (tools/reference_check.py), never the reference.
    `left`: a dict that receives what each linear layer is left holding
    after the last token ({layer: (state, conv inputs)}); `carried`: such a
    dict to start from (linear_attention)."""
    with jax.default_matmul_precision(precision):
        tokens = jnp.asarray(tokens)
        block = block or tokens.shape[0]
        x = jnp.asarray(params["embed"], jnp.float32)[tokens]
        layers: Sequence[dict] = params["layers"]
        for i in range(cfg.num_layers):
            lp = layers[i]
            h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
            if cfg.layer_types[i] == LINEAR:
                y, end = linear_attention(h, lp, cfg, (carried or {}).get(i))
                x = x + y
                if left is not None:
                    left[i] = end
            else:
                x = x + attention(h, lp, cfg, block)
            x = x + experts(rms_norm(x, lp["mlp_norm"], cfg.rms_eps), lp, cfg)
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
    expert at a time (all 40 of a published layer at once are 2.5 GB)."""

    def __init__(self, leaf):
        self._leaf = leaf

    def __getitem__(self, e: int):
        return _dense(jax.tree_util.tree_map(lambda a: a[e], self._leaf))


class _Layers:
    """Layer i's weights, made float32 when asked for (one layer of a large
    model at a time, its experts one at a time). The served stacks are by
    kind: layer i is the n-th of its kind."""

    _NAMES = {"moe_gate": "router", "moe_w1": "w1", "moe_w2": "w2",
              "moe_w3": "w3"}

    def __init__(self, stacked: dict, kinds: tuple):
        self._stacked, self._kinds = stacked, tuple(kinds)

    def __getitem__(self, i: int) -> dict:
        kind = self._kinds[i]
        n = self._kinds[:i].count(kind)
        pick = jax.tree_util.tree_map(lambda a: a[n], self._stacked[kind])
        return {self._NAMES.get(k, k):
                _Experts(v) if k.startswith("moe_w") else _dense(v)
                for k, v in pick.items()}


def from_served(params: dict, layer_types) -> dict:
    """The served parameter pytree (params["layers"][kind] stacked on a
    leading axis, every matrix laid out for x @ W, possibly int8) as the
    reference takes it; layer_types: the kind of each layer."""
    return {"embed": _dense(params["embed"]),
            "final_norm": _dense(params["final_norm"]),
            "lm_head": _dense(params["lm_head"]),
            "layers": _Layers(params["layers"], layer_types)}
