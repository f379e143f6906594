"""Llama-family decoder (Llama 2/3, Mistral, Qwen2/2.5, TinyLlama, ...).

Role in the framework: the flagship text engine — what llama.cpp's GGUF
decoder is to the reference (/root/reference/backend/cpp/llama-cpp/
grpc-server.cpp drives llama.cpp's model; here the model IS JAX code).

Design (TPU-first, not a torch translation):
- pure functions over a param pytree; layers STACKED on a leading axis and
  executed with lax.scan → one compiled layer body, low compile time, and
  XLA pipelines the weight prefetch (HBM→VMEM) across layers.
- bf16 weights/activations, f32 norms/softmax/logits head.
- GQA with a slot-contiguous, head-major KV cache [L, B, KVH, T, D] carried
  through scan (trailing (T, D) dims = the Mosaic-legal Pallas tiling).
- tensor parallelism by GSPMD: param PartitionSpecs (see param_specs) put
  heads/ffn on the `model` mesh axis; activations get with_sharding_constraint
  hints; XLA inserts the all-reduces (the NCCL-free answer to vLLM's
  tensor_parallel_size — /root/reference/backend/python/vllm/backend.py:106).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from localai_tpu.models import kv
# (the names tests and the engine import from here)
from localai_tpu.models.kv import (  # noqa: F401
    EXPERTS, FULL, LATENT, LINEAR, SSM, WINDOW, PeriodKV, _decode_dq,
)
from localai_tpu.ops.norms import rms_norm
from localai_tpu.ops.rope import RopeConfig, rope_table, apply_rope
from localai_tpu.ops.kvcache import (
    QuantKV, dequant, init_quant, is_quant_kind, padded_len, requantize,
)
from localai_tpu.ops.quant import qmatmul
from localai_tpu.parallel.mesh import (
    activate_mesh, constrain, current_mesh, seq_axis_size,
)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_position: int = 8192
    rms_eps: float = 1e-5
    rope_base: float = 10000.0
    rope_scaling: str = "none"          # none|linear|yarn|llama3
    rope_scale_factor: float = 1.0
    rope_original_max_position: int = 8192
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attn_factor: float | None = None
    qkv_bias: bool = False              # Qwen2
    tie_embeddings: bool = False
    sliding_window: int | None = None   # Mistral
    num_experts: int = 0                # Mixtral MoE (0 = dense MLP)
    experts_per_tok: int = 2
    # expert width where it is not intermediate_size (moe_intermediate_size)
    moe_intermediate_size: int | None = None
    # window and full attention layers in one model (Mellum2): one of
    # FULL / WINDOW per layer. None = every layer alike (sliding_window, if
    # set, then applies to all of them over a full-length cache, as Mistral).
    # WINDOW layers attend over sliding_window tokens, hold a ring cache
    # (init_kv_cache) and rotate with window_rope; FULL layers with `rope`.
    layer_types: tuple[str, ...] | None = None
    window_rope: RopeConfig | None = None
    dtype: str = "bfloat16"
    # LINEAR layers (a gated delta rule with per-channel decay over a
    # recurrent state, ops/kda.py) beside FULL ones: heads x head size of
    # the state, the short convolution's taps, the rank of the two low-rank
    # gates (decay, output), and whether beta is doubled (eigenvalues of
    # I - beta k k^T in (-1, 1)). Such a model's weights are stacked BY KIND
    # (params["layers"][kind]): the kinds' leaves differ in shape.
    linear_heads: int = 0
    linear_head_dim: int = 0
    linear_conv: int = 4
    linear_gate_rank: int = 0
    linear_neg_eigval: bool = False
    use_rope: bool = True               # False: no position encoding at all
    attn_gate: bool = False             # sigmoid(W x) on softmax attention's
                                        # output, elementwise, before wo
    # an expert layer that holds a SHARE of the experts: num_experts are
    # held here, experts [first_expert, first_expert + num_experts) of the
    # router_experts the router scores (0: it holds them all); a shared
    # expert of shared_expert_width beside them (0: none)
    router_experts: int = 0
    first_expert: int = 0
    shared_expert_width: int = 0
    routed_scale: float = 1.0
    # the router: sigmoid scores where not a softmax over the experts, and a
    # per-expert bias (the leaf moe_bias) added to the scores for the CHOICE
    # of the top k only, never to the weights
    router_sigmoid: bool = False
    router_bias: bool = False
    # the first leading_dense_layers layers have a dense SwiGLU of
    # intermediate_size where the rest have experts: a weight stack of their
    # own (params["leading"]) and a cache place each, after the period's
    # (cache_kinds); they run before the layer scan, unrolled (_scan_layers)
    leading_dense_layers: int = 0
    qk_norm: bool = False       # RMSNorm over head_dim on q and k, before RoPE
    post_norms: bool = False    # attention's and the MLP's OUTPUT normalised
                                # before the residual add (sandwich norms)
    nope_kinds: tuple[str, ...] = ()    # layer kinds that do not rotate
    embed_scale: float = 1.0    # on the token embeddings
    # LATENT layers (latent attention, MLA): the query through a low-rank
    # pair with an RMSNorm between (q_lora_rank), keys and values made of a
    # cached LATENT of kv_lora_rank (RMSNorm'd) by an up-projection, heads
    # of qk_nope_head_dim + qk_rope_head_dim key columns (the last rotated,
    # and shared by every head) and v_head_dim value columns. layer_types is
    # then LATENT for every layer: the one kind that may stand alone there
    # (it has a cache class of its own, kv.LatentKV, which the layer scan
    # reaches by kind), with or without leading dense layers
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # SSM layers (Mamba-2 state-space mixers, ops/ssd.py): heads x channels
    # a head (the inner width), the groups that share B and C, the state
    # size, the causal convolution's taps (with a bias), the chunk of the
    # chunked form. Such a model's layers are ONE of a mixer (SSM, FULL) or
    # a feed-forward part (EXPERTS) each, x + part(norm(x)): layer_types
    # names all three, the weights are stacked by kind, and only the mixers
    # have a place in the cache (cache_places)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # the experts' form: "swiglu" (three matrices, silu(W1 x) W3 x) or
    # "relu2" (two, W2 relu(W1 x)^2, the shared expert too); and the width
    # of the latent the routed experts work in (0: the hidden size): two
    # more matrices a layer, in before the experts and out after their
    # weighted sum (the router and the shared expert read the hidden state)
    expert_act: str = "swiglu"
    moe_latent: int = 0

    def __post_init__(self):
        if self.router_experts and not (
                0 <= self.first_expert
                <= self.router_experts - self.num_experts):
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert} + "
                f"{self.num_experts}) are not among the router's "
                f"{self.router_experts}")
        lead = self.leading_dense_layers
        if lead and (self.layer_types is None or not self.num_experts
                     or not 0 < lead < self.num_layers):
            raise ValueError(
                f"{lead} leading dense layers need a model with experts, "
                "more layers than that, and layer_types (a one-kind stack "
                "is one scan over one kind of MLP)")
        if self.layer_types is None:
            if self.kv_lora_rank:
                raise ValueError("kv_lora_rank (latent attention) needs "
                                 f"layer_types of {LATENT!r}")
            return
        kinds = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", kinds)
        object.__setattr__(self, "nope_kinds", tuple(self.nope_kinds))
        if (len(kinds) != self.num_layers
                or set(kinds) - {FULL, WINDOW, LINEAR, LATENT, SSM, EXPERTS}):
            raise ValueError(
                f"layer_types needs {self.num_layers} entries of "
                f"{FULL!r}/{WINDOW!r}/{LINEAR!r}/{LATENT!r}/{SSM!r}/"
                f"{EXPERTS!r}, got {kinds}")
        if self.expert_act not in ("swiglu", "relu2"):
            raise ValueError(f"expert_act {self.expert_act!r}: swiglu or "
                             "relu2")
        if SSM in kinds or EXPERTS in kinds:
            if (set(kinds) - {FULL, SSM, EXPERTS} or EXPERTS not in kinds
                    or not self.num_experts or lead):
                raise ValueError(
                    "state-space layers and layers that are a feed-forward "
                    "part alone come together, beside full-attention "
                    f"layers, with experts and no leading layers; got "
                    f"{kinds}")
            if SSM in kinds and not (self.ssm_heads and self.ssm_head_dim
                                     and self.ssm_state
                                     and self.ssm_heads % self.ssm_groups
                                     == 0):
                raise ValueError(
                    "state-space layers need ssm_heads (a multiple of "
                    "ssm_groups), ssm_head_dim and ssm_state")
            return
        if LATENT in kinds:
            if set(kinds) != {LATENT}:
                raise ValueError(
                    "latent layers beside layers of another kind are not "
                    "taken: their weights differ in shape and are one stack")
            if not (self.kv_lora_rank and self.q_lora_rank
                    and self.qk_nope_head_dim and self.qk_rope_head_dim
                    and self.v_head_dim):
                raise ValueError(
                    "latent layers need kv_lora_rank, q_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
            return
        if len(set(kinds)) == 1:
            raise ValueError(
                "layer_types with one kind of layer: leave it None (and set "
                "sliding_window for an all-window model)")
        if WINDOW in kinds and (not self.sliding_window
                                or self.sliding_window < 1):
            raise ValueError("window layers need a sliding_window")
        if LINEAR in kinds and not (self.linear_heads and self.linear_head_dim
                                    and self.linear_gate_rank):
            raise ValueError("linear layers need linear_heads, "
                             "linear_head_dim and linear_gate_rank")
        if lead and LINEAR in kinds:
            raise ValueError("leading dense layers in a model with linear "
                             "layers (weights stacked by kind) are not taken")

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def stacked_by_kind(self) -> bool:
        return self.layer_types is not None and bool(
            {LINEAR, SSM, EXPERTS} & set(self.layer_types))

    @property
    def split_layers(self) -> bool:
        """Whether a layer is a mixer or a feed-forward part ALONE (its own
        norm, its own residual add): the kinds then name both."""
        return self.layer_types is not None and EXPERTS in self.layer_types

    @property
    def expert_layers(self) -> int:
        """How many layers have experts."""
        if not self.num_experts:
            return 0
        return (self.layers_of(EXPERTS) if self.split_layers
                else self.num_layers - self.leading_dense_layers)

    @property
    def drawn_by_leaf(self) -> bool:
        """Whether the weights are made leaf by leaf from layer_leaves (all
        a layer of this config holds) and not as the flat dict a Llama,
        Mixtral or Mellum2 stack always was (kept: those models' draws)."""
        return bool(self.stacked_by_kind or self.leading_dense_layers
                    or self.kv_lora_rank
                    or self.qk_norm or self.post_norms or self.attn_gate
                    or self.shared_expert_width or self.router_bias
                    or self.router_experts)

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def leading_kinds(self) -> tuple[str, ...]:
        """The layer kinds of the leading dense layers."""
        return (self.layer_types or ())[:self.leading_dense_layers]

    @property
    def period(self) -> tuple[str, ...] | None:
        """The shortest run of layer kinds that repeats to give layer_types
        after the leading dense layers (the body of the layer scan); None
        for a one-kind model."""
        if self.layer_types is None:
            return None
        kinds = self.layer_types[self.leading_dense_layers:]
        n = len(kinds)
        for p in range(1, n + 1):
            if n % p == 0 and kinds == kinds[:p] * (n // p):
                return kinds[:p]

    @property
    def cache_kinds(self) -> tuple[str, ...] | None:
        """The layer kind of each place of the cache (kv.PeriodKV.slots):
        the period's places, each [L / period, ...], then one place of
        [1, ...] a leading layer."""
        return self.period and tuple(
            k for k in self.period if k != EXPERTS) + self.leading_kinds

    @property
    def cache_places(self) -> tuple | None:
        """For each place of the period, its place in the cache, or None
        for a layer without a mixer (EXPERTS)."""
        if self.period is None:
            return None
        at = iter(range(len(self.period)))
        return tuple(None if k == EXPERTS else next(at) for k in self.period)

    def rotates(self, kind: str | None) -> bool:
        """Whether a layer of this kind rotates q and k (RoPE)."""
        return self.use_rope and kind not in self.nope_kinds

    def rope_of(self, kind: str | None) -> RopeConfig:
        if kind == WINDOW and self.window_rope is not None:
            return self.window_rope
        if kind == LATENT:      # the position key's columns alone rotate
            return dataclasses.replace(self.rope,
                                       head_dim=self.qk_rope_head_dim)
        return self.rope

    @property
    def rope(self) -> RopeConfig:
        return RopeConfig(
            head_dim=self.head_dim,
            base=self.rope_base,
            scaling=self.rope_scaling,
            scale_factor=self.rope_scale_factor,
            original_max_position=self.rope_original_max_position,
            low_freq_factor=self.rope_low_freq_factor,
            high_freq_factor=self.rope_high_freq_factor,
            beta_fast=self.rope_beta_fast,
            beta_slow=self.rope_beta_slow,
            attn_factor=self.rope_attn_factor,
        )

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)


# ---------------------------------------------------------------- params

def init_params(cfg: LlamaConfig, key, dtype=None):
    """Random init (tests + training). Layout matches load_safetensors output."""
    dtype = dtype or cfg.jdtype
    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, L, I = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers, cfg.intermediate_size
    if cfg.num_experts:
        I = cfg.expert_width
    ks = jax.random.split(key, 10)

    def norm(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    if cfg.drawn_by_leaf:
        def leaf(k, name, shape, how):
            if how == "ones":
                return jnp.ones(shape, dtype)
            if isinstance(how, str):
                return special_init(k, how, shape)
            out = norm(k, shape, how)
            return out.astype(jnp.float32) if name == "moe_gate" else out

        params = {"embed": norm(ks[7], (cfg.vocab_size, h), h),
                  "final_norm": jnp.ones((h,), dtype)}
        if not cfg.tie_embeddings:
            params["lm_head"] = norm(ks[8], (h, cfg.vocab_size), h)
        return fill_stacks(cfg, params, leaf, ks[0])

    layers = {
        "attn_norm": jnp.ones((L, h), dtype),
        "wq": norm(ks[0], (L, h, nh * hd), h),
        "wk": norm(ks[1], (L, h, nkv * hd), h),
        "wv": norm(ks[2], (L, h, nkv * hd), h),
        "wo": norm(ks[3], (L, nh * hd, h), nh * hd),
        "mlp_norm": jnp.ones((L, h), dtype),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        layers["moe_gate"] = norm(ks[4], (L, h, E), h).astype(jnp.float32)
        layers["moe_w1"] = norm(ks[5], (L, E, h, I), h)
        layers["moe_w2"] = norm(ks[6], (L, E, I, h), I)
        layers["moe_w3"] = norm(ks[9], (L, E, h, I), h)
    else:
        layers.update({
            "w_gate": norm(ks[4], (L, h, I), h),
            "w_up": norm(ks[5], (L, h, I), h),
            "w_down": norm(ks[6], (L, I, h), I),
        })
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, nh * hd), dtype)
        layers["bk"] = jnp.zeros((L, nkv * hd), dtype)
        layers["bv"] = jnp.zeros((L, nkv * hd), dtype)
    params = {
        "embed": norm(ks[7], (cfg.vocab_size, h), h),
        "layers": layers,
        "final_norm": jnp.ones((h,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(ks[8], (h, cfg.vocab_size), h)
    return params


def layer_leaves(cfg: LlamaConfig, kind: str, dense: bool = False) -> dict:
    """One layer's leaves for a model whose weights are drawn leaf by leaf
    (cfg.drawn_by_leaf): name -> (shape without the layer axis, how it is
    drawn: a matrix's fan-in, "ones", or one of special_init's names).
    Matrices are the names that start with `w` or `moe_w`
    (ops/quant.quantize_params). dense: a leading dense layer's (a SwiGLU of
    intermediate_size where the others have their experts)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    if kind == EXPERTS:
        return {"mlp_norm": ((h,), "ones"), **_expert_leaves(cfg)}
    out = {"attn_norm": ((h,), "ones")}
    if kind == SSM:
        nh, gn = cfg.ssm_heads, cfg.ssm_groups * cfg.ssm_state
        inner = nh * cfg.ssm_head_dim
        c = inner + 2 * gn
        out.update({
            # z (the gate) | x, B, C (through the convolution) | dt a head
            "w_in": ((h, inner + c + nh), h),
            "conv": ((c, cfg.ssm_conv), cfg.ssm_conv),
            "conv_bias": ((c,), 16),
            "dt_bias": ((nh,), "dt_bias"), "A_log": ((nh,), "A_log"),
            "D": ((nh,), "D"), "ssm_norm": ((inner,), "ones"),
            "w_out": ((inner, h), inner)})
        return out
    if kind == LINEAR:
        nh, d, r = cfg.linear_heads, cfg.linear_head_dim, cfg.linear_gate_rank
        c = nh * d
        out.update({
            "wq": ((h, c), h), "wk": ((h, c), h), "wv": ((h, c), h),
            "wo": ((c, h), c),
            # the decay gate's and the output gate's low-rank pairs, beta
            "w_f1": ((h, r), h), "w_f2": ((r, c), 16 * r),
            "w_g1": ((h, r), h), "w_g2": ((r, c), r),
            "w_b": ((h, nh), h),
            # depthwise causal convolution over q, k and v's channels
            "conv": ((3 * c, cfg.linear_conv), cfg.linear_conv),
            "A_log": ((nh,), "A_log"), "dt_bias": ((c,), "dt_bias"),
            "o_norm": ((d,), "ones"),
        })
    elif kind == LATENT:
        nh, r, qr = cfg.num_heads, cfg.kv_lora_rank, cfg.q_lora_rank
        n, p, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        out.update({
            "wq_a": ((h, qr), h), "q_a_norm": ((qr,), "ones"),
            "wq_b": ((qr, nh * (n + p)), qr),
            # the latent and, beside it, the position key all heads share
            "wkv_a": ((h, r + p), h), "kv_a_norm": ((r,), "ones"),
            # a head's k_nope and v side by side, head-major
            "wkv_b": ((r, nh * (n + v)), r),
            "wo": ((nh * v, h), nh * v)})
    else:
        nh, nkv = cfg.num_heads, cfg.num_kv_heads
        out.update({"wq": ((h, nh * hd), h), "wk": ((h, nkv * hd), h),
                    "wv": ((h, nkv * hd), h), "wo": ((nh * hd, h), nh * hd)})
        if cfg.qk_norm:
            out.update({"q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones")})
        if cfg.attn_gate:
            out["w_agate"] = ((h, nh * hd), h)
    if cfg.split_layers:
        return out
    if cfg.post_norms:
        out["attn_post_norm"] = ((h,), "ones")
    out["mlp_norm"] = ((h,), "ones")
    if cfg.post_norms:
        out["mlp_post_norm"] = ((h,), "ones")
    if dense or not cfg.num_experts:
        i = cfg.intermediate_size
        out.update({"w_gate": ((h, i), h), "w_up": ((h, i), h),
                    "w_down": ((i, h), i)})
        return out
    out.update(_expert_leaves(cfg))
    return out


def _expert_leaves(cfg: LlamaConfig) -> dict:
    """An expert layer's leaves beside its norm: the router (and its
    selection bias), the routed experts held (two matrices each under
    relu2, three under swiglu; over the latent where the model has one, and
    then the pair of matrices into and out of it), the shared expert."""
    h, e, i = cfg.hidden_size, cfg.num_experts, cfg.expert_width
    gated = cfg.expert_act == "swiglu"
    routers = cfg.router_experts or e
    lat = cfg.moe_latent or h
    out = {"moe_gate": ((h, routers), h),
           "moe_w1": ((e, lat, i), lat), "moe_w2": ((e, i, lat), i)}
    if gated:
        out["moe_w3"] = ((e, lat, i), lat)
    if cfg.router_bias:
        out["moe_bias"] = ((routers,), "moe_bias")
    if cfg.moe_latent:
        out.update({"w_lat_in": ((h, lat), h), "w_lat_out": ((lat, h), lat)})
    if cfg.shared_expert_width:
        w = cfg.shared_expert_width
        if gated:
            out["ws_gate"] = ((h, w), h)
        out.update({"ws_up": ((h, w), h), "ws_down": ((w, h), w)})
    return out


def layer_stacks(cfg: LlamaConfig) -> dict:
    """The weight stacks of a model drawn leaf by leaf: where the stack lies
    in the params -> (layers in it, one layer's leaves). By kind where the
    kinds' leaves differ in shape (linear layers); else the scanned layers
    under "layers" and the leading dense layers under "leading"."""
    if cfg.stacked_by_kind:
        return {("layers", kind): (cfg.layers_of(kind),
                                   layer_leaves(cfg, kind))
                for kind in sorted(set(cfg.layer_types))}
    lead = cfg.leading_dense_layers
    # (window and full layers have the same leaves; latent ones stand alone)
    kind = LATENT if cfg.kv_lora_rank else FULL
    out = {("layers",): (cfg.num_layers - lead, layer_leaves(cfg, kind))}
    if lead:
        out[("leading",)] = (lead, layer_leaves(cfg, kind, dense=True))
    return out


def fill_stacks(cfg: LlamaConfig, params: dict, leaf, key=None) -> dict:
    """`params` with layer_stacks' stacks in their places, each leaf made
    by `leaf(its key or None, name, shape with the layer axis, how)`."""
    for n, (path, (count, leaves)) in enumerate(layer_stacks(cfg).items()):
        kk = (len(leaves) * [None] if key is None else
              jax.random.split(jax.random.fold_in(key, n), len(leaves)))
        stack = {name: leaf(kk[i], name, (count, *shape), how)
                 for i, (name, (shape, how)) in enumerate(leaves.items())}
        at = params
        for step in path[:-1]:
            at = at.setdefault(step, {})
        at[path[-1]] = stack
    return params


def special_init(key, name: str, shape):
    """The leaves that are no matrix and no gain. A linear layer's decay,
    by the family's initialisation: A_log = log U(1, 16) a head; dt_bias
    such that softplus(dt_bias) is log-uniform in 1e-3..1e-1 a channel.
    With w_f2 drawn small (layer_leaves: the input's part moves the gate by
    a factor of about 1.3) a token's decay exp(-A softplus(.)) lies between
    about 0.1 and 0.999 and mostly in 0.9-0.999: never 0 or 1, so a decay
    left out or misapplied shows. The router's selection bias (moe_bias):
    N(0, 0.02^2), small and not zero, so that a bias left out of the choice,
    or added to the weights, shows. A state-space layer's A_log and dt_bias
    are a head's and drawn the same way (its family's initialisation too:
    time_step_min / time_step_max are 1e-3 / 1e-1); its skip D is U(0.5,
    1.5), not the family's ones, so that a D term left out shows."""
    if name == "moe_bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name == "D":
        return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)


def param_specs(cfg: LlamaConfig, qbits: int | None = None):
    """PartitionSpecs over mesh axes ('data','model'): Megatron-style TP.

    qkv/gate/up column-parallel, wo/down row-parallel, lm_head vocab-parallel,
    embed replicated. XLA GSPMD inserts the psum after wo/w_down.

    With `qbits` the projection leaves become {"q", "s"} spec dicts matching
    ops/quant.quantize's layout (the flagship int8-W recipe under a mesh):
    `q` shards exactly like the bf16 weight it replaces; the per-output-
    channel scale [..., 1, out] keeps the output-axis sharding and replicates
    the reduced-away input axis — so a row-parallel wo keeps its scales
    whole on every chip while its int8 body shards on the input axis.
    """
    if cfg.drawn_by_leaf:
        # replicated: such a model has been served on one chip only (its
        # expert layer already holds one chip's share of a wider layout)
        def rep(name, shape):
            spec = P(*(None,) * (len(shape) + 1))
            matrix = name.startswith("w") or name.startswith("moe_w")
            return {"q": spec, "s": spec} if qbits and matrix else spec

        head = P(None, None)
        specs = {"embed": P(None, None), "final_norm": P(None)}
        if not cfg.tie_embeddings:
            specs["lm_head"] = {"q": head, "s": head} if qbits else head
        return fill_stacks(
            cfg, specs, lambda _, name, shape, how: rep(name, shape[1:]))
    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, None, "model"),
        "wk": P(None, None, "model"),
        "wv": P(None, None, "model"),
        "wo": P(None, "model", None),
        "mlp_norm": P(None, None),
    }
    if cfg.num_experts:
        # expert parallelism: experts sharded over the `model` axis (the
        # GSPMD answer to EP — XLA reduces the masked combine across shards)
        layers["moe_gate"] = P(None, None, None)
        layers["moe_w1"] = P(None, "model", None, None)
        layers["moe_w2"] = P(None, "model", None, None)
        layers["moe_w3"] = P(None, "model", None, None)
    else:
        layers.update({
            "w_gate": P(None, None, "model"),
            "w_up": P(None, None, "model"),
            "w_down": P(None, "model", None),
        })
    if cfg.qkv_bias:
        layers["bq"] = P(None, "model")
        layers["bk"] = P(None, "model")
        layers["bv"] = P(None, "model")
    specs = {
        "embed": P(None, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
    if qbits:
        # mirror ops/quant.quantize_params' selection: every projection
        # matrix becomes {q, s}; norms/biases/embed/moe_gate stay dense
        def qspec(spec):
            body = tuple(spec)
            return {"q": spec, "s": P(*body[:-2], None, body[-1])}

        for k in list(layers):
            if k.startswith("w") or k.startswith("moe_w"):
                layers[k] = qspec(layers[k])
        if not cfg.tie_embeddings:
            specs["lm_head"] = qspec(specs["lm_head"])
    return specs


def replicated_specs(cfg: LlamaConfig, qbits: int | None = None):
    """Fully-replicated PartitionSpecs (same tree as param_specs, incl. the
    quantized {q, s} leaves when qbits is given). The right placement for a
    draft model whose dims don't divide the TP axis: drafts are small by
    design, so every chip holds a full copy."""
    return jax.tree_util.tree_map(lambda _: P(), param_specs(cfg, qbits))


def max_model_axis(cfg: LlamaConfig, n_devices: int) -> int:
    """Largest divisor of n_devices usable as the TP ('model') mesh axis: it
    must divide every dimension param_specs/kv_cache_spec shard on it."""
    dims = [
        cfg.num_heads * cfg.head_dim,
        cfg.num_kv_heads * cfg.head_dim,
        cfg.intermediate_size,
        cfg.num_kv_heads,  # kv cache shards the head axis
    ]
    if cfg.num_experts:
        dims.append(cfg.num_experts)  # expert parallelism
    if not cfg.tie_embeddings:
        dims.append(cfg.vocab_size)  # vocab-parallel lm_head
    for d in range(n_devices, 0, -1):
        if n_devices % d == 0 and all(dim % d == 0 for dim in dims):
            return d
    return 1


def kv_cache_spec(cache_type: str = ""):
    """KV cache [L, B, KVH, T, D]: slots on `data`, kv heads on `model`."""
    spec = P(None, "data", "model", None, None)
    if is_quant_kind(cache_type):
        return QuantKV(q=spec, s=spec)
    return spec


def paged_pool_spec():
    """Paged block pool [L, NB, KVH, BS, D] (and its QuantKV scale twin):
    the physical-block axis stays replicated — the host allocator hands out
    block ids with no notion of placement — and KV heads shard on `model`,
    the same head-parallelism the dense cache uses. Holds for both the q and
    s leaves of a QuantKV pool (same leading dims)."""
    return P(None, None, "model", None, None)


def ring_len(cfg: LlamaConfig, max_len: int, prefill_chunk: int,
             cache_type: str = "") -> int:
    """Tokens a WINDOW layer's ring holds per slot: the window plus one
    prefill chunk (extend writes a chunk before its queries read the window
    behind them), rounded up to the int8 scale tile, and never more than a
    full-length cache would be."""
    quant = is_quant_kind(cache_type)
    ring = cfg.sliding_window + prefill_chunk
    full = padded_len(max_len) if quant else max_len
    return min(padded_len(ring) if quant else ring, full)


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int, dtype=None,
                  cache_type: str = "", prefill_chunk: int | None = None):
    """Head-major cache [L, B, KVH, T, D] — trailing (T, D) dims are the
    Mosaic-legal tiling for the Pallas decode kernel, and the decode hot path
    reads it with zero transposes.

    cache_type "int8"/"q8_0" (reference CacheTypeKey/Value,
    /root/reference/backend/backend.proto:257-258) stores int8 + per-token
    scales (ops/kvcache.py) at half the HBM; the token axis is then padded to
    the 128 scale tile (extra rows are never read — lengths mask them).

    A model with layer_types gets a PeriodKV pair instead: FULL layers at
    max_len, WINDOW layers at ring_len (`prefill_chunk` is then required: the
    longest window `extend` will be given), LINEAR layers their state
    [L/p, B, H, D, D] float32 (in the K tree) and their short convolution's
    last inputs [L/p, B, K-1, 3 H D] in `dtype` (in the V tree): kv.StateKV.
    LATENT layers one buffer [L/p, B, T, W] in the K tree (kv.LatentKV: the
    latent and the position key a token, W = kv.latent_row_width) and None
    in the V tree; never int8. SSM layers their state [L/p, B, H, P, N]
    float32 and their convolution's last inputs [L/p, B, K-1, C]
    (kv.SsmKV); a layer that is a feed-forward part alone has no place.
    Leading dense layers have a place each after the period's
    (cfg.cache_kinds), sized by their kind.
    """
    quant = is_quant_kind(cache_type)
    dtype = dtype or cfg.jdtype

    def one(layers, t):
        shape = (layers, batch, cfg.num_kv_heads,
                 padded_len(t) if quant else t, cfg.head_dim)
        if quant:
            return init_quant(shape), init_quant(shape)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    if cfg.layer_types is None:
        return one(cfg.num_layers, max_len)
    ring = None
    if WINDOW in cfg.layer_types:
        if prefill_chunk is None:
            raise ValueError("a model with window layers sizes their ring "
                             "from prefill_chunk")
        ring = ring_len(cfg, max_len, prefill_chunk, cache_type)
    period = cfg.period
    n = (cfg.num_layers - cfg.leading_dense_layers) // len(period)

    def state():
        nh, d = cfg.linear_heads, cfg.linear_head_dim
        return (jnp.zeros((n, batch, nh, d, d), jnp.float32),
                jnp.zeros((n, batch, cfg.linear_conv - 1, 3 * nh * d), dtype))

    def ssm():
        c = (cfg.ssm_heads * cfg.ssm_head_dim
             + 2 * cfg.ssm_groups * cfg.ssm_state)
        return (jnp.zeros((n, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32),
                jnp.zeros((n, batch, cfg.ssm_conv - 1, c), dtype))

    def latent(layers):
        if quant:
            raise ValueError(
                f"cache_type {cache_type!r} is not supported for latent "
                "layers: an int8 latent needs a kernel and a tolerance of "
                "its own; serve it with cache_type_k: \"\"")
        width = kv.latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        return jnp.zeros((layers, batch, max_len, width), dtype), None

    # a place of the period holds its n layers, a leading layer's place one
    pairs = [state() if kind == LINEAR else ssm() if kind == SSM
             else latent(n if j < len(period) else 1) if kind == LATENT
             else one(n if j < len(period) else 1,
                      ring if kind == WINDOW else max_len)
             for j, kind in enumerate(cfg.cache_kinds)]
    return (PeriodKV(tuple(k for k, _ in pairs)),
            PeriodKV(tuple(v for _, v in pairs)))


# ---------------------------------------------------------------- forward

# jax.named_scope below names the model's parts in the XLA ops' metadata
# (`tf_op` in a device trace), so a trace viewer and tools/trace_gaps.py group
# `fusion.256` and its kin by part. Trace-time only: the compilation cache's
# key leaves metadata out, so a scope around XLA ops changes no key. A Pallas
# kernel is the exception: its serialized body carries the scope it was traced
# under (and the source lines of its call stack), and the body is in the key.
# So no scope encloses a kernel call; the kernels go by their own names
# (ragged_decode_q8, flash_prefill, paged_scatter_append): the views of
# models/kv.py put "cache_update" around their XLA scatters alone.
@jax.named_scope("attention")
def _qkv(x, lp, cfg: LlamaConfig, spec=None):
    """QKV projections. `spec` (optional) is the head-parallel output
    constraint (P(batch_ax, seq_ax, 'model')) threaded into qmatmul so TP
    keeps the (possibly int8) projection weights resident-sharded. Callers
    under shard_map (parallel/pipeline.py) leave it None."""
    b, s, _ = x.shape
    q = qmatmul(x, lp["wq"], spec)
    k = qmatmul(x, lp["wk"], spec)
    v = qmatmul(x, lp["wv"], spec)
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


@jax.named_scope("lm_head")
def _lm_head(x32, params):
    """Vocabulary projection in f32 (tied embeddings or separate, possibly
    int8-quantized, lm_head)."""
    from localai_tpu.ops.quant import is_quantized

    head = params.get("lm_head", None)
    if head is None:
        return x32 @ params["embed"].astype(jnp.float32).T
    if is_quantized(head):
        # int8 values are exact in bf16, so a bf16×bf16 dot with f32
        # accumulation loses only the f32→bf16 rounding of the activations —
        # noise next to the int8 weight quantization — while halving the
        # projection's HBM traffic vs dequant-to-f32 (2.2 ms → ~1 ms/step
        # on v5e at the 128k vocab)
        y = jnp.dot(x32.astype(jnp.bfloat16), head["q"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        return y * head["s"].astype(jnp.float32)
    return qmatmul(x32, head)


def _relu2(a):
    """relu(a)^2: the activation of experts that are not gated."""
    return jnp.square(jax.nn.relu(a))


ROUTED, DENSE = "routed", "dense"
# A call of this many tokens or more takes the routed form (where the model
# leaves a choice): the least prefill bucket served. PERF.md section 6, PR 32
ROUTED_FROM_TOKENS = 64


def expert_form(cfg: LlamaConfig, tokens: int, mesh=None,
                in_stack: bool = True) -> str:
    """Which form a call of `tokens` tokens (batch x sequence) takes through
    an expert layer, from what the call can observe: ROUTED (_moe_routed:
    only the pairs a token chose, a tile of one expert at a time) or DENSE
    (_moe_mlp: every expert on every token under a mask). _scan_layers and
    _mlp decide by it, and the engine counts expert tokens by it.

    A model with a shared expert or a share of its experts is written as
    the routed layer only. A mesh whose `model` axis shards the experts
    keeps the dense form: its masked combine is what GSPMD turns into the
    all-reduce, and a (layer, expert) slice out of a sharded stack is not.
    in_stack: the experts reach the layer in their stacks, as _scan_layers
    hands them to a forward over a cache (every program the engine
    serves). A forward without a cache (forward_train, the embeddings, a
    pipeline stage) keeps the dense form: it is what train.py takes a
    gradient through, and the tile loop's dynamic count and the kernel
    have none. Otherwise the shape decides: a decode step's rows read
    every expert's weights whichever form runs, and the dense form does it
    without the tiles; a prompt's tokens would compute E / k times what
    they chose."""
    if cfg.shared_expert_width or (
            cfg.router_experts and cfg.router_experts != cfg.num_experts):
        return ROUTED
    if not in_stack or (mesh is not None and not cfg.stacked_by_kind
                        and dict(mesh.shape).get("model", 1) > 1):
        return DENSE
    return ROUTED if tokens >= ROUTED_FROM_TOKENS else DENSE


def _mlp(x, lp, cfg=None, spec_prefix=None):
    """Gated MLP. `spec_prefix` (optional tuple, e.g. ('data', None)) is the
    leading batch/seq sharding of the activation: when given, gate/up outputs
    are constrained ffn-parallel (…, 'model') and the down projection back to
    (…, None) — the hints that keep TP weights sharded through the scan."""
    if "moe_gate" in lp:
        if expert_form(cfg, x.shape[0] * x.shape[1], current_mesh(),
                       isinstance(lp["moe_w1"], _InStack)) == ROUTED:
            return _moe_routed(x, lp, cfg)
        return _moe_mlp(x, lp, cfg)
    up_spec = down_spec = None
    if spec_prefix is not None:
        up_spec = P(*spec_prefix, "model")
        down_spec = P(*spec_prefix, None)
    # a dense layer of a model with experts: one of its leading layers
    with jax.named_scope("mlp/leading_dense" if cfg is not None
                         and cfg.num_experts else "mlp"):
        return qmatmul(jax.nn.silu(qmatmul(x, lp["w_gate"], up_spec))
                       * qmatmul(x, lp["w_up"], up_spec),
                       lp["w_down"], down_spec)


def _route(logits, lp, cfg: LlamaConfig):
    """Router logits [..., R] float32 -> (weights [..., k] float32, experts
    [..., k]): softmax scores over the R experts, or a sigmoid of each; the
    k best, by score plus the selection bias where the layer has one (the
    bias chooses and never weighs); the chosen experts' scores renormalised
    to sum to 1 (times cfg.routed_scale in the callers)."""
    k = cfg.experts_per_tok
    scores = (jax.nn.sigmoid(logits) if cfg.router_sigmoid
              else jax.nn.softmax(logits, axis=-1))
    if "moe_bias" in lp:
        _, top_i = jax.lax.top_k(scores + lp["moe_bias"], k)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
    else:
        top_w, top_i = jax.lax.top_k(scores, k)
    return top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9), top_i


@jax.named_scope("experts")
def _moe_mlp(x, lp, cfg: LlamaConfig):
    """Mixtral top-k routed experts (reference: the MoE GGUFs llama.cpp
    serves within ggml — SURVEY §2.4 expert-parallel row; HF semantics:
    softmax router → top-k → renormalize → weighted expert sum).

    Dense dispatch: every expert runs on every token and the top-k mask
    zeroes the rest — einsum-shaped for the MXU and for GSPMD expert
    parallelism (experts sharded on the `model` mesh axis; XLA turns the
    masked combine into an all-reduce). Served where expert_form says
    DENSE: a decode step's rows of a model that holds every expert (all the
    weights are read whichever form runs), any call under a mesh that
    shards the experts, and a forward without a cache (forward_train: the
    form with a gradient). A served prompt's tokens take _moe_routed."""
    from localai_tpu.ops.quant import dequantize, is_quantized

    def dq(p):
        return dequantize(p, x.dtype) if is_quantized(p) else p

    with jax.named_scope("router"):
        gate = lp["moe_gate"].astype(jnp.float32)
        top_w, top_i = _route(x.astype(jnp.float32) @ gate, lp, cfg)
        if cfg.routed_scale != 1.0:     # (1.0: the program Mixtral had)
            top_w = top_w * cfg.routed_scale
        E = gate.shape[-1]
        combine = jnp.einsum(
            "bske,bsk->bse",
            jax.nn.one_hot(top_i, E, dtype=jnp.float32), top_w)
    with jax.named_scope("expert_einsums"):
        w1, w2, w3 = dq(lp["moe_w1"]), dq(lp["moe_w2"]), dq(lp["moe_w3"])
        h1 = jnp.einsum("bsh,ehi->bsei", x, w1)
        h3 = jnp.einsum("bsh,ehi->bsei", x, w3)
        y = jnp.einsum("bsei,eih->bseh", jax.nn.silu(h1) * h3, w2)
        return jnp.einsum("bseh,bse->bsh", y, combine.astype(x.dtype))


@dataclasses.dataclass
class _InStack:
    """A layer's expert weights left WHERE THEY LIE: the kind's whole stack
    [L, E, in, out] (int8: {q, s}) and the layer's index in it. The routed
    layer slices (layer, expert) out in one step, a tile at a time; sliced
    a layer at a time first, the loop's operand is a copy of all the
    layer's experts (0.6 GB a layer at the cell's widths: 12 ms a step)."""
    stack: object
    layer: object

    def parts(self):
        """(body [L, E, in, out], scales [L, E, out] or None)."""
        from localai_tpu.ops.quant import is_quantized

        if is_quantized(self.stack):
            return self.stack["q"], self.stack["s"][..., 0, :]
        return self.stack, None

    def whole(self):
        """This layer's (body [E, in, out], scales [E, out] or None)."""
        return tuple(a if a is None else jax.lax.dynamic_index_in_dim(
            a, self.layer, keepdims=False) for a in self.parts())


def _leading(p):
    """The array of a weight leaf that has its leading axes (int8: `q`)."""
    if isinstance(p, _InStack):
        p = p.stack
    return p["q"] if isinstance(p, dict) else p


def _grouped_experts(xt, eid, weight, up, gate, down, tm: int):
    """SwiGLU experts (gate None: relu^2 experts of two matrices, up and
    down) over the token-expert pairs of xt [N, h]: eid [N, k]
    is the expert (among those held) each of a token's k pairs chose, or
    the number held for a pair of no expert here; weight [N, k] float32 is
    the pair's share of its token. up, gate, down: _InStack (the stacks the
    weights lie in, int8 or not, and the layer's index). Returns the
    tokens' weighted sums [N, h] float32.

    Each expert's pairs get rows of their own in a layout of TILES of `tm`
    rows, a group padded to whole tiles, so that a tile has one expert.
    Over the tiles IN USE (a dynamic count: sum of ceil(group / tm), at
    most `tiles`), that expert's three matrices are sliced out of the
    stacks where they lie and multiplied with the tile: on a TPU by the
    grouped product kernel (ops/pallas/grouped_matmul.py), else by a loop
    in XLA. Every pair is computed, none is dropped; the static worst case
    is every group ending one row into a tile. An expert no pair chose is
    not read at all. (jax.lax.ragged_dot lowers to a dense product over
    every group on this chip's compiler: 40 times the operations, PERF.md
    section 6.)

    Both stop at `used`: the loop's trip count and the kernel's grid (its
    first bound is that traced count, PR 53: a chip that holds an eighth of
    the router's experts uses a sixth of the static tiles). The rows of the
    tiles past `used` are zeros out of the loop and NOT WRITTEN by the
    kernel: there they hold what the buffer held, NaN and inf included.
    Nothing of them comes back: such a row is of no pair, so its weight
    `row_w` and its column of `sel` are 0, `lost` takes a row that is not
    finite out of the way back, and a finite leftover times 0 adds 0.

    No sort and no gather: a pair's row is its group's first row plus its
    place in the group (a running count), and the rows are filled, and a
    token's k results summed, by products with one 0/1 matrix on the
    matrix unit (a gather of 12 k rows costs this chip 1 ms, the two
    products 0.3; they cost tokens x rows, PERF.md section 6, PR 32). The
    pair's weight is applied in float32, to the float32 activation of its
    row before the down product (which is linear in it), so the way back
    only adds. A 0/1 product would hand a NaN or an inf of ANY row to
    every row (0 x NaN): a token or a result row that is not finite is
    kept out of the products and its token given back as NaN, alone, as a
    gather leaves it."""
    n, k = eid.shape
    h = xt.shape[-1]
    held = _leading(up).shape[-3]
    tiles = -(-(n * k + held * (tm - 1)) // tm)
    rows = tiles * tm
    # float32 activations (tests, a float32 load): the 0/1 products exact
    exact = jax.lax.Precision.HIGHEST if xt.dtype == jnp.float32 else None
    with jax.named_scope("dispatch"):
        chose = (eid.reshape(-1, 1) == jnp.arange(held)).astype(jnp.int32)
        sizes = chose.sum(0)                               # [E] pairs each
        nth = ((jnp.cumsum(chose, 0) - 1) * chose).sum(-1)  # place in group
        per = -(-sizes // tm)                              # tiles a group
        tile_end = jnp.cumsum(per)
        used = tile_end[-1]
        row = (chose * ((tile_end - per) * tm)).sum(-1) + nth
        row = jnp.where(chose.any(-1), row, -1).reshape(n, k)
        tile_e = jnp.minimum(
            (jnp.arange(tiles)[:, None] >= tile_end).sum(-1), held - 1)
        at = row[..., None] == jnp.arange(rows)            # [N, k, rows]
        sel = at.any(1).astype(xt.dtype)                   # [N, rows] 0/1
        # a row's weight (0 for a row of no pair)
        row_w = (at * weight[..., None]).sum((0, 1)).reshape(tiles, tm, 1)
        bad = ~jnp.isfinite(xt).all(-1)                    # [N]
        xp = jnp.dot(sel.T, jnp.where(bad[:, None], 0, xt),
                     precision=exact).reshape(tiles, tm, h)

    def product(a, w, e):
        body, scale = w.parts()
        le = (jnp.asarray(w.layer, jnp.int32), e)
        y = a @ jax.lax.dynamic_slice(
            body, (*le, 0, 0), (1, 1, *body.shape[2:]))[0, 0].astype(a.dtype)
        if scale is None:
            return y
        return y * jax.lax.dynamic_slice(
            scale, (*le, 0), (1, 1, scale.shape[2]))[0, 0].astype(y.dtype)

    def weighted(u, g, w):
        """silu(u) g (no gate: relu(u)^2) under the rows' weights, in
        float32."""
        u = u.astype(jnp.float32)
        if g is None:
            return (_relu2(u) * w).astype(xt.dtype)
        return (jax.nn.silu(u) * g.astype(jnp.float32) * w).astype(xt.dtype)

    def tile(t, out):
        e = tile_e[t]
        a = jax.lax.dynamic_index_in_dim(xp, t, keepdims=False)
        act = weighted(product(a, up, e),
                       None if gate is None else product(a, gate, e),
                       jax.lax.dynamic_index_in_dim(row_w, t, keepdims=False))
        return jax.lax.dynamic_update_index_in_dim(
            out, product(act, down, e), t, 0)

    def grouped(a, w):
        from localai_tpu.ops.pallas.grouped_matmul import grouped_matmul
        from localai_tpu.ops.quant import is_quantized

        body, scale = ((w.stack["q"], w.stack["s"]) if is_quantized(w.stack)
                       else (w.stack, None))
        return grouped_matmul(a, body, scale, tile_e, used, w.layer)

    with jax.named_scope("expert_einsums"):
        if kv._pallas(current_mesh() is None):
            out = grouped(weighted(
                grouped(xp, up), None if gate is None else grouped(xp, gate),
                row_w), down)
        else:
            out = jax.lax.fori_loop(0, used, tile,
                                    jnp.zeros((tiles, tm, h), xt.dtype))
    with jax.named_scope("dispatch"):
        out = out.reshape(rows, h)
        lost = ~jnp.isfinite(out).all(-1)                  # [rows]
        y = jnp.dot(sel, jnp.where(lost[:, None], 0, out), precision=exact,
                    preferred_element_type=jnp.float32)    # k rows summed
        bad |= jnp.dot(sel, lost.astype(sel.dtype),
                       preferred_element_type=jnp.float32) > 0
        return jnp.where(bad[:, None], jnp.nan, y)


def _tile_rows(tokens: int, k: int, experts: int) -> int:
    """Rows of a tile of _grouped_experts, between 8 and 128 (about where a
    tile's products stop waiting for its expert's weights): twice the
    pairs an expert gets if routing is even, so that most groups are one
    tile; from 512 tokens on once that, because the way into the padded
    rows and back costs tokens x rows and by then outweighs the second
    tile of every other group (Mellum2 at 512 tokens: 1.21 ms a layer at
    64 rows, 1.50 at 128; at 256 tokens 0.82 at 64, 0.89 at 32; PERF.md
    section 6, PR 32)."""
    even = tokens * k / experts * (1 if tokens >= 512 else 2)
    return int(min(128, max(8, 2 ** math.ceil(math.log2(even)))))


@jax.named_scope("experts")
def _moe_routed(x, lp, cfg: LlamaConfig, grouped: bool = True):
    """The routed expert layer: the layer holds experts [first, first + E)
    of the R the router scores, all of them (first 0, E = R: a prompt's
    tokens of any MoE model on one chip, expert_form) or a SHARE, with or
    without a shared expert. The router, the top-k and the renormalisation are
    over all R; the layer returns the shared expert plus the chosen experts
    that are HERE, each under its weight among all k chosen. What the
    absent experts would add is left out (the other chips of an
    expert-parallel layout hold them; on one chip there is no exchange).

    grouped (what is served): the token-expert pairs that land here, each
    expert's in tiles of their own, as grouped products over the int8
    expert weights (_grouped_experts: every pair is computed, the static
    worst case is all N k of them). Not grouped (the tests' and the
    bench's twin): every held expert on every token under the combine
    mask; on the chip it is slower for a share at 512 tokens (3.7 against
    1.4 ms a layer) and at a decode step's 32 rows (0.90 against 0.48);
    tools/moe_layer_bench.py, PERF.md section 6, PR 32.
    """
    b, s, h = x.shape
    k = cfg.experts_per_tok
    xt = x.reshape(b * s, h)
    n = b * s
    held = _leading(lp["moe_w1"]).shape[-3]
    with jax.named_scope("router"):
        top_w, top_i = _route(                                 # over [N, R]
            xt.astype(jnp.float32) @ lp["moe_gate"].astype(jnp.float32),
            lp, cfg)
        top_w = top_w * cfg.routed_scale
        local = top_i - cfg.first_expert                       # [N, k]
        here = (local >= 0) & (local < held)
    w1, w2, w3 = (None if n not in lp
                  else lp[n] if isinstance(lp[n], _InStack)
                  else _InStack(jax.tree_util.tree_map(lambda a: a[None],
                                                       lp[n]), 0)
                  for n in ("moe_w1", "moe_w2", "moe_w3"))
    hidden = xt
    if "w_lat_in" in lp:    # the routed experts work in a latent
        with jax.named_scope("latent_in"):
            xt = qmatmul(hidden, lp["w_lat_in"])
    if grouped:
        y = _grouped_experts(
            xt, jnp.where(here, local, held), top_w, w1, w3, w2,
            _tile_rows(n, k, lp["moe_gate"].shape[-1]))    # [N, h] float32
    else:
        with jax.named_scope("router"):
            combine = jnp.einsum(
                "nke,nk->ne",
                jax.nn.one_hot(jnp.where(here, local, held), held,
                               dtype=jnp.float32), top_w)
        with jax.named_scope("expert_einsums"):
            def dq(w):
                w, scale = w.whole()
                w = w.astype(x.dtype)
                return w if scale is None else w * scale[:, None, :].astype(
                    x.dtype)

            h1 = jnp.einsum("nh,ehi->nei", xt, dq(w1))
            if w3 is None:
                act = _relu2(h1)
            else:
                h3 = jnp.einsum("nh,ehi->nei", xt, dq(w3))
                act = jax.nn.silu(h1) * h3
            y = jnp.einsum("nei,eih->neh", act, dq(w2))
            y = jnp.einsum("neh,ne->nh", y.astype(jnp.float32), combine)
    if "w_lat_out" in lp:
        with jax.named_scope("latent_out"):
            y = qmatmul(y.astype(x.dtype), lp["w_lat_out"]).astype(
                jnp.float32)
    if "ws_up" in lp:
        with jax.named_scope("shared"):
            if "ws_gate" in lp:
                act = (jax.nn.silu(qmatmul(hidden, lp["ws_gate"]))
                       * qmatmul(hidden, lp["ws_up"]))
            else:
                act = _relu2(qmatmul(hidden, lp["ws_up"]))
            y = y + qmatmul(act, lp["ws_down"]).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, h)


# Activation sharding hints: hard constraints when a mesh is active (raises on
# a wrong spec), identity otherwise. See localai_tpu/parallel/mesh.py.
_shard_act = constrain


def _seq_ax():
    """'seq' when the ambient mesh carries the ring-attention axis, else None
    (specs naming absent axes would raise)."""
    return "seq" if seq_axis_size(current_mesh()) > 1 else None


def kernel_tiers(cfg: LlamaConfig, mesh, *, paged: bool,
                 tiered: bool = False) -> dict[str, str]:
    """Which implementation serves each hot-path op for an engine of this
    shape — the same predicates the forwards consult at trace time, named
    for the backend's device report. 'pallas-interpret' means the Pallas
    kernels run in the interpreter (forced off-TPU): correct, never fast."""
    from localai_tpu.ops.pallas.flash_attention import _interpret

    pallas = "pallas-interpret" if _interpret() else "pallas"
    attn = pallas if kv._pallas_attention(mesh) else "xla"
    with activate_mesh(mesh):
        paged_kernel = pallas if paged and kv._pallas_paged_scatter(
            cfg.num_kv_heads) else "xla"
    prefill = "xla-ring" if attn == "xla" and seq_axis_size(mesh) > 1 \
        else attn
    tiers = {
        # first-chunk prompt attention (prefill); the KV lifecycle tier
        # masks it per slot on XLA
        "prefill_attention": "xla" if tiered else prefill,
        # later chunks of a long prompt and spec verify windows (extend)
        # attend against the cache on XLA: a dense cache's full-length rows
        # block by block up to the context the chunk has
        # (kv.DenseKV.attend_window; a ring beside them whole), a pool's, a
        # tier's and any row under a sequence axis whole (mha_extend). A
        # latent layer's rows by the same blocks in the kernel where decode
        # takes one (kv.LatentKV.attend_window)
        "chunk_attention": (
            "xla" if paged or tiered or seq_axis_size(mesh) > 1
            else attn if attn != "xla" and LATENT in (cfg.layer_types or ())
            else "xla-blocks"),
        # the tiered (ring-mapped) cache read has no kernel yet
        "decode_attention": "xla" if tiered else attn,
        "decode_kv_write": paged_kernel,
        "prefill_kv_write": "xla",
    }
    if cfg.num_experts:
        # a prompt's tokens through the experts (expert_form): the grouped
        # product kernel on one chip, its XLA loop under a mesh, the dense
        # einsums where the mesh shards the experts
        routed = expert_form(cfg, ROUTED_FROM_TOKENS, mesh) == ROUTED
        tiers["prefill_experts"] = (
            "xla-dense" if not routed
            else pallas if kv._pallas(mesh is None) else "xla")
    return tiers


def rope_tables(cfg: LlamaConfig, max_len: int):
    """The (cos, sin) the forwards take: one pair of tables for a one-kind
    model; for a model with layer_types a pair of dicts keyed by layer kind
    (window and full layers rotate differently, or one kind not at all:
    cfg.rotates)."""
    if cfg.layer_types is None:
        return rope_table(cfg.rope, max_len)
    tabs = {kind: rope_table(cfg.rope_of(kind), max_len)
            for kind in ((LATENT,) if LATENT in cfg.layer_types
                         else (FULL, WINDOW)) if cfg.rotates(kind)}
    return ({kind: t[0] for kind, t in tabs.items()},
            {kind: t[1] for kind, t in tabs.items()})


def _attn_scope(kind):
    """Device time by layer kind: a mixed model's attention (projections,
    kernel and all: it has no older compile-cache key to keep) is traced
    under attention/<kind>; a one-kind model's as it always was."""
    return (contextlib.nullcontext() if kind is None
            else jax.named_scope(f"attention/{kind}"))


def _layer_params(layers, i):
    """One layer's weights, sliced out of the [L, ...] stack where they are
    used, as scan slices its xs (a [period, ...] slice of a folded stack is
    copied whole every iteration: 1.6 GB of experts a period at Mellum2's
    widths)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers)


def _block(cfg: LlamaConfig, x, lp, kind, cos, sin, positions, attend, spec):
    """The transformer block, once: attn_norm → QKV (→ RMSNorm of q and k a
    head) → RoPE by layer kind (a LATENT layer: the query and the latent row
    it caches, _latent_qk) → `attend` (→ output gate) → wo → mlp_norm →
    MLP over the residual x [B, S, H]; where the layer has post-norms, wo's
    and the MLP's outputs are normalised before they are added. Every
    forward (and a pipeline stage) is this block over its own
    `attend(q, k, v) -> (attn [B, S, H, D], out)`: self-attention over a
    prompt, or a write into the cache's view and a read back; `out` goes
    back to the scan (the view written; K and V to write after the block).
    `attend` opens `_attn_scope(kind)` around what counts as attention, and
    around no cache-writing kernel.

    spec: (batch axis, sequence axis) of the activations, e.g. ('data',
    None): under a mesh the projections' outputs are constrained head-/ffn-
    parallel on 'model' and the residual back to replicated, the hints that
    keep TP weights resident-sharded through the scan. None under shard_map
    (parallel/pipeline.py), where constraints are illegal."""
    b, s, _ = x.shape
    sharded = lambda *tail: spec and P(*spec, *tail)  # noqa: E731
    h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    if kind == LINEAR:
        x, out = _linear_mixer(cfg, x, h, lp, attend)
    elif kind == SSM:
        x, out = _ssm_mixer(cfg, x, h, lp, attend)
    else:
        with _attn_scope(kind):
            if kind == LATENT:
                q, k = _latent_qk(cfg, h, lp, cos[kind], sin[kind],
                                  positions)
                v = None    # (the view makes values of the rows it holds)
            else:
                q, k, v = _qkv(h, lp, cfg, spec=sharded("model"))
                if "q_norm" in lp:
                    with jax.named_scope("qk_norm"):
                        q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
                        k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
                if cfg.rotates(kind):
                    lcos, lsin = ((cos, sin) if kind is None
                                  else (cos[kind], sin[kind]))
                    q = apply_rope(q, lcos, lsin, positions)
                    k = apply_rope(k, lcos, lsin, positions)
            if spec is not None:
                q = _shard_act(q, sharded("model", None))
        attn, out = attend(q, k, v)
        with _attn_scope(kind), jax.named_scope("attention"):
            attn = attn.reshape(b, s, -1)
            if "w_agate" in lp:
                attn = attn * jax.nn.sigmoid(qmatmul(h, lp["w_agate"]))
            o = qmatmul(attn, lp["wo"], spec=sharded(None))
            if "attn_post_norm" in lp:
                o = rms_norm(o, lp["attn_post_norm"], cfg.rms_eps)
            x = x + o
    if "mlp_norm" not in lp:    # a layer that is its mixer alone
        return x, out
    m = _mlp(rms_norm(x, lp["mlp_norm"], cfg.rms_eps), lp, cfg,
             spec_prefix=spec)
    if "mlp_post_norm" in lp:
        m = rms_norm(m, lp["mlp_post_norm"], cfg.rms_eps)
    x = x + m
    if spec is not None:
        x = _shard_act(x, sharded(None))
    return x, out


def _latent_qk(cfg: LlamaConfig, h, lp, cos, sin, positions):
    """A LATENT layer's query and the row it caches, of the normed input h
    [B, S, H]: q [B, S, heads, N + P] = W_qb RMSNorm(W_qa h), its last P
    columns rotated; row [B, S, R + P] = W_kva h, its first R (the latent)
    RMSNorm'd, its last P (the position key ALL heads share) rotated. Keys
    and values are made of rows by W_kvb where they are attended over
    (kv.LatentKV), never here."""
    b, s, _ = h.shape
    n, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("q_lora"):
        q = qmatmul(rms_norm(qmatmul(h, lp["wq_a"]), lp["q_a_norm"],
                             cfg.rms_eps), lp["wq_b"])
        q = q.reshape(b, s, cfg.num_heads, -1)
        q = jnp.concatenate(
            [q[..., :n], apply_rope(q[..., n:], cos, sin, positions)], -1)
    with jax.named_scope("kv_lora"):
        row = qmatmul(h, lp["wkv_a"])
        k_pe = apply_rope(row[..., None, r:], cos, sin, positions)[..., 0, :]
        row = jnp.concatenate(
            [rms_norm(row[..., :r], lp["kv_a_norm"], cfg.rms_eps), k_pe], -1)
    return q, row


def _linear_mixer(cfg: LlamaConfig, x, h, lp, attend):
    """A LINEAR layer's mixer over the normed input h [B, S, H]: q, k, v
    before their short convolution, side by side (the cache's view keeps
    the convolution's tail and the state, and does the rest:
    kv.StateKV); the per-channel log-decay g = -exp(A_log) softplus(W_f2
    W_f1 h + dt_bias) <= 0 and beta = sigmoid(W_b h), doubled where the
    config allows negative eigenvalues; `attend(u, conv, g, beta) ->
    (o [B, S, heads, D], out)`; then RMSNorm a head, the low-rank sigmoid
    output gate, and wo."""
    b, s, _ = h.shape
    nh = cfg.linear_heads
    with _attn_scope(LINEAR):
        u = jnp.concatenate([qmatmul(h, lp[w]) for w in ("wq", "wk", "wv")],
                            axis=-1)
        f32 = jnp.float32
        z = qmatmul(qmatmul(h, lp["w_f1"]), lp["w_f2"]).astype(f32)
        g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            (z + lp["dt_bias"].astype(f32)).reshape(b, s, nh, -1))
        beta = jax.nn.sigmoid(qmatmul(h, lp["w_b"]).astype(f32))
        if cfg.linear_neg_eigval:
            beta = 2.0 * beta
    o, out = attend(u, lp["conv"], g, beta)
    with _attn_scope(LINEAR):
        o = rms_norm(o, lp["o_norm"], cfg.rms_eps).astype(h.dtype)
        gate = jax.nn.sigmoid(qmatmul(qmatmul(h, lp["w_g1"]), lp["w_g2"]))
        return x + qmatmul(o.reshape(b, s, -1) * gate, lp["wo"]), out


def _ssm_mixer(cfg: LlamaConfig, x, h, lp, attend):
    """An SSM (Mamba-2) layer's mixer over the normed input h [B, S, H]:
    [z | xBC | dt] = W_in h; `attend(xBC, dt) -> (y [B, S, heads, P]
    float32, out)` is the cache's view's (kv.SsmKV: the convolution with
    its tail, the state, D x); then y silu(z), RMSNorm over each of the
    groups' channels with the layer's gain, and W_out."""
    b, s, _ = h.shape
    nh = cfg.ssm_heads
    inner = nh * cfg.ssm_head_dim
    f32 = jnp.float32
    with _attn_scope(SSM), jax.named_scope("in_proj"):
        zxd = qmatmul(h, lp["w_in"])
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:-nh], zxd[..., -nh:])
    y, out = attend(xbc, dt)
    with _attn_scope(SSM):
        with jax.named_scope("gated_norm"):
            y = y.reshape(b, s, cfg.ssm_groups, -1) * jax.nn.silu(
                z.astype(f32)).reshape(b, s, cfg.ssm_groups, -1)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_eps)
            y = (y.reshape(b, s, inner)
                 * lp["ssm_norm"].astype(f32)).astype(h.dtype)
        with jax.named_scope("out_proj"):
            return x + qmatmul(y, lp["w_out"]), out


def _expert_layer(cfg: LlamaConfig, x, lp):
    """A layer that is a feed-forward part alone (EXPERTS): x +
    MLP(RMSNorm(x)); no mixer, no cache."""
    return x + _mlp(rms_norm(x, lp["mlp_norm"], cfg.rms_eps), lp, cfg)


def _scan_layers(cfg: LlamaConfig, block, x, params, cache):
    """Run `block(x, lp, view, kind) -> (x, view)` over the layer stacks of
    `params` and return (x, (k_cache, v_cache)) as the engine holds them. `cache` is the
    forward's kv.view; how it rides the scan is its `carried`, read here
    and nowhere else. Carried (a dense stack, a ring): the stacks are the
    scan's CARRY and the block gets the view `at` the layer's index — no
    cache is an xs or a ys, nothing is sliced out, put back or copied. Not
    carried (a block pool; no cache): k, v and a tier's read-only cold
    pools are the scan's xs and ys, so XLA slices each layer's pool out of
    the stack and writes it back (and copies the stack where a loop carries
    it) — what a paged or tiered cache still pays (kv.PagedKV).

    One kind of layer: lax.scan over [L, ...] with kind None, the program a
    one-kind model always had. With layer_types `cache` is one view per
    place in the PERIOD of kinds and the scan's body is one period,
    unrolled: compile time grows with the period, not the depth. Leading
    dense layers (params["leading"], the cache places after the period's)
    run before the scan as an unrolled prefix: their kinds need not follow
    the period, each has a cache place of its own, and they are few."""
    layers = params["layers"]
    period = cfg.period
    # a call that takes the routed form leaves the experts IN their stacks
    # (_InStack: the tile loop slices (layer, expert) in one step); handed
    # in as xs, or sliced a layer at a time, they reach the loop as a COPY
    # of the layer's experts (1.4 GB a layer at Mixtral's widths)
    views = cache if period else (cache,)
    routed = bool(cfg.num_experts) and expert_form(
        cfg, x.shape[0] * x.shape[1], current_mesh(),
        any(c.k is not None for c in views)) == ROUTED

    def weights(stack, n):
        """Layer n's weights out of a [L, ...] stack."""
        if not routed:
            return _layer_params(stack, n)
        lp = _layer_params({k: v for k, v in stack.items()
                            if not k.startswith("moe_w")}, n)
        lp.update({k: _InStack(v, n) for k, v in stack.items()
                   if k.startswith("moe_w")})
        return lp

    if period is None:
        experts = {k: v for k, v in layers.items()
                   if routed and k.startswith("moe_w")}
        rest = {k: v for k, v in layers.items() if k not in experts}
        in_stack = lambda lp, i: {  # noqa: E731
            **lp, **{k: _InStack(v, i) for k, v in experts.items()}}
    if period is None and cache.carried:
        def layer(carry, xs):
            x, k, v = carry
            lp, i = xs
            x, view = block(x, in_stack(lp, i), cache.at(k, v, i), None)
            return (x, view.k, view.v), None

        (x, k, v), _ = jax.lax.scan(
            layer, (x, cache.k, cache.v),
            (rest, jnp.arange(cfg.num_layers)))
        return x, (k, v)
    if period is None:
        def layer(x, xs):
            lp, k, v, *cold = xs
            if experts:
                lp = in_stack(*lp)
            x, view = block(x, lp, cache.at(k, v, cold=cold), None)
            return x, (view.k, view.v)

        return jax.lax.scan(
            layer, x,
            ((rest, jnp.arange(cfg.num_layers)) if experts else rest,
             cache.k, cache.v, *cache.cold))
    p = len([c for c in cfg.cache_places if c is not None])
    periods = (cfg.num_layers - cfg.leading_dense_layers) // len(period)
    lead = list(cache[p:])
    for j, kind in enumerate(cfg.leading_kinds):
        lp = _layer_params(params["leading"], j)
        x, lead[j] = block(
            x, lp, lead[j].at(lead[j].k, lead[j].v, 0).of_layer(lp), kind)

    def place(i, j, kind):
        if not cfg.stacked_by_kind:
            return weights(layers, i * len(period) + j)
        # stacks by kind: this layer's place among its kind's
        return weights(layers[kind],
                       i * period.count(kind) + period[:j].count(kind))

    def step(carry, i):
        x, ks, vs = carry
        ks, vs = list(ks), list(vs)
        for j, (kind, c) in enumerate(zip(period, cfg.cache_places)):
            lp = place(i, j, kind)
            if c is None:       # no mixer, no place in the cache
                x = _expert_layer(cfg, x, lp)
                continue
            x, view = block(x, lp, cache[c].at(ks[c], vs[c], i).of_layer(lp),
                            kind)
            ks[c], vs[c] = view.k, view.v
        return (x, tuple(ks), tuple(vs)), None

    (x, ks, vs), _ = jax.lax.scan(
        step, (x, tuple(c.k for c in cache[:p]), tuple(c.v for c in cache[:p])),
        jnp.arange(periods))
    return x, (PeriodKV(ks + tuple(c.k for c in lead)),
               PeriodKV(vs + tuple(c.v for c in lead)))


def _embed(params, cfg: LlamaConfig, tokens, inject=None):
    """Token embeddings; where inject's is_embed is set, its `extra` rows
    (the multimodal path, models/llava.py, splices image features here)."""
    x = params["embed"].astype(cfg.jdtype)[tokens]
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    if inject is not None:
        extra, is_embed = inject
        x = jnp.where(is_embed[..., None], extra.astype(x.dtype), x)
    return x


def prefill(params, cfg: LlamaConfig, tokens, lengths, cos, sin,
            k_cache, v_cache, slot_map, table=None, inject=None, kvt=None):
    """Process padded prompt batch, writing K/V into slot rows of the cache.

    tokens: [B, S] i32 (padded); lengths: [B]; slot_map: [B] i32 — which cache
    slot each batch row writes into; cos/sin: rope tables; table: optional
    paged block table (ops/paged.py). inject (extra [B, S, H], is_embed
    [B, S] bool), optional: positions with is_embed take `extra` rows instead
    of the token embedding — the multimodal path (models/llava.py) splices
    projected image features into the prompt here.
    Returns (last_token_logits [B, V] f32, k_cache, v_cache).
    """
    b, s = tokens.shape
    cache = kv.view(cfg, k_cache, v_cache, table, kvt)
    self_attention = kv.prompt_attention(cache, slot_map)
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    sax = _seq_ax()
    x = _shard_act(_embed(params, cfg, tokens, inject), P("data", sax, None))

    def layer(x, lp, view, kind):
        if kind in (LINEAR, SSM):   # (what the kind's mixer hands over)
            def mix(*a):
                with _attn_scope(kind):
                    return view.prompt(*a, slot_map, lengths)

            return _block(cfg, x, lp, kind, cos, sin, positions, mix,
                          ("data", sax))

        def attend(q, k, v):
            with _attn_scope(kind):
                return view.self_attend(self_attention, q, k, v,
                                        lengths), (k, v)

        x, (k, v) = _block(cfg, x, lp, kind, cos, sin, positions, attend,
                           ("data", sax))
        # unique=False: batched admission pads groups by repeating a real
        # request's plan (engine _flush_admits), so slot_map can repeat
        return x, view.write(k, v, slot_map, positions, end=lengths,
                             unique=False)

    x, (k_cache, v_cache) = _scan_layers(cfg, layer, x, params, cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return _lm_head(last.astype(jnp.float32), params), k_cache, v_cache


def decode_step(params, cfg: LlamaConfig, tokens, lengths, cos, sin,
                k_cache, v_cache, active=None, table=None, kvt=None):
    """One continuous-batching decode step over ALL slots.

    tokens: [B] i32 — last sampled token per slot; lengths: [B] — cache entries
    valid per slot BEFORE this token (the new token is written at index
    lengths). Inactive slots just compute garbage that is masked host-side.
    `active` [B] bool (optional): inactive slots redirect their cache write to
    the last cache row (never a readable position — the engine terminates at
    max_context-1) so a decode step can run concurrently with a chunked
    prefill into an inactive slot without corrupting it.
    `table` [B, MAXB] i32 (optional): block-paged cache (ops/paged.py) — the
    redirect then goes to the trash block (kv.PagedKV).
    Returns (logits [B, V] f32, k_cache, v_cache).
    """
    positions = lengths[:, None]  # [B,1]
    cache = kv.view(cfg, k_cache, v_cache, table, kvt, active=active)
    x = _embed(params, cfg, tokens)[:, None, :]  # [B,1,H]
    x = _shard_act(x, P("data", None, None))

    def layer(x, lp, view, kind):
        def attend(q, k, v):
            # the new token lands in the cache FIRST; attention then reads
            # it back with the rest (lengths + 1 counts it)
            wrote = view.append(k, v, lengths, positions)
            with _attn_scope(kind):
                return wrote.decode(q, lengths + 1), wrote

        if kind in (LINEAR, SSM):
            def attend(*a):  # noqa: F811
                with _attn_scope(kind):
                    return view.step(*a)

        return _block(cfg, x, lp, kind, cos, sin, positions, attend,
                      ("data", None))

    x, (k_cache, v_cache) = _scan_layers(cfg, layer, x, params, cache)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _lm_head(x[:, 0].astype(jnp.float32), params)
    return logits, k_cache, v_cache


def build_decode_loop(step_fn, *, max_steps: int, limit: int):
    """While-loop variant of the fused decode block (Kernel Looping,
    arXiv:2410.23668): up to `max_steps` sample→decode iterations run as ONE
    on-device `lax.while_loop` dispatch, with per-slot stop conditions
    evaluated from device-resident state — no host round trip per block, no
    host-side power-of-two step ladder.

    `step_fn` is the engine's fused sample→decode body
    (params, cos, sin, kc, vc, sampler, last_logits, lengths, active,
    mask_bits, fast_width, table) → (tokens, logprobs, kc, vc, sampler,
    logits, lengths) — the SAME body the scan block and the single-step
    dispatch run, so per-slot RNG streams are identical across paths.

    Per-iteration stop conditions (computed on device, per slot):
    - EOS-set membership: sampled token ∈ `eos_ids` for slots with
      `check_eos` (host clears it for ignore_eos requests);
    - token budget: the slot produced `remaining` tokens this dispatch
      (max_tokens net of in-flight reservations, shipped per dispatch);
    - context margin: the slot's cache length reached `limit` (static,
      max_context minus the decode margin) — the host then finishes the
      request or context-shifts it and the loop resumes next dispatch.

    A finished slot is frozen: its sampler key and last_logits stop
    advancing (so a context-shifted slot resumes the exact RNG stream the
    single-step path would have used), its length stops, and its cache
    writes redirect to the trash row/block via `step_fn`'s active mask.
    The loop EARLY-EXITS once every live slot froze — a dispatch costs only
    the steps it actually ran (`steps_run` proves it).

    Grammar-constrained slots ride the same loop via the optional device
    automaton tables (gstate [B] i32 per-slot state, gmasks [S, ceil(V/32)]
    u32 packed allowed-token rows, gtrans [S, V] i32): each iteration
    gathers the slot's mask row, hard-masks sampling with it (the fused
    sample body's grammar path), and advances the state through gtrans on
    the emitted token — no host resync inside the loop. State row 0 is the
    all-ones/self-loop identity, so unconstrained slots stay bit-identical
    to the maskless variant (an all-true jnp.where is the logits exactly,
    and _draw is width-independent).

    Tokens land in an on-device ring buffer [max_steps, B]; the engine
    streams them out via async device→host copies (engine._AsyncFetch).
    Returns (tokens [max_steps, B], logprobs [max_steps, B], n_out [B],
    steps_run, kc, vc, sampler, last_logits, lengths) — slot b's valid
    tokens are rows 0..n_out[b)-1.
    """

    def decode_loop(params, cos, sin, kc, vc, sampler, last_logits, lengths,
                    active, remaining, check_eos, eos_ids, table=None,
                    fast_width=None, kvt=None, gstate=None, gmasks=None,
                    gtrans=None):
        B = lengths.shape[0]
        grammar = gmasks is not None
        if gstate is None:
            gstate = jnp.zeros((B,), jnp.int32)
        init = (
            jnp.int32(0),                            # steps run
            ~active,                                 # done (per slot)
            jnp.zeros((B,), jnp.int32),              # n_out
            jnp.zeros((max_steps, B), jnp.int32),    # token ring buffer
            jnp.zeros((max_steps, B), jnp.float32),  # logprob ring buffer
            gstate,                                  # grammar automaton state
            kc, vc, sampler, last_logits, lengths,
        )

        def cond(carry):
            i, done = carry[0], carry[1]
            return (i < max_steps) & jnp.any(~done)

        def body(carry):
            (i, done, n_out, toks, lps, gstate, kc, vc, sampler,
             last_logits, lengths) = carry
            live = ~done
            prev_key = sampler.key
            mask = gmasks[gstate] if grammar else None
            tokens, lp, kc, vc, sampler, logits, lengths = step_fn(
                params, cos, sin, kc, vc, sampler, last_logits, lengths,
                live, mask, fast_width, table, kvt)
            # freeze finished slots: their key stream and last_logits hold
            # at the finishing token (step_fn already gates lengths and
            # token_counts on the active mask)
            sampler = dataclasses.replace(
                sampler, key=jnp.where(live[:, None], sampler.key, prev_key))
            last_logits = jnp.where(live[:, None], logits, last_logits)
            toks = toks.at[i].set(tokens)
            lps = lps.at[i].set(lp)
            n_out = n_out + live.astype(jnp.int32)
            is_eos = check_eos & jnp.any(
                tokens[:, None] == eos_ids[None, :], axis=1)
            if grammar:
                # advance the automaton on the emitted token; only a live
                # slot's state moves. gtrans rows self-loop on EOS in
                # accepting states and send masked-off tokens to the
                # identity row 0 — neither is ever taken: sampling already
                # excluded them.
                gstate = jnp.where(live, gtrans[gstate, tokens], gstate)
            done = done | (live & (is_eos | (n_out >= remaining)
                                   | (lengths >= limit)))
            return (i + 1, done, n_out, toks, lps, gstate, kc, vc, sampler,
                    last_logits, lengths)

        (steps, _, n_out, toks, lps, _, kc, vc, sampler, last_logits,
         lengths) = jax.lax.while_loop(cond, body, init)
        return (toks, lps, n_out, steps, kc, vc, sampler, last_logits,
                lengths)

    return decode_loop


def hidden_states(params, cfg: LlamaConfig, tokens, lengths=None):
    """Full-sequence causal forward → final-norm hidden states [B, S, H].
    `lengths` masks padded positions out of attention (defaults to full)."""
    b, s = tokens.shape
    cos, sin = rope_tables(cfg, s)
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    cache = kv.view(cfg, None, None)
    self_attention = kv.prompt_attention()
    sax = _seq_ax()
    x = _shard_act(_embed(params, cfg, tokens), P("data", sax, None))

    def layer(x, lp, view, kind):
        def attend(q, k, v):
            with _attn_scope(kind):
                return view.self_attend(self_attention, q, k, v,
                                        lengths), view

        if kind in (LINEAR, SSM):
            def attend(*a):  # noqa: F811
                with _attn_scope(kind):
                    return view.prompt(*a, None, lengths)

        return _block(cfg, x, lp, kind, cos, sin, positions, attend,
                      ("data", sax))

    x, _ = _scan_layers(cfg, layer, x, params, cache)
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


def extend(params, cfg: LlamaConfig, tokens, start, cos, sin,
           k_cache, v_cache, slot_map=None, with_logits=True, last_pos=None,
           table=None, inject=None, full_window=False, redirect=None,
           kvt=None):
    """Forward a window of S tokens per slot starting at cache offset
    `start` [B] — the speculative-decoding verification pass (reference knob:
    DraftModel/NDraft, /root/reference/backend/backend.proto:218,150) and the
    chunked-prefill workhorse. Writes window K/V into the cache and returns
    logits for EVERY window position [B, S, V] plus the updated caches.

    slot_map [B] (optional): which cache slot each batch row reads/writes
    (defaults to row i ↔ slot i). with_logits=False skips the vocabulary
    projection (non-final prefill chunks need only the KV writes) and
    returns (None, k_cache, v_cache). last_pos [B] (optional): project only
    the hidden state at that window position → logits [B, V], avoiding the
    [B, S, V] buffer when a single row is wanted (final prefill chunk; what
    follows it is padding). full_window: every position sits inside the
    slot's allocation (mid chunks); redirect [B]: kv.PagedKV.
    """
    b, s = tokens.shape
    cache = kv.view(cfg, k_cache, v_cache, table, kvt, redirect=redirect)
    rows = jnp.arange(b) if slot_map is None else slot_map
    positions = start[:, None] + jnp.arange(s)[None, :]
    x = _embed(params, cfg, tokens, inject)

    def layer(x, lp, view, kind):
        def attend(q, k, v):
            wrote = view.write(k, v, rows, positions, last=last_pos,
                               full_window=full_window)
            with _attn_scope(kind), jax.named_scope("attention"):
                return wrote.attend_window(q, positions, start, rows,
                                           slot_map is not None), wrote

        if kind in (LINEAR, SSM):
            def attend(*a):  # noqa: F811
                with _attn_scope(kind):
                    return view.chunk(
                        *a, rows, start,
                        None if last_pos is None else last_pos + 1)

        return _block(cfg, x, lp, kind, cos, sin, positions, attend,
                      ("data", None))

    x, (k_cache, v_cache) = _scan_layers(cfg, layer, x, params, cache)
    if not with_logits:
        return None, k_cache, v_cache
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if last_pos is not None:
        x = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)[:, 0]
    return _lm_head(x.astype(jnp.float32), params), k_cache, v_cache


def cache_shift(cfg: LlamaConfig, k_cache, v_cache, lengths, slot, *,
                keep: int, discard: int):
    """llama.cpp-style context shift for one slot (reference ctx_shift,
    /root/reference/backend/cpp/llama-cpp/grpc-server.cpp:311): keep the
    first `keep` sink tokens, evict the next `discard`, slide the rest left.

    Cached K is stored post-RoPE, so the moved entries are re-rotated by
    -discard positions (a pure rotation by angle -discard·inv_freq — the
    YaRN/llama3 attention mscale is a uniform factor and commutes with it).
    `keep`/`discard` are static → one compiled program per engine.
    Returns (k_cache, v_cache, lengths) with lengths[slot] -= discard.
    """
    from localai_tpu.ops.rope import rope_freqs

    kv.no_mixed(cfg, "cache_shift")
    inv_freq, _ = rope_freqs(cfg.rope)
    ang = discard * inv_freq                     # [D/2]
    c, s = jnp.cos(ang), jnp.sin(ang)

    T = k_cache.shape[3]
    quant = isinstance(k_cache, QuantKV)
    # quantized caches shift in f32 and requantize the slot (fresh scales);
    # only the shifted slot pays the dequant→requant round trip
    ks = dequant(k_cache[:, slot], jnp.float32) if quant else k_cache[:, slot]
    vs = dequant(v_cache[:, slot], jnp.float32) if quant else v_cache[:, slot]
    ks_m = jnp.roll(ks, -discard, axis=2)
    vs_m = jnp.roll(vs, -discard, axis=2)
    # R(-d): x1' = x1·cos + x2·sin ; x2' = x2·cos - x1·sin
    x1, x2 = jnp.split(ks_m.astype(jnp.float32), 2, axis=-1)
    ks_rot = jnp.concatenate([x1 * c + x2 * s, x2 * c - x1 * s],
                             axis=-1).astype(ks.dtype)
    idx = jnp.arange(T)[None, None, :, None]
    length = lengths[slot]
    move = (idx >= keep) & (idx < length - discard)
    k_new = jnp.where(move, ks_rot, ks)
    v_new = jnp.where(move, vs_m, vs)
    if quant:
        kq = requantize(k_cache[:, slot], k_new)
        vq = requantize(v_cache[:, slot], v_new)
        k_cache = QuantKV(k_cache.q.at[:, slot].set(kq.q),
                          k_cache.s.at[:, slot].set(kq.s))
        v_cache = QuantKV(v_cache.q.at[:, slot].set(vq.q),
                          v_cache.s.at[:, slot].set(vq.s))
    else:
        k_cache = k_cache.at[:, slot].set(k_new)
        v_cache = v_cache.at[:, slot].set(v_new)
    lengths = lengths.at[slot].add(-discard)
    return k_cache, v_cache, lengths


def cache_shift_paged(cfg: LlamaConfig, k_pool, row_table, *,
                      keep_blocks: int, discard_blocks: int):
    """Block-granular context shift for ONE paged slot (reference ctx_shift
    against a unified cache, grpc-server.cpp:311; dense analog: cache_shift).

    With paged storage the SLIDE is free — the host permutes the slot's
    table row (keep the first `keep_blocks` sink blocks, drop the next
    `discard_blocks`, tail moves left; freed blocks re-append as fresh tail
    capacity). The only physical work is K's RoPE correction: every kept
    tail block re-rotates by -discard_blocks*BLOCK positions, IN PLACE in
    the pool. V blocks never move or change.

    row_table [MAXB] i32 is the PRE-permutation map; tail blocks (virtual
    index >= keep_blocks+discard_blocks, physical != 0) are rotated;
    everything else scatters to the trash block (unique=False — those rows
    collide there by design). Returns the updated k_pool."""
    from localai_tpu.ops.paged import BLOCK
    from localai_tpu.ops.rope import rope_freqs

    kv.no_mixed(cfg, "cache_shift_paged")
    inv_freq, _ = rope_freqs(cfg.rope)
    ang = (discard_blocks * BLOCK) * inv_freq
    c, s = jnp.cos(ang), jnp.sin(ang)

    # only the tail blocks move — gather/rotate/scatter just those
    # (keep_blocks + discard_blocks is static under jit, so this is a
    # plain slice, not a dynamic gather)
    tail = row_table[keep_blocks + discard_blocks:]
    quant = isinstance(k_pool, QuantKV)
    kb = k_pool[:, tail]                         # [L, TAIL, KVH, BS, D]
    kf = dequant(kb, jnp.float32) if quant else kb.astype(jnp.float32)
    x1, x2 = jnp.split(kf, 2, axis=-1)
    rot = jnp.concatenate([x1 * c + x2 * s, x2 * c - x1 * s], axis=-1)

    target = jnp.where(tail != 0, tail, 0)       # unallocated entries → trash
    if quant:
        rq = requantize(kb, rot)
        k_pool = QuantKV(
            k_pool.q.at[:, target].set(rq.q, unique_indices=False),
            k_pool.s.at[:, target].set(rq.s, unique_indices=False))
        return k_pool
    return k_pool.at[:, target].set(rot.astype(k_pool.dtype),
                                    unique_indices=False)


def forward_train(params, cfg: LlamaConfig, tokens):
    """Full-sequence causal forward → logits [B, S, V] (training / eval path)."""
    x = hidden_states(params, cfg, tokens)
    return _lm_head(x.astype(jnp.float32), params)


def encode_pooled(params, cfg: LlamaConfig, tokens, lengths, normalize=True):
    """Masked-mean-pooled embeddings [B, H] f32 — the embeddings path
    (reference: mean_pooling + Embedding RPC,
    /root/reference/backend/python/transformers/backend.py:37,323)."""
    b, s = tokens.shape
    x = hidden_states(params, cfg, tokens, lengths).astype(jnp.float32)
    mask = (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.float32)
    pooled = (x * mask[..., None]).sum(1) / jnp.maximum(mask.sum(1)[:, None], 1.0)
    if normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
    return pooled
