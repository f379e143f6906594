"""Checkpoint loading: HF safetensors → stacked JAX param pytree.

Reference analog: `LoadModel` in the llama.cpp backend reads GGUF
(/root/reference/backend/cpp/llama-cpp/grpc-server.cpp:505) and vLLM loads HF
checkpoints (/root/reference/backend/python/vllm/backend.py:92-122). Here the
on-disk format is HF safetensors (the TPU-ecosystem standard); tensors are
read lazily per-shard, transposed into our [in, out] matmul layout, stacked
on a leading layer axis (the lax.scan layout), and — when a mesh is given —
placed directly as sharded jax.Arrays so a TP-sharded load never materializes
the full model on one chip.
"""
from __future__ import annotations

import json
import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from localai_tpu.models.llama import (
    EXPERTS, FULL, LATENT, LINEAR, SSM, WINDOW, LlamaConfig, param_specs,
)

# HF architectures the Llama-family decoder covers (SURVEY §2.2 row 1 scope).
LLAMA_FAMILY = {
    "LlamaForCausalLM": {},
    "MistralForCausalLM": {},
    "MixtralForCausalLM": {"moe": True},
    "Qwen2ForCausalLM": {"qkv_bias": True},
    "TinyLlamaForCausalLM": {},
    # window and full attention layers in one model, each kind its own RoPE,
    # sparse experts of moe_intermediate_size (Mellum2)
    "MellumForCausalLM": {"moe": True},
    # gated delta-rule linear-attention layers beside NoPE gated GQA layers
    # (gqa_layers), a shared expert, routed experts of which this process
    # may hold a share (Solar-Open2)
    "SolarOpen2ForCausalLM": {"moe": True},
    # what no key of an afmoe config.json states, because every model of
    # the architecture has it: RMSNorm on q and k a head, an elementwise
    # sigmoid gate on attention's output, a norm on the attention's and the
    # MLP's output (sandwich), window layers that rotate beside full layers
    # that do not, a bias in the router's choice (Trinity)
    "AfmoeForCausalLM": {"moe": True, "fields": {
        "qk_norm": True, "attn_gate": True, "post_norms": True,
        "nope_kinds": (FULL,), "router_bias": True},
        # a checkpoint's tensors, leaf -> name under model.layers.N. ({e}:
        # an expert of the router's). From memory of the architecture's
        # modelling code; no checkpoint was at hand to check them against.
        # post_attention_layernorm is the norm on attention's OUTPUT here
        "tensors": {
            "attn_norm": "input_layernorm.weight",
            "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
            "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
            "w_agate": "self_attn.gate_proj.weight",
            "q_norm": "self_attn.q_norm.weight",
            "k_norm": "self_attn.k_norm.weight",
            "attn_post_norm": "post_attention_layernorm.weight",
            "mlp_norm": "pre_mlp_layernorm.weight",
            "mlp_post_norm": "post_mlp_layernorm.weight",
            "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
            "w_down": "mlp.down_proj.weight",
            "moe_gate": "mlp.router.gate.weight",
            "moe_bias": "mlp.expert_bias",
            "moe_w1": "mlp.experts.{e}.gate_proj.weight",
            "moe_w3": "mlp.experts.{e}.up_proj.weight",
            "moe_w2": "mlp.experts.{e}.down_proj.weight",
            "ws_gate": "mlp.shared_experts.gate_proj.weight",
            "ws_up": "mlp.shared_experts.up_proj.weight",
            "ws_down": "mlp.shared_experts.down_proj.weight"}},
    # latent attention (the config's kv_lora_rank and its kin say so), a
    # leading dense layer, sandwich norms where `sandwich_norm` says so. No
    # key of a pangu_ultra_moe config.json names the router's scores: every
    # model of the architecture takes a sigmoid of each, without a
    # selection bias (from memory of the modelling code; a softmax reading
    # would drop this one field and nothing else)
    "PanguUltraMoEForCausalLM": {"moe": True, "fields": {
        "router_sigmoid": True},
        # (from memory of the architecture's modelling code, unchecked;
        # pre_mlp_layernorm and post_mlp_layernorm only under sandwich_norm,
        # where post_attention_layernorm is the norm on attention's OUTPUT)
        "tensors": {
            "attn_norm": "input_layernorm.weight",
            "wq_a": "self_attn.q_a_proj.weight",
            "q_a_norm": "self_attn.q_a_layernorm.weight",
            "wq_b": "self_attn.q_b_proj.weight",
            "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
            "kv_a_norm": "self_attn.kv_a_layernorm.weight",
            "wkv_b": "self_attn.kv_b_proj.weight",
            "wo": "self_attn.o_proj.weight",
            "attn_post_norm": "post_attention_layernorm.weight",
            "mlp_norm": "pre_mlp_layernorm.weight",
            "mlp_post_norm": "post_mlp_layernorm.weight",
            "w_gate": "mlp.gate_proj.weight", "w_up": "mlp.up_proj.weight",
            "w_down": "mlp.down_proj.weight",
            "moe_gate": "mlp.gate.weight",
            "moe_w1": "mlp.experts.{e}.gate_proj.weight",
            "moe_w3": "mlp.experts.{e}.up_proj.weight",
            "moe_w2": "mlp.experts.{e}.down_proj.weight",
            "ws_gate": "mlp.shared_experts.gate_proj.weight",
            "ws_up": "mlp.shared_experts.up_proj.weight",
            "ws_down": "mlp.shared_experts.down_proj.weight"}},
    # Mamba-2 state-space layers, NoPE GQA layers and latent expert layers,
    # ONE part a layer (hybrid_override_pattern: M, *, E). What no key of a
    # nemotron_h config.json states: the attention layers apply no position
    # encoding (rope_theta and partial_rotary_factor are not used), and the
    # router takes a sigmoid of each score and chooses by score + a
    # selection bias (from the family's description; the modelling code was
    # not at hand). Synthetic weights only: the weights are stacked by kind
    # (load_params), and a checkpoint's names (backbone.layers.N.mixer.*,
    # by the family's convention, unchecked) are in the benchmark's
    # configuration file, not here
    "NemotronHForCausalLM": {"moe": True, "fields": {
        "router_sigmoid": True, "router_bias": True, "use_rope": False,
        "expert_act": "relu2"}},
}
# config.json files that name no architecture
_ARCH_OF_MODEL_TYPE = {"mellum": "MellumForCausalLM",
                       "solar_open2": "SolarOpen2ForCausalLM",
                       "afmoe": "AfmoeForCausalLM",
                       "pangu_ultra_moe": "PanguUltraMoEForCausalLM",
                       "nemotron_h": "NemotronHForCausalLM"}
_PATTERN_KINDS = {"M": SSM, "*": FULL, "E": EXPERTS}
_LAYER_KINDS = {"full_attention": FULL, "sliding_attention": WINDOW}


def _rope_fields(rs: dict | None, theta: float, max_position: int) -> dict:
    """LlamaConfig's rope_* fields from one HF rope_scaling/rope_parameters
    dict (either may carry its own rope_theta)."""
    rs = rs if isinstance(rs, dict) else {}
    kw: dict[str, Any] = {"rope_base": rs.get("rope_theta", theta)}
    rope_type = rs.get("rope_type", rs.get("type"))
    if rope_type in (None, "default"):
        return kw
    kw["rope_scaling"] = rope_type
    kw["rope_scale_factor"] = rs.get("factor", 1.0)
    kw["rope_original_max_position"] = rs.get(
        "original_max_position_embeddings", max_position)
    if rope_type == "llama3":
        kw["rope_low_freq_factor"] = rs.get("low_freq_factor", 1.0)
        kw["rope_high_freq_factor"] = rs.get("high_freq_factor", 4.0)
    if rope_type == "yarn":
        kw["rope_beta_fast"] = rs.get("beta_fast", 32.0)
        kw["rope_beta_slow"] = rs.get("beta_slow", 1.0)
        kw["rope_attn_factor"] = rs.get("attention_factor")
    return kw


def _either(hf: dict, *names, default=None):
    """The first of `names` the config has (families spell a key their own
    way: routed_scaling_factor / route_scale)."""
    return next((hf[n] for n in names if hf.get(n) is not None), default)


def _expert_layer_fields(hf: dict, held: int) -> dict:
    """What an expert layer has beside its routed experts: shared experts
    (n of them are one SwiGLU n times as wide), a scale on the routed sum,
    softmax or sigmoid scores, leading layers with a dense MLP in the
    experts' place, and the SHARE this process holds
    (`localai_expert_share`: the published router width and the first
    expert held; the experts' count then says how many are held). What the
    layer cannot honour is refused by name."""
    scoring = _either(hf, "scoring_func", "score_func", default="softmax")
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(
            f"scoring_func {scoring!r} is not supported: the router scores "
            "by a float32 softmax over all experts or a sigmoid of each")
    for name in ("n_group", "topk_group", "num_expert_groups",
                 "num_limited_groups"):
        if (hf.get(name) or 1) > 1:
            raise ValueError(
                f"{name} {hf[name]} is not supported: the router chooses "
                "its top k among all experts, not among groups of them")
    if _either(hf, "norm_topk_prob", "route_norm") is False:
        raise ValueError(
            "norm_topk_prob / route_norm: false is not supported: the "
            "expert layer renormalises the top-k router weights "
            "(models/llama.py _route), and would silently compute another "
            "model")
    kw: dict[str, Any] = {
        "shared_expert_width":
        (_either(hf, "n_shared_experts", "num_shared_experts") or 0)
        * (hf.get("moe_intermediate_size") or hf["intermediate_size"]),
        "routed_scale": float(_either(hf, "routed_scaling_factor",
                                      "route_scale", default=1.0)),
        "router_sigmoid": scoring == "sigmoid",
        "leading_dense_layers": int(_either(
            hf, "first_k_dense_replace", "num_dense_layers", default=0)),
    }
    share = hf.get("localai_expert_share")
    if share:
        kw["router_experts"] = int(share["router_experts"])
        kw["first_expert"] = int(share.get("first_expert", 0))
        if kw["router_experts"] < held:
            raise ValueError(
                f"localai_expert_share: a router of {kw['router_experts']} "
                f"cannot have {held} experts held")
    return kw


def _linear_fields(hf: dict, n_layers: int) -> dict:
    """Linear-attention layers beside softmax ones: `gqa_layers` lists the
    softmax (FULL) layers, every other layer is LINEAR."""
    la = hf["linear_attn_config"]
    full = sorted(set(hf.get("gqa_layers") or ()))
    if not full or full[-1] >= n_layers or len(full) == n_layers:
        raise ValueError(
            f"gqa_layers {full} must name some, not all, of the "
            f"{n_layers} layers")
    kinds = tuple(FULL if i in full else LINEAR for i in range(n_layers))
    period = next(p for p in range(1, n_layers + 1) if n_layers % p == 0
                  and kinds == kinds[:p] * (n_layers // p))
    if period == n_layers and len(full) > 1:
        raise ValueError(
            f"gqa_layers {full} is not periodic over {n_layers} layers: the "
            "layer stack is a scan over one period of layer kinds")
    if la.get("num_kv_heads") not in (None, la["num_heads"]):
        raise ValueError(
            "linear_attn_config.num_kv_heads other than num_heads (grouped "
            "keys and values in a linear layer) is not supported")
    if hf.get("kda_use_full_proj"):
        raise ValueError(
            "kda_use_full_proj: true is not supported: the decay gate is the "
            "low-rank pair W_f2 W_f1")
    return {
        "layer_types": kinds,
        "linear_heads": la["num_heads"], "linear_head_dim": la["head_dim"],
        "linear_conv": la.get("short_conv_kernel_size", 4),
        # no key gives the gates' rank: the family's convention, head_dim
        "linear_gate_rank": la["head_dim"],
        "linear_neg_eigval": bool(hf.get("kda_allow_neg_eigval", False)),
        "use_rope": bool(hf.get("use_rope", True)),
        "attn_gate": bool(hf.get("use_gqa_gate", False)),
    }


def _latent_fields(hf: dict, n_layers: int) -> dict:
    """Latent attention (MLA), where the config has kv_lora_rank: every
    layer is LATENT. What the layer cannot honour is refused by name."""
    missing = [k for k in ("q_lora_rank", "qk_nope_head_dim",
                           "qk_rope_head_dim", "v_head_dim")
               if not hf.get(k)]
    if missing:
        raise ValueError(
            f"kv_lora_rank without {missing}: a latent layer's query goes "
            "through a low-rank pair too, and its heads' widths are stated")
    if hf.get("rope_scaling") or hf.get("rope_parameters"):
        raise ValueError(
            "rope_scaling on a latent layer is not supported: the position "
            "key rotates by rope_theta alone (a YaRN factor would also "
            "scale the softmax, which the layer does not)")
    if hf.get("num_key_value_heads") not in (None, hf["num_attention_heads"]):
        raise ValueError(
            "num_key_value_heads other than num_attention_heads on a latent "
            "layer: the up-projection makes keys and values for every head")
    return {"layer_types": (LATENT,) * n_layers,
            "kv_lora_rank": hf["kv_lora_rank"],
            "q_lora_rank": hf["q_lora_rank"],
            "qk_nope_head_dim": hf["qk_nope_head_dim"],
            "qk_rope_head_dim": hf["qk_rope_head_dim"],
            "v_head_dim": hf["v_head_dim"],
            # a query head's width (the softmax scale is its -1/2 power)
            "head_dim": hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]}


def _ssm_fields(hf: dict, n_layers: int) -> dict:
    """A model of state-space, attention and expert layers, one part a
    layer: `hybrid_override_pattern` has a letter a layer, M (Mamba-2), *
    (attention) or E (experts). What the layers cannot honour is refused by
    name."""
    pattern = hf["hybrid_override_pattern"]
    if "-" in pattern:
        raise ValueError(
            "hybrid_override_pattern with a `-` (a dense MLP layer) is not "
            "supported: a layer that is a feed-forward part alone has "
            "experts")
    if set(pattern) - set(_PATTERN_KINDS) or len(pattern) != n_layers:
        raise ValueError(
            f"hybrid_override_pattern: {n_layers} letters of "
            f"{sorted(_PATTERN_KINDS)} expected, got {pattern!r}")
    for name in ("mamba_proj_bias", "use_bias", "mlp_bias"):
        if hf.get(name):
            raise ValueError(
                f"{name}: true is not supported: no projection of a "
                "state-space, attention or expert layer has a bias")
    if (hf.get("num_nextn_predict_layers") or 0) > 0:
        raise ValueError(
            f"num_nextn_predict_layers {hf['num_nextn_predict_layers']} is "
            "not supported: the multi-token-prediction layers are a draft "
            "head, and speculative decoding takes a separate draft model "
            "(set it to 0: they are not part of the forward pass)")
    if hf.get("use_conv_bias") is False:
        raise ValueError("use_conv_bias: false is not supported: the "
                         "state-space layer's convolution has a bias")
    for name, want in (("mamba_hidden_act", "silu"),
                       ("mlp_hidden_act", "relu2")):
        if hf.get(name, want) != want:
            raise ValueError(f"{name} {hf[name]!r} is not supported: "
                             f"{want} is what the layer computes")
    kw = {"layer_types": tuple(_PATTERN_KINDS[c] for c in pattern),
          "ssm_heads": hf["mamba_num_heads"],
          "ssm_head_dim": hf["mamba_head_dim"],
          "ssm_groups": hf.get("n_groups", 1),
          "ssm_state": hf["ssm_state_size"],
          "ssm_conv": hf.get("conv_kernel", 4),
          "ssm_chunk": hf.get("chunk_size", 128),
          "moe_latent": hf.get("moe_latent_size") or 0,
          "rms_eps": _either(hf, "norm_eps", "layer_norm_epsilon",
                             default=1e-5)}
    if hf.get("moe_shared_expert_intermediate_size"):
        kw["shared_expert_width"] = (
            (hf.get("n_shared_experts") or 1)
            * hf["moe_shared_expert_intermediate_size"])
    return kw


def _read_config(model_dir: str) -> tuple[dict, str]:
    """config.json and the architecture it names (or its model_type does)."""
    with open(os.path.join(model_dir, "config.json")) as f:
        hf: dict[str, Any] = json.load(f)
    return hf, (hf.get("architectures")
                or [_ARCH_OF_MODEL_TYPE.get(hf.get("model_type"),
                                            "LlamaForCausalLM")])[0]


def load_config(model_dir: str, dtype: str | None = None) -> LlamaConfig:
    """Parse HF config.json into a LlamaConfig. `dtype` overrides the compute
    dtype (activations follow params; bf16 is the TPU default)."""
    hf, arch = _read_config(model_dir)
    if hf.get("model_type") == "llava" or arch.startswith("Llava"):
        # vision-language checkpoint: the language side is a plain
        # Llama-family config nested under text_config (the vision side
        # loads separately — models/llava.py)
        hf = dict(hf["text_config"])
        arch = (hf.get("architectures")
                or [{"llama": "LlamaForCausalLM",
                     "mistral": "MistralForCausalLM",
                     "qwen2": "Qwen2ForCausalLM"}.get(
                        hf.get("model_type", "llama"), "LlamaForCausalLM")])[0]
    if arch not in LLAMA_FAMILY:
        raise ValueError(f"unsupported architecture {arch!r}")
    extra = LLAMA_FAMILY[arch]

    num_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // num_heads

    max_position = hf.get("max_position_embeddings", 8192)
    kw: dict[str, Any] = dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=num_heads,
        num_kv_heads=hf.get("num_key_value_heads", num_heads),
        head_dim=head_dim,
        max_position=max_position,
        rms_eps=hf.get("rms_norm_eps", 1e-5),
        tie_embeddings=hf.get("tie_word_embeddings", False),
        sliding_window=hf.get("sliding_window"),
        qkv_bias=hf.get("attention_bias", extra.get("qkv_bias", False)),
    )
    experts = hf.get("num_experts", hf.get("num_local_experts",
                                           hf.get("n_routed_experts")))
    mlp_list = list(hf.get("mlp_layer_types") or ())
    mlp_kinds = set(mlp_list)
    # dense entries before the first sparse one: leading dense layers
    dense_first = mlp_list.index("sparse") if "sparse" in mlp_list else 0
    if mlp_kinds - {"sparse", "dense"} or (
            "sparse" in mlp_kinds and "dense" in mlp_list[dense_first:]):
        raise ValueError(
            f"mlp_layer_types {sorted(mlp_kinds)}: dense layers are taken "
            "before the first sparse one only (leading dense layers), the "
            "rest of the stack is one scan over one kind of MLP")
    if mlp_kinds == {"dense"}:
        experts = None
    if (extra.get("moe") or experts) and mlp_kinds != {"dense"}:
        kw["num_experts"] = experts or 8
        kw["experts_per_tok"] = hf.get("num_experts_per_tok", 2)
        kw["moe_intermediate_size"] = hf.get("moe_intermediate_size")
        kw.update(_expert_layer_fields(hf, kw["num_experts"]))
        kw["leading_dense_layers"] = (kw["leading_dense_layers"]
                                      or dense_first)
    if hf.get("mup_enabled"):
        kw["embed_scale"] = float(hf["hidden_size"]) ** 0.5
    if hf.get("sandwich_norm"):
        kw["post_norms"] = True
    kw.update(extra.get("fields", {}))
    if dtype is not None:
        # int8 = weight quantization; activations/KV stay bf16
        kw["dtype"] = ("bfloat16" if dtype in ("int8", "q8", "int4", "q4")
                       else dtype)

    if hf.get("linear_attn_config"):
        kw.update(_linear_fields(hf, kw["num_layers"]))
    if hf.get("kv_lora_rank"):
        kw.update(_latent_fields(hf, kw["num_layers"]))
    if hf.get("hybrid_override_pattern"):
        kw.update(_ssm_fields(hf, kw["num_layers"]))
    kinds = hf.get("layer_types")
    if kinds:
        unknown = set(kinds) - set(_LAYER_KINDS)
        if unknown or len(kinds) != kw["num_layers"]:
            raise ValueError(
                f"layer_types: {kw['num_layers']} entries of "
                f"{sorted(_LAYER_KINDS)} expected, got {len(kinds)} with "
                f"{sorted(unknown)}")
        if hf.get("use_sliding_window") is False:
            kinds = ["full_attention"] * len(kinds)
    # one kind of layer is the one-kind path: all full has no window, all
    # windowed is Mistral's (a full-length cache under the window mask)
    one_kind = next(iter(set(kinds))) if kinds and len(set(kinds)) == 1 \
        else None
    if one_kind == "full_attention":
        kw["sliding_window"] = None
    if one_kind and _LAYER_KINDS[one_kind] in kw.get("nope_kinds", ()):
        kw["use_rope"] = False      # the one kind there is does not rotate

    theta = hf.get("rope_theta", 10000.0)
    rs = hf.get("rope_scaling") or hf.get("rope_parameters") or None
    if isinstance(rs, dict) and any(k in rs for k in _LAYER_KINDS):
        # rope_parameters keyed by layer type: one RoPE per kind
        kw.update(_rope_fields(rs.get(one_kind or "full_attention"), theta,
                               max_position))
        if kinds and not one_kind:
            kw["window_rope"] = LlamaConfig(
                head_dim=head_dim, **_rope_fields(
                    rs.get("sliding_attention"), theta, max_position)).rope
    else:
        kw.update(_rope_fields(rs, theta, max_position))
    if kinds and not one_kind:
        kw["layer_types"] = tuple(_LAYER_KINDS[k] for k in kinds)
    return LlamaConfig(**kw)


class _SafetensorsFile:
    """Minimal host-side safetensors reader: 8-byte header length, JSON header
    {name: {dtype, shape, data_offsets}}, then raw little-endian tensor data.
    mmap + np.frombuffer keeps every tensor on HOST memory (bf16 via ml_dtypes)
    so a TP-sharded load never materializes the full model on one chip —
    unlike framework-mode safe_open, which commits to the default device.
    """

    _DTYPES = {
        "F64": np.float64, "F32": np.float32, "F16": np.float16,
        "I64": np.int64, "I32": np.int32, "I16": np.int16, "I8": np.int8,
        "U8": np.uint8, "BOOL": np.bool_,
    }

    def __init__(self, path: str):
        import mmap

        import ml_dtypes

        self._DTYPES = dict(self._DTYPES)
        self._DTYPES["BF16"] = ml_dtypes.bfloat16
        self._f = open(path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        (hlen,) = np.frombuffer(self._mm[:8], np.uint64)
        self._header: dict[str, Any] = json.loads(self._mm[8 : 8 + int(hlen)])
        self._header.pop("__metadata__", None)
        self._base = 8 + int(hlen)

    def keys(self):
        return self._header.keys()

    def get(self, name: str) -> np.ndarray:
        meta = self._header[name]
        lo, hi = meta["data_offsets"]
        arr = np.frombuffer(
            self._mm[self._base + lo : self._base + hi],
            self._DTYPES[meta["dtype"]],
        )
        return arr.reshape(meta["shape"])

    def close(self):
        self._mm.close()
        self._f.close()


class _TensorReader:
    """Lazy per-tensor host reads across safetensors shards."""

    def __init__(self, model_dir: str):
        self.dir = model_dir
        self.index = self._shard_index(model_dir)
        self._open: dict[str, _SafetensorsFile] = {}

    @staticmethod
    def _shard_index(model_dir: str) -> dict[str, str]:
        """tensor name → safetensors filename (single-file or index.json)."""
        idx = os.path.join(model_dir, "model.safetensors.index.json")
        if os.path.exists(idx):
            with open(idx) as f:
                return json.load(f)["weight_map"]
        name = "model.safetensors"
        if os.path.exists(os.path.join(model_dir, name)):
            f = _SafetensorsFile(os.path.join(model_dir, name))
            try:
                return {k: name for k in f.keys()}
            finally:
                f.close()
        raise FileNotFoundError(f"no safetensors checkpoint in {model_dir}")

    @staticmethod
    def _variants(name: str):
        """Key spellings across HF save layouts: plain Llama, classic LLaVA
        (language_model.model.* + language_model.lm_head.*), and the 4.52+
        LLaVA relayout (model.language_model.* + top-level lm_head.*)."""
        yield name
        yield "language_model." + name
        if name.startswith("model."):
            yield "model.language_model." + name[len("model."):]

    def _resolve(self, name: str) -> str | None:
        for v in self._variants(name):
            if v in self.index:
                return v
        return None

    def __contains__(self, name: str) -> bool:
        return self._resolve(name) is not None

    def get(self, name: str) -> np.ndarray:
        key = self._resolve(name)
        if key is None:
            raise KeyError(name)
        fname = self.index[key]
        if fname not in self._open:
            self._open[fname] = _SafetensorsFile(os.path.join(self.dir, fname))
        return self._open[fname].get(key)

    def close(self):
        for f in self._open.values():
            f.close()
        self._open.clear()


def load_params(
    model_dir: str,
    cfg: LlamaConfig,
    *,
    dtype=None,
    mesh=None,
    specs=None,
):
    """Load + restructure a HF Llama-family checkpoint.

    HF stores projection weights as [out, in]; our matmuls are x @ W so every
    projection is transposed once here, at load time. Per-layer tensors are
    stacked on a leading [L, ...] axis to match the lax.scan execution layout
    (models/llama.py init_params). With `mesh`, each stacked param is placed
    as a NamedSharding'ed jax.Array per param_specs (Megatron-style TP).

    dtype="int8"/"int4" loads bf16 then quantizes projections per output
    channel (the GGUF-quant analog, int4 being the exllama2/Q4 role). On a
    single chip that happens on device (ops/quant.quantize_params); under a
    `mesh` each projection quantizes PER HOST-READ SHARD (numpy, right after
    the safetensors read) and only the int8 payload + f32 scales are
    device_put under param_specs(cfg, qbits=...) — the full bf16 stack is
    never materialized on one host buffer or one chip, which is what lets
    an 8B int8 recipe board a 16GB-per-chip v5e-8.
    """
    qbits = {"int8": 8, "q8": 8, "int4": 4, "q4": 4}.get(dtype)
    quantize = qbits is not None
    host_quant = quantize and mesh is not None
    if quantize:
        dtype = "bfloat16"
    dtype = jnp.dtype(dtype) if dtype is not None else cfg.jdtype

    if _is_synthetic(model_dir):
        # benchmark checkpoints: config.json declares the geometry, weights
        # are deterministic random init on device — lets the serving path be
        # measured at flagship scale without writing tens of GB to disk
        return _synthetic_params(cfg, dtype=dtype, mesh=mesh,
                                 qbits=qbits, specs=specs)
    if cfg.drawn_by_leaf:
        tensors = LLAMA_FAMILY.get(_read_config(model_dir)[1], {}).get(
            "tensors")
        if tensors is None or cfg.stacked_by_kind or mesh is not None:
            raise ValueError(
                "no checkpoint of this architecture has been at hand: its "
                "tensors' names are not known here (or not for a mesh), and "
                "only synthetic weights (localai_synthetic) can be loaded")
        params = _load_by_leaf(_TensorReader(model_dir), cfg, tensors, dtype)
        if quantize:
            from localai_tpu.ops.quant import quantize_params

            params = quantize_params(params, bits=qbits)
        return params

    r = _TensorReader(model_dir)
    if mesh is not None and specs is None:
        specs = param_specs(cfg, qbits=qbits if host_quant else None)

    def put(x, spec):
        # host numpy → cast on host → single device_put (sharded when meshed)
        if isinstance(x, dict):
            # host-quantized {"q", "s"} (mesh path): spec is the matching
            # {"q", "s"} dict from param_specs(qbits=...). int4 ships in an
            # int8 container and casts AFTER the sharded placement (the
            # elementwise astype runs distributed, never regathering)
            q = jax.device_put(x["q"], NamedSharding(mesh, spec["q"]))
            if qbits == 4:
                q = q.astype(jnp.int4)
            return {"q": q,
                    "s": jax.device_put(x["s"], NamedSharding(mesh, spec["s"]))}
        x = x if x.dtype == dtype else x.astype(dtype)
        if mesh is not None:
            return jax.device_put(x, NamedSharding(mesh, spec))
        return jnp.asarray(x)

    def hq(t: np.ndarray):
        # mirror the device path bit for bit: checkpoint dtype → bf16 (the
        # load cast) → f32 quantization (quantize_np == ops.quant.quantize)
        from localai_tpu.ops.quant import quantize_np

        return quantize_np(np.asarray(t).astype(dtype), qbits)

    def stack(fmt: str, transpose: bool, quant: bool = False):
        if quant and host_quant:
            qs, ss = [], []
            for i in range(cfg.num_layers):
                t = r.get(fmt.format(i=i))
                d = hq(t.T if transpose else t)
                qs.append(d["q"])
                ss.append(d["s"])
            return {"q": np.stack(qs), "s": np.stack(ss)}
        ts = []
        for i in range(cfg.num_layers):
            t = r.get(fmt.format(i=i))
            ts.append(t.T if transpose else t)
        return np.stack(ts)

    L = "model.layers.{i}."
    layers = {
        "attn_norm": stack(L + "input_layernorm.weight", False),
        "wq": stack(L + "self_attn.q_proj.weight", True, quant=True),
        "wk": stack(L + "self_attn.k_proj.weight", True, quant=True),
        "wv": stack(L + "self_attn.v_proj.weight", True, quant=True),
        "wo": stack(L + "self_attn.o_proj.weight", True, quant=True),
        "mlp_norm": stack(L + "post_attention_layernorm.weight", False),
    }
    if cfg.num_experts:
        # Mixtral MoE: experts stacked [L, E, in, out]
        # (block_sparse_moe.gate + experts.N.w{1,2,3})
        def stack_experts(which: str):
            if host_quant:
                qs, ss = [], []
                for i in range(cfg.num_layers):
                    row = [hq(r.get(f"model.layers.{i}.block_sparse_moe."
                                    f"experts.{e}.{which}.weight").T)
                           for e in range(cfg.num_experts)]
                    qs.append(np.stack([d["q"] for d in row]))
                    ss.append(np.stack([d["s"] for d in row]))
                return {"q": np.stack(qs), "s": np.stack(ss)}
            out = []
            for i in range(cfg.num_layers):
                row = [r.get(f"model.layers.{i}.block_sparse_moe."
                             f"experts.{e}.{which}.weight").T
                       for e in range(cfg.num_experts)]
                out.append(np.stack(row))
            return np.stack(out)

        layers["moe_gate"] = stack(
            L + "block_sparse_moe.gate.weight", True)
        layers["moe_w1"] = stack_experts("w1")
        layers["moe_w2"] = stack_experts("w2")
        layers["moe_w3"] = stack_experts("w3")
    else:
        layers.update({
            "w_gate": stack(L + "mlp.gate_proj.weight", True, quant=True),
            "w_up": stack(L + "mlp.up_proj.weight", True, quant=True),
            "w_down": stack(L + "mlp.down_proj.weight", True, quant=True),
        })
    if cfg.qkv_bias:
        layers["bq"] = stack(L + "self_attn.q_proj.bias", False)
        layers["bk"] = stack(L + "self_attn.k_proj.bias", False)
        layers["bv"] = stack(L + "self_attn.v_proj.bias", False)

    lspecs = specs["layers"] if specs else {k: None for k in layers}
    layers = {k: put(v, lspecs[k]) for k, v in layers.items()}

    params = {
        "embed": put(
            r.get("model.embed_tokens.weight"), specs["embed"] if specs else None
        ),
        "layers": layers,
        "final_norm": put(
            r.get("model.norm.weight"), specs["final_norm"] if specs else None
        ),
    }
    if not cfg.tie_embeddings:
        name = "lm_head.weight"
        if name not in r:
            raise ValueError(
                "config says untied embeddings but lm_head.weight is missing"
            )
        head = hq(r.get(name).T) if host_quant else r.get(name).T
        params["lm_head"] = put(head, specs["lm_head"] if specs else None)
    r.close()
    if quantize and not host_quant:
        from localai_tpu.ops.quant import quantize_params

        params = quantize_params(params, bits=qbits)
    return params


def _load_by_leaf(r: "_TensorReader", cfg: LlamaConfig, tensors: dict, dtype):
    """A checkpoint's tensors into the stacks models/llama.layer_stacks
    names, leaf by leaf: layer N of the checkpoint is the N-th leading layer
    or the (N - leading)-th of the rest; of the router's experts the share
    held is read ([first_expert, first_expert + num_experts)). Matrices are
    transposed to [in, out]; the router and its bias stay float32."""
    from localai_tpu.models.llama import layer_stacks

    def read(name, first, shape):
        def one(n, **kw):
            t = r.get(f"model.layers.{n}." + tensors[name].format(**kw))
            return t.T if t.ndim == 2 else t

        x = np.stack([
            np.stack([one(n, e=cfg.first_expert + e)
                      for e in range(cfg.num_experts)])
            if name.startswith("moe_w") else one(n)
            for n in range(first, first + shape[0])])
        if x.shape != shape:
            raise ValueError(f"{name}: the checkpoint gives {x.shape}, the "
                             f"config {shape}")
        return jnp.asarray(x).astype(
            jnp.float32 if name in ("moe_gate", "moe_bias") else dtype)

    params = {
        "embed": jnp.asarray(r.get("model.embed_tokens.weight")).astype(dtype),
        "final_norm": jnp.asarray(r.get("model.norm.weight")).astype(dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = jnp.asarray(r.get("lm_head.weight").T).astype(
            dtype)
    for (where,), (count, leaves) in layer_stacks(cfg).items():
        first = 0 if where == "leading" else cfg.leading_dense_layers
        params[where] = {name: read(name, first, (count, *shape))
                         for name, (shape, _) in leaves.items()}
    r.close()
    return params


def _synthetic_params(cfg: LlamaConfig, *, dtype, mesh=None, qbits=None,
                      specs=None):
    """Deterministic random params at any scale. The quantized case generates
    the {q, s} leaves DIRECTLY — an 8B bf16 intermediate would not fit
    next to itself on a 16GB chip — and, under a mesh, shards them per
    param_specs(qbits=...) like the safetensors path."""
    from localai_tpu.models.llama import init_params
    from localai_tpu.parallel.mesh import shard_params

    if qbits is None:
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
        if mesh is not None:
            params = shard_params(params, specs or param_specs(cfg), mesh)
        return params

    h, hd = cfg.hidden_size, cfg.head_dim
    nh, nkv, L, inter = (cfg.num_heads, cfg.num_kv_heads, cfg.num_layers,
                         cfg.expert_width if cfg.num_experts
                         else cfg.intermediate_size)
    # the RBG generator, not threefry: an 8B model draws 8 G elements here,
    # and on a v5e threefry took 215 s for them and (drawing int32, as
    # randint does whatever dtype is asked for) peaked the load at 14.5 GB
    # of the chip's 16.9 (chip run, PR 21)
    key = jax.random.key(0, impl="rbg")
    qmax = 7 if qbits == 4 else 127
    qdtype = jnp.int4 if qbits == 4 else jnp.int8

    @partial(jax.jit, static_argnames=("shape",))
    def qbody(k, shape):
        bits = jax.random.bits(k, shape, jnp.uint8)
        if qbits == 4:
            return ((bits % 15).astype(jnp.int8) - 7).astype(qdtype)
        # all 256 byte values, -128 folded onto -127 (symmetric range)
        return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8), -127)

    def qrand(k, shape, fan_in):
        # int body + per-output-channel scale sized so dequantized weights
        # have ~1/sqrt(fan_in) std, matching init_params' distribution
        s = jnp.full(shape[:-2] + (1, shape[-1]),
                     (fan_in ** -0.5) * (1.73 / qmax), jnp.float32)
        return {"q": qbody(k, shape), "s": s}

    ks = jax.random.split(key, 12)
    if cfg.drawn_by_leaf:
        from localai_tpu.models.llama import fill_stacks, special_init

        def leaf(k, name, shape, how):
            if how == "ones":
                return jnp.ones(shape, dtype)
            if isinstance(how, str):
                return special_init(k, how, shape)
            if name.startswith("w") or name.startswith("moe_w"):
                return qrand(k, shape, how)
            x = jax.random.normal(k, shape, jnp.float32) * (how ** -0.5)
            return x if name == "moe_gate" else x.astype(dtype)

        params = {
            "embed": (jax.random.normal(ks[7], (cfg.vocab_size, h),
                                        jnp.float32)
                      * (h ** -0.5)).astype(dtype),
            "final_norm": jnp.ones((h,), dtype)}
        if not cfg.tie_embeddings:
            params["lm_head"] = qrand(ks[8], (h, cfg.vocab_size), h)
        return fill_stacks(cfg, params, leaf, ks[0])
    layers = {
        "attn_norm": jnp.ones((L, h), dtype),
        "wq": qrand(ks[0], (L, h, nh * hd), h),
        "wk": qrand(ks[1], (L, h, nkv * hd), h),
        "wv": qrand(ks[2], (L, h, nkv * hd), h),
        "wo": qrand(ks[3], (L, nh * hd, h), nh * hd),
        "mlp_norm": jnp.ones((L, h), dtype),
    }
    if cfg.num_experts:
        E = cfg.num_experts
        layers["moe_gate"] = (
            jax.random.normal(ks[9], (L, h, E), jnp.float32) * (h ** -0.5))
        layers["moe_w1"] = qrand(ks[4], (L, E, h, inter), h)
        layers["moe_w2"] = qrand(ks[5], (L, E, inter, h), inter)
        layers["moe_w3"] = qrand(ks[6], (L, E, h, inter), h)
    else:
        layers.update({
            "w_gate": qrand(ks[4], (L, h, inter), h),
            "w_up": qrand(ks[5], (L, h, inter), h),
            "w_down": qrand(ks[6], (L, inter, h), inter),
        })
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, nh * hd), dtype)
        layers["bk"] = jnp.zeros((L, nkv * hd), dtype)
        layers["bv"] = jnp.zeros((L, nkv * hd), dtype)
    params = {
        "embed": (jax.random.normal(ks[7], (cfg.vocab_size, h), jnp.float32)
                  * (h ** -0.5)).astype(dtype),
        "layers": layers,
        "final_norm": jnp.ones((h,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qrand(ks[8], (h, cfg.vocab_size), h)
    if mesh is not None:
        params = shard_params(params, specs or param_specs(cfg, qbits=qbits),
                              mesh)
    return params


def _is_synthetic(model_dir: str) -> bool:
    """True for benchmark checkpoints: config.json with
    "localai_synthetic": true AND the LOCALAI_ALLOW_SYNTHETIC=1 env opt-in.
    Without the opt-in a stray config key can never make a production server
    silently serve random weights — the missing-safetensors error stands."""
    if os.environ.get("LOCALAI_ALLOW_SYNTHETIC") != "1":
        return False
    try:
        with open(os.path.join(model_dir, "config.json")) as fh:
            return bool(json.load(fh).get("localai_synthetic"))
    except (OSError, ValueError):
        return False


def load_tokenizer(model_dir: str):
    """Tokenizer for a model dir; None for synthetic benchmark checkpoints
    (callers drive the engine with prompt_ids)."""
    from localai_tpu.engine.tokenizer import Tokenizer

    try:
        return Tokenizer.from_dir(model_dir)
    except FileNotFoundError:
        if not _is_synthetic(model_dir):
            raise
        return None


def load_model(model_dir: str, *, dtype=None, mesh=None):
    """config.json + safetensors + tokenizer in one call → (cfg, params, tok)."""
    cfg = load_config(model_dir, dtype=dtype)
    params = load_params(model_dir, cfg, dtype=dtype, mesh=mesh)
    return cfg, params, load_tokenizer(model_dir)
