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
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from localai_tpu.ops.norms import rms_norm
from localai_tpu.ops.rope import RopeConfig, rope_table, apply_rope
from localai_tpu.ops.attention import mha_prefill, mha_decode
from localai_tpu.ops.kvcache import (
    QuantKV, cache_scatter, dequant, init_quant, is_quant_kind, padded_len,
    requantize,
)
from localai_tpu.ops.quant import qmatmul
from localai_tpu.parallel.mesh import constrain


FULL, WINDOW = "full", "window"     # LlamaConfig.layer_types entries


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

    def __post_init__(self):
        if self.layer_types is None:
            return
        kinds = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", kinds)
        if len(kinds) != self.num_layers or set(kinds) - {FULL, WINDOW}:
            raise ValueError(
                f"layer_types needs {self.num_layers} entries of "
                f"{FULL!r}/{WINDOW!r}, got {kinds}")
        if len(set(kinds)) == 1:
            raise ValueError(
                "layer_types with one kind of layer: leave it None (and set "
                "sliding_window for an all-window model)")
        if not self.sliding_window or self.sliding_window < 1:
            raise ValueError("window layers need a sliding_window")

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def period(self) -> tuple[str, ...] | None:
        """The shortest run of layer kinds that repeats to give layer_types
        (the body of the layer scan); None for a one-kind model."""
        kinds = self.layer_types
        if kinds is None:
            return None
        n = len(kinds)
        for p in range(1, n + 1):
            if n % p == 0 and kinds == kinds[:p] * (n // p):
                return kinds[:p]

    def rope_of(self, kind: str | None) -> RopeConfig:
        if kind == WINDOW and self.window_rope is not None:
            return self.window_rope
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
    import jax

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


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PeriodKV:
    """K (or V) of a model with window and full layers: one cache per place
    in the period of layer kinds, `slots[j]` of [L/period, B, KVH, T_j, D]
    (dense or QuantKV) — T_j the served context for a FULL layer, the ring
    for a WINDOW one. The layer scan of decode_step, prefill and extend
    CARRIES the slots, as it carries the one [L, ...] cache of a one-kind
    model, and place j of period i writes and reads slots[j][i] where it
    lies (_scan_layers_carry: no layer's cache is sliced out, copied or put
    back)."""
    slots: tuple


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
    longest window `extend` will be given).
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
    if prefill_chunk is None:
        raise ValueError("a model with window layers sizes their ring from "
                         "prefill_chunk")
    ring = ring_len(cfg, max_len, prefill_chunk, cache_type)
    period = cfg.period
    pairs = [one(cfg.num_layers // len(period),
                 ring if kind == WINDOW else max_len) for kind in period]
    return (PeriodKV(tuple(k for k, _ in pairs)),
            PeriodKV(tuple(v for _, v in pairs)))


# jax.named_scope below names the model's parts in the XLA ops' metadata
# (`tf_op` in a device trace), so a trace viewer and tools/trace_gaps.py group
# `fusion.256` and its kin by part. Trace-time only: the compilation cache's
# key leaves metadata out, so a scope around XLA ops changes no key. A Pallas
# kernel is the exception: its serialized body carries the scope it was traced
# under (and the source lines of its call stack), and the body is in the key.
# So no scope encloses a kernel call; the kernels go by their own names
# (ragged_decode_q8, flash_prefill, paged_scatter_append).
@jax.named_scope("cache_update")
def _cache_write(kc, vc, k, v, rows, positions, table=None, unique=True,
                 redirect=None, kvt=None, ring_keep=None, layer=None):
    """Scatter window K/V [B, S, KVH, D] into head-major caches [B', KVH, T, D]
    at (rows[b], :, positions[b, s]). With a paged `table` [B, MAXB] the cache
    is a block pool [NB, KVH, BS, D] and (slot, position) resolves to
    (table[slot, pos // BS], :, pos % BS) — ops/paged.py layout.

    layer (i32 scalar, dense only): the caches are the [L, B', KVH, T, D]
    stack and the write lands at (layer, rows[b], :, positions[b, s]) — one
    scatter into the stack, which stays where it is.

    redirect [B] bool (paged only): rows flagged True write to the TRASH
    block (physical 0, ops/paged.py) at offset (row*S + s) % BLOCK instead
    of through their table — the inactive-slot redirect for decode (S=1)
    and the spec-verify window (S=gamma+1). Routing by PHYSICAL block keeps
    the garbage out of every real block (a slot's own table can map its
    last virtual block to a RETAINED warm-prefix block); the per-(row, s)
    offsets keep the scatter collision-free only while B*S <= BLOCK —
    callers must drop the uniqueness assertion beyond that.

    unique=True asserts the scatter rows never collide: decode rows target
    distinct slots (one row per slot; redirected rows get distinct trash
    offsets), so the assertion holds and keeps XLA on the in-place scatter
    path — without it the table-gathered indices are unprovably unique and
    the layer scan re-materializes the whole pool every decode step
    (O(pool) per token). Callers pass unique=False when collisions are
    REAL: batched admission pads groups by repeating a plan
    (engine._flush_admits), and a final prefill chunk's padded tail
    positions resolve to shared trash offsets — don't lie to the compiler
    on those paths (both are per-request, not per-token).

    kvt (paged only, KV lifecycle tier — engine/kvtier.py): per-slot
    residency arrays {"sb": [B], "rw": [B], ...}; raw block indices are
    ring-mapped (ops/paged.ring_block_map) before the table lookup, so a
    windowed slot's writes reuse its O(window) ring columns in place.
    Full-policy slots carry the identity sentinel — same program, no
    recompile across policy mixes. Uniqueness survives the mapping: the
    ring's wrap period (rw*BLOCK tokens) exceeds any single write window
    by construction (kvtier.ring_blocks margins).

    ring_keep [B, S] bool (dense only): the cache is a WINDOW layer's ring of
    R = T rows, position p lives in row p mod R, and an entry that is not
    kept (an inactive decode row, a prompt's padding, what a prompt longer
    than the ring has before its tail) is aimed at row R: out of bounds,
    which a scatter drops. A ring has no spare row to take such writes."""
    kvh = kc.shape[-3]
    if table is None:
        if ring_keep is not None:
            ring = kc.shape[-2]
            positions = jnp.where(ring_keep, positions % ring, ring)
        idx = (rows[:, None, None], jnp.arange(kvh)[None, :, None],
               positions[:, None, :])
        if layer is not None:
            idx = (layer, *idx)
    else:
        from localai_tpu.ops.paged import BLOCK

        raw = positions // BLOCK
        if kvt is not None:
            from localai_tpu.ops.paged import ring_block_map

            raw = ring_block_map(raw, kvt["sb"][rows][:, None],
                                 kvt["rw"][rows][:, None])
        pb = table[rows[:, None], raw]                     # [B, S] physical
        off = positions % BLOCK
        if redirect is not None:
            # distinct per-(row, window-pos) trash offsets: collision-free
            # (and so assertable-unique) as long as B*S <= BLOCK
            s = positions.shape[1]
            tr_off = (rows[:, None] * s
                      + jnp.arange(s)[None, :]) % BLOCK
            pb = jnp.where(redirect[:, None], 0, pb)
            off = jnp.where(redirect[:, None], tr_off, off)
        idx = (pb[:, None, :], jnp.arange(kvh)[None, :, None],
               off[:, None, :])
    if isinstance(kc, QuantKV):
        return (cache_scatter(kc, idx, k.transpose(0, 2, 1, 3), unique),
                cache_scatter(vc, idx, v.transpose(0, 2, 1, 3), unique))
    kc = kc.at[idx].set(k.transpose(0, 2, 1, 3), unique_indices=unique)
    vc = vc.at[idx].set(v.transpose(0, 2, 1, 3), unique_indices=unique)
    return kc, vc


# ---------------------------------------------------------------- forward

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


def _mlp(x, lp, cfg=None, spec_prefix=None):
    """Gated MLP. `spec_prefix` (optional tuple, e.g. ('data', None)) is the
    leading batch/seq sharding of the activation: when given, gate/up outputs
    are constrained ffn-parallel (…, 'model') and the down projection back to
    (…, None) — the hints that keep TP weights sharded through the scan."""
    if "moe_gate" in lp:
        return _moe_mlp(x, lp, cfg.experts_per_tok if cfg else 2)
    up_spec = down_spec = None
    if spec_prefix is not None:
        up_spec = P(*spec_prefix, "model")
        down_spec = P(*spec_prefix, None)
    with jax.named_scope("mlp"):
        return qmatmul(jax.nn.silu(qmatmul(x, lp["w_gate"], up_spec))
                       * qmatmul(x, lp["w_up"], up_spec),
                       lp["w_down"], down_spec)


@jax.named_scope("experts")
def _moe_mlp(x, lp, k: int):
    """Mixtral top-k routed experts (reference: the MoE GGUFs llama.cpp
    serves within ggml — SURVEY §2.4 expert-parallel row; HF semantics:
    softmax router → top-k → renormalize → weighted expert sum).

    Dense dispatch: every expert runs on every token and the top-k mask
    zeroes the rest — einsum-shaped for the MXU and for GSPMD expert
    parallelism (experts sharded on the `model` mesh axis; XLA turns the
    masked combine into an all-reduce). Top-k gather/scatter dispatch is a
    later optimization for large-E prefill."""
    from localai_tpu.ops.quant import dequantize, is_quantized

    def dq(p):
        return dequantize(p, x.dtype) if is_quantized(p) else p

    with jax.named_scope("router"):
        gate = lp["moe_gate"].astype(jnp.float32)
        logits = x.astype(jnp.float32) @ gate                  # [B, S, E]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
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


# Activation sharding hints: hard constraints when a mesh is active (raises on
# a wrong spec), identity otherwise. See localai_tpu/parallel/mesh.py.
_shard_act = constrain


def _seq_ax():
    """'seq' when the ambient mesh carries the ring-attention axis, else None
    (specs naming absent axes would raise)."""
    from localai_tpu.parallel.mesh import current_mesh, seq_axis_size

    return "seq" if seq_axis_size(current_mesh()) > 1 else None


def _tiered_kv(kc, vc, table_rows, sb, rw, length, ctab=None, ck=None,
               cv=None):
    """Materialize the RESIDENT (ring-mapped) cache view for the KV
    lifecycle tier (engine/kvtier.py): the per-slot table gather
    [B, MAXB*BS] plus explicit true positions and row validity, optionally
    concatenated with the dequantized int8 cold tier.

    table_rows [B, MAXB]; sb/rw/length [B] (already row-indexed by the
    caller). ctab [B, MAXB_FULL] (quantize_cold): cold block per raw
    virtual block, 0 = not demoted; ck/cv are the cold QuantKV pools for
    this layer. Demoted blocks drop out of the hot view (their ring column
    may already hold a newer generation's rows) and are read from the cold
    pool at their true positions instead. Returns
    (k [B, KVH, T, D], v, pos [B, T], ok [B, T]) — `ok` covers residency +
    freshness (+ demotion state); retention masking (window/sinks) is the
    attention caller's layer."""
    from localai_tpu.ops.paged import (
        BLOCK, paged_view, resident_block_positions, resident_row_positions,
    )

    maxb = table_rows.shape[1]
    kr, vr = paged_view(kc, table_rows), paged_view(vc, table_rows)
    pos, ok = resident_row_positions(maxb, sb, rw, length)
    k, v = dequant(kr), dequant(vr)
    if ctab is not None:
        b = pos.shape[0]
        mb_full = ctab.shape[1]
        raw, _ = resident_block_positions(maxb, sb, rw, length)
        demoted = ctab != 0                                # [B, MAXB_FULL]
        hot_dem = jnp.take_along_axis(
            demoted, jnp.clip(raw, 0, mb_full - 1), axis=1)
        hot_dem = hot_dem & (raw >= 0) & (raw < mb_full)   # [B, MAXB]
        keep = jnp.broadcast_to(~hot_dem[:, :, None],
                                (b, maxb, BLOCK)).reshape(b, maxb * BLOCK)
        ok = ok & keep
        ckr = paged_view(ck, ctab)
        cvr = paged_view(cv, ctab)
        posc = jnp.arange(mb_full * BLOCK, dtype=jnp.int32)[None, :]
        okc = jnp.broadcast_to(demoted[:, :, None],
                               (b, mb_full, BLOCK)).reshape(b,
                                                            mb_full * BLOCK)
        okc = okc & (posc < length[:, None])
        k = jnp.concatenate([k, dequant(ckr).astype(k.dtype)], axis=2)
        v = jnp.concatenate([v, dequant(cvr).astype(v.dtype)], axis=2)
        pos = jnp.concatenate(
            [pos, jnp.broadcast_to(posc, (b, mb_full * BLOCK))], axis=1)
        ok = jnp.concatenate([ok, okc], axis=1)
    return k, v, pos, ok


def _decode_dq(q, kc, vc, lengths, sliding_window=None, table=None,
               kvt=None, ck=None, cv=None, ring=False, layer=None):
    """XLA decode attention over a (possibly quantized) cache: dequant is
    fused into the consuming dots by XLA; quantized caches still halve HBM
    capacity on this path. A paged cache is materialized per layer via
    gather (reference tier — the Pallas kernels stream through the table).
    `layer`: kc/vc are [L, ...] stacks and layer `layer` of them is read.

    kvt (KV lifecycle tier, engine/kvtier.py): per-slot residency arrays —
    the gather covers only the RESIDENT ring view (O(sinks+window) rows for
    windowed slots, identity for full-policy slots in the same program) and
    the mask derives from true ring positions; with quantize_cold (ck/cv —
    this layer's cold pools) the exited-window blocks attend from the int8
    cold tier instead of being dropped."""
    if layer is not None:
        kc, vc = kc[layer], vc[layer]
    if kvt is not None:
        from localai_tpu.ops.attention import mha_decode_masked

        cold = "cold_tab" in kvt
        k, v, pos, ok = _tiered_kv(
            kc, vc, table, kvt["sb"], kvt["rw"], lengths,
            ctab=kvt["cold_tab"] if cold else None, ck=ck, cv=cv)
        if cold:
            mask = ok  # demotion state decides hot vs cold; nothing evicted
        else:
            mask = ok & ((pos >= (lengths - kvt["window"])[:, None])
                         | (pos < kvt["sinks"][:, None]))
        return mha_decode_masked(q, k, v, mask)
    if ring:
        # a WINDOW layer's ring: row p mod R holds position p, so the rows
        # in the window are those at most window - 1 behind the newest
        from localai_tpu.ops.attention import mha_decode_masked

        mask = (_ring_back(lengths - 1, kc.shape[2])
                < jnp.minimum(lengths, sliding_window)[:, None])
        return mha_decode_masked(q, dequant(kc), dequant(vc), mask)
    if table is not None:
        from localai_tpu.ops.paged import paged_view

        kc, vc = paged_view(kc, table), paged_view(vc, table)
    return mha_decode(q, dequant(kc), dequant(vc), lengths,
                      sliding_window=sliding_window)


def _pallas_attention(mesh) -> bool:
    """Whether attention runs on the Pallas kernels: on TPU without a mesh
    (a mesh sends attention to XLA so GSPMD shards the einsums).
    LOCALAI_FORCE_PALLAS=1 forces Pallas (interpreter off-TPU — tests);
    LOCALAI_NO_PALLAS=1 is the one deliberate way to XLA on a TPU. Nothing
    else chooses: a kernel Mosaic refuses fails the compile that uses it
    (LoadModel's warmup) with its own message."""
    import os

    if os.environ.get("LOCALAI_FORCE_PALLAS") == "1":
        return True
    return (mesh is None and os.environ.get("LOCALAI_NO_PALLAS") != "1"
            and jax.default_backend() == "tpu")


def _pallas_paged_scatter(cfg: LlamaConfig | None) -> bool:
    """Whether the paged decode write uses the Pallas scatter-append kernel
    (ops/pallas/paged_scatter.py) instead of the XLA scatter: on TPU or
    under LOCALAI_FORCE_PALLAS; XLA on CPU and under LOCALAI_NO_PALLAS.

    Under a mesh the pool shards its KV-head axis on 'model' and the kernel
    runs per-shard via shard_map (paged_scatter_append_sharded) — usable iff
    the KV-head count divides the TP axis; otherwise the XLA scatter tier
    handles the (unevenly shardable) pool."""
    import os

    from localai_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        if cfg is None:
            return False
        tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if cfg.num_kv_heads % int(tp):
            return False
    if os.environ.get("LOCALAI_FORCE_PALLAS") == "1":
        return True
    return (os.environ.get("LOCALAI_NO_PALLAS") != "1"
            and jax.default_backend() == "tpu")


def kernel_tiers(cfg: LlamaConfig, mesh, *, paged: bool,
                 ragged: bool = False, tiered: bool = False) -> dict[str, str]:
    """Which implementation serves each hot-path op for an engine of this
    shape — the same predicates the forwards consult at trace time, named
    for the backend's device report. 'pallas-interpret' means the Pallas
    kernels run in the interpreter (forced off-TPU): correct, never fast."""
    from localai_tpu.ops.pallas.flash_attention import _interpret
    from localai_tpu.parallel.mesh import activate_mesh, seq_axis_size

    pallas = "pallas-interpret" if _interpret() else "pallas"
    attn = pallas if _pallas_attention(mesh) else "xla"
    with activate_mesh(mesh):
        paged_kernel = pallas if paged and _pallas_paged_scatter(cfg) \
            else "xla"
    prefill = "xla-ring" if attn == "xla" and seq_axis_size(mesh) > 1 \
        else attn
    tiers = {
        # first-chunk prompt attention (prefill); the KV lifecycle tier
        # masks it per slot on XLA
        "prefill_attention": "xla" if tiered else prefill,
        # later chunks of a long prompt and spec verify windows (extend)
        # attend against the cache on XLA in every configuration
        "chunk_attention": "xla",
        # the tiered (ring-mapped) cache read has no kernel yet
        "decode_attention": "xla" if tiered else attn,
        "decode_kv_write": paged_kernel,
        "prefill_kv_write": "xla",
    }
    if ragged:
        # ragged ticks: one flat-stream attention + row-DMA write kernel
        # pair, selected like the paged decode write
        tiers["ragged_attention"] = "xla" if tiered else paged_kernel
        tiers["ragged_kv_write"] = paged_kernel
    return tiers


def _attn_impls():
    """Select attention kernels at trace time: Pallas (fused, online-softmax)
    on single-chip TPU; XLA reference under a mesh (GSPMD shards the einsums)
    or on CPU — see _pallas_attention."""
    from localai_tpu.parallel.mesh import current_mesh, seq_axis_size

    mesh = current_mesh()
    if _pallas_attention(mesh):
        from localai_tpu.ops.pallas import (
            flash_prefill, ragged_decode, ragged_decode_q8,
        )

        def attn_decode(q, kc, vc, lengths, sliding_window=None, table=None,
                        kvt=None, ck=None, cv=None, ring=False, layer=None):
            if kvt is not None:
                # KV lifecycle tier: the ring-position/tier-map read rides
                # the XLA reference path for now — the Pallas decode kernel
                # has no per-slot ring-geometry scalar prefetch yet (the
                # WRITE side is kernel-native: paged_scatter's targets are
                # ring-mapped before the DMA kernel). TODO(kvtier): teach
                # _decode_kernel the ring map + per-block dtype tier.
                return _decode_dq(q, kc, vc, lengths,
                                  sliding_window=sliding_window, table=table,
                                  kvt=kvt, ck=ck, cv=cv)
            if isinstance(kc, QuantKV):
                return ragged_decode_q8(q, kc.q, kc.s, vc.q, vc.s, lengths,
                                        sliding_window=sliding_window,
                                        table=table, ring=ring, layer=layer)
            return ragged_decode(q, kc, vc, lengths,
                                 sliding_window=sliding_window, table=table,
                                 ring=ring, layer=layer)

        return (lambda q, k, v, lengths, sliding_window=None:
                flash_prefill(q, k, v, lengths, sliding_window=sliding_window),
                attn_decode)
    if seq_axis_size(mesh) > 1:
        # sequence parallelism: prefill rides the ppermute ring over the
        # 'seq' axis (parallel/ring_attention.py); decode (S=1) stays on
        # the XLA path with GSPMD sharding
        from localai_tpu.parallel.ring_attention import ring_prefill

        return (lambda q, k, v, lengths, sliding_window=None:
                ring_prefill(q, k, v, lengths, mesh=mesh,
                             sliding_window=sliding_window),
                _decode_dq)
    return mha_prefill, _decode_dq


def rope_tables(cfg: LlamaConfig, max_len: int):
    """The (cos, sin) the forwards take: one pair of tables for a one-kind
    model; for a model with layer_types a pair of dicts keyed by layer kind
    (window and full layers rotate differently)."""
    if cfg.layer_types is None:
        return rope_table(cfg.rope, max_len)
    tabs = {kind: rope_table(cfg.rope_of(kind), max_len)
            for kind in (FULL, WINDOW)}
    return ({kind: t[0] for kind, t in tabs.items()},
            {kind: t[1] for kind, t in tabs.items()})


def _layer_rope(cos, sin, kind):
    return (cos, sin) if kind is None else (cos[kind], sin[kind])


def _layer_window(cfg: LlamaConfig, kind):
    """The attention window of a layer of `kind` (None: a one-kind model)."""
    return None if kind == FULL else cfg.sliding_window


def _attn_scope(kind):
    """Device time by layer kind: a mixed model's attention (projections,
    kernel and all: it has no older compile-cache key to keep) is traced
    under attention/<kind>; a one-kind model's as it always was."""
    return (contextlib.nullcontext() if kind is None
            else jax.named_scope(f"attention/{kind}"))


def _ring_back(newest, ring: int):
    """[B, R]: how many positions behind `newest` [B] the entry in each row
    of a ring of R rows is (row p mod R holds position p)."""
    return jnp.mod(newest[:, None] - jnp.arange(ring)[None, :], ring)


def _layer_params(layers, i):
    """One layer's weights, sliced out of the [L, ...] stack where they are
    used, as scan slices its xs (a [period, ...] slice of a folded stack is
    copied whole every iteration: 1.6 GB of experts a period at Mellum2's
    widths)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), layers)


def _scan_layers_carry(cfg: LlamaConfig, body, x, layers, k_cache, v_cache):
    """_scan_layers with the stacked caches as the scan's CARRY: run
    `body(x, lp, kc, vc, kind, li=i) -> (x, (kc, vc))` over the layers, where
    kc / vc are the whole [L', B, KVH, T, D] stacks (a one-kind model's
    cache; one slot of a PeriodKV) and `i` is the layer's index into them.
    `body` writes and reads layer i where it lies — one scatter into the
    stack, a kernel whose index maps take i, a gather of the rows a chunk
    attends over — so no cache is an xs or a ys and nothing is sliced out,
    put back or copied (on a v5e the xs/ys form cost a decode step 3.6 ms of
    20 on Mixtral-8x7B at 6 layers and 12.5 of 37 on Mellum2; PERF.md)."""
    period = cfg.period
    if period is None:
        def layer(carry, xs):
            x, kc, vc = carry
            lp, i = xs
            x, (kc, vc) = body(x, lp, kc, vc, None, li=i)
            return (x, kc, vc), None

        (x, k_cache, v_cache), _ = jax.lax.scan(
            layer, (x, k_cache, v_cache),
            (layers, jnp.arange(cfg.num_layers)))
        return x, (k_cache, v_cache)
    p = len(period)

    def step(carry, i):
        x, ks, vs = carry
        ks, vs = list(ks), list(vs)
        for j, kind in enumerate(period):
            x, (ks[j], vs[j]) = body(x, _layer_params(layers, i * p + j),
                                     ks[j], vs[j], kind, li=i)
        return (x, tuple(ks), tuple(vs)), None

    (x, ks, vs), _ = jax.lax.scan(
        step, (x, k_cache.slots, v_cache.slots),
        jnp.arange(cfg.num_layers // p))
    return x, (PeriodKV(ks), PeriodKV(vs))


def _scan_layers(cfg: LlamaConfig, body, x, layers, k_cache=None,
                 v_cache=None, extra=(), carry=False):
    """Run `body(x, lp, kc, vc, kind, *extra) -> (x, (kc, vc))` over the
    layer stack and return (x, (k_cache, v_cache)). carry=True (a dense
    cache, no extras): _scan_layers_carry instead.

    One kind of layer: lax.scan over [L, ...] with kind None, the program a
    one-kind model always had. With layer_types the scan's body is one
    PERIOD of kinds, unrolled, over a PeriodKV's per-place caches: compile
    time grows with the period, not the depth.

    The caches are the scan's xs and ys, so XLA slices each layer's cache
    out of the stack for `body` and writes it back (and copies the stack
    where a loop carries it). That is what a paged or tiered cache still
    pays, whose per-layer pools a kernel aliases, and what a forward with no
    cache (hidden_states) has nothing to pay for; every forward over a dense
    cache takes _scan_layers_carry."""
    if carry:
        return _scan_layers_carry(cfg, body, x, layers, k_cache, v_cache)
    period = cfg.period
    if period is None:
        def layer(x, xs):
            lp, kc, vc, *ex = xs
            return body(x, lp, kc, vc, None, *ex)

        return jax.lax.scan(layer, x, (layers, k_cache, v_cache, *extra))
    if extra:
        raise NotImplementedError("layer_types with per-layer extras")
    p = len(period)
    cached = k_cache is not None
    ks = k_cache.slots if cached else (None,) * p
    vs = v_cache.slots if cached else (None,) * p

    def step(x, xs):
        i, kcs, vcs = xs
        ko, vo = [], []
        for j, kind in enumerate(period):
            x, (kc, vc) = body(x, _layer_params(layers, i * p + j), kcs[j],
                               vcs[j], kind)
            ko.append(kc)
            vo.append(vc)
        return x, (tuple(ko), tuple(vo))

    x, (ko, vo) = jax.lax.scan(
        step, x, (jnp.arange(cfg.num_layers // p), ks, vs))
    if not cached:
        return x, (None, None)
    return x, (PeriodKV(ko), PeriodKV(vo))


def _no_mixed(cfg: LlamaConfig, what: str):
    if cfg.layer_types is not None:
        raise NotImplementedError(
            f"{what} does not take a model with window and full layers "
            "(layer_types): it knows one cache per layer stack")


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
    attn_prefill, _ = _attn_impls()
    if kvt is not None:
        # KV lifecycle tier: first-chunk self-attention under the per-slot
        # sink+window retention mask (engine/kvtier.py). quantize_cold slots
        # keep full causal coverage (exited content is demoted, not
        # dropped), so the window term is lifted to a sentinel there.
        from localai_tpu.ops.attention import mha_prefill_tiered

        _sinks = kvt["sinks"][slot_map]
        _window = kvt["window"][slot_map]
        if "cold_tab" in kvt:
            _window = jnp.full_like(_window, jnp.int32(1 << 30))

        def attn_prefill(q, k, v, lengths, sliding_window=None):  # noqa: F811
            return mha_prefill_tiered(q, k, v, lengths, _sinks, _window)
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    sax = _seq_ax()
    x = params["embed"].astype(cfg.jdtype)[tokens]
    if inject is not None:
        extra, is_embed = inject
        x = jnp.where(is_embed[..., None], extra.astype(x.dtype), x)
    x = _shard_act(x, P("data", sax, None))

    if table is not None or kvt is not None:
        _no_mixed(cfg, "a paged or tiered prefill")

    stacked = table is None and kvt is None   # as in decode_step

    def layer(x, lp, kc, vc, kind, li=None):
        lcos, lsin = _layer_rope(cos, sin, kind)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        with _attn_scope(kind):
            q, k, v = _qkv(h, lp, cfg, spec=P("data", sax, "model"))
            q = apply_rope(q, lcos, lsin, positions)
            k = apply_rope(k, lcos, lsin, positions)
            q = _shard_act(q, P("data", sax, "model", None))
            attn = attn_prefill(q, k, v, lengths,
                                sliding_window=_layer_window(cfg, kind))
            with jax.named_scope("attention"):
                x = x + qmatmul(attn.reshape(b, s, -1), lp["wo"],
                                spec=P("data", sax, None))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(h, lp, cfg, spec_prefix=("data", sax))
        x = _shard_act(x, P("data", sax, None))
        keep = None
        if kind == WINDOW:
            # a ring takes the prompt's own tokens only, and of a prompt
            # longer than the ring its tail
            keep = ((positions < lengths[:, None])
                    & (positions >= lengths[:, None] - kc.shape[-2]))
        # unique=False: batched admission pads groups by repeating a real
        # request's plan (engine _flush_admits), so slot_map can repeat
        kc, vc = _cache_write(kc, vc, k, v, slot_map, positions, table,
                              unique=False, kvt=kvt, ring_keep=keep, layer=li)
        return x, (kc, vc)

    x, (k_cache, v_cache) = _scan_layers(
        cfg, layer, x, params["layers"], k_cache, v_cache, carry=stacked)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
    )[:, 0]
    logits = _lm_head(last.astype(jnp.float32), params)
    return logits, k_cache, v_cache


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
    redirect row then resolves through the table's last virtual block, which
    is the trash block for any slot not allocated to full context.
    Returns (logits [B, V] f32, k_cache, v_cache).
    """
    b = tokens.shape[0]
    if table is not None or kvt is not None:
        _no_mixed(cfg, "a paged or tiered decode step")
    kv_quant = isinstance(k_cache, QuantKV)
    _, attn_decode = _attn_impls()
    positions = lengths[:, None]  # [B,1]
    redirect = None
    if active is not None and table is not None:
        # paged: inactive rows write to the trash block at distinct per-row
        # offsets (_cache_write redirect) — never through their own table,
        # whose last virtual block can be a RETAINED warm-prefix block
        redirect = ~active
    unique = table is None or b <= 128
    # paged Pallas tier: the per-step write is a scatter-append DMA kernel
    # (O(slots) traffic, provably in place) instead of an XLA scatter
    # through gathered physical indices — the scatter XLA de-optimizes into
    # a full-pool copy inside the fused decode block (VERDICT Weak #2)
    kernel_write = table is not None and _pallas_paged_scatter(cfg)
    # under a mesh the pool shards its KV-head axis: the kernel runs
    # per-shard via shard_map (pallas_call has no GSPMD partitioning rule —
    # without this the partitioner would all-gather the whole pool)
    write_mesh = None
    if kernel_write:
        from localai_tpu.parallel.mesh import current_mesh

        write_mesh = current_mesh()
    x = params["embed"].astype(cfg.jdtype)[tokens][:, None, :]  # [B,1,H]
    x = _shard_act(x, P("data", None, None))
    # KV lifecycle tier: the cold pools (per-layer, like kc/vc) ride the scan
    # as extra READ-ONLY xs — the demote copy is a separate host-driven jit
    # (engine._demote_fn), so ys stays (kc, vc)
    cold = kvt is not None and "cold_tab" in kvt
    sb = rw = None
    if kvt is not None:
        sb, rw = kvt["sb"], kvt["rw"]

    # a dense cache rides the layer scan as its carry and every touch of it
    # names the layer (_scan_layers_carry; prefill and extend do the same);
    # paged and tiered pools keep the xs/ys form their kernels' aliasing was
    # written for
    stacked = table is None and kvt is None

    def layer(x, lp, kc, vc, kind, ck=None, cv=None, li=None):
        ring = kind == WINDOW
        lcos, lsin = _layer_rope(cos, sin, kind)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        with _attn_scope(kind):
            q, k, v = _qkv(h, lp, cfg, spec=P("data", None, "model"))
            q = apply_rope(q, lcos, lsin, positions)
            k = apply_rope(k, lcos, lsin, positions)
            q = _shard_act(q, P("data", None, "model", None))
        if kernel_write:
            from localai_tpu.ops.pallas import (
                paged_scatter_append, paged_scatter_append_q8,
                paged_scatter_append_q8_sharded, paged_scatter_append_sharded,
            )

            if kv_quant:
                if write_mesh is not None:
                    kq, ks, vq, vs = paged_scatter_append_q8_sharded(
                        write_mesh, kc.q, kc.s, vc.q, vc.s, k[:, 0], v[:, 0],
                        lengths, table, active, sb=sb, rw=rw)
                else:
                    kq, ks, vq, vs = paged_scatter_append_q8(
                        kc.q, kc.s, vc.q, vc.s, k[:, 0], v[:, 0], lengths,
                        table, active, sb=sb, rw=rw)
                kc, vc = QuantKV(kq, ks), QuantKV(vq, vs)
            elif write_mesh is not None:
                kc, vc = paged_scatter_append_sharded(
                    write_mesh, kc, vc, k[:, 0], v[:, 0], lengths, table,
                    active, sb=sb, rw=rw)
            else:
                kc, vc = paged_scatter_append(kc, vc, k[:, 0], v[:, 0],
                                              lengths, table, active,
                                              sb=sb, rw=rw)
        else:
            wpos, keep = positions, None
            if ring:
                # an inactive row's write is dropped (_cache_write)
                keep = (jnp.ones((b, 1), bool) if active is None
                        else active[:, None])
            elif active is not None and table is None:
                # dense: each row owns its slot row, so T-1 (never readable
                # — the engine terminates at max_context-2) is a safe
                # per-row target
                wpos = jnp.where(active[:, None], positions,
                                 kc.shape[-2] - 1)
            kc, vc = _cache_write(kc, vc, k, v, jnp.arange(b), wpos, table,
                                  unique=unique, redirect=redirect, kvt=kvt,
                                  ring_keep=keep, layer=li)
        with _attn_scope(kind):
            attn = attn_decode(q, kc, vc, lengths + 1,
                               sliding_window=_layer_window(cfg, kind),
                               table=table, kvt=kvt, ck=ck, cv=cv, ring=ring,
                               layer=li)
            with jax.named_scope("attention"):
                x = x + qmatmul(attn.reshape(b, 1, -1), lp["wo"],
                                spec=P("data", None, None))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(h, lp, cfg, spec_prefix=("data", None))
        return x, (kc, vc)

    x, (k_cache, v_cache) = _scan_layers(
        cfg, layer, x, params["layers"], k_cache, v_cache,
        extra=(kvt["cold_k"], kvt["cold_v"]) if cold else (), carry=stacked)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _lm_head(x[:, 0].astype(jnp.float32), params)
    return logits, k_cache, v_cache


def ragged_forward(params, cfg: LlamaConfig, tokens, cos, sin,
                   k_cache, v_cache, block_seq, qstart, qlen, kvlen,
                   tables, logit_rows, kvt=None, inject=None):
    """Mixed prefill+decode forward over ONE flat token stream (ragged
    continuous batching, arXiv:2604.15464): decode tokens and chunked-prefill
    windows from different requests pack into a single [T] stream and run as
    one dispatch on the paged tier — no per-bucket padding, no separate
    prefill and decode programs on mixed ticks.

    tokens: [T] i32, T a multiple of ops.pallas.QBLK (8); every sequence's
    rows start on a QBLK boundary (the engine packs this way) so each 8-row
    kernel block belongs to exactly one sequence. Per-sequence metadata
    ([NSEQ], padded with dead entries):
      qstart[s]/qlen[s] — the sequence's row span in the stream (row units);
      kvlen[s] — cache length INCLUDING this chunk (decode: old length + 1);
      tables [NSEQ, MAXB] — block table into the paged pool;
      block_seq [NQB=T/QBLK] — sequence id per q block, -1 for padding
      blocks. logit_rows [NSEQ] — flat row of each sequence's last token
      (decode rows and final prefill chunks; mid-prefill chunks may point
      anywhere — their logits are ignored host-side). A 2-D logit_rows
      [NSEQ, R] gathers R rows per sequence instead (logits [NSEQ, R, V]) —
      the spec-as-ragged verify pass needs the distribution at every row of
      its draft window, not just the last.

    inject: optional (extra [T, H] float, is_embed [T] bool) — rows with
    is_embed take `extra` directly instead of the token-id embedding lookup
    (multimodal prefill chunks pack their projected image/audio embeddings
    into the same flat stream; reference: LLaVA-style mm prompt splicing).

    Everything per-ROW (rope positions, scatter targets) derives on device
    from that per-sequence metadata, so the host ships O(NSEQ) scalars, not
    O(T). Padding rows write to the trash block (physical 0) and produce
    garbage attention output that never reaches a logit row.

    k_cache/v_cache: paged pools [L, NB, KVH, BS, D] (QuantKV int8 twin
    supported). Returns (logits [NSEQ, V] f32, k_cache, v_cache). Tier
    selection matches the decode path: Pallas ragged kernels on TPU (or
    LOCALAI_FORCE_PALLAS), sharded per KV-head shard under a TP mesh, XLA
    gather/scatter twins otherwise."""
    from localai_tpu.ops.pallas import (
        QBLK, ragged_attention_xla, ragged_attention_xla_q8,
        ragged_paged_attention, ragged_paged_attention_q8,
        ragged_paged_attention_q8_sharded, ragged_paged_attention_sharded,
        ragged_scatter_append, ragged_scatter_append_q8,
        ragged_scatter_append_q8_sharded, ragged_scatter_append_sharded,
        ragged_scatter_xla, ragged_scatter_xla_q8,
    )

    _no_mixed(cfg, "ragged_forward")
    t = tokens.shape[0]
    kv_quant = isinstance(k_cache, QuantKV)
    blk = (k_cache.q if kv_quant else k_cache).shape[3]        # pool BS
    use_kernel = _pallas_paged_scatter(cfg)
    mesh = None
    if use_kernel:
        from localai_tpu.parallel.mesh import current_mesh

        mesh = current_mesh()
    block_seq = block_seq.astype(jnp.int32)
    qstart, qlen = qstart.astype(jnp.int32), qlen.astype(jnp.int32)
    kvlen = kvlen.astype(jnp.int32)

    # per-row derivations (device-side, from per-seq metadata): sequence id,
    # liveness, absolute position, and the (physical block, in-block row)
    # scatter target. Dead rows target trash (block 0) at per-row offsets —
    # collisions there only overwrite other dead rows.
    rows = jnp.arange(t, dtype=jnp.int32)
    sid = block_seq[rows // QBLK]
    s = jnp.maximum(sid, 0)
    live = (sid >= 0) & (rows >= qstart[s]) & (rows < qstart[s] + qlen[s])
    pos = kvlen[s] - qlen[s] + (rows - qstart[s])
    pos = jnp.where(live, jnp.clip(pos, 0, cos.shape[0] - 1), 0)
    raw = pos // blk
    if kvt is not None:
        # KV lifecycle tier: fold raw blocks into the per-sequence ring
        # before the table lookup (kvt ships [NSEQ] geometry, like tables)
        from localai_tpu.ops.paged import ring_block_map

        raw = ring_block_map(raw, kvt["sb"][s], kvt["rw"][s])
    pb = jnp.where(live, tables[s, raw], 0)
    off = jnp.where(live, pos % blk, rows % blk)

    def write(kc, vc, kn, vn):
        if use_kernel and kv_quant:
            if mesh is not None:
                kq, ks, vq, vs = ragged_scatter_append_q8_sharded(
                    mesh, kc.q, kc.s, vc.q, vc.s, kn, vn, pb, off)
            else:
                kq, ks, vq, vs = ragged_scatter_append_q8(
                    kc.q, kc.s, vc.q, vc.s, kn, vn, pb, off)
            return QuantKV(kq, ks), QuantKV(vq, vs)
        if use_kernel:
            if mesh is not None:
                return ragged_scatter_append_sharded(mesh, kc, vc, kn, vn,
                                                     pb, off)
            return ragged_scatter_append(kc, vc, kn, vn, pb, off)
        if kv_quant:
            kq, ks, vq, vs = ragged_scatter_xla_q8(
                kc.q, kc.s, vc.q, vc.s, kn, vn, pb, off)
            return QuantKV(kq, ks), QuantKV(vq, vs)
        return ragged_scatter_xla(kc, vc, kn, vn, pb, off)

    def attend(qf, kc, vc):
        sw = cfg.sliding_window
        if kvt is not None:
            # tiered reads ride the XLA twins (ring positions + retention
            # masking); the ragged kernel's table streaming has no ring
            # inverse yet. TODO(kvtier): _kv_map + _row_mask ring support.
            if kv_quant:
                return ragged_attention_xla_q8(
                    qf, kc.q, kc.s, vc.q, vc.s, block_seq, qstart, qlen,
                    kvlen, tables, sliding_window=sw, kvt=kvt)
            return ragged_attention_xla(qf, kc, vc, block_seq, qstart,
                                        qlen, kvlen, tables,
                                        sliding_window=sw, kvt=kvt)
        if use_kernel and kv_quant:
            if mesh is not None:
                return ragged_paged_attention_q8_sharded(
                    mesh, qf, kc.q, kc.s, vc.q, vc.s, block_seq, qstart,
                    qlen, kvlen, tables, sliding_window=sw)
            return ragged_paged_attention_q8(
                qf, kc.q, kc.s, vc.q, vc.s, block_seq, qstart, qlen, kvlen,
                tables, sliding_window=sw)
        if use_kernel:
            if mesh is not None:
                return ragged_paged_attention_sharded(
                    mesh, qf, kc, vc, block_seq, qstart, qlen, kvlen,
                    tables, sliding_window=sw)
            return ragged_paged_attention(qf, kc, vc, block_seq, qstart,
                                          qlen, kvlen, tables,
                                          sliding_window=sw)
        if kv_quant:
            return ragged_attention_xla_q8(
                qf, kc.q, kc.s, vc.q, vc.s, block_seq, qstart, qlen, kvlen,
                tables, sliding_window=sw)
        return ragged_attention_xla(qf, kc, vc, block_seq, qstart, qlen,
                                    kvlen, tables, sliding_window=sw)

    emb = params["embed"].astype(cfg.jdtype)[tokens]           # [T, H]
    if inject is not None:
        extra, is_embed = inject
        emb = jnp.where(is_embed[:, None], extra.astype(cfg.jdtype), emb)
    x = emb[None]                                              # [1, T, H]

    def layer(x, xs):
        lp, kc, vc = xs
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(h, lp, cfg, spec=P(None, None, "model"))
        q = apply_rope(q, cos, sin, pos[None])
        k = apply_rope(k, cos, sin, pos[None])
        q = _shard_act(q, P(None, None, "model", None))
        # current chunk lands in the pool FIRST (decode_step convention:
        # attention then reads it back through the table — kvlen already
        # counts it), so prefill chunks attend to themselves paged
        kc, vc = write(kc, vc, k[0], v[0])
        attn = attend(q[0], kc, vc)
        with jax.named_scope("attention"):
            x = x + qmatmul(attn.reshape(1, t, -1), lp["wo"],
                            spec=P(None, None, None))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(h, lp, cfg, spec_prefix=(None, None))
        return x, (kc, vc)

    x, (k_cache, v_cache) = jax.lax.scan(
        layer, x, (params["layers"], k_cache, v_cache)
    )
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    # [NSEQ, H] for 1-D logit_rows, [NSEQ, R, H] for the 2-D spec windows
    last = x[0][logit_rows.astype(jnp.int32)]
    logits = _lm_head(last.astype(jnp.float32), params)
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
                sampler,
                key=jnp.where(live[:, None], sampler.key, prev_key))
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
            done = done | (live & (is_eos
                                   | (n_out >= remaining)
                                   | (lengths >= limit)))
            return (i + 1, done, n_out, toks, lps, gstate, kc, vc, sampler,
                    last_logits, lengths)

        (steps, _, n_out, toks, lps, _, kc, vc, sampler, last_logits,
         lengths) = jax.lax.while_loop(cond, body, init)
        return (toks, lps, n_out, steps, kc, vc, sampler, last_logits,
                lengths)

    return decode_loop


# fused ragged-loop exit codes (device → host; engine maps them onto the
# telemetry.sched pack reason codes at consume time)
RLOOP_EXIT_STEPS_CAP = 0   # ran the full max_steps budget
RLOOP_EXIT_FINISH = 1      # a decode slot finished (EOS/max_tokens/context)
RLOOP_EXIT_PREFILL = 2     # host-set prefill/admission-pending flag


def build_ragged_loop(ragged_step, decode_step, *, max_steps: int,
                      limit: int):
    """Fused multi-step ragged tick (Kernel Looping over the ragged pack):
    the mixed ragged dispatch plus up to `max_steps - 1` follow-on decode
    iterations run as ONE device program, so every live decode slot keeps
    advancing without a host round trip per token.

    The re-pack between iterations degenerates to pure data movement on
    device: iteration 0 runs `ragged_step` (the engine's single-step mixed
    body — sample, splice into the flat stream, one ragged_forward over
    decode rows + prefill chunks, set_len/logit_set commits), after which
    every datum the next decode step needs (lengths, last_logits, sampler
    state, block tables, grammar `gstate`) is already device-resident.
    Iterations >= 1 therefore run `decode_step` (the SAME fused
    sample→decode body the dense while loop uses) over the decode-live
    slots — a [B]-row step, not a re-run of the [T]-row ragged forward, so
    a multi-step dispatch costs ragged + (steps-1) x dense instead of
    steps x ragged. Slots mid-prefill (or whose final chunk just packed,
    sampler row pending host install) sit the continuation out frozen.

    With `has_pack=False` the ragged iteration is skipped entirely and the
    program is the pure-decode loop for ragged engines: `build_decode_loop`
    semantics plus the early-exit conditions below. Per-slot RNG streams are
    bit-identical to the single-step paths either way (`_draw` is width-
    independent and finished slots freeze key/last_logits exactly as the
    dense loop does).

    The loop EARLY-EXITS (cond, evaluated per iteration) when:
    - any decode slot finishes (EOS set / `remaining` budget / `limit`
      context margin — the PR 6 stop conditions): the host can admit into
      the freed slot immediately instead of waiting out the step cap;
    - `prefill_pending` (a traced bool shipped per dispatch) says the host
      has prefill chunks or admissible queue work: the dispatch collapses
      to a single iteration so TTFT stays at ragged levels;
    - the `max_steps` budget is spent.
    Host-arbitration cases (host-only grammar masks, stop strings) never
    reach this program — the engine falls back to the single-step ragged
    dispatch and records `loop_early_exit_host_arbitration`.

    Returns (toks [max_steps, B], lps [max_steps, B], n_out [B], steps_run,
    exit_code, kc, vc, sampler, last_logits, lengths); slot b's valid
    tokens are ring rows 0..n_out[b)-1 and exit_code is one of the
    RLOOP_EXIT_* constants (finish wins over prefill wins over steps_cap).
    """

    def ragged_loop(params, cos, sin, kc, vc, sampler, last_logits, lengths,
                    is_decode, remaining, check_eos, eos_ids,
                    prefill_pending, pack=None, table=None, kvt=None,
                    fast_width=None, gstate=None, gmasks=None, gtrans=None,
                    *, has_pack: bool):
        B = lengths.shape[0]
        grammar = gmasks is not None
        if gstate is None:
            gstate = jnp.zeros((B,), jnp.int32)
        done = ~is_decode
        n_out = jnp.zeros((B,), jnp.int32)
        toks = jnp.zeros((max_steps, B), jnp.int32)
        lps = jnp.zeros((max_steps, B), jnp.float32)

        def stops(tokens, n_out, lengths, live):
            is_eos = check_eos & jnp.any(
                tokens[:, None] == eos_ids[None, :], axis=1)
            return live & (is_eos | (n_out >= remaining)
                           | (lengths >= limit))

        i0 = jnp.int32(0)
        if has_pack:
            # iteration 0, unrolled: the exact single-step mixed ragged
            # body. Every packed decode row samples and advances (the
            # device cannot unpack a row), so the host only routes packs
            # here when each decode entry has remaining budget >= 1.
            mask0 = gmasks[gstate] if grammar else None
            (tokens, lp, kc, vc, sampler, last_logits, lengths) = \
                ragged_step(params, cos, sin, kc, vc, sampler, last_logits,
                            lengths, pack["tokens"], pack["decode_slot"],
                            is_decode, pack["set_len"], pack["logit_set"],
                            pack["logit_rows"], pack["block_seq"],
                            pack["qstart"], pack["qlen"], pack["kvlen"],
                            table, kvt, mask0, pack.get("inject"))
            toks = toks.at[0].set(tokens)
            lps = lps.at[0].set(lp)
            n_out = n_out + is_decode.astype(jnp.int32)
            if grammar:
                gstate = jnp.where(is_decode, gtrans[gstate, tokens], gstate)
            done = done | stops(tokens, n_out, lengths, is_decode)
            i0 = jnp.int32(1)

        init = (i0, done, n_out, toks, lps, gstate, kc, vc, sampler,
                last_logits, lengths)

        def cond(carry):
            i, done = carry[0], carry[1]
            # first-finish exit: unlike build_decode_loop (which keeps
            # looping until EVERY slot froze), one finished decode slot
            # ends the dispatch — early-exit admission
            return ((i < max_steps) & jnp.any(~done)
                    & ~jnp.any(is_decode & done) & ~prefill_pending)

        def body(carry):
            (i, done, n_out, toks, lps, gstate, kc, vc, sampler,
             last_logits, lengths) = carry
            live = ~done
            prev_key = sampler.key
            mask = gmasks[gstate] if grammar else None
            tokens, lp, kc, vc, sampler, logits, lengths = decode_step(
                params, cos, sin, kc, vc, sampler, last_logits, lengths,
                live, mask, fast_width, table, kvt)
            sampler = dataclasses.replace(
                sampler,
                key=jnp.where(live[:, None], sampler.key, prev_key))
            last_logits = jnp.where(live[:, None], logits, last_logits)
            toks = toks.at[i].set(tokens)
            lps = lps.at[i].set(lp)
            n_out = n_out + live.astype(jnp.int32)
            if grammar:
                gstate = jnp.where(live, gtrans[gstate, tokens], gstate)
            done = done | stops(tokens, n_out, lengths, live)
            return (i + 1, done, n_out, toks, lps, gstate, kc, vc, sampler,
                    last_logits, lengths)

        (steps, done, n_out, toks, lps, _, kc, vc, sampler, last_logits,
         lengths) = jax.lax.while_loop(cond, body, init)
        exit_code = jnp.where(
            jnp.any(is_decode & done), jnp.int32(RLOOP_EXIT_FINISH),
            jnp.where(prefill_pending & jnp.any(~done),
                      jnp.int32(RLOOP_EXIT_PREFILL),
                      jnp.int32(RLOOP_EXIT_STEPS_CAP)))
        return (toks, lps, n_out, steps, exit_code, kc, vc, sampler,
                last_logits, lengths)

    return ragged_loop


def hidden_states(params, cfg: LlamaConfig, tokens, lengths=None):
    """Full-sequence causal forward → final-norm hidden states [B, S, H].
    `lengths` masks padded positions out of attention (defaults to full)."""
    b, s = tokens.shape
    cos, sin = rope_tables(cfg, s)
    positions = jnp.arange(s)[None, :].repeat(b, 0)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    attn_prefill, _ = _attn_impls()
    sax = _seq_ax()
    x = params["embed"].astype(cfg.jdtype)[tokens]
    x = _shard_act(x, P("data", sax, None))

    def layer(x, lp, _kc, _vc, kind):
        lcos, lsin = _layer_rope(cos, sin, kind)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q, k, v = _qkv(h, lp, cfg, spec=P("data", sax, "model"))
        q = apply_rope(q, lcos, lsin, positions)
        k = apply_rope(k, lcos, lsin, positions)
        q = _shard_act(q, P("data", sax, "model", None))
        attn = attn_prefill(q, k, v, lengths,
                            sliding_window=_layer_window(cfg, kind))
        x = x + qmatmul(attn.reshape(b, s, -1), lp["wo"],
                        spec=P("data", sax, None))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(h, lp, cfg, spec_prefix=("data", sax))
        x = _shard_act(x, P("data", sax, None))
        return x, (None, None)

    x, _ = _scan_layers(cfg, layer, x, params["layers"])
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
    [B, S, V] buffer when a single row is wanted (final prefill chunk).
    """
    from localai_tpu.ops.attention import mha_extend, mha_extend_tiered

    b, s = tokens.shape
    if table is not None or kvt is not None or redirect is not None:
        _no_mixed(cfg, "a paged, tiered or redirected extend")
    rows = jnp.arange(b) if slot_map is None else slot_map
    positions = start[:, None] + jnp.arange(s)[None, :]
    x = params["embed"].astype(cfg.jdtype)[tokens]
    if inject is not None:
        # multimodal chunk: image-feature rows replace token embeddings
        # (see prefill's inject)
        extra, is_embed = inject
        x = jnp.where(is_embed[..., None], extra.astype(x.dtype), x)
    # KV lifecycle tier (engine/kvtier.py): chunk windows write through the
    # ring map and attend against the resident view at true positions.
    # Padded final-chunk tails land in ring margin columns (never the live
    # window — kvtier.ring_blocks reserves a full prefill chunk of margin)
    # at positions > every real query, so the kv_pos <= q_pos mask hides
    # them until real tokens overwrite those rows.
    cold = kvt is not None and "cold_tab" in kvt
    stacked = table is None and kvt is None   # as in decode_step

    def rows_of(cache, li):
        """The rows this window attends over, of layer li of a dense stack
        (a paged or tiered cache is read through its table instead)."""
        return cache[li] if slot_map is None else cache[li, rows]

    def layer(x, lp, kc, vc, kind, ck=None, cv=None, li=None):
        ring = kind == WINDOW
        lcos, lsin = _layer_rope(cos, sin, kind)
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        with _attn_scope(kind):
            q, k, v = _qkv(h, lp, cfg, spec=P("data", None, "model"))
            q = apply_rope(q, lcos, lsin, positions)
            k = apply_rope(k, lcos, lsin, positions)
        # paged uniqueness: a window whose positions all sit inside the
        # slot's allocation (mid prefill chunks — callers pass
        # full_window=True) never collides; a FINAL chunk's padded tail
        # resolves to shared TRASH offsets with different values — a
        # genuine collision, so the assertion would be a lie there. A
        # redirect (paged spec verify: inactive rows' windows route to the
        # trash block) gets distinct per-(row, pos) offsets, so it stays
        # unique while B*S fits one block (beyond that the engine warns at
        # init — engine._build_jit).
        from localai_tpu.ops.paged import BLOCK as _PB

        keep = None
        if ring:
            # the chunk's writes wrap; every query must still find the
            # window - 1 tokens before it, which the chunk's own newest
            # writes overwrite unless the ring holds window + chunk
            size = kc.shape[-2]
            if size < lcos.shape[0] and size < cfg.sliding_window + s:
                raise ValueError(
                    f"a ring of {size} tokens cannot take a window of "
                    f"{cfg.sliding_window} behind a chunk of {s}")
            # a final chunk's padding is not written: nothing in a ring is
            # out of the way
            keep = (jnp.ones((b, s), bool) if last_pos is None
                    else jnp.arange(s)[None, :] <= last_pos[:, None])
        red_ok = redirect is None or b * s <= _PB
        kc, vc = _cache_write(
            kc, vc, k, v, rows, positions, table,
            unique=(table is None or full_window or redirect is not None)
            and red_ok,
            redirect=redirect, kvt=kvt, ring_keep=keep, layer=li)
        with _attn_scope(kind), jax.named_scope("attention"):
            if kvt is not None:
                kr, vr, kv_pos, kv_ok = _tiered_kv(
                    kc, vc, table[rows], kvt["sb"][rows], kvt["rw"][rows],
                    start + s,
                    ctab=kvt["cold_tab"][rows] if cold else None,
                    ck=ck, cv=cv)
                attn = mha_extend_tiered(
                    q, kr, vr, positions, kv_pos, kv_ok,
                    kvt["sinks"][rows], kvt["window"][rows],
                    drop_window=not cold)
            elif ring:
                kr, vr = rows_of(kc, li), rows_of(vc, li)
                newest = start + s - 1
                kv_pos = newest[:, None] - _ring_back(newest, kr.shape[2])
                attn = mha_extend_tiered(
                    q, dequant(kr), dequant(vr), positions, kv_pos,
                    kv_pos >= 0, jnp.zeros((b,), jnp.int32),
                    jnp.full((b,), cfg.sliding_window, jnp.int32))
            else:
                if table is not None:
                    from localai_tpu.ops.paged import paged_view

                    kr = paged_view(kc, table[rows])
                    vr = paged_view(vc, table[rows])
                else:
                    kr, vr = rows_of(kc, li), rows_of(vc, li)
                attn = mha_extend(q, dequant(kr), dequant(vr), positions,
                                  sliding_window=_layer_window(cfg, kind))
            x = x + qmatmul(attn.reshape(b, s, -1), lp["wo"],
                            spec=P("data", None, None))
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + _mlp(h, lp, cfg, spec_prefix=("data", None))
        return x, (kc, vc)

    x, (k_cache, v_cache) = _scan_layers(
        cfg, layer, x, params["layers"], k_cache, v_cache,
        extra=(kvt["cold_k"], kvt["cold_v"]) if cold else (), carry=stacked)
    if not with_logits:
        return None, k_cache, v_cache
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    if last_pos is not None:
        x = jnp.take_along_axis(x, last_pos[:, None, None], axis=1)[:, 0]
        return _lm_head(x.astype(jnp.float32), params), k_cache, v_cache
    logits = _lm_head(x.astype(jnp.float32), params)
    return logits, k_cache, v_cache


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

    _no_mixed(cfg, "cache_shift")
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

    _no_mixed(cfg, "cache_shift_paged")
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
    pooled = (x * mask[..., None]).sum(1) / jnp.maximum(
        mask.sum(1)[:, None], 1.0
    )
    if normalize:
        pooled = pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9
        )
    return pooled
