"""HBM fit estimation — the gguf-parser VRAM-estimate role.

Reference: /root/reference/pkg/xsysinfo/gguf.go estimates whether a GGUF fits
VRAM before loading. Here the estimate is computed from the HF config
geometry (the same numbers the loader uses), covering weights, the KV cache
(dense or int8), and a working-set allowance — and compared against the
attached accelerator's memory (memory_stats when the runtime exposes it,
a per-generation table otherwise).
"""
from __future__ import annotations

import dataclasses
from typing import Any

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2,
                "int8": 1, "q8": 1, "int4": 0.5, "q4": 0.5}

# per-chip HBM for the TPU generations the capability detector reports
_HBM_TABLE = {"tpu-v4": 32 << 30, "tpu-v5e": 16 << 30,
              "tpu-v5p": 95 << 30, "tpu-v6e": 32 << 30}


@dataclasses.dataclass
class MemoryEstimate:
    weights_bytes: int
    kv_cache_bytes: int
    working_bytes: int
    total_bytes: int
    hbm_bytes: int | None

    @property
    def fits(self) -> bool | None:
        if self.hbm_bytes is None:
            return None
        return self.total_bytes <= self.hbm_bytes

    def to_dict(self) -> dict[str, Any]:
        return {
            "weights_bytes": self.weights_bytes,
            "kv_cache_bytes": self.kv_cache_bytes,
            "working_bytes": self.working_bytes,
            "total_bytes": self.total_bytes,
            "hbm_bytes": self.hbm_bytes,
            "fits": self.fits,
        }


def param_count(cfg) -> int:
    """LlamaConfig → parameter count (dense or MoE)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    qk = cfg.num_heads * hd
    kv = cfg.num_kv_heads * hd
    attn = h * qk + 2 * h * kv + qk * h
    if cfg.num_experts:
        mlp = cfg.num_experts * 3 * h * cfg.intermediate_size \
            + h * cfg.num_experts
    else:
        mlp = 3 * h * cfg.intermediate_size
    per_layer = attn + mlp + 2 * h
    embed = cfg.vocab_size * h * (1 if cfg.tie_embeddings else 2)
    return embed + cfg.num_layers * per_layer + h


def hbm_table_bytes(capability: str) -> int | None:
    """Per-generation HBM lookup (no accelerator runtime touched — safe for
    the control-plane process, which must never init jax)."""
    return _HBM_TABLE.get(capability)


def detect_hbm_bytes() -> int | None:
    """Attached accelerator memory: memory_stats()['bytes_limit'] when the
    runtime exposes it, else the generation table, else None (CPU).
    Initializes the device client — backend-process only."""
    import jax

    from localai_tpu.system.capabilities import capability_of

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    stats = dev.memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return hbm_table_bytes(capability_of(dev.platform, dev.device_kind))


def estimate(cfg, *, slots: int, context: int, dtype: str = "bfloat16",
             cache_type: str = "", hbm_bytes: int | None = None,
             draft_cfg=None, shards: int = 1,
             kv_shards: int | None = None,
             kv_pages: int = 0,
             detect_hbm: bool = True) -> MemoryEstimate:
    """PER-CHIP serving-memory estimate for a Llama-family config at the
    given engine shape (reference role: initializers' VRAM guesser guarding
    LoadModel). `shards` divides the weights (the TP 'model' axis — data
    replicas hold full copies); `kv_shards` divides the KV cache (sharded
    over BOTH axes: slots on 'data', kv heads on 'model'; defaults to
    `shards`). `kv_pages` > 0 sizes a PAGED cache (ops/paged.py): the pool is
    kv_pages 128-token blocks shared across slots, so slots × context stops
    being the dense product."""
    wbytes = int(param_count(cfg) * _DTYPE_BYTES.get(dtype, 2))
    if _DTYPE_BYTES.get(dtype, 2) < 2:
        # quantized weights carry f32 per-channel scales (~1/in_dim overhead)
        wbytes = int(wbytes * 1.02)

    kv_elem = 1 if cache_type in ("int8", "q8_0", "q8") else 2
    kv_tokens = kv_pages * 128 if kv_pages > 0 else slots * context
    kv = (2 * cfg.num_layers * kv_tokens * cfg.num_kv_heads
          * cfg.head_dim * kv_elem)
    if cache_type in ("int8", "q8_0", "q8"):
        kv += 2 * cfg.num_layers * kv_tokens * cfg.num_kv_heads * 4

    if draft_cfg is not None:
        wbytes += int(param_count(draft_cfg) * _DTYPE_BYTES.get(dtype, 2))
        kv += (2 * draft_cfg.num_layers * slots * draft_cfg.num_kv_heads
               * context * draft_cfg.head_dim * 2)

    wbytes = wbytes // max(shards, 1)
    kv = kv // max(kv_shards if kv_shards is not None else shards, 1)

    # working set: logits [slots, V] f32 ×2 (last + sampled), sampler state,
    # transient fusion buffers — a conservative 512MB + logits
    working = 2 * slots * cfg.vocab_size * 4 + (512 << 20)

    hbm = hbm_bytes
    if hbm is None and detect_hbm:
        hbm = detect_hbm_bytes()
    total = wbytes + kv + working
    return MemoryEstimate(wbytes, kv, working, total, hbm)
