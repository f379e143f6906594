"""Quantized KV cache — int8 storage with per-token scales.

Reference parity: llama.cpp exposes KV-cache quantization via
`CacheTypeKey`/`CacheTypeValue` (/root/reference/backend/backend.proto:257-258,
mapped at backend/cpp/llama-cpp/grpc-server.cpp:236-251). Here the same knob
halves the decode working set on TPU: K/V live in HBM as int8 with one f32
scale per (token, kv-head), computed symmetrically over the head_dim axis —
the same granularity as llama.cpp's q8_0 blocks (32 elems there, head_dim
here; head_dim is the natural TPU tile).

Layout is chosen for Mosaic, not for numpy: the scales of cache
[..., T, D] are stored as [..., T // 128, 128] (token t ↦ element
[t // 128, t % 128]) so the trailing two dims of any Pallas block over them
are (rows, 128) — tile-legal — and a 128-token KV block's scales are exactly
one aligned scale row. `T` must therefore be a multiple of 128; callers round
up (extra rows are inert — every read is masked by `lengths`). The dense
decode kernel moves several such blocks of every KV head of a row in one
grid step (512 tokens of T = 1536, 1024 of 8192: 1 MB of K + V on Mixtral
where one head's 128 tokens were 32 KB) and applies as many scale rows; a
row's whole scale strip rides beside its first block. At one 16 KB block a
step the kernel was bound by DMA latency and its count of grid steps, at a
twentieth of the chip's bandwidth (PERF.md §6, PR 30).

The XLA (non-Pallas) attention paths read the cache through `dequant`, which
XLA fuses into the consuming dot where it can; HBM *capacity* is halved
either way, and the int8 Pallas decode kernel
(ops/pallas/flash_attention.py:ragged_decode_q8) also halves decode HBM
*traffic* — the thing decode is actually bound by. On the paged Pallas tier
the per-step cache WRITE quantizes through `quantize_tokens` and lands via
the scatter-append DMA kernel (ops/pallas/paged_scatter.py) instead of
`cache_scatter`'s XLA scatter.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

SCALE_TILE = 128
# int8 symmetric range; 1/127 floor keeps zero vectors exactly zero
_QMAX = 127.0
_EPS = 1e-8

KV_KINDS = ("", "bf16", "f16", "f32", "int8", "q8_0")


def is_quant_kind(kind: str | None) -> bool:
    """True for the cache-type strings that select int8 storage (accepts the
    reference's llama.cpp spelling `q8_0` as well as plain `int8`)."""
    return (kind or "").lower() in ("int8", "q8_0", "q8")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantKV:
    """One int8 cache tensor: `q` [..., T, D] int8, `s` [..., T//128, 128] f32.

    Behaves enough like the dense array it replaces that the model code's
    `cache.shape[3]`, `cache[rows]`, and lax.scan-over-layers all work
    unchanged.
    """
    q: jax.Array
    s: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def __getitem__(self, idx):
        # leading-axis indexing only (layer scan / slot gather); token and
        # head_dim axes must stay whole because `s` mirrors only the lead dims
        return QuantKV(self.q[idx], self.s[idx])


def padded_len(t: int) -> int:
    """Round a cache length up to the scale-tile multiple the layout needs."""
    return -(-t // SCALE_TILE) * SCALE_TILE


def init_quant(shape, *, scale_dtype=jnp.float32) -> QuantKV:
    """Zero cache of logical shape [..., T, D] (T already tile-padded)."""
    *lead, t, d = shape
    if t % SCALE_TILE:
        raise ValueError(f"quantized cache length {t} not a multiple of "
                         f"{SCALE_TILE} (use padded_len)")
    return QuantKV(
        jnp.zeros(shape, jnp.int8),
        jnp.zeros((*lead, t // SCALE_TILE, SCALE_TILE), scale_dtype),
    )


def quantize_tokens(x):
    """Per-token symmetric int8 over the trailing head_dim axis.

    x: [..., D] (any lead shape) → (q int8 same shape, scale f32 lead shape).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, _EPS) / _QMAX
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def token_scales(cache: QuantKV):
    """Scales as [..., T] (flattening the tile layout back to token order)."""
    *lead, rows, tile = cache.s.shape
    return cache.s.reshape(*lead, rows * tile)


def dequant(cache, dtype=jnp.bfloat16):
    """QuantKV → dense [..., T, D]; dense arrays pass through untouched."""
    if not isinstance(cache, QuantKV):
        return cache
    s = token_scales(cache)[..., None]
    return (cache.q.astype(jnp.float32) * s).astype(dtype)


def cache_scatter(cache: QuantKV, idx, values, unique: bool = True) -> QuantKV:
    """Scatter dense token vectors into the quantized cache.

    idx: advanced-index tuple addressing [..., T] positions of the cache's
    lead+token axes (the same tuple the dense path hands to `.at[idx].set`);
    values: matching [..., D] dense rows. `unique` asserts non-colliding
    rows (see models/kv.py DenseKV.write for when that holds) — the
    assertion keeps XLA on the in-place scatter path inside the layer scan.
    """
    q, scale = quantize_tokens(values)
    *lead_idx, tok_idx = idx
    s_idx = (*lead_idx, tok_idx // SCALE_TILE, tok_idx % SCALE_TILE)
    return QuantKV(cache.q.at[idx].set(q, unique_indices=unique),
                   cache.s.at[s_idx].set(scale, unique_indices=unique))


def requantize(cache: QuantKV, dense) -> QuantKV:
    """Dense [..., T, D] → fresh QuantKV with cache's layout (context-shift
    rewrites go through here after operating in f32)."""
    q, scale = quantize_tokens(dense)
    *lead, t = scale.shape
    return QuantKV(q, scale.reshape(*lead, t // SCALE_TILE, SCALE_TILE)
                   .astype(cache.s.dtype))
