"""Weight quantization: per-channel symmetric int8 — the TPU answer to
llama.cpp's GGUF quants (reference ModelOptions dtype/quant surface,
/root/reference/backend/backend.proto:175-265; F16Memory/LowVRAM knobs).

A quantized tensor is {"q": int8 [.., in, out], "s": f32 [.., 1, out]}
(per-output-channel scales). `qmatmul` computes x @ (q * s) with the scale
folded AFTER the int8→bf16 cast so XLA fuses dequant into the matmul epilogue;
HBM traffic halves vs bf16, which is what decode throughput is bound by.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def quantize(w, bits: int = 8):
    """f32/bf16 weight [..., in, out] → {"q": int8|int4, "s": f32 [..., 1, out]}.

    Scales reduce over the INPUT axis only: leading dims (the stacked layer
    axis of the scan layout) keep their own scales — reducing them away
    would give every layer one shared scale AND break lax.scan's leading-axis
    agreement between q [L, in, out] and s.

    bits=4 stores jnp.int4 (the exllama2/GGUF-Q4 role — half the HBM traffic
    of int8 again; XLA packs two nibbles per byte)."""
    if bits not in (4, 8):
        raise ValueError(f"unsupported quantization width {bits}")
    qmax = 7 if bits == 4 else 127
    qdtype = jnp.int4 if bits == 4 else jnp.int8
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(w32 / scale), -qmax, qmax).astype(qdtype)
    return {"q": q, "s": scale.astype(jnp.float32)}


def quantize_np(w, bits: int = 8):
    """Host-side (numpy) mirror of `quantize`, for the mesh-sharded loader:
    each safetensors shard quantizes right after its host read, so only the
    int8 payload + f32 scales ever cross `device_put` — the full bf16 stack
    is never materialized on host or chip. Bit-identical to the device path
    (IEEE max/div/mul, round-half-even). int4 keeps an int8 container; the
    loader casts to jnp.int4 AFTER the sharded placement (numpy has no int4).
    """
    import numpy as np

    if bits not in (4, 8):
        raise ValueError(f"unsupported quantization width {bits}")
    qmax = 7 if bits == 4 else 127
    w32 = np.asarray(w, np.float32)
    amax = np.max(np.abs(w32), axis=-2, keepdims=True)
    scale = np.maximum(amax, 1e-8) / qmax
    q = np.clip(np.rint(w32 / scale), -qmax, qmax).astype(np.int8)
    return {"q": q, "s": scale.astype(np.float32)}


def is_quantized(p) -> bool:
    return isinstance(p, dict) and set(p.keys()) == {"q", "s"}


def dequantize(p, dtype=jnp.bfloat16):
    return (p["q"].astype(jnp.float32) * p["s"]).astype(dtype)


def qmatmul(x, p, spec=None):
    """x @ W for a (possibly) quantized W; activations keep their dtype.

    `spec` (optional PartitionSpec) is an output-activation sharding hint:
    under an active mesh it is applied as a hard constraint so GSPMD keeps
    the (possibly int8) weight resident-sharded and computes the local
    partial product instead of all-gathering W — the TP decode contract.
    Callers inside shard_map must leave it None (constraints are illegal
    under manual axes)."""
    if not is_quantized(p):
        y = x @ p
    else:
        # int8 → activation dtype, scale folded per output channel
        w = p["q"].astype(x.dtype)
        y = x @ w
        y = y * p["s"].reshape((1,) * (y.ndim - 1) + (-1,)).astype(y.dtype)
    if spec is not None:
        from localai_tpu.parallel.mesh import constrain

        y = constrain(y, spec)
    return y


def quantize_params(params, *, bits: int = 8, skip=("embed", "final_norm")):
    """Quantize every projection matrix in a llama param tree (norms, biases
    and embeddings stay high-precision, like llama.cpp's mixed layouts)."""
    out = {}
    for k, v in params.items():
        if k in ("layers", "leading"):      # the scanned stack, and the
            out[k] = {                       # leading dense layers' own
                lk: (quantize(lv, bits)
                     if lk.startswith("w") or lk.startswith("moe_w") else lv)
                for lk, lv in v.items()
            }
        elif k == "lm_head":
            out[k] = quantize(v, bits)
        else:
            out[k] = v
    return out
