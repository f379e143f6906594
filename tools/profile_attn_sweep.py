"""Slot-count sweep of decode attention on the real chip: where is the
B=16 -> B=32 cliff in ragged_decode_q8, and does the XLA path have it?

Usage: python tools/profile_attn_sweep.py
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, n=50, warmup=5):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3  # ms


def main():
    from localai_tpu.system.device import configure_compile_cache

    configure_compile_cache()
    from localai_tpu.ops.pallas import ragged_decode_q8
    from localai_tpu.ops.attention import mha_decode
    from localai_tpu.ops.kvcache import QuantKV, dequant

    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}")
    H, KVH, D, T = 32, 8, 128, 1024
    rng = np.random.default_rng(0)
    for B in (8, 16, 20, 24, 32, 48):
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.bfloat16)
        kq = jnp.asarray(rng.integers(-127, 127, (B, KVH, T, D)), jnp.int8)
        ks = jnp.asarray(rng.random((B, KVH, T // 128, 128)) * 0.01 + 0.01,
                         jnp.float32)
        vq = jnp.asarray(rng.integers(-127, 127, (B, KVH, T, D)), jnp.int8)
        vs = jnp.asarray(rng.random((B, KVH, T // 128, 128)) * 0.01 + 0.01,
                         jnp.float32)
        lengths = jnp.full((B,), T - 8, jnp.int32)

        pal = jax.jit(lambda q, kq, ks, vq, vs, l:
                      ragged_decode_q8(q, kq, ks, vq, vs, l))
        ms_pal = timeit(pal, q, kq, ks, vq, vs, lengths)

        def xla(q, kq, ks, vq, vs, l):
            kc = QuantKV(kq, ks)
            vc = QuantKV(vq, vs)
            return mha_decode(q, dequant(kc), dequant(vc), l)
        ms_xla = timeit(jax.jit(xla), q, kq, ks, vq, vs, lengths)

        kv_mb = 2 * B * KVH * T * D / 1e6
        floor = kv_mb / 1e3 / 819 * 1e3
        print(f"[B={B:3d}] pallas {ms_pal:7.3f} ms | xla {ms_xla:7.3f} ms | "
              f"kv {kv_mb:5.0f} MB floor {floor:5.3f} ms")


if __name__ == "__main__":
    main()
