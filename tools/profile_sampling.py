"""Sampler cost breakdown on the real chip (fast path reads 1.6-1.9 ms/step
— ~8% of the decode step; the full path reads 20-74 ms and de-optimizes any
batch containing one wide-top_k request).

Times, at B=16/32 over the 128k vocab:
  - lax.top_k at width 64 / 256 / 1024 (the fast path's dominant op)
  - lax.approx_max_k at the same widths (TPU-native partial reduction)
  - full two-sort path (_filtered_sorted) for reference
  - the elementwise pipeline_logits chain alone

Usage: python tools/profile_sampling.py [--cpu]
"""
from __future__ import annotations

import argparse
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
    return (time.perf_counter() - t0) / n * 1e3


def main():
    from localai_tpu.system.device import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--vocab", type=int, default=128256)
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from localai_tpu.ops.sampling import SamplerState, sample

    V = args.vocab
    rng = np.random.default_rng(0)
    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)} vocab={V}")
    for B in (16, 32):
        logits = jnp.asarray(rng.standard_normal((B, V)), jnp.float32)
        for W in (64, 256, 1024):
            tk = jax.jit(lambda x, w=W: jax.lax.top_k(x, w))
            ms_t = timeit(tk, logits)
            ak = jax.jit(lambda x, w=W: jax.lax.approx_max_k(x, w))
            ms_a = timeit(ak, logits)
            print(f"[B={B}] W={W:5d}: lax.top_k {ms_t:7.3f} ms | "
                  f"approx_max_k {ms_a:7.3f} ms")
        st = SamplerState.init(B, V)
        fast = jax.jit(lambda lg, s: sample(lg, s, None, topk_width=64))
        ms_f = timeit(fast, logits, st)
        full = jax.jit(lambda lg, s: sample(lg, s, None))
        ms_full = timeit(full, logits, st, n=10)
        print(f"[B={B}] sample fast(64) {ms_f:7.3f} ms | full {ms_full:7.3f} ms")


if __name__ == "__main__":
    main()
