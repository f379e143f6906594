"""Bisect the decode step on the real chip: where do the non-floor ms go?

The weight-stream floor of decode_step at int8 8B is ~10 ms (8 GB at a v5e's
819 GB/s); the step itself is not measured on current code. This times each
constituent in isolation and a cumulative knockout chain:

  - full decode_step
  - layer stack with attention + cache-write knocked out (pure matmul chain)
  - layer stack with ONLY cache-write knocked out
  - cache write alone (layer-scan of quantized scatters)
  - lm_head alone, sampling alone (known), embed+rope overhead

Usage: python tools/profile_step_bisect.py [--slots 16,32] [--ctx 1024]
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


def timeit(fn, *args, n=20, warmup=3):
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
    ap.add_argument("--slots", default="16,32")
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--size", default="8b")
    ap.add_argument("--cpu", action="store_true", help="local smoke")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from bench import write_synthetic_checkpoint
    import tempfile

    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.models import llama as M
    from localai_tpu.ops.rope import apply_rope, rope_table
    from localai_tpu.ops.quant import qmatmul

    tmp = tempfile.mkdtemp(prefix="bisect-")
    ckpt = write_synthetic_checkpoint(args.size, tmp)
    cfg = load_config(ckpt, dtype="int8")
    params = load_params(ckpt, cfg, dtype="int8")
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}")

    T = args.ctx
    cos, sin = rope_table(cfg.rope, T)
    for B in [int(s) for s in args.slots.split(",")]:
        kc, vc = M.init_kv_cache(cfg, B, T, cache_type="int8")
        tokens = jnp.zeros((B,), jnp.int32)
        lengths = jnp.full((B,), T - 8, jnp.int32)
        active = jnp.ones((B,), bool)

        full = jax.jit(lambda p, t, l, kc, vc, a:
                       M.decode_step(p, cfg, t, l, cos, sin, kc, vc, a))
        ms_full = timeit(full, params, tokens, lengths, kc, vc, active)

        # pure matmul chain: per-layer qkv+wo+mlp, no attention / no writes
        def matmul_chain(p, t):
            x = p["embed"].astype(cfg.jdtype)[t][:, None, :]

            def layer(x, lp):
                h = M.rms_norm(x, lp["attn_norm"], cfg.rms_eps)
                q, k, v = M._qkv(h, lp, cfg)
                # stand-in for attention output with the right shape
                a = q.reshape(B, 1, -1)
                x = x + qmatmul(a, lp["wo"])
                h = M.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
                x = x + M._mlp(h, lp, cfg)
                return x, None

            x, _ = jax.lax.scan(layer, x, p["layers"])
            x = M.rms_norm(x, p["final_norm"], cfg.rms_eps)
            return M._lm_head(x[:, 0].astype(jnp.float32), p)
        ms_mm = timeit(jax.jit(matmul_chain), params, tokens)

        # attention+rope, no cache write (reads the existing cache)
        positions = lengths[:, None]
        _, attn_decode = M._attn_impls()

        def no_write(p, t, l):
            x = p["embed"].astype(cfg.jdtype)[t][:, None, :]

            def layer(x, xs):
                lp, kcl, vcl = xs
                h = M.rms_norm(x, lp["attn_norm"], cfg.rms_eps)
                q, k, v = M._qkv(h, lp, cfg)
                q = apply_rope(q, cos, sin, positions)
                a = attn_decode(q, kcl, vcl, l + 1,
                                sliding_window=cfg.sliding_window)
                x = x + qmatmul(a.reshape(B, 1, -1), lp["wo"])
                h = M.rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
                x = x + M._mlp(h, lp, cfg)
                return x, None

            x, _ = jax.lax.scan(layer, x, (p["layers"], kc, vc))
            x = M.rms_norm(x, p["final_norm"], cfg.rms_eps)
            return M._lm_head(x[:, 0].astype(jnp.float32), p)
        ms_nw = timeit(jax.jit(no_write), params, tokens, lengths)

        # cache write alone: the layer scan of quantized scatters
        def write_only(kc, vc, t, l):
            k = jnp.ones((B, 1, cfg.num_kv_heads, cfg.head_dim), cfg.jdtype)

            def layer(c, xs):
                kcl, vcl = xs
                kcl, vcl = M._cache_write(kcl, vcl, k, k, jnp.arange(B),
                                          l[:, None])
                return c, (kcl, vcl)

            _, (kc, vc) = jax.lax.scan(layer, jnp.float32(0), (kc, vc))
            return kc, vc
        wo = jax.jit(write_only, donate_argnums=(0, 1))
        # donation: feed each call's output back as the next input — one
        # resident pair, no 20x cache allocation (a 23-pair pre-allocation
        # OOMs the 16 GB chip at the 8b geometry)
        pair = M.init_kv_cache(cfg, B, T, cache_type="int8")
        for _ in range(3):
            pair = wo(pair[0], pair[1], tokens, lengths)
        jax.block_until_ready(pair)
        t0 = time.perf_counter()
        for _ in range(20):
            pair = wo(pair[0], pair[1], tokens, lengths)
        jax.block_until_ready(pair)
        ms_w = (time.perf_counter() - t0) / 20 * 1e3
        del pair

        print(f"[B={B:3d}] full {ms_full:7.2f} | matmul-chain {ms_mm:7.2f} | "
              f"+attn(no-write) {ms_nw:7.2f} | write-only {ms_w:7.2f} | "
              f"attn-cost {ms_nw - ms_mm:6.2f} | write-cost "
              f"{ms_full - ms_nw:6.2f}")


if __name__ == "__main__":
    main()
