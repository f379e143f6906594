"""On-chip paged-vs-dense decode breakdown (round-5: bench32 paged hit
91.7 tok/s vs 726.7 dense-16 — find the regression).

Times, at several slot counts on the real chip, ctx 1024, int8 KV:
  - ragged_decode_q8 attention alone: dense cache vs paged pool+table
  - full jitted decode_step: dense vs paged
  - the paged cache-write scatter alone (decode_step minus attention diff)

Usage: python tools/profile_paged.py [--slots 16,32] [--ctx 1024]
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
    return (time.perf_counter() - t0) / n * 1e3  # ms


def main():
    from localai_tpu.system.device import configure_compile_cache

    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="16,32")
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--size", default="8b")
    args = ap.parse_args()

    from bench import write_synthetic_checkpoint
    import tempfile

    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.models.llama import decode_step, init_kv_cache
    from localai_tpu.ops.paged import BLOCK, init_paged
    from localai_tpu.ops.pallas import ragged_decode_q8
    from localai_tpu.ops.rope import rope_table

    tmp = tempfile.mkdtemp(prefix="profp-")
    ckpt = write_synthetic_checkpoint(args.size, tmp)
    cfg = load_config(ckpt, dtype="int8")
    params = load_params(ckpt, cfg, dtype="int8")
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}")

    T = args.ctx
    maxb = T // BLOCK
    cos, sin = rope_table(cfg.rope, T)
    for B in [int(s) for s in args.slots.split(",")]:
        kc, vc = init_kv_cache(cfg, B, T, cache_type="int8")
        nblocks = B * maxb + 1
        pkc, pvc = init_paged(cfg.num_layers, nblocks, cfg.num_kv_heads,
                              cfg.head_dim, cache_type="int8")
        # identity-ish table: slot b's virtual block v -> physical 1+b*maxb+v
        table = (1 + np.arange(B)[:, None] * maxb
                 + np.arange(maxb)[None, :]).astype(np.int32)
        tab = jnp.asarray(table)
        lengths = jnp.full((B,), T - 8, jnp.int32)
        q = jnp.ones((B, 1, cfg.num_heads, cfg.head_dim), jnp.bfloat16)

        attn_d = jax.jit(lambda q, kq, ks, vq, vs, l:
                         ragged_decode_q8(q, kq, ks, vq, vs, l))
        ms_d = timeit(attn_d, q, kc.q[0], kc.s[0], vc.q[0], vc.s[0],
                      lengths, n=50)
        attn_p = jax.jit(lambda q, kq, ks, vq, vs, l, t:
                         ragged_decode_q8(q, kq, ks, vq, vs, l, table=t))
        ms_p = timeit(attn_p, q, pkc.q[0], pkc.s[0], pvc.q[0], pvc.s[0],
                      lengths, tab, n=50)
        print(f"[B={B:3d}] attn/layer dense {ms_d:6.3f} ms | paged {ms_p:6.3f}"
              f" ms | ratio {ms_p/ms_d:4.1f}x")

        tokens = jnp.zeros((B,), jnp.int32)
        active = jnp.ones((B,), bool)
        step_d = jax.jit(lambda p, t, l, kc, vc, a:
                         decode_step(p, cfg, t, l, cos, sin, kc, vc, a))
        ms_sd = timeit(step_d, params, tokens, lengths, kc, vc, active, n=20)
        step_p = jax.jit(lambda p, t, l, kc, vc, a, tb:
                         decode_step(p, cfg, t, l, cos, sin, kc, vc, a, tb))
        ms_sp = timeit(step_p, params, tokens, lengths, pkc, pvc, active,
                       tab, n=20)
        print(f"[B={B:3d}] decode_step dense {ms_sd:7.2f} ms "
              f"({B/ms_sd*1e3:6.0f} tok/s) | paged {ms_sp:7.2f} ms "
              f"({B/ms_sp*1e3:6.0f} tok/s) | ratio {ms_sp/ms_sd:4.1f}x")


if __name__ == "__main__":
    main()
