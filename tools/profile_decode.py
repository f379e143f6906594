"""On-chip decode step-time breakdown (VERDICT r4 weak #2: 35ms observed vs
~17ms int8 weight-streaming floor at 8 slots — find the missing 18ms).

Times, at several slot counts, on the real chip:
  - full jitted decode_step (int8 weights, int8 KV)
  - decode minus lm_head (tied tiny head) -> lm_head share
  - ragged_decode_q8 attention alone
  - sample() fast path alone
  - qmatmul effective bandwidth over one layer's weights vs the raw int8
    stream floor (is XLA fusing the int8->bf16 convert into the dot?)

Usage: python tools/profile_decode.py [--slots 8,16,32] [--ctx 1024]
Writes nothing; prints a table to stdout.
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
    ap.add_argument("--slots", default="8,16,32")
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--size", default="8b")
    args = ap.parse_args()

    from bench import write_synthetic_checkpoint, param_count
    import tempfile

    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.models.llama import decode_step, init_kv_cache
    from localai_tpu.ops.rope import rope_table
    from localai_tpu.ops.sampling import SamplerState, sample

    tmp = tempfile.mkdtemp(prefix="prof-")
    ckpt = write_synthetic_checkpoint(args.size, tmp)
    cfg = load_config(ckpt, dtype="int8")
    params = load_params(ckpt, cfg, dtype="int8")
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"device: {getattr(dev, 'device_kind', dev.platform)}")
    n_params = param_count(args.size)
    wbytes = n_params  # int8 ~ 1 byte/param
    print(f"params: {n_params/1e9:.2f}B  int8 stream: {wbytes/1e9:.2f} GB")

    # raw int8 stream floor: reduce every weight byte once
    @jax.jit
    def stream_all(ps):
        tot = jnp.float32(0)
        for leaf in jax.tree.leaves(ps):
            tot += jnp.sum(leaf.astype(jnp.float32))
        return tot

    ms = timeit(stream_all, params, n=10)
    print(f"stream-all-params (sum reduce): {ms:.1f} ms "
          f"-> {wbytes/ms/1e6:.0f} GB/s effective")

    # qmatmul vs raw: one big layer weight
    from localai_tpu.ops.quant import qmatmul
    H, I = cfg.hidden_size, cfg.intermediate_size
    w = params["layers"]["w_gate"]
    w0 = jax.tree.map(lambda x: x[0], w)  # [H, I] int8 dict
    for B in (8, 32):
        x = jnp.ones((B, H), jnp.bfloat16)
        f = jax.jit(lambda x, w: qmatmul(x, w))
        ms = timeit(f, x, w0, n=50)
        gb = H * I / 1e9
        print(f"qmatmul [B={B}] {H}x{I} int8: {ms:.3f} ms "
              f"-> {gb/ms*1e3:.0f} GB/s (floor would be ~bw)")
        # stacked over L like the scan does
        xs = jnp.ones((B, H), jnp.bfloat16)

        def scan_mm(x, w):
            def body(c, lw):
                return c + qmatmul(x, lw)[:, :H], None
            out, _ = jax.lax.scan(body, jnp.zeros((B, H), jnp.bfloat16), w)
            return out
        f2 = jax.jit(scan_mm)
        ms = timeit(f2, xs, w, n=10)
        gb = cfg.num_layers * H * I / 1e9
        print(f"scan-qmatmul [B={B}] {cfg.num_layers}x{H}x{I}: {ms:.2f} ms "
              f"-> {gb/ms*1e3:.0f} GB/s")

    T = args.ctx
    cos, sin = rope_table(cfg.rope, T)
    for B in [int(s) for s in args.slots.split(",")]:
        kc, vc = init_kv_cache(cfg, B, T, cache_type="int8")
        sampler = SamplerState.init(B, cfg.vocab_size)
        tokens = jnp.zeros((B,), jnp.int32)
        lengths = jnp.full((B,), T - 8, jnp.int32)
        active = jnp.ones((B,), bool)

        step = jax.jit(lambda p, t, l, kc, vc, a:
                       decode_step(p, cfg, t, l, cos, sin, kc, vc, a))
        ms_full = timeit(step, params, tokens, lengths, kc, vc, active, n=20)

        # attention alone
        from localai_tpu.ops.pallas import ragged_decode_q8
        q = jnp.ones((B, 1, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
        attn = jax.jit(lambda q, kq, ks, vq, vs, l:
                       ragged_decode_q8(q, kq, ks, vq, vs, l))
        ms_attn_1 = timeit(attn, q, kc.q[0], kc.s[0], vc.q[0], vc.s[0],
                           lengths, n=50)

        # sampling alone (fast path width 64)
        logits = jnp.zeros((B, cfg.vocab_size), jnp.float32)
        samp = jax.jit(lambda lg, st: sample(lg, st, None, topk_width=64))
        ms_samp = timeit(samp, logits, sampler, n=50)
        # sampling full path
        samp_full = jax.jit(lambda lg, st: sample(lg, st, None))
        ms_samp_full = timeit(samp_full, logits, sampler, n=20)

        # lm_head alone
        from localai_tpu.models.llama import _lm_head
        xlast = jnp.ones((B, H), jnp.float32)
        lmh = jax.jit(lambda x, p: _lm_head(x, p))
        ms_head = timeit(lmh, xlast, params, n=50)

        print(f"[B={B:3d} ctx={T}] decode_step {ms_full:7.2f} ms "
              f"({B/ms_full*1e3:6.0f} tok/s) | attn/layer {ms_attn_1:6.3f} "
              f"(x{cfg.num_layers}={ms_attn_1*cfg.num_layers:6.2f}) | "
              f"lm_head {ms_head:6.2f} | sample(fast) {ms_samp:6.2f} "
              f"full {ms_samp_full:6.2f}")


if __name__ == "__main__":
    main()
