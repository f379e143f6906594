#!/usr/bin/env python3
"""Latent attention's two paths, alone, on the chip, at the cell's shapes
(openpangu-ultra-moe-ep16-d6: 128 heads, a latent of 512 + 64 a token in
rows of 640 bfloat16, 32 slots x 12288, int8 W_kvb), each against its share
of the roofline (benchmark/harness/roofline_mla.py, benchmark/peaks/):

    python tools/mla_kernel_bench.py [--seed 40]

- `mla_decode` (ops/pallas/mla.py) over a stack of two layers: `--live` rows
  of 32 live at lengths around `--context` (drawn from --seed), every block
  size of `--blocks`; its XLA twin (ops/mla.mla_decode_xla); and the whole
  absorbed step (the query through W_UK, the kernel, the output through
  W_UV: kv.LatentKV.decode);
- a 512-token chunk's attention at each context of `--contexts`, over the
  same cache: EXPANDING (kv.LatentKV.attend_window: every visited block of
  rows through W_kvb, heads of 192 / 128) in the kernel that is served
  (ops/pallas/mla.py: mla_chunk, at every `--chunk-heads` heads a grid
  step) and in its twin, the XLA block loop
  (ops/attention.mha_extend_blocks), with `differs_by` between the two;
  and ABSORBED (the chunk's queries through W_UK, the rows as they lie as
  one KV head of 640 / 512 for all 128 heads, the output through W_UV) in
  the XLA loop, checked against the expanding loop.

Times are the host's clock round `--reps` calls that end in
block_until_ready. The table goes to stdout and to
chiprun_out/mla_kernel_bench.json. `--cpu-rehearsal` proves the script at a
tiny size on the CPU and times nothing.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--live", type=int, default=30)
    ap.add_argument("--context", type=int, default=6144)
    ap.add_argument("--contexts", default="2048,6144,8192,12288")
    ap.add_argument("--chunk-heads", default="",
                    help="heads a grid step of mla_chunk, beside its own")
    ap.add_argument("--blocks", default="512,1024,2048")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "mla_kernel_bench.json"))
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LOCALAI_FORCE_PALLAS"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import roofline_mla as rm
    from localai_tpu.models import kv
    from localai_tpu.ops import mla
    from localai_tpu.ops.attention import mha_extend_blocks
    from localai_tpu.ops.pallas.mla import mla_chunk, mla_decode
    from localai_tpu.ops.quant import quantize

    rehearsal = args.cpu_rehearsal
    if not rehearsal and jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        peaks = json.load(f)
    if rehearsal:
        B, T, H, R, P, N, V, S = 4, 256, 4, 64, 16, 32, 32, 32
        contexts, blocks, reps = [64, 192], [128], 1
        args.live, args.context = 3, 160
        dtype = jnp.float32
    else:
        B, T, H, R, P, N, V, S = 32, 12288, 128, 512, 64, 128, 128, 512
        contexts = [int(c) for c in args.contexts.split(",")]
        blocks = [int(b) for b in args.blocks.split(",")]
        reps, dtype = args.reps, jnp.bfloat16
    width = kv.latent_row_width(R, P)
    scale = (N + P) ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    rng = np.random.default_rng(args.seed)
    # rows as a layer caches them: a unit-RMS latent, a position key, zeros
    rows = jnp.concatenate([
        jax.random.normal(ks[0], (2, B, T, R + P), jnp.float32),
        jnp.zeros((2, B, T, width - R - P), jnp.float32)], -1).astype(dtype)
    w_kvb = quantize(jax.random.normal(ks[1], (R, H * (N + V)), jnp.float32)
                     * R ** -0.5)
    if rehearsal:
        w_kvb = (w_kvb["q"] * w_kvb["s"]).astype(dtype)
    view = kv.LatentKV(rows, None, layer=1, heads=H, nope=N, rope=P, rank=R,
                       vdim=V, w_kvb=w_kvb)

    def timed(fn, *a):
        out = fn(*a)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / reps, out

    report = {"device": [jax.devices()[0].platform,
                         jax.devices()[0].device_kind],
              "rehearsal": rehearsal, "shapes": dict(
                  rows=B, context=T, heads=H, rank=R, rope=P, nope=N, vdim=V,
                  row_width=width, chunk=S), "rows": []}

    def row(name, seconds, cost=None, **more):
        r = {"name": name, "ms": None if rehearsal else seconds * 1e3, **more}
        if cost is not None and not rehearsal:
            least = rm.least_seconds(cost, peaks)
            r.update(roofline_pct=rm.roofline_share(cost, peaks, seconds),
                     least_ms=least["seconds"] * 1e3, bound=least["bound"],
                     gb_per_s=cost["bytes"] / seconds / 1e9,
                     tflop_per_s=cost["ops"] / seconds / 1e12)
        report["rows"].append(r)
        print(json.dumps(r), flush=True)

    # ---- decode: the kernel alone, its twin, the absorbed step around it
    lengths = np.zeros((B,), np.int32)
    live = rng.permutation(B)[:args.live]
    lengths[live] = np.clip(rng.normal(args.context, args.context / 4,
                                       size=len(live)), 64, T - 1).astype(int)
    lens = jnp.asarray(lengths)
    q_lat = jnp.pad(jax.random.normal(ks[2], (B, H, R + P), jnp.float32),
                    ((0, 0), (0, 0), (0, width - R - P))).astype(dtype)
    cost = rm.mla_decode_cost(float(lengths.sum()), len(live), H, R, P,
                              jnp.dtype(dtype).itemsize)
    twin = jax.jit(lambda q, c, n: mla.mla_decode_xla(q, c[1], n, R, scale))
    sec_twin, want = timed(twin, q_lat, rows, lens)
    for bk in blocks:
        kern = jax.jit(lambda q, c, n, bk=bk: mla_decode(
            q, c, n, 1, rank=R, scale=scale, block_k=bk))
        sec, got = timed(kern, q_lat, rows, lens)
        err = float(jnp.abs(got.astype(jnp.float32)
                            - want.astype(jnp.float32))[live].max())
        assert err < (1e-4 if rehearsal else 0.05), err
        row(f"mla_decode, {len(live)} of {B} rows live, mean context "
            f"{int(lengths[live].mean())}, block_k {bk}", sec, cost,
            max_err=err)
    row("mla_decode_xla (XLA twin), same rows", sec_twin, cost)
    q = jax.random.normal(ks[3], (B, 1, H, N + P), jnp.float32).astype(dtype)
    step = jax.jit(
        lambda q, c, n: dataclasses.replace(view, k=c).decode(q, n))
    sec, _ = timed(step, q, rows, lens)
    row("absorbed decode step of one layer (W_UK, mla_decode, W_UV)", sec,
        cost)

    # ---- a chunk's attention, both forms over the same cache
    cq = jax.random.normal(ks[4], (1, S, H, N + P), jnp.float32).astype(dtype)
    slot = jnp.asarray([1], jnp.int32)

    def expanding(q, c, start):
        positions = start[:, None] + jnp.arange(S)[None, :]
        return dataclasses.replace(view, k=c).attend_window_xla(
            q, positions, start, slot, True)

    def kernel(heads):
        return lambda q, c, start: mla_chunk(
            q, c, w_kvb, start, slot, 1, rank=R, nope=N, scale=scale,
            block=min(kv.CHUNK_BLOCK, T), heads_per_step=heads)

    def absorbed(q, c, start):
        positions = start[:, None] + jnp.arange(S)[None, :]
        block = min(kv.CHUNK_BLOCK, T)
        q_lat = jnp.pad(mla.absorb(q, w_kvb, N),
                        ((0, 0),) * 3 + ((0, width - R - P),))

        def fetch(first):
            blk = jax.lax.dynamic_slice(
                c, (1, 1, first, 0), (1, 1, block, width))[0]
            return blk[:, None], blk[:, None, :, :R]

        o = mha_extend_blocks(q_lat, fetch, 1, T, positions, start,
                              block=block, scale=scale, v_dim=R)
        return mla.unabsorb(o, w_kvb, N)

    def differ(a, b):
        err = float(jnp.abs(a.astype(jnp.float32)
                            - b.astype(jnp.float32)).max())
        assert err < (1e-4 if rehearsal else 0.05), err
        return err

    kernels = [("kernel (served)", None)] + [
        (f"kernel, {g} heads a grid step", int(g))
        for g in args.chunk_heads.split(",") if g]
    for ctx in contexts:
        start = jnp.asarray([ctx - S], jnp.int32)

        def chunk_row(name, fn, form="expanding", **against):
            sec, out = timed(jax.jit(fn), cq, rows, start)
            row(f"chunk of {S} at context {ctx}, {form}, {name}", sec,
                rm.mla_chunk_cost(S, ctx - S, H, R, P, N, V, form,
                                  jnp.dtype(dtype).itemsize,
                                  1.0 if isinstance(w_kvb, dict) else
                                  jnp.dtype(dtype).itemsize),
                **{k: differ(out, v) for k, v in against.items()})
            return out

        loop = chunk_row("XLA loop", expanding)
        for name, heads in kernels:
            chunk_row(name, kernel(heads), differs_by=loop)
        chunk_row("XLA loop", absorbed, "absorbed", forms_differ_by=loop)
    report["crossing_tokens"] = rm.crossing_tokens(H, R, P, N, V)
    print(json.dumps({"crossing_tokens": report["crossing_tokens"]}))

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
