#!/usr/bin/env python3
"""A prompt chunk's attention over a full-length dense cache, alone on the
chip, at the cells' shapes:

    python tools/chunk_attention_bench.py [--seed 36] [--models solar,mellum2]

For each model (an int8 cache stack [2, rows, KVH, T, D] as served, bf16
queries of one 512-token chunk, one gathered row) and each context the chunk
may have (`start` + 512 of T): milliseconds a call of the whole-row
reference (kv.NoKV.attend_window -> ops/attention.mha_extend) and of the
blockwise form served (kv.DenseKV.attend_window -> mha_extend_blocks) at
each block size of `--blocks`, the largest difference between the two, and
`verify`: a speculative-verification window (every slot a row, 5 tokens).

The table goes to stdout and to chiprun_out/chunk_attention_bench.json.
`--cpu-rehearsal` proves the script at a tiny size on the CPU and times
nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# name: (query heads, KV heads, head size, T, slots, contexts)
SHAPES = {
    "solar": (64, 8, 128, 16384, 32, (512, 2048, 3584, 8192, 16384)),
    "mellum2": (32, 4, 128, 8192, 32, (512, 2048, 4096, 8192)),
    "trinity": (48, 8, 128, 16384, 32, (512, 4096, 8192, 16384)),
    "mixtral": (32, 8, 128, 1536, 32, (512, 1024, 1536)),
}
TINY = {name: (4, 2, 16, 1536, 4, (512, 1024, 1536)) for name in SHAPES}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--models", default="solar,mellum2,trinity,mixtral")
    ap.add_argument("--blocks", default="256,512,1024,2048")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "chunk_attention_bench.json"))
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import kv
    from localai_tpu.ops.kvcache import QuantKV

    rehearsal = args.cpu_rehearsal
    if not rehearsal and jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    blocks = [int(b) for b in args.blocks.split(",")]
    served_block = kv.CHUNK_BLOCK
    reps = 1 if rehearsal else args.reps
    rows_out = []

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))            # compiles
        if rehearsal:
            return out, None
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / reps * 1e3

    for name in args.models.split(","):
        h, kvh, d, t, slots, contexts = (TINY if rehearsal else SHAPES)[name]
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        shape = (2, slots, kvh, t, d)

        def cache(key):
            return QuantKV(
                jax.random.randint(key, shape, -127, 128, jnp.int8),
                jnp.full((*shape[:3], t // 128, 128), 0.01, jnp.float32))

        k, v = cache(keys[0]), cache(keys[1])

        def attend(form, q, k, v, start, rows):
            view = kv.DenseKV(k, v, None, layer=jnp.int32(1))
            positions = start[:, None] + jnp.arange(q.shape[1])[None, :]
            return form(view, q, positions, start, rows, rows is not None)

        for what, b, s in (("chunk", 1, 512), ("verify", slots, 5)):
            q = jax.random.normal(keys[2], (b, s, h, d), jnp.bfloat16)
            rows = jnp.array([slots - 1]) if b == 1 else None
            # one program a form: `start` is traced, CHUNK_BLOCK read when
            # the first context traces it
            whole = jax.jit(lambda *a: attend(kv.NoKV.attend_window, *a))
            served = {block: jax.jit(
                lambda *a: attend(kv.DenseKV.attend_window, *a))
                for block in blocks}
            for ctx in contexts:
                start = jnp.full((b,), ctx - s, jnp.int32)
                ref, ms = timed(whole, q, k, v, start, rows)
                line = {"model": name, "call": what, "context": ctx, "T": t,
                        "whole_row_ms": ms}
                for block, fn in served.items():
                    kv.CHUNK_BLOCK = block
                    out, ms = timed(fn, q, k, v, start, rows)
                    line[f"blocks_{block}_ms"] = ms
                    line[f"blocks_{block}_diff"] = float(jnp.max(jnp.abs(
                        out.astype(jnp.float32) - ref.astype(jnp.float32))))
                rows_out.append(line)
                print(json.dumps(line), flush=True)
    kv.CHUNK_BLOCK = served_block
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "rehearsal": rehearsal, "rows": rows_out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
