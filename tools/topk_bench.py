#!/usr/bin/env python3
"""Sampling's top-k alone, on the chip: `lax.top_k` over the whole
vocabulary row against the two-stage form the decode programs take
(ops/sampling._top_k: the blocks' maxima, then the chosen blocks), at the
five served vocabularies and both widths of the sort-free path, B = 32:

    python tools/topk_bench.py [--seed 41]

A call's time is the host's clock round `--reps` calls enqueued back to back
and one block_until_ready, over `--reps`: each call a program of its own, as
the decode loop holds it (`lax.top_k` of 64 to 256 over a long row is the
chip's TopK custom call there; inside a bare `fori_loop` XLA makes a whole
stable sort of it, seven times as slow: not what is served, not timed). Beside
it the row's bytes (B x V x 4, which either form must read once) over that
time, as a share of the bandwidth in benchmark/peaks/. Both forms' outputs are
compared, bit for bit, on float32 rows (`identical`) and on rows rounded to
bfloat16, which tie by the hundred (`identical_ties`); `ties_by_index` says
whether the chip's own call put those ties in index order (a stable argsort):
at 512 wide it does not (XLA splits that call into two unstable sorts), and
there no form can repeat it. The table goes to stdout and to
chiprun_out/topk_bench.json. `--cpu-rehearsal` proves the script at a tiny
size on the CPU and times nothing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

VOCABS = (200192, 196608, 153600, 98304, 32000)
WIDTHS = (64, 512)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=41)
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "topk_bench.json"))
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from localai_tpu.ops import sampling

    rehearsal = args.cpu_rehearsal
    if not rehearsal and jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        peaks = json.load(f)
    B, vocabs, reps = 32, VOCABS, args.reps
    if rehearsal:
        B, vocabs, reps = 2, (66000, 9000), 2

    def ms_a_call(fn, x):
        jax.block_until_ready(fn(x))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(reps):
                out = fn(x)
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best / reps * 1e3

    def same(a, b):
        return all(np.array_equal(x, y) for x, y in zip(a, b))

    rng = np.random.default_rng(args.seed)
    rows = []
    for v in vocabs:
        x = jnp.asarray(rng.standard_normal((B, v)).astype(np.float32) * 4)
        ties = x.astype(jnp.bfloat16).astype(jnp.float32)
        by_index = np.argsort(-np.asarray(ties), axis=-1, kind="stable")
        least_ms = B * v * 4 / peaks["hbm_bytes_per_s"] * 1e3
        for w in WIDTHS:
            one = jax.jit(lambda l, w=w: jax.lax.top_k(l, w))
            served = jax.jit(lambda l, w=w: sampling._top_k(l, w))
            row = {"vocab": v, "width": w, "batch": B,
                   "two_stage": sampling.topk_by_blocks(v, w),
                   "identical": same(one(x), served(x)),
                   "identical_ties": same(one(ties), served(ties)),
                   "ties_by_index": np.array_equal(one(ties)[1],
                                                   by_index[:, :w]),
                   "row_bytes": B * v * 4}
            if not rehearsal:
                t1, t2 = ms_a_call(one, x), ms_a_call(served, x)
                row.update(one_stage_ms=t1, served_ms=t2,
                           one_stage_bw_share=least_ms / t1,
                           served_bw_share=least_ms / t2)
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = {"device": jax.devices()[0].device_kind, "reps": reps,
           "seed": args.seed, "rows": rows}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    if not all(r["identical"] and (r["identical_ties"]
                                   or not r["ties_by_index"]) for r in rows):
        print("the two forms disagree", file=sys.stderr)
        return 1
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
