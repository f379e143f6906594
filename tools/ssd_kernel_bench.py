#!/usr/bin/env python3
"""The kernels a state-space (Mamba-2) layer adds, alone, on the chip, at the
cell's shapes (nemotron3-super-120b-ep4-d22: 128 heads x 64 x 128 float32
state, 8 groups, 32 slots, 512-token chunks cut at 128), each against its
share of the roofline (benchmark/harness/roofline_ssd.py, benchmark/peaks/):

    python tools/ssd_kernel_bench.py [--seed 42]

- `ssd_decode` (ops/pallas/ssd.py) over a stack of two layers: every row
  live, the cell's mix (`--live` rows of 32 live), and its XLA twin;
- `ssd_chunk` (ops/ssd.py, the XLA form) over one 512-token chunk of one
  row, products at HIGHEST precision (as served) and at the default.

The table goes to stdout and to chiprun_out/ssd_kernel_bench.json.
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--live", type=int, default=30)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "ssd_kernel_bench.json"))
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LOCALAI_FORCE_PALLAS"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import roofline_ssd as rs
    from localai_tpu.ops import ssd
    from localai_tpu.ops.pallas.ssd import ssd_decode

    rehearsal = args.cpu_rehearsal
    if not rehearsal and jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        peaks = json.load(f)
    B, H, P, N, G, T = ((4, 32, 8, 128, 2, 64) if rehearsal
                        else (32, 128, 64, 128, 8, 512))
    reps = 2 if rehearsal else args.reps
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 12)

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
              "rehearsal": rehearsal, "rows": []}

    def row(name, seconds, cost=None, **more):
        r = {"name": name, "ms": None if rehearsal else seconds * 1e3, **more}
        if cost is not None and not rehearsal:
            least = rs.least_seconds(cost, peaks)
            r.update(roofline_pct=rs.roofline_share(cost, peaks, seconds),
                     least_ms=least["seconds"] * 1e3, bound=least["bound"],
                     gb_per_s=cost["bytes"] / seconds / 1e9)
        report["rows"].append(r)
        print(json.dumps(r), flush=True)

    def draws(shape_x, shape_dt, shape_g, at):
        x = jax.random.normal(ks[at], shape_x)
        dt = jnp.exp(jax.random.uniform(ks[at + 1], shape_dt,
                                        minval=jnp.log(1e-3),
                                        maxval=jnp.log(1e-1)))
        bm = jax.nn.silu(jax.random.normal(ks[at + 2], shape_g))
        cm = jax.nn.silu(jax.random.normal(ks[at + 3], shape_g))
        return x, dt, bm, cm

    a = -jax.random.uniform(ks[0], (H,), minval=1.0, maxval=16.0)

    # ---- ssd_decode (the convolution and its tail are XLA's beside it: the
    # cost leaves them out, tail_bytes 0 and no taps)
    x, dt, bm, cm = draws((B, H, P), (B, H), (B, G, N), 1)
    stack = jax.random.normal(ks[5], (2, B, H, P, N))
    live_n = min(args.live, B)
    live = np.zeros((B,), bool)
    live[np.random.default_rng(args.seed).permutation(B)[:live_n]] = True
    want_y, _ = ssd.ssd_step(x, dt, a, bm, cm, stack[1])

    def cost_of(rows):
        return rs.ssd_decode_cost(rows, H, P, N, G, taps=0, tail_bytes=0.0)

    for name, mask in (("all rows live", np.ones((B,), bool)),
                       (f"{live_n} of {B} rows live", live)):
        step = jax.jit(lambda s, m: ssd_decode(x, dt, a, bm, cm, s, 1, m),
                       donate_argnums=(0,))
        y, s1 = step(stack + 0, jnp.asarray(mask))
        err = float(jnp.abs(jnp.where(mask[:, None, None], y - want_y,
                                      0)).max())
        assert err < 1e-3, err
        assert bool((s1[1][~mask] == stack[1][~mask]).all())
        state = [stack + 0]

        def run(m):
            y, state[0] = step(state[0], m)
            return y

        sec, _ = timed(run, jnp.asarray(mask))
        row(f"ssd_decode, {name}", sec, cost_of(int(mask.sum())), max_err=err)
    twin = jax.jit(lambda s: ssd.ssd_step(x, dt, a, bm, cm, s))
    sec, _ = timed(twin, stack[1])
    row("ssd_step (XLA twin), all rows", sec, cost_of(B))

    # ---- ssd_chunk
    cx, cdt, cbm, ccm = draws((1, T, H, P), (1, T, H), (1, T, G, N), 6)
    s0 = stack[0, :1]
    chunk_cost = rs.ssd_chunk_cost(T, H, P, N, G)
    served = jax.jit(lambda s: ssd.ssd_chunk(cx, cdt, a, cbm, ccm, s))
    sec, (y_hi, _) = timed(served, s0)
    ry, _ = ssd.ssd_recurrent(cx, cdt, a, cbm, ccm, s0)
    row("ssd_chunk (XLA, HIGHEST: as served)", sec, chunk_cost,
        max_err=float(jnp.abs(y_hi - ry).max()),
        y_abs_max=float(jnp.abs(ry).max()))
    hi = ssd._HI
    ssd._HI = None
    try:
        loose = jax.jit(lambda s: ssd.ssd_chunk(cx, cdt, a, cbm, ccm, s))
        sec, (y_lo, _) = timed(loose, s0)
    finally:
        ssd._HI = hi
    row("ssd_chunk (XLA, default precision)", sec, chunk_cost,
        max_err=float(jnp.abs(y_lo - ry).max()))

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
