#!/usr/bin/env python3
"""The kernels a linear-attention layer adds, alone, on the chip, at the
cell's shapes (solar-open2-250b-ep8-d8: 64 heads x 128 x 128 float32 state,
32 slots, 512-token chunks), each against its share of the roofline
(benchmark/harness/roofline_kda.py, benchmark/peaks/):

    python tools/kda_kernel_bench.py [--seed 31]

- `kda_decode` (ops/pallas/kda.py) over a stack of two layers: every row
  live, the cell's mix (`--live` rows of 32 live), and its XLA twin;
- `kda_chunk` over one 512-token chunk of one row: the kernel a chip
  serves (ops/pallas/kda.py), and its XLA twin (ops/kda.py) with products at
  HIGHEST precision (as the CPU and a mesh serve it) and at the default;
  the kernel and the twin again at the admission groups' shapes, [4, 512]
  and [8, 256]. `differs_by`: the kernel's output against the twin's;
  `max_err`: against the token-by-token scan.

The routed expert layer at this cell's shapes: tools/moe_layer_bench.py.

The table goes to stdout and to chiprun_out/kda_kernel_bench.json.
`--cpu-rehearsal` proves the script at a tiny size on the CPU and times
nothing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=31)
    ap.add_argument("--live", type=int, default=27)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kda_kernel_bench.json"))
    args = ap.parse_args()
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["LOCALAI_FORCE_PALLAS"] = "1"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import roofline_kda as rk
    from localai_tpu.ops import kda
    from localai_tpu.ops.pallas.kda import kda_chunk, kda_decode

    rehearsal = args.cpu_rehearsal
    if not rehearsal and jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        peaks = json.load(f)
    B, H, D, T = (4, 32, 128, 128) if rehearsal else (32, 64, 128, 512)
    dk = 16 if rehearsal else D
    reps = 2 if rehearsal else args.reps
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 12)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731

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
            least = rk.least_seconds(cost, peaks)
            r.update(roofline_pct=rk.roofline_share(cost, peaks, seconds),
                     least_ms=least["seconds"] * 1e3, bound=least["bound"],
                     gb_per_s=cost["bytes"] / seconds / 1e9)
        report["rows"].append(r)
        print(json.dumps(r), flush=True)

    # ---- kda_decode
    q = unit(jax.random.normal(ks[0], (B, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, H, dk)))
    v = jax.random.normal(ks[2], (B, H, D))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, H, dk), minval=-7., maxval=0.))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H)))
    stack = jax.random.normal(ks[5], (2, B, H, dk, D))
    live_n = min(args.live, B)
    live = np.zeros((B,), bool)
    live[np.random.default_rng(args.seed).permutation(B)[:live_n]] = True
    want_o, want_s = kda.kda_step(q, k, v, g, beta, stack[1])
    for name, mask in (("all rows live", np.ones((B,), bool)),
                       (f"{live_n} of {B} rows live", live)):
        step = jax.jit(lambda s, m: kda_decode(q, k, v, g, beta, s, 1, m),
                       donate_argnums=(0,))
        o, s1 = step(stack + 0, jnp.asarray(mask))
        err = float(jnp.abs(jnp.where(mask[:, None, None], o - want_o,
                                      0)).max())
        assert err < 1e-3, err
        assert bool((s1[1][~mask] == stack[1][~mask]).all())
        state = [stack + 0]

        def run(m):
            o, state[0] = step(state[0], m)
            return o

        sec, _ = timed(run, jnp.asarray(mask))
        row(f"kda_decode, {name}", sec,
            rk.kda_decode_cost(int(mask.sum()), H, dk, D), max_err=err)
    twin = jax.jit(lambda s: kda.kda_step(q, k, v, g, beta, s))
    sec, _ = timed(twin, stack[1])
    row("kda_step (XLA twin), all rows", sec, rk.kda_decode_cost(B, H, dk, D))

    # ---- kda_chunk
    if rehearsal:       # the kernel tiles a key of whole lane tiles
        H, dk = 4, D
    for rows, tokens in ((1, T), (4, T), (8, T // 2)):
        shape = (rows, tokens, H)
        cq = unit(jax.random.normal(ks[6], (*shape, dk))) * dk ** -0.5
        ck = unit(jax.random.normal(ks[7], (*shape, dk)))
        cv = jax.random.normal(ks[8], (*shape, D))
        cg = -jnp.exp(jax.random.uniform(ks[9], (*shape, dk), minval=-7.,
                                         maxval=0.))
        cb = 2 * jax.nn.sigmoid(jax.random.normal(ks[10], shape))
        s0 = jax.random.normal(ks[11], (rows, H, dk, D))
        at = f"[{rows}, {tokens}]"
        cost = rk.kda_chunk_cost(tokens, H, dk, D, rows=rows)
        ro, rs = kda.kda_recurrent(cq, ck, cv, cg, cb, s0)
        twin = jax.jit(lambda s: kda.kda_chunk(cq, ck, cv, cg, cb, s))
        sec_twin, (o_hi, s_hi) = timed(twin, s0)
        # the kernel's inputs as the served program has them: [B, S, H D]
        # arrays (what the projections and the convolution leave) seen as
        # [B, S, H, D], q and k made unit vectors inside (`unit_qk`; these
        # are unit vectors already: the work is timed, `differs_by` is read
        # with it off)
        wide = [a.reshape(rows, tokens, -1) for a in (cq, ck, cv, cg)]

        def kernel(s, unit_qk=True):
            q, k, v, g = (a.reshape(*shape, -1) for a in wide)
            return kda_chunk(q, k, v, g, cb, s, unit_qk=unit_qk)

        sec, _ = timed(jax.jit(kernel), s0)
        o_k, s_k = jax.jit(functools.partial(kernel, unit_qk=False))(s0)
        differs = max(float(jnp.abs(o_k - o_hi).max()),
                      float(jnp.abs(s_k - s_hi).max()))
        assert differs < 1e-3, differs
        row(f"kda_chunk {at} (Pallas kernel: as served)", sec, cost,
            max_err=float(jnp.abs(o_k - ro).max()), differs_by=differs)
        row(f"kda_chunk {at} (XLA twin, HIGHEST)", sec_twin, cost,
            max_err=float(jnp.abs(o_hi - ro).max()))
        if rows > 1:
            continue
        hi = kda._HI
        kda._HI = None
        try:
            loose = jax.jit(lambda s: kda.kda_chunk(cq, ck, cv, cg, cb, s))
            sec, (o_lo, _) = timed(loose, s0)
        finally:
            kda._HI = hi
        row(f"kda_chunk {at} (XLA twin, default precision)", sec, cost,
            max_err=float(jnp.abs(o_lo - ro).max()))

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
