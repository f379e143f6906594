#!/usr/bin/env python3
"""One expert layer alone on the chip, both forms, at the cells' shapes:

    python tools/moe_layer_bench.py [--seed 32] [--models mixtral,mellum2,solar]

For each model (int8 expert weights in a two-layer stack, bf16 activations,
as served) and each call shape (a decode step's 32 rows, the prefill
buckets), the routed form (models/llama._moe_routed: each expert's
pairs in tiles of their own, a tile's expert read out of the stack) and the dense form
(_moe_mlp, every expert on every token under the mask; for a model that
holds a share, _moe_routed's masked twin): milliseconds a call, the share of
max(weight-read time, useful-FLOP time) each reaches
(benchmark/peaks/TPU_v5_lite.json: the chosen experts' int8 weights read
once, 3 x 2 x h x w operations a token-expert pair), the tiles in use and
how full they are, and which form `expert_form` picks there. `--tiles`
times the routed form at other tile sizes too.

The table goes to stdout and to chiprun_out/moe_layer_bench.json.
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

# name: (hidden, experts held, expert width, router width, top-k, call shapes)
SHAPES = {
    "mixtral": (4096, 8, 14336, 8, 2,
                ((32, 1), (1, 64), (2, 64), (1, 256), (1, 512), (4, 512))),
    "mellum2": (2304, 64, 896, 64, 8,
                ((32, 1), (1, 64), (2, 64), (1, 256), (1, 512), (4, 256),
                 (4, 512))),
    "solar": (4096, 40, 1280, 320, 8, ((32, 1), (1, 512))),
}
TINY = {
    "mixtral": (64, 4, 96, 4, 2, ((4, 1), (1, 64))),
    "mellum2": (64, 8, 32, 8, 4, ((4, 1), (1, 64))),
    "solar": (64, 4, 32, 16, 4, ((4, 1), (1, 64))),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=32)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--models", default="mixtral,mellum2,solar")
    ap.add_argument("--layers", type=int, default=2,
                    help="depth of the weight stacks (the layer timed is "
                         "the last)")
    ap.add_argument("--tiles", default="",
                    help="comma-separated tile sizes to time the routed "
                         "form at, besides the one it chooses")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "moe_layer_bench.json"))
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from localai_tpu.models import llama
    from localai_tpu.models.llama import LlamaConfig, _InStack
    from localai_tpu.ops.pallas.grouped_matmul import grouped_matmul

    rehearsal = args.cpu_rehearsal
    if not rehearsal and jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    with open(os.path.join(ROOT, "benchmark", "peaks",
                           "TPU_v5_lite.json")) as f:
        peaks = json.load(f)
    reps = 2 if rehearsal else args.reps
    report = {"device": [jax.devices()[0].platform,
                         jax.devices()[0].device_kind],
              "rehearsal": rehearsal, "rows": []}

    def timed(fn, *a):
        out = fn(*a)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t) / reps, out

    for name in args.models.split(","):
        hidden, held, width, routers, topk, calls = (
            TINY if rehearsal else SHAPES)[name]
        cfg = LlamaConfig(hidden_size=hidden, num_experts=held,
                          experts_per_tok=topk, moe_intermediate_size=width,
                          router_experts=routers if routers != held else 0)
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        qw = lambda kk, shape: {  # noqa: E731
            "q": jax.random.randint(kk, shape, -127, 128, jnp.int8),
            "s": jnp.full(shape[:-2] + (1, shape[-1]),
                          shape[-2] ** -0.5 / 73, jnp.float32)}
        gate = jax.random.normal(ks[0], (hidden, routers)) * 0.02
        stacks = {"moe_w1": qw(ks[1], (args.layers, held, hidden, width)),
                  "moe_w3": qw(ks[2], (args.layers, held, hidden, width)),
                  "moe_w2": qw(ks[3], (args.layers, held, width, hidden))}

        def routed(x, gate, stacks, layer):
            lp = {"moe_gate": gate, **{k: _InStack(v, layer)
                                       for k, v in stacks.items()}}
            return llama._moe_routed(x, lp, cfg)

        def dense(x, gate, stacks, layer):
            lp = {"moe_gate": gate, **jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, layer,
                                                       keepdims=False),
                stacks)}
            if routers != held:
                return llama._moe_routed(x, lp, cfg, grouped=False)
            return llama._moe_mlp(x, lp, cfg)

        for b, s in calls:
            n = b * s
            x = jax.random.normal(ks[4], (b, s, hidden), jnp.bfloat16)
            # what the router chooses here, for the counts
            probs = jax.nn.softmax(
                x.reshape(n, hidden).astype(jnp.float32) @ gate, axis=-1)
            chosen = np.asarray(jax.lax.top_k(probs, topk)[1])
            sizes = np.bincount(chosen[chosen < held].ravel(),
                                minlength=held)
            pairs = int(sizes.sum())
            cost = {"ops": pairs * 3 * 2.0 * hidden * width,
                    "bytes": float((sizes > 0).sum()) * 3 * hidden * width}
            least = max(cost["ops"] / peaks["bf16_flops"],
                        cost["bytes"] / peaks["hbm_bytes_per_s"])
            picked = llama.expert_form(cfg, n)
            outs = {}

            def row(form, seconds, **more):
                r = {"model": name, "tokens": [b, s], "form": form,
                     "served": form == picked, "pairs": pairs,
                     "ms": None if rehearsal else seconds * 1e3,
                     "least_ms": least * 1e3,
                     "roofline_pct": (None if rehearsal
                                      else 100 * least / seconds), **more}
                report["rows"].append(r)
                print(json.dumps(r), flush=True)

            own = llama._tile_rows(n, topk, routers)
            tiles = [own] + [int(t) for t in args.tiles.split(",") if t
                             and int(t) != own]
            # (tile, LOCALAI_NO_PALLAS): as served, then the tile loop in
            # XLA (the kernel's twin), then other tiles
            for tm, xla in [(own, False), (own, True)] + [
                    (t, False) for t in tiles[1:]]:
                keep, env = llama._tile_rows, os.environ.get(
                    "LOCALAI_NO_PALLAS")
                llama._tile_rows = lambda *_a, tm=tm: tm
                if xla:
                    os.environ["LOCALAI_NO_PALLAS"] = "1"
                try:
                    # a new function a variant: jit caches by the function
                    sec, out = timed(jax.jit(lambda *a: routed(*a)), x, gate,
                                     stacks, jnp.int32(args.layers - 1))
                finally:
                    llama._tile_rows = keep
                    if xla:
                        os.environ.pop("LOCALAI_NO_PALLAS")
                        if env is not None:
                            os.environ["LOCALAI_NO_PALLAS"] = env
                used = int((-(-sizes // tm)).sum())
                row(llama.ROUTED + (", XLA loop" if xla else "")
                    + ("" if tm == own else f", tile {tm}"),
                    sec, tile=tm, tiles_in_use=used,
                    tile_fill=pairs / max(used * tm, 1))
                outs.setdefault(llama.ROUTED, out)
                if not xla and not rehearsal:
                    # the three grouped products alone, over the same tiles:
                    # what is left of the call is the sort, the padded
                    # gather and the way back
                    per = -(-sizes // tm)
                    n_tiles = -(-(n * topk + held * (tm - 1)) // tm)
                    tile_e = np.full((n_tiles,), held - 1, np.int32)
                    tile_e[:used] = np.repeat(np.arange(held), per)
                    xp = jax.random.normal(ks[5], (n_tiles, tm, hidden),
                                           jnp.bfloat16)

                    def products(xp, stacks, tile_e, used, layer):
                        g = lambda a, w: grouped_matmul(  # noqa: E731
                            a, stacks[w]["q"], stacks[w]["s"], tile_e, used,
                            layer)
                        return g(jax.nn.silu(g(xp, "moe_w1"))
                                 * g(xp, "moe_w3"), "moe_w2")

                    sec, _ = timed(jax.jit(products), xp, stacks,
                                   jnp.asarray(tile_e), jnp.int32(used),
                                   jnp.int32(args.layers - 1))
                    row(f"the three products alone, tile {tm}", sec,
                        tile=tm, tiles_in_use=used)
            sec, outs[llama.DENSE] = timed(jax.jit(dense), x, gate, stacks,
                                           jnp.int32(args.layers - 1))
            row(llama.DENSE, sec)
            err = float(jnp.abs(
                outs[llama.ROUTED].astype(jnp.float32)
                - outs[llama.DENSE].astype(jnp.float32)).max())
            scale = float(jnp.abs(
                outs[llama.DENSE].astype(jnp.float32)).max())
            print(f"  {name} {b}x{s}: routed against dense max abs {err:.4g} "
                  f"(of {scale:.4g})", flush=True)
            if not err <= 0.05 * scale + 1e-6:
                print("  THE TWO FORMS DISAGREE", file=sys.stderr)
                return 1
        del stacks
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
