#!/usr/bin/env python3
"""One expert layer alone on the chip, both forms, at the cells' shapes:

    python tools/moe_layer_bench.py [--seed 32] [--models mixtral,mellum2,...]

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
times the routed form at other tile sizes too. The grouped product kernel
(ops/pallas/grouped_matmul.py) also alone, over the call's own tiles: each
product's time, the static tiles, the tiles in use and the grid steps it
walks (read off the lowered call: a traced first bound ends at the tiles in
use), and `differs_by` against the tile loop in XLA with `used` at 0, 1, what
the router gave and every tile. `--repo` takes the package from another
checkout (the parent's, unpacked beside this one).

The table goes to stdout and to chiprun_out/moe_layer_bench.json.
`--cpu-rehearsal` proves the script at a tiny size on the CPU and times
nothing.
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

# name: (hidden, experts held, expert width, router width, top-k, call shapes)
SHAPES = {
    "mixtral": (4096, 8, 14336, 8, 2,
                ((32, 1), (1, 64), (2, 64), (1, 256), (1, 512), (4, 512))),
    "mellum2": (2304, 64, 896, 64, 8,
                ((32, 1), (1, 64), (2, 64), (1, 256), (1, 512), (4, 256),
                 (4, 512))),
    "solar": (4096, 40, 1280, 320, 8,
              ((32, 1), (1, 512), (4, 512), (8, 256))),
    "trinity": (3072, 32, 3072, 256, 4,
                ((32, 1), (1, 512), (4, 512), (8, 256))),
    "openpangu": (7680, 16, 2048, 256, 8,
                  ((32, 1), (1, 512), (4, 512), (8, 256))),
    # the experts work in the latent: relu^2 experts of two matrices
    "nemotron": (1024, 128, 2688, 512, 22,
                 ((32, 1), (1, 512), (4, 512), (8, 256))),
}
RELU2 = ("nemotron",)
TINY = {
    "mixtral": (64, 4, 96, 4, 2, ((4, 1), (1, 64))),
    "mellum2": (64, 8, 32, 8, 4, ((4, 1), (1, 64))),
    "solar": (64, 4, 32, 16, 4, ((4, 1), (1, 64))),
    "trinity": (64, 4, 64, 32, 4, ((4, 1), (1, 64))),
    "openpangu": (128, 2, 64, 32, 8, ((4, 1), (1, 64))),
    "nemotron": (32, 8, 96, 32, 6, ((4, 1), (1, 64))),
}


def _grid(fn, *args):
    """The grid of the one pallas_call in fn(*args), a traced bound as
    None."""
    import jax

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["grid_mapping"].grid
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (grid,) = calls(jax.make_jaxpr(fn)(*args).jaxpr)
    return [d if isinstance(d, int) else None for d in grid]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=32)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--models", default=",".join(SHAPES))
    ap.add_argument("--calls", default="",
                    help="only these call shapes of a model's, as 32x1,1x512")
    ap.add_argument("--repo", default=ROOT,
                    help="the checkout whose localai_tpu is timed")
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
    sys.path.insert(0, os.path.abspath(args.repo))
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
              "repo": os.path.abspath(args.repo), "rehearsal": rehearsal,
              "rows": []}
    worst = 0.0

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
        if name in RELU2:
            cfg = dataclasses.replace(cfg, expert_act="relu2")
            del stacks["moe_w3"]

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
            if args.calls and f"{b}x{s}" not in args.calls.split(","):
                continue
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
                if xla:
                    continue
                # the grouped products alone, over the same tiles: what is
                # left of the call is the way into the padded rows and back
                per = -(-sizes // tm)
                n_tiles = -(-(n * topk + held * (tm - 1)) // tm)
                tile_e = np.full((n_tiles,), held - 1, np.int32)
                tile_e[:used] = np.repeat(np.arange(held), per)
                tile_e = jnp.asarray(tile_e)
                layer = jnp.int32(args.layers - 1)
                xp = jax.random.normal(ks[5], (n_tiles, tm, hidden),
                                       jnp.bfloat16)

                def product(a, w, tile_e, used, layer):
                    return grouped_matmul(a, w["q"], w["s"], tile_e, used,
                                          layer)

                def loop(a, w, tile_e, used, layer):
                    """The kernel's twin: _grouped_experts' tile loop."""
                    def tile(t, out):
                        at = (layer, tile_e[t], 0, 0)
                        y = a[t] @ jax.lax.dynamic_slice(
                            w["q"], at, (1, 1, *w["q"].shape[2:])
                        )[0, 0].astype(a.dtype)
                        y = y * jax.lax.dynamic_slice(
                            w["s"], at, (1, 1, *w["s"].shape[2:])
                        )[0, 0].astype(y.dtype)
                        return out.at[t].set(y)

                    return jax.lax.fori_loop(
                        0, used, tile, jnp.zeros(
                            (*a.shape[:2], w["q"].shape[-1]), a.dtype))

                def products(xp, stacks, tile_e, used, layer):
                    g = lambda a, w: product(  # noqa: E731
                        a, stacks[w], tile_e, used, layer)
                    if name in RELU2:
                        return g(jnp.square(jax.nn.relu(g(xp, "moe_w1"))),
                                 "moe_w2")
                    return g(jax.nn.silu(g(xp, "moe_w1"))
                             * g(xp, "moe_w3"), "moe_w2")

                def rows_for(w):
                    """Tiles of rows as wide as stack w's matrices are
                    deep."""
                    return jax.random.normal(
                        ks[5], (n_tiles, tm, stacks[w]["q"].shape[-2]),
                        jnp.bfloat16)

                # the tiles in use against the twin's, with `used` at 0
                # (the call has to run: no row of it is read), 1, the
                # router's and every tile
                up = stacks["moe_w1"]
                kernel, twin = jax.jit(product), jax.jit(loop)
                differs = {}
                for u in sorted({0, 1, used, n_tiles}):
                    got, want = (np.asarray(f(
                        xp, up, tile_e, jnp.int32(u), layer)[:u],
                        np.float32) for f in (kernel, twin))
                    differs[u] = float(np.abs(got - want).max(initial=0)
                                       / max(np.abs(want).max(initial=0),
                                             1e-30))
                worst = max(worst, *differs.values())
                grids = {w: _grid(product, rows_for(w), stacks[w], tile_e,
                                  jnp.int32(used), layer) for w in stacks}
                more = dict(
                    tile=tm, static_tiles=n_tiles, tiles_in_use=used,
                    grid=["used" if d is None else d
                          for d in grids["moe_w1"]],
                    grid_steps={w: int(np.prod([
                        max(used, 1) if d is None else d for d in g]))
                        for w, g in grids.items()},
                    differs_by={str(u): d for u, d in differs.items()})
                if rehearsal:
                    row(f"the products alone, tile {tm}", 0.0, **more)
                    continue
                sec, _ = timed(jax.jit(products), xp, stacks, tile_e,
                               jnp.int32(used), layer)
                row(f"the products alone, tile {tm}", sec, ms_each={
                    w: timed(kernel, rows_for(w), stacks[w], tile_e,
                             jnp.int32(used), layer)[0] * 1e3
                    for w in ("moe_w1", "moe_w2")}, **more)
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
    print(f"the kernel against the tile loop in XLA: differs_by at most "
          f"{worst:.3g}", flush=True)
    if not worst <= 0.02:
        print("  THE KERNEL AND ITS TWIN DISAGREE", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
