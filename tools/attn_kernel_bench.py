#!/usr/bin/env python3
"""The dense decode attention kernel alone, at the cells' shapes, on the chip.

    python tools/attn_kernel_bench.py [--seed N] [--reps 40] [--repo DIR]

What a decode step asks of `ragged_decode_q8`, and nothing else: 32 rows of
an int8 [L, B, KVH, T, D] stack, one call a layer with the layer's index,
the rows' lengths drawn from `--seed` as a cell's traffic would leave them
mid-run (a prompt of the `chat` or `long-short` mix plus a uniform share of
an output; a row that is not decoding keeps its length, as a finished slot
does). Per shape and per `block_k` of the ladder (`-`: what the kernel
picks from the shapes): ms a call and a decode step's worth of calls, the
grid steps a call, GB/s over the bytes the ACTIVE rows hold (K, V and
scales up to each row's length; for a ring, what it holds of its window),
and the largest difference from the XLA twin over the active rows.

`dead=pay` hands the kernel every row's length, as `decode_step` did until
PR 30; `dead=skip` hands it 0 for the rows that are not decoding.
`--repo DIR` imports `localai_tpu` from another checkout (the parent,
unpacked): a kernel without a `block_k` argument runs as it is.

Nothing a cell runs imports this file. It fails without a TPU
(`--cpu-rehearsal`: tiny shapes in the interpreter, which times nothing).
"""
from __future__ import annotations

import argparse
import inspect
import json
import math
import os
import sys
import time

import numpy as np

# (layers, KVH, G, T, ring window, traffic mix, rows decoding of 32)
SHAPES = {
    "mixtral-8x7b-d6": (6, 8, 4, 1536, None, "chat", 20),
    "mellum2.full": (4, 4, 8, 8192, None, "long-short", 27),
    "mellum2.ring": (12, 4, 8, 1536, 1024, "long-short", 27),
}
LADDER = (None, 128, 256, 512, 1024, 2048)
B, D = 32, 128


def _lognormal(rng, n, median, sigma, lo, hi):
    return np.clip(rng.lognormal(math.log(median), sigma, n), lo, hi)


def draw_lengths(mix: str, t: int, ring: bool, rng) -> np.ndarray:
    """[B] context lengths mid-run: benchmark/traffic/<mix>.json's prompt
    and a uniform share of its output, within the served context (a ring's
    rows count every token so far and may pass T)."""
    prompt = _lognormal(rng, B, 256, 0.9, 16, 1536)
    ctx = t
    if mix == "long-short":
        ctx = 8192
        long = _lognormal(rng, B, 4096, 0.2, 16, 7680)
        prompt = np.where(rng.random(B) < 0.3, long, prompt)
    out = _lognormal(rng, B, 128, 0.7, 16, 512) * rng.random(B)
    total = np.minimum(prompt + out, ctx - 2).astype(np.int32)
    return np.maximum(total if ring else np.minimum(total, t - 2), 1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=30)
    ap.add_argument("--reps", type=int, default=40,
                    help="decode steps' worth of calls a timing")
    ap.add_argument("--repo", default=None,
                    help="import localai_tpu from this checkout")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--out", default="chiprun_out/attn_kernel_bench.json")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(
        args.repo or os.path.join(os.path.dirname(__file__), "..")))

    import jax
    import jax.numpy as jnp

    from localai_tpu.models.llama import _decode_dq
    from localai_tpu.ops.kvcache import QuantKV
    from localai_tpu.ops.pallas import ragged_decode_q8

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.cpu_rehearsal:
        print("attn_kernel_bench: no TPU (times come from a chip run only)",
              file=sys.stderr)
        return 2
    takes_block_k = "block_k" in inspect.signature(
        ragged_decode_q8).parameters
    rng = np.random.default_rng(args.seed)
    rows = []
    for name in args.shapes.split(","):
        layers, kvh, g, t, window, mix, n_active = SHAPES[name]
        if args.cpu_rehearsal:
            layers, t = 2, min(t, 1024)
        ring = window is not None
        kw = dict(sliding_window=window, ring=True) if ring else {}
        lengths = draw_lengths(mix, t, ring, rng)
        active = np.zeros(B, bool)
        active[rng.permutation(B)[:n_active]] = True
        held = np.minimum(lengths, window if ring else t)[active].sum()
        held_bytes = int(held) * kvh * 2 * (D + 4)      # K, V: int8 + f32 scale
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        shape = (layers, B, kvh, t, D)

        def pool(key):
            kq, ks = jax.random.split(key)
            return QuantKV(
                jax.random.randint(kq, shape, -127, 128, jnp.int8),
                jax.random.uniform(ks, (layers, B, kvh, t // 128, 128),
                                   jnp.float32, 0.005, 0.02))

        k, v = pool(keys[0]), pool(keys[1])
        q = jax.random.normal(keys[2], (B, 1, kvh * g, D), jnp.bfloat16)
        want = np.asarray(_decode_dq(q, k[0], v[0], jnp.asarray(lengths),
                                     **kw), np.float32)[active]
        for dead in ("pay", "skip"):
            lens = jnp.asarray(lengths if dead == "pay"
                               else np.where(active, lengths, 0))
            for bk in LADDER if takes_block_k else (None,):
                if bk is not None and (t % bk or 4 * kvh * bk * D > 8 << 20):
                    continue
                bkw = dict(kw, block_k=bk) if bk is not None else kw

                @jax.jit
                def steps(q, k, v, lens):
                    def body(i, acc):
                        out = ragged_decode_q8(q, k.q, k.s, v.q, v.s, lens,
                                               layer=i % layers, **bkw)
                        return acc + out.astype(jnp.float32)
                    return jax.lax.fori_loop(
                        0, args.reps * layers, body,
                        jnp.zeros(q.shape, jnp.float32))

                got = np.asarray(ragged_decode_q8(
                    q, k.q, k.s, v.q, v.s, lens, layer=jnp.int32(0), **bkw),
                    np.float32)[active]
                steps(q, k, v, lens).block_until_ready()
                t0 = time.perf_counter()
                steps(q, k, v, lens).block_until_ready()
                call_s = (time.perf_counter() - t0) / (args.reps * layers)
                used = bk
                if bk is None and takes_block_k:
                    from localai_tpu.ops.pallas.flash_attention import \
                        _dense_block_k
                    used = _dense_block_k(t, kvh, D, 1)
                rows.append({
                    "shape": name, "dead": dead, "block_k": bk or "-",
                    "block_k_used": used, "ms_call": call_s * 1e3,
                    "ms_step": call_s * 1e3 * layers,
                    # before PR 30: one head and 128 tokens a grid step
                    "grid_steps_call": (B * (t // used) if used
                                        else B * kvh * (t // 128)),
                    "gb_s_active": held_bytes / call_s / 1e9,
                    "active_rows": n_active, "active_tokens": int(held),
                    "max_err": float(np.abs(got - want).max()),
                })
                print(json.dumps(rows[-1]), flush=True)
    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "timed": on_tpu, "seed": args.seed, "repo": args.repo or ".",
              "rows": rows}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print("| shape | dead rows | block_k | ms a call | ms a step | grid steps "
          "a call | GB/s (active rows) | max err |\n" + "| --- " * 8 + "|")
    for r in rows:
        ms = (f"{r['ms_call']:.4f} | {r['ms_step']:.3f}" if on_tpu
              else "not measured | not measured")
        gbs = f"{r['gb_s_active']:.1f}" if on_tpu else "not measured"
        print(f"| {r['shape']} | {r['dead']} | {r['block_k']} "
              f"({r['block_k_used']}) | {ms} | {r['grid_steps_call']} | "
              f"{gbs} | {r['max_err']:.4f} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
