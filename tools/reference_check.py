#!/usr/bin/env python3
"""A served configuration against the plain float32 reference, on the chip.

    python tools/reference_check.py --config benchmark/configs/<name>.json

One process: the engine's own compiled programs for the configuration as
it is served (the file's `serving`: synthetic int8 weights, int8 KV, every
slot, the whole context), driven as the engine drives them. A long prompt
goes through chunked prefill (past the window layers' ring, where the model
has one), a short one through a prefill bucket while the long one is half
way, the short row decodes beside the long one's last chunks (a decode step
with an inactive row in it), then both rows decode together. The logits at
each row's last prompt position and after every decode step are compared
with localai_tpu/testing/reference_lm.py on the same token ids: the
reference gets the dequantised weights and is computed after the caches are
freed, a layer and a block of query positions at a time, so that it fits.

What the served path adds to the reference's float32 is bfloat16
activations, int8 KV read as bfloat16 and, on random weights, a near tie in
the router now and then (another expert with about the same weight).
Half of the decode steps run one program a step, the other half inside the
fused loop (`_dev_decode_loop`, 8 steps a dispatch), which hands back its
tokens and the last step's logits. Reported per row: the relative error
|served - reference| / |reference| (L2 over the vocabulary) wherever the
served path showed its logits, and, at every position a token was picked
from (greedy), whether the reference would have picked it (`top1_share`)
or had it among its five best (`top5_share`).

What a fault reads like is measured, not argued: the same comparison is
made against the reference GIVEN the fault (the served path stays as it
is), which is the distance a served path with that fault would show, to
first order. Readings (my chip run, PR 27: 6000- and 300-token rows, 64 and
69 picked tokens, 32 of each in the fused loop), long row / short row:

    reading                         rel median     rel max        top-1        top-5
    sound                           0.058 / 0.044  0.130 / 0.101  0.98 / 0.74  1.00 / 1.00
    served against the reference
      in bfloat16 products          0.055 / 0.045  0.130 / 0.106  0.98 / 0.75  1.00 / 0.97
    window mask off                 1.012 / -      1.021 / -      0.00 / -     0.00 / -
    whole ring read (1024 + 512)    0.669 / -      0.690 / -      0.00 / -     0.17 / -
    window layers, full layers'
      RoPE (YaRN)                   0.635 / 0.382  0.646 / 0.407  0.14 / 0.14  0.84 / 0.43

(a row no longer than the window cannot tell the first two). At the prompt's
end, before any int8 KV is read back, the sound rows read 0.028 / 0.021.
The served path is as far from the reference in bfloat16 products as from
the float32 one: its 4 to 6% is not the products' precision (int8 KV and
router ties are what is left). Limits (exit code 1 when the sound reading is
beyond one, or a fault is within all of them), each between the sound
reading and the nearest fault's:

- MEDIAN_REL 0.15: sound 0.058, the nearest fault 0.382.
- WORST_REL 0.25: sound 0.130 (position 6001, a router tie), the nearest
  fault 0.407 (and 0.373 at its prompt's end).
- TOP1_SHARE 0.5: sound 0.74 (the short row: random weights leave the two
  best logits close), the nearest fault 0.14. `top5_share` is reported and
  has no limit: the RoPE fault leaves it at 0.84 on the long row.

Not planted: a window one token short or long (1 key of 1024 under nearly
uniform attention moves nothing any metric here could see).

`--cpu-rehearsal` runs the same script on the configuration's tiny
`rehearsal` geometry on the CPU: it proves the script, and that the served
path is the reference's mathematics (float32, tight), never a speed.
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
NOTES = ("source", "reduced", "published", "assumed", "deployment", "serving",
         "rehearsal")                       # benchmark/harness/server.py
MEDIAN_REL, WORST_REL, TOP1_SHARE = 0.15, 0.25, 0.5
GROUP = 8                   # decode steps a dispatch of the fused loop


T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[reference_check +{time.monotonic() - T0:6.1f}s] {msg}",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--long", type=int, default=6000)
    ap.add_argument("--short", type=int, default=300)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--seed", type=int, default=27)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--sound-only", action="store_true",
                    help="skip the planted faults and the bfloat16 control")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "reference_check.json"))
    args = ap.parse_args()

    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.ops.sampling import SamplingParams, sampler_row
    from localai_tpu.testing import reference_lm as ref

    with open(args.config) as f:
        doc = json.load(f)
    hf = {k: v for k, v in doc.items() if k not in NOTES}
    srv = dict(doc["serving"])
    if args.cpu_rehearsal:
        hf.update(doc["rehearsal"]["geometry"])
        srv.update(doc["rehearsal"]["serving"])
        # small chunks, so that the tiny model's rings wrap too
        srv["prefill_chunk"] = 64
        srv["prefill_buckets"] = [64]
        args.long, args.short, args.steps = 400, 40, 16
    elif jax.default_backend() != "tpu":
        print("no TPU here: run it through the chip tool, or rehearse with "
              "--cpu-rehearsal", file=sys.stderr)
        return 1
    hf["localai_synthetic"] = True
    work = os.path.join(ROOT, ".bench_work", "reference_check")
    os.makedirs(work, exist_ok=True)
    with open(os.path.join(work, "config.json"), "w") as f:
        json.dump(hf, f)

    cfg = load_config(work, dtype=srv["dtype"])
    params = load_params(work, cfg, dtype=srv["dtype"])
    jax.block_until_ready(params)
    chunk = min(srv.get("prefill_chunk", 512), srv["context_size"])
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=srv["parallel"], max_context=srv["context_size"],
        prefill_buckets=tuple(srv["prefill_buckets"]), prefill_chunk=chunk,
        cache_type=srv["cache_type_k"], kv_pages=srv.get("kv_pages", 0)))
    dev = jax.devices()[0]
    say(f"{os.path.basename(args.config)} on {dev.platform} "
        f"{dev.device_kind!r}: {cfg.num_layers} layers "
        f"{cfg.period or 'of one kind'}, {srv['parallel']} slots x "
        f"{srv['context_size']}, chunk {chunk}; engine metrics "
        f"{ {k: v for k, v in eng.metrics.items() if '__' in k and ('kv_' in k or 'layers' in k)} }")

    rng = np.random.default_rng(args.seed)
    vocab = cfg.vocab_size
    rows = {0: list(rng.integers(8, vocab, size=args.long)),
            1: list(rng.integers(8, vocab, size=args.short))}
    bucket = min(b for b in srv["prefill_buckets"] if b >= args.short)
    greedy = sampler_row(SamplingParams(temperature=0.0), vocab,
                         fallback_seed=1, include_bias=False)
    served: dict = {0: {}, 1: {}}      # row -> position -> logits [V]
    B = srv["parallel"]

    def note(row: int):
        pos = len(rows[row]) - 1
        served[row][pos] = np.asarray(eng._last_logits[row], np.float32)

    def decode(active_rows):
        active = np.zeros((B,), bool)
        active[list(active_rows)] = True
        tokens, _ = eng._dev_decode(active).wait()
        for r in active_rows:
            rows[r].append(int(tokens[r]))
            note(r)

    long_ids = list(rows[0])
    starts = list(range(0, args.long, chunk))
    short_steps = 0
    for n, pos in enumerate(starts):
        part = long_ids[pos:pos + chunk]
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :len(part)] = part
        if pos + chunk >= args.long:
            eng._dev_extend_final(buf, pos, len(part), 0, greedy, None)
            note(0)
        else:
            eng._dev_extend_mid(buf, pos, 0)
        if n == len(starts) // 2:
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :args.short] = rows[1]
            eng._dev_admit(ids, args.short, 1, greedy, None)
            note(1)
        elif n > len(starts) // 2 and short_steps < args.steps // 4:
            decode([1])                 # beside the long row's prefill
            short_steps += 1
    # half of the steps one program a step, the other half inside the fused
    # loop (the program the served path decodes with), GROUP steps a
    # dispatch: the loop hands back its tokens and the last step's logits
    singles = args.steps // 2
    for _ in range(singles):
        decode([0, 1])
    for _ in range((args.steps - singles) // GROUP):
        active = np.zeros((B,), bool)
        active[:2] = True
        remaining = np.zeros((B,), np.int32)
        remaining[:2] = GROUP
        toks, _, n_out, _ = eng._dev_decode_loop(
            active, remaining, np.zeros((B,), bool)).wait()
        for r in (0, 1):
            assert int(n_out[r]) == GROUP, (r, n_out)
            rows[r].extend(int(t) for t in np.asarray(toks)[:GROUP, r])
            note(r)
    say(f"served: row 0 {args.long} prompt + {len(rows[0]) - args.long} "
        f"tokens, row 1 {args.short} + {len(rows[1]) - args.short} "
        f"({short_steps} of them beside row 0's prefill, the last "
        f"{(args.steps - singles) // GROUP * GROUP} of each in the fused "
        f"loop); "
        f"{ {k: v for k, v in eng.metrics.items() if k.startswith('decode_')} }")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    # free everything but the weights before the reference runs
    for name in ("_kc", "_vc", "_sampler", "_last_logits"):
        setattr(eng, name, None)
    del eng
    rcfg = ref.RefConfig.from_hf(hf)
    rparams = ref.from_served(params)
    n_prompt = {0: args.long, 1: args.short}

    def compare(row: int, cfg, precision: str = "highest") -> dict:
        """The served row against one computation of the reference: the
        logits where the served path showed them, and at every position a
        token was picked from, whether the reference would have picked it."""
        t = time.monotonic()
        ids = np.asarray(rows[row])
        hidden = ref.hidden_states(rparams, cfg, ids, block=512,
                                   precision=precision)
        picked_at = np.arange(n_prompt[row] - 1, len(ids) - 1)
        want = np.asarray(ref.head(rparams, cfg, hidden[
            jnp.asarray(picked_at)], precision=precision), np.float32)
        order = np.argsort(-want, axis=1)
        picked = ids[picked_at + 1]
        rank = np.array([int(np.nonzero(order[i] == picked[i])[0][0])
                         for i in range(len(picked))])
        at = sorted(served[row])
        got = np.stack([served[row][p] for p in at])
        mine = want[np.searchsorted(picked_at, [p for p in at
                                                if p <= picked_at[-1]])]
        got = got[:len(mine)]       # the very last logits picked no token
        norm = np.linalg.norm(mine, axis=1)
        rel = np.linalg.norm(got - mine, axis=1) / norm
        return {"prompt_tokens": n_prompt[row], "logit_positions": len(rel),
                "rel_at_prompt_end": float(rel[0]),
                "rel_median": float(np.median(rel)),
                "rel_max": float(rel.max()),
                "rel_max_at_position": int(at[int(np.argmax(rel))]),
                "abs_max": float(np.abs(got - mine).max()),
                "logit_abs_max": float(np.abs(mine).max()),
                "picked_positions": len(picked),
                "top1_share": float(np.mean(rank == 0)),
                "top5_share": float(np.mean(rank < 5)),
                "top5_share_in_loop": float(np.mean(rank[-in_loop:] < 5)),
                "rank_max": int(rank.max()),
                "reference_seconds": time.monotonic() - t}

    in_loop = (args.steps - singles) // GROUP * GROUP
    window = rcfg.sliding_window
    # what each planted fault reads like, and one control: the reference is
    # given the fault (the served path stays as it is), so a reading is the
    # distance a served path WITH that fault would show, to first order
    variants = {"sound": (rcfg, "highest", (0, 1))}
    if not args.sound_only:
        variants["reference_in_bfloat16"] = (rcfg, "bfloat16", (0, 1))
        if window and ref.WINDOW in rcfg.layer_types:
            swapped = dict(rcfg.rope)
            swapped[ref.WINDOW] = rcfg.rope[ref.FULL]
            variants.update({
                # rows no longer than the window cannot tell these two
                "fault_window_mask_off": (dataclasses.replace(
                    rcfg, sliding_window=1 << 30), "highest", (0,)),
                "fault_whole_ring_read": (dataclasses.replace(
                    rcfg, sliding_window=window + chunk), "highest", (0,)),
                "fault_window_layers_full_rope": (dataclasses.replace(
                    rcfg, rope=swapped), "highest", (0, 1)),
            })
    report = {"config": args.config,
              "device": [dev.platform, dev.device_kind],
              "peak_bytes_in_use": peak, "steps_in_fused_loop": in_loop,
              "readings": {}}
    for name, (cfg_v, precision, which) in variants.items():
        report["readings"][name] = {}
        for row in which:
            r = compare(row, cfg_v, precision)
            report["readings"][name][str(row)] = r
            say(f"{name} row {row}: {json.dumps(r)}")
    ok = all(r["rel_median"] <= MEDIAN_REL and r["rel_max"] <= WORST_REL
             and r["top1_share"] >= TOP1_SHARE
             for r in report["readings"]["sound"].values())
    caught = {name: any(r["rel_median"] > MEDIAN_REL
                        or r["rel_max"] > WORST_REL
                        or r["top1_share"] < TOP1_SHARE
                        for r in rs.values())
              for name, rs in report["readings"].items()
              if name.startswith("fault_")}
    report["within_limits"] = ok
    report["faults_beyond_limits"] = caught
    report["limits"] = {"median_rel": MEDIAN_REL, "worst_rel": WORST_REL,
                        "top1_share": TOP1_SHARE}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)
    return 0 if ok and all(caught.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
