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

A configuration with linear-attention layers (`gqa_layers`; PR 31: Solar-
Open2 as one of eight chips that share each layer) runs the same script with
other rows and other faults. Slot 1 first serves another tenant (600
tokens), so that it holds a state; the short row (default 2500 tokens, past
every prefill bucket) is then admitted into that slot half way through the
long row's (12000 tokens) chunked prefill, a chunk of its own after each of
the long row's. Faults, each given to the reference: the slot's state NOT
RESET at admission (the reference starts the short row's linear layers from
what the first tenant left), the decay gate off, beta not doubled, the GQA
layers' output gate off, and the expert share offset by the experts held
(another chip's experts); a third row of 96 tokens goes into another slot
that served a tenant, because over 2500 tokens a stale state has decayed
away and only a brief row can tell. Readings (my chip run, PR 31), long /
short row: sound median 0.061 / 0.077, largest 0.161 / 0.129, top-1 0.89 /
0.77; decay gate off 1.13 / 1.11, beta not doubled 0.465 / 0.476, GQA gate
off 1.00 / 1.03, share offset 0.563 / 0.609: the limits above hold for this
configuration too (PERF.md section 6).

A configuration of the `afmoe` architecture (PR 35: Trinity-Large-Preview as
one of eight chips that share each layer) runs the same script against
localai_tpu/testing/reference_afmoe.py with the three rows above (12000
tokens through chunked prefill past the 4096 + 512 ring, 2500 through chunks
of its own, 96 through one chunk; no first tenant: no layer holds a state)
and twelve faults, each given to the reference: the window mask off (the
long row), and on the 2500-token row the full layers rotated, q/k norm off,
the output gate off, softmax for sigmoid scores, the selection bias added
to the weights, the bias left out of the choice, route_scale off, the share
offset by the experts held, the post-norms off, the embedding's scale off,
the leading layer run as an expert layer. Readings (my chip run, PR 35),
12000- / 2500- / 96-token row: sound median 0.0126 / 0.0135 / 0.0165, largest
0.159 / 0.015 / 0.175 (router ties; 0.224 in another run), top-1 0.94 / 0.94
/ 0.97; the reference in bfloat16 products 0.0142 / 0.0150 (the served path
is as far from it as from the float32 one: its 1.3% is int8, not the
products); the faults' medians: window mask off 1.02, full layers rotated
0.093 / 0.040 (largest 0.18 / 0.16, top-1 0.75 / 0.91: the nearest), q/k
norm off 0.147, gate off 0.50, softmax 0.22, route_scale off 0.119, share
offset 0.33, post-norms off 1.16, embedding's scale off 0.83, leading layer
as expert layer 0.81; the bias left out of the choice 0.018 (largest 0.357)
and the bias added to the weights 0.0139 (largest 0.019: no whole-path
reading tells it) are told by the router alone. Limits for this
architecture: MEDIAN_REL_AFMOE 0.025 (between 0.0165 and 0.040; the other
configurations' 0.15 would pass the rotation), WORST_REL and TOP1_SHARE as
above, ROUTER_REL 0.006 (sound 0.00015, the bias in the weights 0.018),
ROUTER_CHOICE 0.15 (sound 0.014, the bias out of the choice 0.74).

A configuration with latent attention (`kv_lora_rank`; PR 40: openPangu-
Ultra-MoE-718B as one of sixteen chips that share each layer) runs the same
script against localai_tpu/testing/reference_pangu.py (NOT absorbed: every
position's keys and values expanded from its latent) with three rows: 7000
tokens through 14 chunks of the expanding path, 300 through a prefill bucket
half way (self-attention over its own expanded rows), 96 through one chunk;
all three then decode through the absorbed kernel over the bfloat16 latent
cache, half the steps in the fused loop. Ten faults, each given to the
reference and read on the 300-token row: k_pe not rotated, RMSNorm_kva left
out, RMSNorm_qa left out, the softmax scale 128^-1/2, routed_scaling_factor
off, the post-norms off, the values read from columns 64-576 of the cached
row, softmax for sigmoid scores, the share offset by the experts held, the
leading layer run as an expert layer. Readings and the limit of this
architecture (MEDIAN_REL_LATENT 0.02, between the sound rows' 0.0112-0.0130
and the nearest fault's 0.0299; WORST_REL and TOP1_SHARE as above): at the
constant below and in PERF.md section 6, PR 40.

A configuration with state-space layers (`hybrid_override_pattern`; PR 42:
Nemotron-3-Super-120B-A12B as one of four chips that share each layer) runs
the same script against localai_tpu/testing/reference_nemotron_h.py (the
published pattern layer by layer, the recurrence a token at a time) with
three rows under the cell's lengths: 4500 tokens through 9 chunks of the
chunked scan, 300 through a prefill bucket half way, 96 through one chunk;
slots 1 and 2 served a 600-token tenant first (the states there are reset at
admission, on the device); all three then decode through the state kernel,
half the steps in the fused loop. Beside the whole path three parts are read
alone, because their faults move the logits by less than a router tie does
(top-22 of 512 on random weights: PERF.md section 6, PR 42): the ROUTER (as
for afmoe: a token's weights over the router's width, and whose chosen
experts differ: top-21 for top-22, x 5 left out, the bias out of the
choice), the STATE of the first state-space layer in the reused slot
(`state_rel`: served against the reference's, walked as deep as that layer:
a state not reset shows), and a state-space layer's RECURRENCE given the
same float32 inputs, through kv.SsmKV's chunks and the decode kernel
(`ssm_alone_rel`: a state kept in bfloat16 shows). Faults given to the
reference: a bfloat16 state, relu for relu^2, the D term dropped, the
convolution's bias dropped, the gate after the norm, top-21, x 5 left out,
the latent projection missing, the bias out of the choice, dt_bias dropped,
the share offset, the state not reset. Limits of this architecture and
their readings: at the constants below and in PERF.md section 6, PR 42.

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
# afmoe (five layers, sandwich norms) reads a median of 0.013-0.017 sound and
# 0.040 under its nearest fault, full layers rotated on the 2500-token row
MEDIAN_REL_AFMOE = 0.025
# the router alone (afmoe; router_reading): a token's weights, and the share
# of tokens whose chosen experts differ
ROUTER_REL, ROUTER_CHOICE = 0.006, 0.15
# latent attention (six layers, sandwich norms, a bfloat16 latent cache; my
# chip run, PR 40), 7000- / 300- / 96-token row: sound median 0.0112 / 0.0125
# / 0.0130 (largest 0.145 / 0.180 / 0.096: router ties; top-1 0.953 / 0.957 /
# 0.984); the reference in bfloat16 products 0.0148 / 0.0165 (no limit tells
# it: the served path is bfloat16 too); the faults' medians on the 300-token
# row: softmax for sigmoid scores 0.0299 (the nearest; largest 0.118, top-1
# 0.884), RMSNorm_qa left out 0.0410, routed_scaling_factor off 0.0784,
# RMSNorm_kva left out 0.109, share offset 0.255, scale 128^-1/2 0.275, k_pe
# not rotated 0.468, post-norms off 0.831, leading layer as expert layer
# 0.974, values from columns 64-576 1.415. The limit: between 0.0130 and
# 0.0299, a factor of 1.5 from each
MEDIAN_REL_LATENT = 0.02
# state-space layers (22 layers, 2 of them int8-KV attention; my chip run,
# PR 42), 4500- / 300- / 96-token row: sound median 0.172 / 0.177 / 0.151,
# largest 0.423 / 0.399 / 0.301, top-1 0.52 / 0.60 / 0.66: ten times the
# other architectures', because top-22 of 512 sigmoid scores on random
# weights are near ties and a changed choice moves a weight of 5 / 22
# (PERF.md section 6, PR 42: at small widths on the CPU in bfloat16 0.098,
# 0.027 with the scale at 1). The faults' medians on the 300-token row: conv
# bias dropped 0.414 (the nearest the whole path has to tell; top-1 0.22),
# gate after the norm 0.454, bias out of the choice 0.460 (top-1 0.27), x 5
# left out 0.578, D dropped 0.592, dt_bias dropped 0.636, relu for relu^2
# 0.674, share offset 0.752, latent projection missing 0.763 (their largest
# 0.49-0.85). MEDIAN_REL_SSM: between 0.177 and 0.414, a factor of 1.5 from
# each; WORST_REL_SSM: between the sound 0.423 and the 0.65 and more of the
# faults it is for; TOP1_SHARE_SSM: between the sound 0.52 and 0.27. Three
# faults move the whole path by less than a router tie does and are told by
# a part read alone: top-21 (median 0.231) by the router (choice differs
# 1.0, weights 0.204: ROUTER_CHOICE, ROUTER_REL as afmoe's; sound 0.049 and
# 0.00026); a state not reset (0.232) by STATE_REL, the reused slot's first
# state-space layer's state through the model (sound 0.041, not reset
# 0.121); a bfloat16 state (0.194) by SSM_ALONE_REL, the recurrence alone
# on the same float32 inputs (sound 0.00002, bfloat16 0.0073)
MEDIAN_REL_SSM, WORST_REL_SSM, TOP1_SHARE_SSM = 0.27, 0.55, 0.35
STATE_REL, SSM_ALONE_REL = 0.07, 0.001
GROUP = 8                   # decode steps a dispatch of the fused loop


T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[reference_check +{time.monotonic() - T0:6.1f}s] {msg}",
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--long", type=int, default=None,
                    help="default 6000; 12000 with linear-attention layers")
    ap.add_argument("--short", type=int, default=None,
                    help="default 300; 2500 with linear-attention layers")
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
    with open(args.config) as f:
        doc = json.load(f)
    hf = {k: v for k, v in doc.items() if k not in NOTES}
    srv = dict(doc["serving"])
    linear = bool(hf.get("linear_attn_config"))
    afmoe = hf.get("model_type") == "afmoe"
    latent = bool(hf.get("kv_lora_rank"))
    ssm = bool(hf.get("hybrid_override_pattern"))
    # a vocabulary near 200 k, 16 k of context: three rows, the short one
    # past every bucket (latent: through one), the head made float32 a slice
    # at a time
    large = linear or afmoe or latent or ssm
    if ssm:
        from localai_tpu.testing import reference_nemotron_h as ref
    elif linear:
        from localai_tpu.testing import reference_linear as ref
    elif afmoe:
        from localai_tpu.testing import reference_afmoe as ref
    elif latent:
        from localai_tpu.testing import reference_pangu as ref
    else:
        from localai_tpu.testing import reference_lm as ref
    args.long = args.long or (7000 if latent else 4500 if ssm
                              else 12000 if large else 6000)
    args.short = args.short or (2500 if large and not (latent or ssm)
                                else 300)
    if args.cpu_rehearsal:
        hf.update(doc["rehearsal"]["geometry"])
        srv.update(doc["rehearsal"]["serving"])
        # small chunks, so that the tiny model's rings wrap too
        srv["prefill_chunk"] = 64
        srv["prefill_buckets"] = [64]
        args.long, args.short, args.steps = 400, 40, 16
        if large and not (latent or ssm):   # the short row too: chunks
            args.short = 150
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
    if large:
        # a third, brief row (with linear layers: into a slot that served a
        # tenant before: over 2500 tokens a stale state has decayed away,
        # over 96 it has not)
        rows[2] = list(rng.integers(8, vocab,
                                    size=30 if args.cpu_rehearsal else 96))
    live = sorted(rows)
    prompt_len = {r: len(ids) for r, ids in rows.items()}
    fits = [b for b in srv["prefill_buckets"] if b >= args.short]
    bucket = min(fits) if fits else None
    greedy = sampler_row(SamplingParams(temperature=0.0), vocab,
                         fallback_seed=1, include_bias=False)
    served: dict = {r: {} for r in rows}   # row -> position -> logits [V]
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

    def chunked(row: int, ids: list, pos: int) -> bool:
        """One chunk of `ids` from `pos` into slot `row`; True at the last."""
        part = ids[pos:pos + chunk]
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :len(part)] = part
        if pos + chunk >= len(ids):
            eng._dev_extend_final(buf, pos, len(part), row, greedy, None)
            return True
        eng._dev_extend_mid(buf, pos, row)
        return False

    first_tenant = None
    if linear or ssm:
        # slot 1 serves another tenant first, so that it holds a state the
        # short row's admission has to reset
        first_tenant = [int(t) for t in rng.integers(8, vocab, size=600
                                                     if not args.cpu_rehearsal
                                                     else 100)]
        for slot in (1, 2):
            for pos in range(0, len(first_tenant), chunk):
                chunked(slot, first_tenant, pos)
    long_ids, short_ids = list(rows[0]), list(rows[1])
    starts = list(range(0, args.long, chunk))
    short_steps, short_pos = 0, None
    for n, pos in enumerate(starts):
        if chunked(0, long_ids, pos):
            note(0)
        if n == len(starts) // 2 and bucket:
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :args.short] = rows[1]
            eng._dev_admit(ids, args.short, 1, greedy, None)
            note(1)
        elif n == len(starts) // 2:
            short_pos = 0       # too long for a bucket: chunks of its own
        elif short_pos is None and len(starts) // 2 < n < len(starts) - 1 \
                and short_steps < args.steps // 4:
            # beside the long row's prefill, and not after its last chunk: a
            # step in which row 0 idles writes an idle row's logits over the
            # ones row 0 is about to pick its first token from
            decode([1])
            short_steps += 1
        if short_pos is not None and short_pos >= 0:
            if chunked(1, short_ids, short_pos):
                note(1)
                short_pos = None
            else:
                short_pos += chunk
    while short_pos is not None:        # the long row ended first
        if chunked(1, short_ids, short_pos):
            note(1)
            short_pos = None
        else:
            short_pos += chunk
    if large:
        chunked(2, [int(t) for t in rows[2]], 0)
        note(2)
    # half of the steps one program a step, the other half inside the fused
    # loop (the program the served path decodes with), GROUP steps a
    # dispatch: the loop hands back its tokens and the last step's logits
    singles = args.steps // 2
    for _ in range(singles):
        decode(live)
    for _ in range((args.steps - singles) // GROUP):
        active = np.zeros((B,), bool)
        active[:len(live)] = True
        remaining = np.zeros((B,), np.int32)
        remaining[:len(live)] = GROUP
        toks, _, n_out, _ = eng._dev_decode_loop(
            active, remaining, np.zeros((B,), bool)).wait()
        for r in live:
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
    served_state = None
    if ssm:
        # the first state-space layer's state of row 2 (the slot that
        # served a tenant before): after every token of the row (a decode
        # step picks a token and feeds it)
        from localai_tpu.models.llama import SSM

        served_state = {2: np.asarray(
            eng._kc.slots[cfg.cache_kinds.index(SSM)][0, 2], np.float32)}

    # free everything but the weights before the reference runs
    for name in ("_kc", "_vc", "_sampler", "_last_logits"):
        setattr(eng, name, None)
    del eng
    rcfg = ref.RefConfig.from_hf(hf)
    # the embedding goes to the host and a comparison takes the rows its
    # tokens name (a float32 copy of a 196608-row embedding beside the
    # float32 head does not fit the chip); the served copies of both are
    # freed once the reference has its own
    embed_host = np.asarray(params["embed"])
    params = dict(params, embed=params["embed"][:1])
    lm_head = params["lm_head"]
    if large:
        # the head stays int8 and is made float32 a slice of the vocabulary
        # at a time (head_of): 196608 columns in float32 are 3.2 GB
        params["lm_head"] = jnp.zeros((cfg.hidden_size, 1), jnp.float32)
    rparams = (ref.from_served(params, rcfg.layer_types) if linear
               else ref.from_served(params, rcfg.pattern) if ssm
               else ref.from_served(params))
    params.pop("lm_head", None)

    def head_of(cfg_v, hidden, precision):
        if not large:
            return ref.head(rparams, cfg_v, hidden, precision=precision)
        with jax.default_matmul_precision(precision):
            if not isinstance(lm_head, dict):
                return hidden @ jnp.asarray(lm_head, jnp.float32)
            q, scale = lm_head["q"], lm_head["s"]
            return jnp.concatenate(
                [hidden @ (q[:, i:i + 24576].astype(jnp.float32)
                           * scale[:, i:i + 24576])
                 for i in range(0, q.shape[1], 24576)], axis=1)

    def hidden_of(ids, cfg, **kw):
        uniq, inv = np.unique(np.asarray(ids), return_inverse=True)
        return ref.hidden_states(
            dict(rparams, embed=embed_host[uniq].astype(np.float32)), cfg,
            inv, block=128 if large else 512, **kw)

    n_prompt = prompt_len

    def compare(row: int, cfg, precision: str = "highest",
                carried=None) -> dict:
        """The served row against one computation of the reference: the
        logits where the served path showed them, and at every position a
        token was picked from, whether the reference would have picked it."""
        t = time.monotonic()
        ids = np.asarray(rows[row])
        kw = {"carried": carried} if carried is not None else {}
        hidden = hidden_of(ids, cfg, precision=precision, **kw)
        picked_at = np.arange(n_prompt[row] - 1, len(ids) - 1)
        want = np.asarray(head_of(cfg, hidden[jnp.asarray(picked_at)],
                                  precision), np.float32)
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
                "rank_max_at_position": int(picked_at[int(np.argmax(rank))]),
                # of the picks made from logits that were shown: the share
                # that are those logits' largest (a greedy sampler's must be)
                "picked_is_served_argmax": float(np.mean(
                    [int(served[row][p].argmax()) == int(ids[p + 1])
                     for p in at if p + 1 < len(ids)])),
                "reference_seconds": time.monotonic() - t}

    in_loop = (args.steps - singles) // GROUP * GROUP
    window = getattr(rcfg, "sliding_window", None)
    # what each planted fault reads like, and one control: the reference is
    # given the fault (the served path stays as it is), so a reading is the
    # distance a served path WITH that fault would show, to first order
    variants = {"sound": (rcfg, "highest", tuple(live))}
    stale = None

    def other_share() -> int:
        """The first expert of another chip's share: offset by those held."""
        held = rcfg.num_experts
        return (rcfg.first_expert + held if rcfg.first_expert == 0
                else rcfg.first_expert - held)

    if not args.sound_only:
        variants["reference_in_bfloat16"] = (rcfg, "bfloat16", (0, 1))
        if linear:
            # what the first tenant left in slot 1's linear layers
            stale = {}
            hidden_of(first_tenant, rcfg, left=stale)
            variants.update({
                "fault_state_not_reset": (rcfg, "highest", (1, 2)),
                "fault_decay_gate_off": (dataclasses.replace(
                    rcfg, linear_decay=False), "highest", (0, 1)),
                "fault_beta_not_doubled": (dataclasses.replace(
                    rcfg, linear_beta_scale=1.0), "highest", (0, 1)),
                "fault_gqa_gate_off": (dataclasses.replace(
                    rcfg, attn_gate=False), "highest", (0, 1)),
                "fault_share_offset": (dataclasses.replace(
                    rcfg, first_expert=other_share()), "highest", (0, 1)),
            })
        if ssm:
            stale = {}
            hidden_of(first_tenant, rcfg, left=stale)
            faults = {
                "relu_for_relu2": dict(squared=False),
                "d_term_dropped": dict(skip_d=False),
                "conv_bias_dropped": dict(conv_bias=False),
                "gate_after_the_norm": dict(gate_before_norm=False),
                "top_k_less_one": dict(
                    experts_per_tok=rcfg.experts_per_tok - 1),
                "routed_scaling_factor_off": dict(route_scale=1.0),
                "latent_projection_missing": dict(latent_in=False),
                "bias_left_out_of_the_choice": dict(bias_in_choice=False),
                "dt_bias_dropped": dict(dt_bias=False),
                "share_offset": dict(first_expert=other_share()),
            }
            variants["fault_bfloat16_state"] = (dataclasses.replace(
                rcfg, state_dtype="bfloat16"), "highest", (0,))
            variants["fault_state_not_reset"] = (rcfg, "highest", (2,))
            variants.update({
                f"fault_{name}": (dataclasses.replace(rcfg, **over),
                                  "highest", (1,))
                for name, over in faults.items()})
        elif afmoe:
            both = (ref.WINDOW, ref.FULL)
            faults = {
                "full_layers_rotated": dict(rotating=both),
                "qk_norm_off": dict(qk_norm=False),
                "output_gate_off": dict(attn_gate=False),
                "softmax_for_sigmoid": dict(scoring="softmax"),
                "bias_added_to_the_weights": dict(bias_in_weights=True),
                "bias_left_out_of_the_choice": dict(bias_in_choice=False),
                "route_scale_off": dict(route_scale=1.0),
                "share_offset": dict(first_expert=other_share()),
                "post_norms_off": dict(post_norms=False),
                "embed_scale_off": dict(embed_scale=1.0),
                "leading_layer_as_expert_layer": dict(leading_dense=False),
            }
            # a row no longer than the window cannot tell the mask; the
            # others are read on the 2500-token row (a fifth of the time)
            variants["fault_window_mask_off"] = (dataclasses.replace(
                rcfg, sliding_window=1 << 30), "highest", (0,))
            variants.update({
                f"fault_{name}": (dataclasses.replace(rcfg, **over),
                                  "highest",
                                  (0, 1) if "rotated" in name else (1,))
                for name, over in faults.items()})
        elif latent:
            faults = {
                "k_pe_not_rotated": dict(rotate_k_pe=False),
                "kv_a_norm_left_out": dict(kv_a_norm=False),
                "q_a_norm_left_out": dict(q_a_norm=False),
                "scale_of_the_nope_width": dict(
                    scale_width=rcfg.qk_nope_head_dim),
                "routed_scaling_factor_off": dict(route_scale=1.0),
                "post_norms_off": dict(post_norms=False),
                "values_from_shifted_columns": dict(
                    value_shift=rcfg.qk_rope_head_dim),
                "softmax_for_sigmoid": dict(scoring="softmax"),
                "share_offset": dict(first_expert=other_share()),
                "leading_layer_as_expert_layer": dict(leading_dense=False),
            }
            variants.update({
                f"fault_{name}": (dataclasses.replace(rcfg, **over),
                                  "highest", (1,))
                for name, over in faults.items()})
        elif window and ref.WINDOW in rcfg.layer_types:
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

    def router_reading(cfg_v) -> dict:
        """The router ALONE, served (models/llama._route as _moe_routed
        calls it, over the first expert layer's router and bias) against
        the reference's `route`, on 512 inputs of unit RMS: per token the
        distance between the two rows of weights over the router's width,
        over the reference's. A selection bias of N(0, 0.02^2) added to the
        weights moves a token's weights by 2% and, through the eighth of
        the experts held here, the logits by a tenth of what int8 KV and
        bfloat16 do: no whole-path reading can see it, this one does."""
        from localai_tpu.models.llama import _route

        stack = params["layers"]["experts"] if ssm else params["layers"]
        lp = {"moe_gate": stack["moe_gate"][0],
              "moe_bias": stack["moe_bias"][0]}
        x = jax.random.normal(jax.random.PRNGKey(args.seed),
                              (512, cfg.hidden_size), jnp.float32)

        def served_router(x, lp):
            w, e = _route(x @ lp["moe_gate"].astype(jnp.float32), lp, cfg)
            return e, w * cfg.routed_scale

        def rows_of(e, w):
            out = np.zeros((x.shape[0], lp["moe_gate"].shape[-1]), np.float32)
            np.put_along_axis(out, np.asarray(e), np.asarray(w), axis=1)
            return out

        got = rows_of(*jax.jit(served_router)(x, lp))
        with jax.default_matmul_precision("highest"):
            want = rows_of(*ref.route(
                x, {"router": lp["moe_gate"], "bias": lp["moe_bias"],
                    "router_bias": lp["moe_bias"]}, cfg_v))
        rel = (np.linalg.norm(got - want, axis=1)
               / np.linalg.norm(want, axis=1))
        return {"router_rel_median": float(np.median(rel)),
                "router_choice_differs": float(np.mean(
                    ((got != 0) != (want != 0)).any(1)))}

    def state_reading(cfg_v, row: int, carried) -> dict:
        """The first state-space layer's state after a row, served (float32,
        made of bfloat16 activations through the chunked scan and the decode
        kernel) against the reference's, walked as deep as that layer only:
        the distance over the reference's norm."""
        first = rcfg.pattern.index("M")
        left: dict = {}
        kw = {"carried": carried} if carried is not None else {}
        hidden_of(rows[row], cfg_v, left=left, depth=first + 1, **kw)
        want = np.asarray(left[first][0], np.float32)
        return {"state_rel": float(np.linalg.norm(served_state[row] - want)
                                   / np.linalg.norm(want))}

    def ssm_alone_reading(cfg_v) -> dict:
        """A state-space layer's recurrence ALONE, served (kv.SsmKV as the
        forwards drive it: chunks of the chunked scan with the state carried
        between them, then single steps through the decode kernel) against
        the reference's token-by-token scan, both given the same float32
        [z | xBC | dt] of the first state-space layer's W_in over random
        inputs: the distance between the two final states over the
        reference's norm. Upstream of a layer's state the whole path's
        bfloat16 activations and router ties move it by a tenth (state_rel);
        here nothing does, and a state kept in bfloat16 shows."""
        from localai_tpu.models import kv as kvm

        first = rcfg.pattern.index("M")
        rlp = rparams["layers"][first]
        lp = jax.tree_util.tree_map(lambda a: a[0], {
            k: v for k, v in params["layers"]["ssm"].items()
            if k in ("conv", "conv_bias", "dt_bias", "A_log", "D")})
        steps = 16 if args.cpu_rehearsal else 64
        total = 3 * chunk + chunk // 2 + steps
        nh, inner = cfg.ssm_heads, cfg.ssm_heads * cfg.ssm_head_dim
        with jax.default_matmul_precision("highest"):
            h = jax.random.normal(jax.random.PRNGKey(args.seed),
                                  (total, cfg.hidden_size), jnp.float32)
            zxd = h @ rlp["w_in"]
            _, (want, _) = ref.mamba(h, rlp, cfg_v)
        xbc, dt = zxd[None, :, inner:-nh], zxd[None, :, -nh:]
        conv = xbc.shape[-1]

        def view(k, v):
            return kvm.SsmKV(k, v, layer=0, heads=nh, groups=cfg.ssm_groups,
                             state=cfg.ssm_state, chunk_size=cfg.ssm_chunk,
                             lp=lp)

        @jax.jit
        def scan_chunk(k, v, u, d, start, n):
            out = view(k, v).chunk(u, d, jnp.array([0]), start, n)[1]
            return out.k, out.v

        @jax.jit
        def scan_step(k, v, u, d):
            out = view(k, v).step(u, d)[1]
            return out.k, out.v

        k = jnp.zeros((1, 1, nh, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32)
        v = jnp.zeros((1, 1, cfg.ssm_conv - 1, conv), jnp.float32)
        for pos in range(0, total - steps, chunk):
            n = min(chunk, total - steps - pos)
            pad = ((0, 0), (0, chunk - n), (0, 0))
            k, v = scan_chunk(k, v, jnp.pad(xbc[:, pos:pos + n], pad),
                           jnp.pad(dt[:, pos:pos + n], pad),
                           jnp.array([pos]), jnp.array([n]))
        for t in range(total - steps, total):
            k, v = scan_step(k, v, xbc[:, t:t + 1], dt[:, t:t + 1])
        got = np.asarray(k[0, 0], np.float32)
        want = np.asarray(want, np.float32)
        return {"ssm_alone_rel": float(np.linalg.norm(got - want)
                                       / np.linalg.norm(want))}

    for name, (cfg_v, precision, which) in variants.items():
        report["readings"][name] = {}
        for row in which:
            r = compare(row, cfg_v, precision,
                        stale if name == "fault_state_not_reset" else None)
            if (afmoe or ssm) and precision == "highest":
                r.update(router_reading(cfg_v))
            if ssm and row == 0 and precision == "highest":
                r.update(ssm_alone_reading(cfg_v))
            if ssm and row in served_state and precision == "highest":
                r.update(state_reading(
                    cfg_v, row,
                    stale if name == "fault_state_not_reset" else None))
            report["readings"][name][str(row)] = r
            say(f"{name} row {row}: {json.dumps(r)}")
    median_rel = (MEDIAN_REL_AFMOE if afmoe else MEDIAN_REL_LATENT if latent
                  else MEDIAN_REL_SSM if ssm else MEDIAN_REL)
    worst_rel, top1_share = ((WORST_REL_SSM, TOP1_SHARE_SSM) if ssm
                             else (WORST_REL, TOP1_SHARE))
    ok = all(r["rel_median"] <= median_rel and r["rel_max"] <= worst_rel
             and r["top1_share"] >= top1_share
             and r.get("router_rel_median", 0.0) <= ROUTER_REL
             and r.get("router_choice_differs", 0.0) <= ROUTER_CHOICE
             and r.get("state_rel", 0.0) <= STATE_REL
             and r.get("ssm_alone_rel", 0.0) <= SSM_ALONE_REL
             for r in report["readings"]["sound"].values())
    caught = {name: any(r["rel_median"] > median_rel
                        or r["rel_max"] > worst_rel
                        or r["top1_share"] < top1_share
                        or r.get("router_rel_median", 0.0) > ROUTER_REL
                        or r.get("router_choice_differs", 0.0) > ROUTER_CHOICE
                        or r.get("state_rel", 0.0) > STATE_REL
                        or r.get("ssm_alone_rel", 0.0) > SSM_ALONE_REL
                        for r in rs.values())
              for name, rs in report["readings"].items()
              if name.startswith("fault_")}
    report["within_limits"] = ok
    report["faults_beyond_limits"] = caught
    report["limits"] = {"median_rel": median_rel, "worst_rel": worst_rel,
                        "top1_share": top1_share, "router_rel": ROUTER_REL,
                        "router_choice": ROUTER_CHOICE,
                        "state_rel": STATE_REL,
                        "ssm_alone_rel": SSM_ALONE_REL}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)
    return 0 if ok and all(caught.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
