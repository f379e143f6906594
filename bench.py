"""Serving benchmark — the measured answer to BASELINE.md (reference publishes
no numbers; protocol = median of >=5 timed windows after warmup).

Default mode measures THE SERVING PATH: a real backend subprocess spawned by
the ModelManager, driven over gRPC PredictStream — the same surface an HTTP
request rides (BASELINE.md configs #2/#3 ask for the served path, not an
in-process loop). `--mode engine` keeps the in-process Engine measurement.

The flagship geometry is `8b` (Llama-3.1-8B); bf16 8B does not fit a 16GB
v5e chip, so 8b defaults to int8 weights (the GGUF-quant-analog path the
reference's llama.cpp backend also serves with). Checkpoints are synthetic:
config.json declares the geometry and the loader inits weights on device
(engine/loader.py _synthetic_params) — measuring compute, not disk.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
vs_baseline is value / 1000 tok/s/chip — the BASELINE.md north star.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time


SIZES = {
    # geometry dicts are HF config.json bodies (synthetic checkpoints)
    "tiny": dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=32,
                 max_position_embeddings=512, tie_word_embeddings=True),
    "1b": dict(vocab_size=128256, hidden_size=2048, intermediate_size=8192,
               num_hidden_layers=16, num_attention_heads=32,
               num_key_value_heads=8, head_dim=64,
               max_position_embeddings=4096, rope_theta=500000.0,
               tie_word_embeddings=True),
    "3b": dict(vocab_size=128256, hidden_size=3072, intermediate_size=8192,
               num_hidden_layers=28, num_attention_heads=24,
               num_key_value_heads=8, head_dim=128,
               max_position_embeddings=4096, rope_theta=500000.0,
               tie_word_embeddings=True),
    "8b": dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
               num_hidden_layers=32, num_attention_heads=32,
               num_key_value_heads=8, head_dim=128,
               max_position_embeddings=8192, rope_theta=500000.0,
               tie_word_embeddings=False),
}


def note(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def dispatch_stats(metrics: dict) -> dict:
    """Dispatch-fusing scoreboard fields from an engine metrics map (local
    dict or the GetMetrics RPC payload): decode dispatch count, fused
    steps/dispatch, and host-sync wait per generated token. These are the
    numbers the single-dispatch decode loop moves — promoted into the bench
    JSON line so the scoreboard can gate on them."""
    d = int(metrics.get("decode_dispatches", 0))
    s = int(metrics.get("decode_steps_dispatched", 0))
    toks = int(metrics.get("tokens_generated", 0))
    wait = float(metrics.get("host_sync_wait_ms", 0.0))
    return {
        "decode_dispatches": d,
        "decode_steps_dispatched": s,
        "steps_per_dispatch": round(s / max(d, 1), 2),
        "host_sync_wait_ms_per_token": round(wait / max(toks, 1), 4),
    }


def slo_stats(metrics: dict) -> dict:
    """SLO scoreboard fields (ttft_p95_ms / tpot_p50_ms / queue_wait_p50_ms)
    rebuilt from the engine's own streaming histograms (`hist_*` GetMetrics
    keys or the in-process registry's flat() map) — engine-measured, not a
    host stopwatch around the RPC (ISSUE 11)."""
    try:
        from localai_tpu.telemetry import parse_flat, snapshot_from_hists

        snap = snapshot_from_hists(parse_flat(metrics))
    except Exception:
        return {}
    out = {}
    ttft = snap.get("ttft") or {}
    tpot = snap.get("tpot") or {}
    qw = snap.get("queue_wait") or {}
    if ttft.get("count"):
        out["ttft_p95_ms"] = round(ttft["p95_ms"], 3)
    if tpot.get("count"):
        out["tpot_p50_ms"] = round(tpot["p50_ms"], 4)
    if qw.get("count"):
        out["queue_wait_p50_ms"] = round(qw["p50_ms"], 4)
    return out


def sched_base(eng):
    """Snapshot an engine's tick-ledger counters + token count before the
    measured windows, so sched_stats can report window deltas (the compile
    bursts between warmup and the windows also dispatch)."""
    sched = getattr(eng, "_sched", None)
    if sched is None:
        return None
    return (dict(sched.counters), dict(sched.variants),
            int(eng.metrics.get("tokens_generated", 0)))


def sched_stats(eng, base=None, *, toks_per_s=0.0, device_kind="",
                chips=1) -> dict:
    """Scheduler X-ray scoreboard fields (ISSUE 13) from a live engine:
    tick-ledger aggregates (pad-row fraction, reason-
    code counts, per-variant dispatch counts — deltas vs `base` when given)
    plus the per-variant cost-analysis rooflines. When a throughput is
    given, also computes the cost-backed `mfu`: measured tok/s times the
    XLA-modeled FLOPs per generated token (sum of each variant's compiled
    cost weighted by its dispatch count), over the chip peak — replacing
    the old 2*N*tokens guess. rooflines() runs AFTER the measured windows
    (AOT lowering is off the timed path and never touches the jit cache)."""
    sched = getattr(eng, "_sched", None)
    if sched is None:
        return {}
    try:
        roofs = eng.rooflines()
    except Exception:
        roofs = {}
    c0, v0, t0 = base or ({}, {}, 0)
    reasons = {k: n - c0.get(k, 0) for k, n in sched.counters.items()
               if n - c0.get(k, 0)}
    variants = {k: n - v0.get(k, 0) for k, n in sched.variants.items()
                if n - v0.get(k, 0)}
    toks = int(eng.metrics.get("tokens_generated", 0)) - t0
    out = {
        "pad_rows_frac": round(sched.pad_rows_frac(), 4),
        "reason_codes": reasons,
        "sched_variants": variants,
    }
    if roofs:
        out["rooflines"] = {
            name: {"cost_flops": r.get("cost_flops", 0.0),
                   "cost_bytes": r.get("cost_bytes", 0.0),
                   **({"bound": r["bound"],
                       "mfu_ceiling": round(r["mfu"], 4)}
                      if "mfu" in r else {})}
            for name, r in roofs.items()}
        flops = sum((roofs.get(v) or {}).get("cost_flops", 0.0) * n
                    for v, n in variants.items())
        from localai_tpu.system.capabilities import CHIPS

        chip = CHIPS.get(device_kind)   # unknown device: no mfu at all
        if chip and flops > 0 and toks > 0 and toks_per_s > 0:
            peak = chip.bf16_flops * max(chips, 1)
            out["mfu"] = round(toks_per_s * (flops / toks) / peak, 4)
    return out


def ensure_virtual_devices(n: int) -> None:
    """Force an n-device CPU host platform (mode tp's virtual mesh). Must run
    BEFORE jax initializes — XLA_FLAGS is read when the CPU client is
    created; an existing forced count (e.g. the test harness's 8) wins."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def build_tp_mesh(tp: int):
    """('data'=1, 'model'=tp) mesh over the first tp devices."""
    import jax

    from localai_tpu.parallel.mesh import MeshConfig, build_mesh

    return build_mesh(MeshConfig(data=1, model=tp), jax.devices()[:tp])


def write_synthetic_checkpoint(size: str, path: str) -> str:
    body = dict(SIZES[size])
    body.update(architectures=["LlamaForCausalLM"], rms_norm_eps=1e-5,
                localai_synthetic=True)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as fh:
        json.dump(body, fh)
    return path


def param_count(size: str) -> int:
    g = SIZES[size]
    h, i = g["hidden_size"], g["intermediate_size"]
    L, v = g["num_hidden_layers"], g["vocab_size"]
    hd = g.get("head_dim") or h // g["num_attention_heads"]
    qk = g["num_attention_heads"] * hd
    kv = g["num_key_value_heads"] * hd
    per_layer = h * qk + 2 * h * kv + qk * h + 3 * h * i + 2 * h
    return v * h * (1 if g.get("tie_word_embeddings") else 2) + L * per_layer + h


# --------------------------------------------------------------- serve mode

def served_device(handle, on_cpu: bool) -> str:
    """`device_kind` of a served run, from the backend's own device report
    (core/manager.py keeps it on the handle). Roles that report none
    (whisper) get the platform this run pinned through JAX_PLATFORMS."""
    return handle.device.get("device_kind") or ("cpu" if on_cpu else "tpu")


def bench_serve(args, size: str, on_cpu: bool):
    """Measure through the real process boundary: ModelManager-spawned gRPC
    backend, PredictStream per request (what /v1/chat/completions rides)."""
    import numpy as np

    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager

    tmp = tempfile.mkdtemp(prefix="bench-ckpt-")
    ckpt = write_synthetic_checkpoint(size, os.path.join(tmp, size))
    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"  # inherited by the backend
    dtype = args.dtype or ("int8" if size == "8b" else "bfloat16")
    if on_cpu:
        dtype = args.dtype or "float32"
    context = min(args.context, SIZES[size]["max_position_embeddings"])

    if args.tensor_parallel > 1 and on_cpu:
        # the backend subprocess inherits os.environ — give it the virtual
        # devices the requested mesh needs
        ensure_virtual_devices(args.tensor_parallel)
    mcfg = ModelConfig.from_dict({
        "name": f"bench-{size}",
        "backend": "llm",
        "context_size": context,
        "parallel": args.slots,
        "dtype": dtype,
        # int8 KV on the quantized-weight geometries: the llama.cpp analog
        # (cache_type q8_0) and what makes high slot counts fit HBM
        "cache_type_k": "int8" if dtype in ("int8", "int4") else "",
        "kv_pages": args.kv_pages,
        "prefill_buckets": [128, min(512, context)],
        "mesh": ({"data": 1, "model": args.tensor_parallel}
                 if args.tensor_parallel > 1 else {}),
        "parameters": {"model": ckpt},
    })
    app = AppConfig(models_path=tmp, parallel_requests=args.slots)
    manager = ModelManager(app)
    note(f"spawning backend subprocess (size={size} dtype={dtype} "
         f"slots={args.slots} ctx={context})...")
    t0 = time.perf_counter()
    handle = manager.load(mcfg)
    note(f"backend ready in {time.perf_counter() - t0:.1f}s")
    args.device_kind = served_device(handle, on_cpu)
    vocab = SIZES[size]["vocab_size"]
    seed_counter = iter(range(1, 1 << 30))
    seed_lock = threading.Lock()

    def stream(n_tokens, arrivals=None):
        """One PredictStream request; returns (first_token_t, tokens).
        Each call owns a fresh Generator — np Generators are not
        thread-safe and the steady-state windows run these concurrently."""
        with seed_lock:
            seed = next(seed_counter)
        rng = np.random.default_rng(seed)
        ids = rng.integers(1, vocab, args.prompt_len).tolist()
        first, n = None, 0
        for reply in handle.client.predict_stream(
                prompt_ids=ids, tokens=n_tokens, temperature=0.8, top_k=40,
                seed=seed, ignore_eos=True,
                timeout=3600.0):
            now = time.perf_counter()
            if reply.token_ids:  # token event (synthetic ckpts have no text)
                n += 1
                if first is None:
                    first = now
                if arrivals is not None:
                    arrivals.append(now)
        return first, n

    try:
        # warmup: compile prefill buckets + decode step through the wire
        t0 = time.perf_counter()
        ws = [threading.Thread(target=stream, args=(4,))
              for _ in range(min(2, args.slots))]
        [t.start() for t in ws]
        [t.join() for t in ws]
        stream(4)
        note(f"warmup (compile) done in {time.perf_counter() - t0:.1f}s")

        # TTFT: single request against the idle engine, through gRPC
        ttfts = []
        for _ in range(args.windows):
            t0 = time.perf_counter()
            first, _ = stream(2)
            ttfts.append((first - t0) * 1e3)
        ttft_ms = statistics.median(ttfts)
        note(f"ttft p50 {ttft_ms:.1f}ms over {args.windows} runs")

        # steady-state: all slots streaming concurrently; measure the window
        # where every stream is live (max of firsts .. min of lasts)
        tput = []
        for w in range(args.windows):
            arrivals_per = [[] for _ in range(args.slots)]
            threads = [
                threading.Thread(target=stream,
                                 args=(args.decode_steps, arrivals_per[i]))
                for i in range(args.slots)
            ]
            t0 = time.perf_counter()
            [t.start() for t in threads]
            [t.join() for t in threads]
            wall = time.perf_counter() - t0
            all_arr = sorted(a for arr in arrivals_per for a in arr)
            lo = max(arr[0] for arr in arrivals_per if arr)
            hi = min(arr[-1] for arr in arrivals_per if arr)
            in_window = [a for a in all_arr if lo <= a <= hi]
            if hi > lo and len(in_window) > args.slots:
                tput.append((len(in_window) - 1) / (hi - lo))
            else:  # degenerate window; fall back to wall-clock rate
                tput.append(len(all_arr) / wall)
            note(f"window {w}: {tput[-1]:.1f} tok/s "
                 f"({len(all_arr)} tokens, wall {wall:.1f}s)")
        stats = {}
        try:
            m = handle.client.metrics()
            args.slo_metrics = m   # hist_* keys → emit_result's slo_stats
            stats = dispatch_stats(m)
            d, s = m.get("decode_dispatches", 0), m.get(
                "decode_steps_dispatched", 0)
            note(f"engine: {d:.0f} decode dispatches, {s:.0f} steps "
                 f"({s / max(d, 1):.1f} steps/dispatch), "
                 f"{m.get('admit_dispatches', 0):.0f} admit dispatches, "
                 f"host-sync wait "
                 f"{stats['host_sync_wait_ms_per_token']:.3f} ms/token")
        except Exception:
            pass
        if getattr(args, "trace", False):
            try:   # pull the spans before the backend dies
                args.trace_payload = handle.client.trace()
            except Exception as e:
                note(f"trace fetch failed: {e}")
        return statistics.median(tput), ttft_ms, context, dtype, stats
    finally:
        manager.stop_all()
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


# --------------------------------------------------------------- engine mode

def bench_engine(args, size: str, on_cpu: bool, kv_pages: int | None = None,
                 tp: int | None = None):
    """In-process Engine measurement (no RPC overhead) — kernel ceiling.
    `tp` > 1 runs the same workload on a (1, tp) tensor-parallel mesh
    (weights — int8 included — and KV heads sharded on 'model')."""
    import jax
    import numpy as np

    from localai_tpu.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.ops.sampling import SamplingParams

    tp = args.tensor_parallel if tp is None else tp
    mesh = build_tp_mesh(tp) if tp and tp > 1 else None
    tmp = tempfile.mkdtemp(prefix="bench-ckpt-")
    ckpt = write_synthetic_checkpoint(size, os.path.join(tmp, size))
    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    dtype = args.dtype or ("int8" if size == "8b" else "bfloat16")
    if on_cpu:
        dtype = args.dtype or "float32"
    cfg = load_config(ckpt, dtype=dtype)
    context = min(args.context, cfg.max_position)
    params = load_params(ckpt, cfg, dtype=dtype, mesh=mesh)
    jax.block_until_ready(params)
    note("params initialized" + (f" (sharded over 1x{tp} mesh)" if mesh
                                 else ""))

    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=args.slots, max_context=context,
        prefill_buckets=(128, min(512, context)),
        prefill_chunk=min(512, context),
        mesh=mesh,
        # mirror bench_serve's KV config (was silently dense-bf16 before:
        # 32-slot engine-mode runs OOM'd at admit compile)
        cache_type="int8" if dtype in ("int8", "int4") else "",
        kv_pages=args.kv_pages if kv_pages is None else kv_pages,
        # A/B the single-dispatch decode loop (None = engine default 64;
        # 0 regresses to the scan-block ladder for comparison runs)
        **({} if args.decode_loop is None
           else {"decode_loop": args.decode_loop}),
    ))
    rng = np.random.default_rng(0)

    def req(n_tokens):
        return GenRequest(
            prompt_ids=rng.integers(1, cfg.vocab_size, args.prompt_len).tolist(),
            params=SamplingParams(temperature=0.8, top_k=40,
                                  seed=int(rng.integers(1 << 30))),
            max_tokens=n_tokens, ignore_eos=True)

    # pre-compile the decode-loop variants + remaining ladder widths NOW so
    # window 0 measures steady-state, not mid-stream XLA compiles (the old
    # warmup compiled only the shapes its own short requests happened to hit)
    t0 = time.perf_counter()
    eng.warmup()
    note(f"decode programs pre-compiled in {time.perf_counter() - t0:.1f}s")
    sbase = sched_base(eng)   # ledger just reset; aligns the token counter

    t0 = time.perf_counter()
    for _ in range(args.slots):
        eng.submit(req(4))
    while eng.step():
        pass
    # a lone request admits through the K=1 program — compile it now or the
    # first TTFT probe pays the compile (serve-mode warmup already does this)
    eng.submit(req(4))
    while eng.step():
        pass
    note(f"warmup (compile) done in {time.perf_counter() - t0:.1f}s")

    ttfts = []
    for _ in range(args.windows):
        rid, out = eng.submit(req(2))
        t0 = time.perf_counter()
        while out.empty():
            eng.step()
        ttfts.append((time.perf_counter() - t0) * 1e3)
        while eng.step():
            pass
    ttft_ms = statistics.median(ttfts)
    note(f"ttft done: {ttft_ms:.1f}ms")

    tput = []
    for _ in range(args.windows):
        for _ in range(args.slots):
            eng.submit(req(args.decode_steps))
        while not all(s is not None for s in eng._slots):
            eng.step()
        n0 = eng.metrics["tokens_generated"]
        t0 = time.perf_counter()
        steps = max(1, args.decode_steps - 8)
        for _ in range(steps):
            eng.step()
        dt = time.perf_counter() - t0
        tput.append((eng.metrics["tokens_generated"] - n0) / dt)
        while eng.step():
            pass
    m = eng.metrics
    d = max(m["decode_dispatches"], 1)
    stats = dispatch_stats(m)
    dev0 = jax.devices()[0]
    sstats = sched_stats(
        eng, sbase, toks_per_s=statistics.median(tput),
        device_kind=getattr(dev0, "device_kind", dev0.platform),
        chips=tp if tp and tp > 1 else 1)
    if sstats:
        # cost-backed MFU rides separately so the result sites can place it
        # under the top-level `mfu` key
        stats["mfu_cost"] = sstats.pop("mfu", None)
        stats["sched"] = sstats
    note(f"engine: {m['decode_dispatches']} decode dispatches, "
         f"{m['decode_steps_dispatched']} steps "
         f"({m['decode_steps_dispatched'] / d:.1f} steps/dispatch), "
         f"{m['admit_dispatches']} admit dispatches, "
         f"host-sync wait {stats['host_sync_wait_ms_per_token']:.3f} "
         f"ms/token")
    if getattr(args, "trace", False):
        from localai_tpu import telemetry

        args.trace_payload = {
            "spans": telemetry.chrome_events(),
            "pid": os.getpid(),
        }
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    return statistics.median(tput), ttft_ms, context, dtype, stats


def bench_paged(args, size: str, on_cpu: bool):
    """Dense vs paged, SAME workload, ONE process — the regression guard
    VERDICT Weak #2 asked for. Runs the in-process engine measurement twice
    (kv_pages=0, then a pool sized for the workload) and reports the ratio:
    a paged_over_dense well below 1.0 is the pool-rematerialization bug
    pattern and must never ship silently again."""
    from localai_tpu.ops.paged import BLOCK

    dense_tps, dense_ttft, context, dtype, _ = bench_engine(
        args, size, on_cpu, kv_pages=0)
    note(f"dense: {dense_tps:.1f} tok/s")
    pages = args.kv_pages
    if not pages:
        # reservation per slot: prompt + max_tokens + the engine's in-flight
        # margin (2*decode_block+1 == 33 at the default block of 16),
        # capped at the context — mirror engine._blocks_for + trash block
        tokens = min(args.prompt_len + args.decode_steps + 33, context)
        pages = args.slots * (-(-tokens // BLOCK)) + 1
    note(f"paged pool: {pages} blocks")
    paged_tps, paged_ttft, _, _, stats = bench_engine(
        args, size, on_cpu, kv_pages=pages)
    note(f"paged: {paged_tps:.1f} tok/s "
         f"({paged_tps / max(dense_tps, 1e-9):.2f}x dense)")
    return (dense_tps, dense_ttft, paged_tps, paged_ttft, pages, context,
            dtype, stats)


def _longctx_leg(args, cfg, params, *, max_context, kv_policy="",
                 kv_cold_pages=0, prompt_tokens, decode_steps,
                 greedy=False, seed=1):
    """One single-slot long-context leg: admit a `prompt_tokens` prompt,
    wait until prefill completes, then time the pure decode window.
    Returns (tok_s, token_ids, metrics)."""
    import numpy as np

    from localai_tpu.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.engine.kvtier import (
        engine_margin_tokens, parse_policy, resident_blocks,
    )
    from localai_tpu.ops.paged import blocks_needed
    from localai_tpu.ops.sampling import SamplingParams

    chunk = min(512, max_context)
    ec = EngineConfig(max_slots=1, max_context=max_context,
                      prefill_buckets=(128, chunk), prefill_chunk=chunk,
                      kv_pages=1, kv_policy=kv_policy,
                      kv_cold_pages=kv_cold_pages)
    pol = parse_policy(kv_policy)
    if pol.windowed:
        pages = resident_blocks(pol, engine_margin_tokens(ec)) + 3
    else:
        pages = blocks_needed(max_context) + 2
    ec = EngineConfig(max_slots=1, max_context=max_context,
                      prefill_buckets=(128, chunk), prefill_chunk=chunk,
                      kv_pages=pages, kv_policy=kv_policy,
                      kv_cold_pages=kv_cold_pages)
    eng = Engine(cfg, params, None, ec)
    rng = np.random.default_rng(seed)

    def req(n_prompt, n_decode):
        return GenRequest(
            prompt_ids=rng.integers(1, cfg.vocab_size, n_prompt).tolist(),
            params=SamplingParams(temperature=0.0 if greedy else 0.8,
                                  seed=seed),
            max_tokens=n_decode, ignore_eos=True)

    # compile admission + decode on a short request so the timed window
    # below measures steady-state decode, not XLA compiles
    _, out = eng.submit(req(8, 4))
    while eng.step():
        pass
    while not out.empty():
        out.get()

    rng = np.random.default_rng(seed)   # same prompt across legs
    _, out = eng.submit(req(prompt_tokens, decode_steps))
    while eng._slots[0] is None or not eng._slots[0].prefilled:
        eng.step()
    n0 = eng.metrics["tokens_generated"]
    t0 = time.perf_counter()
    while eng.step():
        pass
    dt = time.perf_counter() - t0
    toks = eng.metrics["tokens_generated"] - n0
    ids = []
    while not out.empty():
        o = out.get()
        if o.token_id >= 0:
            ids.append(o.token_id)
    return toks / max(dt, 1e-9), ids, dict(eng.metrics)


def bench_longctx(args, size: str, on_cpu: bool):
    """Long-context KV tier A/B (BASELINE #2f, engine/kvtier.py): decode
    tok/s at ctx long_tokens under sink_window vs ctx-1k under full KV
    (same geometry, one process), plus the tier's two documented parity
    regimes — token-exact when sinks+window cover the whole context, and
    int8-tolerance agreement for quantize_cold (full-precision sinks +
    window, sub-channel-int8 middle)."""
    import jax

    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.ops.paged import BLOCK, blocks_needed

    long_tokens = args.longctx_tokens
    sinks, window = args.kv_sinks, args.kv_window
    decode = args.decode_steps
    tmp = tempfile.mkdtemp(prefix="bench-ckpt-")
    ckpt = write_synthetic_checkpoint(size, os.path.join(tmp, size))
    # the tier exists to serve contexts past the model's native training
    # length — raise the synthetic geometry's rope table to match
    cfgp = os.path.join(ckpt, "config.json")
    with open(cfgp) as fh:
        body = json.load(fh)
    body["max_position_embeddings"] = max(
        body.get("max_position_embeddings", 0),
        long_tokens + decode + 2 * BLOCK)
    with open(cfgp, "w") as fh:
        json.dump(body, fh)
    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    dtype = args.dtype or ("int8" if size == "8b" else "bfloat16")
    if on_cpu:
        dtype = args.dtype or "float32"
    cfg = load_config(ckpt, dtype=dtype)
    params = load_params(ckpt, cfg, dtype=dtype)
    jax.block_until_ready(params)
    note("params initialized")

    policy = f"sink_window(sinks={sinks}, window={window})"
    short_ctx = 1024 + decode + 2 * BLOCK
    short_tok_s, _, _ = _longctx_leg(
        args, cfg, params, max_context=short_ctx, prompt_tokens=1024,
        decode_steps=decode)
    note(f"ctx-1k full: {short_tok_s:.1f} tok/s")
    long_ctx = long_tokens + decode + 2 * BLOCK
    long_tok_s, _, lm = _longctx_leg(
        args, cfg, params, max_context=long_ctx, kv_policy=policy,
        prompt_tokens=long_tokens, decode_steps=decode)
    note(f"ctx-{long_tokens // 1024}k {policy}: {long_tok_s:.1f} tok/s "
         f"({long_tok_s / max(short_tok_s, 1e-9):.2f}x of ctx-1k), "
         f"pool peak {lm['kv_blocks_peak']} blocks, "
         f"{lm['kv_evictions']} evictions")

    # parity probe 1: sinks+window >= context -> nothing ever leaves
    # retention, token streams must be EXACTLY the full-KV ones
    probe_ctx = 512 + 2 * BLOCK
    _, ref_ids, _ = _longctx_leg(
        args, cfg, params, max_context=probe_ctx, prompt_tokens=384,
        decode_steps=32, greedy=True)
    _, tier_ids, _ = _longctx_leg(
        args, cfg, params, max_context=probe_ctx,
        kv_policy="sink_window(sinks=128, window=640)", prompt_tokens=384,
        decode_steps=32, greedy=True)
    parity_exact = tier_ids == ref_ids
    note(f"parity (sinks+window >= ctx): "
         f"{'exact' if parity_exact else 'DIVERGED'}")

    # parity probe 2: quantize_cold with window < prompt — every position
    # stays readable (middle blocks at int8), so agreement vs full KV is
    # bounded by int8 quantization error only (the documented tolerance)
    cold_ctx = 1024 + 2 * BLOCK
    _, ref2, _ = _longctx_leg(
        args, cfg, params, max_context=cold_ctx, prompt_tokens=768,
        decode_steps=32, greedy=True)
    _, cold_ids, cm = _longctx_leg(
        args, cfg, params, max_context=cold_ctx,
        kv_policy="sink_window(sinks=128, window=256, quantize_cold=true)",
        kv_cold_pages=blocks_needed(cold_ctx) + 2, prompt_tokens=768,
        decode_steps=32, greedy=True)
    agree = sum(a == b for a, b in zip(cold_ids, ref2))
    cold_agreement = agree / max(len(ref2), 1)
    note(f"parity (quantize_cold int8): {cold_agreement:.2f} agreement, "
         f"{cm['kv_cold_blocks']} blocks demoted")

    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "short_tok_s": short_tok_s, "long_tok_s": long_tok_s,
        "long_tokens": long_tokens, "policy": policy,
        "kv_blocks_peak": lm["kv_blocks_peak"],
        "kv_evictions": lm["kv_evictions"],
        "parity_exact": parity_exact,
        "parity_cold_agreement": cold_agreement,
        "cold_blocks": cm["kv_cold_blocks"],
        "dtype": dtype,
    }


def bench_embed(args, size: str, on_cpu: bool):
    """BASELINE config #3: /v1/embeddings-path throughput (served gRPC
    Embedding RPC, batch inputs) → embeddings/s."""
    import numpy as np

    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager

    tmp = tempfile.mkdtemp(prefix="bench-ckpt-")
    ckpt = write_synthetic_checkpoint(size, os.path.join(tmp, size))
    # batched embeddings tokenize server-side: give the synthetic checkpoint
    # an instant WordLevel tokenizer ("<n>" → id n, whitespace-split)
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = SIZES[size]["vocab_size"]
    tok = Tokenizer(models.WordLevel(
        {str(i): i for i in range(min(vocab, 1000))}, unk_token="0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(os.path.join(ckpt, "tokenizer.json"))
    with open(os.path.join(ckpt, "tokenizer_config.json"), "w") as fh:
        json.dump({"bos_token": None, "eos_token": None,
                   "add_bos_token": False}, fh)
    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    os.environ["LOCALAI_NO_PREWARM"] = "1"   # embed RPC needs no decode warm
    dtype = args.dtype or ("float32" if on_cpu else "bfloat16")
    mcfg = ModelConfig.from_dict({
        "name": f"bench-{size}", "backend": "llm", "context_size": 512,
        "parallel": 2, "dtype": dtype, "embeddings": True,
        "prefill_buckets": [128], "parameters": {"model": ckpt},
    })
    manager = ModelManager(AppConfig(models_path=tmp))
    handle = manager.load(mcfg)
    args.device_kind = served_device(handle, on_cpu)
    rng = np.random.default_rng(0)
    batch = [" ".join(str(t) for t in rng.integers(1, min(vocab, 999), 24))
             for _ in range(args.embed_batch)]
    try:
        handle.client.embedding(prompts=batch)      # warmup (compile)
        rates = []
        for _ in range(args.windows):
            t0 = time.perf_counter()
            r = handle.client.embedding(prompts=batch)
            dt = time.perf_counter() - t0
            n = len(r.vectors) or len(batch)
            rates.append(n / dt)
            note(f"embed window: {rates[-1]:.1f} embeddings/s ({n} x 24 tok)")
    finally:
        # never leak the accelerator-holding backend into later ladder
        # stages, and never leave checkpoints accumulating in /tmp
        import shutil

        manager.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)
    return statistics.median(rates)


def bench_whisper(args, on_cpu: bool):
    """BASELINE config #4: /v1/audio/transcriptions real-time factor
    (audio-seconds transcribed per wall-second) through the whisper backend."""
    import numpy as np
    import torch
    from transformers import WhisperConfig, WhisperForConditionalGeneration

    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager

    tmp = tempfile.mkdtemp(prefix="bench-whisper-")
    torch.manual_seed(0)
    if on_cpu:
        # CPU smoke: tiny geometry + short clip (whisper-base on CPU f32
        # takes minutes per window — harness validation only)
        wcfg = WhisperConfig(
            vocab_size=51865, d_model=64, encoder_layers=2,
            decoder_layers=2, encoder_attention_heads=4,
            decoder_attention_heads=4, encoder_ffn_dim=128,
            decoder_ffn_dim=128, num_mel_bins=80,
            max_source_positions=1500, max_target_positions=64)
    else:
        # whisper-base geometry (the BASELINE config names whisper-base)
        wcfg = WhisperConfig(
            vocab_size=51865, d_model=512, encoder_layers=6,
            decoder_layers=6, encoder_attention_heads=8,
            decoder_attention_heads=8, encoder_ffn_dim=2048,
            decoder_ffn_dim=2048, num_mel_bins=80,
            max_source_positions=1500, max_target_positions=448)
    m = WhisperForConditionalGeneration(wcfg)
    m.generation_config.forced_decoder_ids = None
    m.generation_config.suppress_tokens = None
    m.generation_config.begin_suppress_tokens = None
    m.save_pretrained(tmp, safe_serialization=True)
    mcfg = ModelConfig.from_dict({
        "name": "bench-whisper", "backend": "whisper",
        "parameters": {"model": tmp},
    })
    manager = ModelManager(AppConfig(models_path=tmp))
    handle = manager.load(mcfg)
    args.device_kind = served_device(handle, on_cpu)
    secs = 5.0 if on_cpu else 20.0
    sr = 16000
    t = np.arange(int(secs * sr)) / sr
    pcm = (0.1 * np.sin(2 * np.pi * 220 * t)).astype(np.float32)
    import struct
    import wave

    wav = os.path.join(tmp, "in.wav")
    with wave.open(wav, "wb") as w:
        w.setnchannels(1)
        w.setsampwidth(2)
        w.setframerate(sr)
        w.writeframes(
            struct.pack(f"<{len(pcm)}h",
                        *(np.clip(pcm, -1, 1) * 32767).astype(np.int16)))
    try:
        handle.client.transcribe(dst=wav, language="en")     # warmup
        rtfs = []
        for _ in range(args.windows):
            t0 = time.perf_counter()
            handle.client.transcribe(dst=wav, language="en")
            rtfs.append(secs / (time.perf_counter() - t0))
            note(f"whisper window: RTF {rtfs[-1]:.2f}x")
    finally:
        import shutil

        manager.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)
    return statistics.median(rtfs)


def bench_session(args, size: str, on_cpu: bool) -> dict:
    """--mode session (ISSUE 17): multi-turn conversations through the host
    KV tier. One in-process engine serves turn 1 of a long conversation,
    other tenants churn its device pool (the retained prefix spills to the
    host tier), then turn 2 arrives — TTFT with host re-admission vs the
    re-prefill baseline vs the warm device-cache hit, plus a worker-restart
    leg (a FRESH engine adopting the survivor HostKVPool) and a greedy
    parity check through the re-admitted int8 blocks.

    ISSUE 19 adds a preempt/resume leg: a mid-decode spill-drain freezes a
    live generation into a ResumeToken, and TTFT-to-next-token resuming on
    a fresh engine that adopts the survivor pool is measured against the
    same token resumed by re-prefilling from scratch (resume_speedup)."""
    import jax
    import numpy as np

    from localai_tpu.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.ops.paged import blocks_needed
    from localai_tpu.ops.sampling import SamplingParams

    tmp = tempfile.mkdtemp(prefix="bench-ckpt-")
    ckpt = write_synthetic_checkpoint(size, os.path.join(tmp, size))
    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    dtype = args.dtype or ("int8" if size == "8b" else "bfloat16")
    if on_cpu:
        dtype = args.dtype or "float32"
    cfg = load_config(ckpt, dtype=dtype)
    S = min(args.session_tokens, cfg.max_position - 192)
    context = S + 192
    params = load_params(ckpt, cfg, dtype=dtype)
    jax.block_until_ready(params)
    note(f"params initialized ({S}-token conversations, ctx {context})")

    # pool sized just above one conversation's footprint so the churn
    # tenants force the released turn-1 chain out of the device pool (the
    # host tier is then its only home); the int8 hot cache makes the
    # spill→readmit round trip byte-exact
    pages = blocks_needed(context) + 1
    budget = args.kv_host_bytes or (1 << 30)

    def mk(kv_host_bytes=0, kvhost=None):
        return Engine(cfg, params, None, EngineConfig(
            max_slots=2, max_context=context,
            prefill_buckets=(128, min(512, context)),
            prefill_chunk=min(512, context),
            cache_type="int8", kv_pages=pages, prompt_cache=True,
            kv_host_bytes=kv_host_bytes), kvhost=kvhost)

    rng = np.random.default_rng(0)
    turn1_ids = rng.integers(1, cfg.vocab_size, S).tolist()
    follow_ids = rng.integers(1, cfg.vocab_size, 64).tolist()

    def greq(ids, n=16):
        return GenRequest(prompt_ids=list(ids), max_tokens=n,
                          params=SamplingParams(temperature=0.0),
                          ignore_eos=True)

    def run_turn(eng, ids, n=16):
        """(ttft_ms, generated token ids) — greedy, fully drained."""
        rid, out = eng.submit(greq(ids, n))
        t0 = time.perf_counter()
        ttft = None
        toks = []
        while True:
            eng.step()
            while not out.empty():
                so = out.get()
                if ttft is None:
                    ttft = (time.perf_counter() - t0) * 1e3
                if so.token_id >= 0:
                    toks.append(so.token_id)
                if so.finished:
                    while eng.step():
                        pass
                    return ttft, toks

    def churn(eng, seeds=(11, 12, 13)):
        """Distinct same-length tenants: reclaims the released turn-1
        chain (host spill on a tiered engine, plain death otherwise)."""
        for s in seeds:
            r = np.random.default_rng(s)
            run_turn(eng, r.integers(1, cfg.vocab_size, S).tolist(), n=4)

    def prewarm(eng, with_host: bool):
        """Compile every program a measured leg will hit: chunked prefill,
        decode, the shared-prefix resume path (prefix hit + suffix-only
        prefill), and (host legs) the spill + readmit programs."""
        w = np.random.default_rng(99).integers(1, cfg.vocab_size, S).tolist()
        ext = np.random.default_rng(97).integers(
            1, cfg.vocab_size, 80).tolist()
        run_turn(eng, w, n=4)
        if with_host:
            churn(eng, seeds=(98, 96))    # spill compile + evict w's chain
        run_turn(eng, w + ext, n=4)       # resume (+ readmit) compile
        if eng._kvhost is not None:
            eng._host_drain()             # settle pending spill fetches

    # -- baseline engine: warm device hit, then the re-prefill floor ------
    note("baseline leg (no host tier)...")
    ebase = mk(0)
    prewarm(ebase, with_host=False)
    ttft1_base, gen1 = run_turn(ebase, turn1_ids)
    conv = turn1_ids + gen1 + follow_ids
    ttft2_warm, out_warm = run_turn(ebase, conv)      # device prefix hit
    churn(ebase)
    ttft2_reprefill, out_reprefill = run_turn(ebase, conv)
    note(f"baseline: warm {ttft2_warm:.1f} ms, "
         f"re-prefill {ttft2_reprefill:.1f} ms")

    # -- host-tier engine: churn spills, turn 2 re-admits -----------------
    note(f"host-tier leg (budget {budget / 1e6:.0f} MB)...")
    ehost = mk(budget)
    prewarm(ehost, with_host=True)
    ttft1, gen1h = run_turn(ehost, turn1_ids)
    assert gen1h == gen1, "turn-1 greedy streams diverged across engines"
    churn(ehost)
    ehost._host_drain()   # spill cost lands on churn time, not turn-2 TTFT
    hits0 = ehost.metrics["kv_host_hits"]
    ttft2_host, out_host = run_turn(ehost, conv)
    ehost._host_drain()
    m = dict(ehost.metrics)
    readmitted = int(m["kv_host_hits"] - hits0)
    note(f"host tier: turn2 {ttft2_host:.1f} ms, {readmitted} blocks "
         f"re-admitted, pool peak {m['kv_host_bytes_peak'] / 1e6:.1f} MB")

    # -- worker restart: fresh engine adopts the survivor pool ------------
    note("restart leg (fresh engine, adopted host pool)...")
    erest = mk(0, kvhost=ehost._kvhost)
    prewarm(erest, with_host=True)
    hits0r = erest.metrics["kv_host_hits"]
    ttft2_restart, out_restart = run_turn(erest, conv)
    rm = dict(erest.metrics)

    # -- preempt/resume leg (ISSUE 19): TTFT-to-next-token after a --------
    # mid-decode spill-drain, resumed on a FRESH engine adopting the
    # survivor pool, vs the same ResumeToken re-prefilled from scratch
    note("preempt/resume leg (spill-drain vs re-prefill)...")
    from localai_tpu.engine.resume import ResumeToken

    def mkp(kv_host_bytes=0, kvhost=None, loop=8, block=4):
        # short fused bursts on the preempting engine so the preempt lands
        # mid-generation instead of after one whole-turn dispatch; the
        # resume engines run one step per dispatch (loop=1, block=1) so
        # TTFT observes the true first post-resume token — readmit vs
        # re-prefill — instead of a shared whole-burst constant (greedy
        # parity across dispatch groupings is the tests/test_decode_loop
        # guarantee). BLOCK-sized prefill chunks: a re-prefill walks the
        # whole conversation one chunk dispatch at a time while a
        # survivor-pool resume pays a single sub-block suffix chunk — the
        # dispatch asymmetry the checkpoint is buying
        return Engine(cfg, params, None, EngineConfig(
            max_slots=2, max_context=context,
            prefill_buckets=(128,), prefill_chunk=128,
            cache_type="int8", kv_pages=pages, prompt_cache=True,
            decode_loop=loop, decode_block=block,
            kv_host_bytes=kv_host_bytes), kvhost=kvhost)

    def run_resume(eng, tok, n):
        """(ttft_ms to the first post-resume token, continuation ids)."""
        rid, out = eng.submit(GenRequest(
            prompt_ids=tok.resume_prompt, max_tokens=n,
            params=SamplingParams(temperature=0.0), ignore_eos=True,
            resume=tok.payload()))
        t0 = time.perf_counter()
        ttft = None
        toks = []
        while True:
            eng.step()
            while not out.empty():
                so = out.get()
                if ttft is None:
                    ttft = (time.perf_counter() - t0) * 1e3
                if so.token_id >= 0:
                    toks.append(so.token_id)
                if so.finished:
                    while eng.step():
                        pass
                    return ttft, toks

    def run_until(eng, ids, n, k):
        """Step until >= k tokens observed, then spill-drain preempt."""
        rid, out = eng.submit(greq(ids, n))
        toks = []
        while len(toks) < k:
            eng.step()
            while not out.empty():
                so = out.get()
                if so.token_id >= 0:
                    toks.append(so.token_id)
                assert not so.finished, "finished before the preempt landed"
        man = eng.preempt()
        while not out.empty():
            so = out.get()
            if so.token_id >= 0:
                toks.append(so.token_id)
        return toks, man

    NPRE = 32
    # uninterrupted reference on its own engine: each preempted run must
    # be a FRESH prefill so the slot owns its whole chain — a prefix hit
    # on a retained reference chain would leave most blocks shared
    # (unspilled) and the resume would re-prefill them anyway
    eref = mkp(0)
    prewarm(eref, with_host=False)
    epre = mkp(budget)
    prewarm(epre, with_host=True)
    eres = mkp(0, kvhost=epre._kvhost, loop=1, block=1)
    prewarm(eres, with_host=True)
    erep = mkp(0, loop=1, block=1)
    prewarm(erep, with_host=False)

    # median of 3 preempt->resume rounds, a fresh prompt each round so
    # every resume is a true survivor-pool readmit and every floor run a
    # true re-prefill (single-shot TTFTs at smoke scale are noise-bound)
    res_ms, rep_ms = [], []
    parity_res = parity_rep = True
    got_pre = []
    for rnd in range(3):
        ids = np.random.default_rng(200 + rnd).integers(
            1, cfg.vocab_size, S).tolist()
        _, ref_pre = run_turn(eref, ids, n=NPRE)
        got_pre, man = run_until(epre, ids, NPRE, 8)
        assert man, "preempt produced no resume manifest"
        assert len(got_pre) < NPRE, "preempt landed after the stream ended"
        tok = ResumeToken.from_dict(man[0])
        nrem = NPRE - tok.generated
        t_res, rest_res = run_resume(eres, tok, nrem)
        t_rep, rest_rep = run_resume(erep, tok, nrem)
        res_ms.append(t_res)
        rep_ms.append(t_rep)
        parity_res = parity_res and (got_pre + rest_res == ref_pre)
        parity_rep = parity_rep and (got_pre + rest_rep == ref_pre)
    ttft_resume = statistics.median(res_ms)
    ttft_reprefill = statistics.median(rep_ms)
    pm = dict(epre.metrics)
    note(f"preempt at {len(got_pre)} toks: resume {ttft_resume:.1f} ms "
         f"(readmit) vs {ttft_reprefill:.1f} ms (re-prefill)")
    resm, repm = dict(eres.metrics), dict(erep.metrics)

    for e in (ebase, ehost, erest, eref, epre, eres, erep):
        e.stop()
    import shutil

    shutil.rmtree(tmp, ignore_errors=True)
    return {
        "dtype": dtype, "session_tokens": S, "context": context,
        "kv_pages": pages, "budget_bytes": budget,
        "ttft1_ms": ttft1, "ttft1_base_ms": ttft1_base,
        "ttft2_warm_ms": ttft2_warm,
        "ttft2_reprefill_ms": ttft2_reprefill,
        "ttft2_host_ms": ttft2_host,
        "ttft2_restart_ms": ttft2_restart,
        "readmitted_blocks": readmitted,
        "restart_readmitted_blocks": int(rm.get("kv_host_hits", 0) - hits0r),
        # greedy parity vs the WARM device hit: spill→readmit on the int8
        # pool is byte-exact, so the host path must reproduce the retained-
        # on-device stream bit for bit. Re-prefill parity is informational
        # only — fresh prefill reads no quantized prefix KV while any
        # cache-resume path (device OR host) does, a pre-existing prefix-
        # cache asymmetry this tier inherits rather than introduces.
        "parity_host": out_host == out_warm,
        "parity_restart": out_restart == out_warm,
        "parity_reprefill": out_reprefill == out_warm,
        "kv_host_bytes_peak": int(m["kv_host_bytes_peak"]),
        "kv_host_spills": int(m["kv_host_spills"]),
        "kv_host_evictions": int(m["kv_host_evictions"]),
        # preempt/resume leg (ISSUE 19); block/readmit counts are
        # cumulative over the 3 measured rounds
        "ttft_resume_ms": ttft_resume,
        "ttft_resume_reprefill_ms": ttft_reprefill,
        "preempt_tokens": len(got_pre),
        "preempt_spilled_blocks": int(pm["preempt_spilled_blocks"]),
        "parity_resume": parity_res,
        "parity_resume_reprefill": parity_rep,
        "resume_readmits": int(resm["resume_readmits"]),
        "resume_reprefills": int(repm["resume_reprefills"]),
    }


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--size", default=None,
                   help="tiny|1b|3b|8b (default: 8b on TPU, tiny on CPU)")
    p.add_argument("--mode", default="serve",
                   choices=["serve", "engine", "embed", "whisper", "paged",
                            "tp", "longctx", "session"],
                   help="serve = gRPC backend subprocess (default); engine = "
                        "in-process; paged = dense AND paged in one process "
                        "with a paged_over_dense ratio; tp = single device "
                        "AND an N-device tensor-parallel mesh in one process "
                        "with a tp_over_single ratio (CPU: virtual 4-device "
                        "mesh); "
                        "longctx = KV lifecycle tier: ctx-32k decode under "
                        "sink_window vs ctx-1k full KV with a "
                        "longctx_over_short ratio, bounded-pool peak, and "
                        "token-parity probes (BASELINE #2f); "
                        "session = multi-turn conversations through the "
                        "host KV tier: turn-2 TTFT with host re-admission "
                        "vs re-prefill vs warm device hit, a worker-restart "
                        "leg, and a greedy-parity check, with "
                        "turn2_over_turn1_ttft + readmit_speedup ratios "
                        "(ISSUE 17); "
                        "embed/whisper = BASELINE configs #3/#4")
    p.add_argument("--embed-batch", type=int, default=256)
    p.add_argument("--dtype", default=None,
                   help="override weights dtype (default: int8 for 8b, else bf16)")
    p.add_argument("--cpu", action="store_true", help="force CPU (local smoke)")
    p.add_argument("--slots", type=int, default=None,
                   help="concurrent streams; default 16 on the int8-KV "
                        "geometries (8b), 8 on dense-KV ones")
    p.add_argument("--prompt-len", type=int, default=120)
    p.add_argument("--decode-steps", type=int, default=128)
    p.add_argument("--windows", type=int, default=5)
    p.add_argument("--context", type=int, default=1024)
    p.add_argument("--decode-loop", type=int, default=None,
                   help="max steps per single-dispatch while-loop decode "
                        "block (engine mode; default: engine's 64; 0 "
                        "disables the loop — scan-ladder comparison runs)")
    p.add_argument("--longctx-tokens", type=int, default=32768,
                   help="long-leg prompt length for --mode longctx")
    p.add_argument("--kv-window", type=int, default=1024,
                   help="sink_window retention window for --mode longctx")
    p.add_argument("--kv-sinks", type=int, default=256,
                   help="attention-sink tokens for --mode longctx")
    p.add_argument("--session-tokens", type=int, default=4096,
                   help="tokens per conversation turn-1 prefix for --mode "
                        "session (the amount the host tier must carry "
                        "across device-pool eviction)")
    p.add_argument("--kv-host-bytes", type=int, default=0,
                   help="host-RAM KV tier budget for --mode session "
                        "(0 = auto 1 GiB); the spill tier catching blocks "
                        "the device pool evicts")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="paged KV pool size in 128-token blocks "
                        "(0 = dense per-slot cache); lets slot count "
                        "oversubscribe context at ctx 8192")
    p.add_argument("--tensor-parallel", type=int, default=0,
                   help="shard the model over N devices (mesh data=1, "
                        "model=N; int8 weights shard too). 0 = single "
                        "device. --mode tp runs both legs and defaults N "
                        "to the largest axis the geometry divides into")
    p.add_argument("--trace", action="store_true",
                   help="telemetry run: record spans (LOCALAI_TRACE) and "
                        "write a Chrome-trace dump")
    p.add_argument("--trace-out", default="bench_trace.json",
                   help="Chrome-trace output path for --trace")
    return p


def emit_result(result: dict, args) -> int:
    """Final scoreboard emission: fold in the --trace stage breakdown and
    the engine-histogram SLO fields; write the Chrome-trace dump, print the
    JSON line."""
    # engine-sourced latency percentiles: serve mode captured the backend's
    # hist_* GetMetrics keys; in-process modes read the live registry.
    # setdefault — a mode publishing its own under-load stopwatch numbers
    # keeps them.
    src = getattr(args, "slo_metrics", None)
    if src is None:
        try:
            from localai_tpu import telemetry

            slo = telemetry.maybe_slo()
            src = slo.flat() if slo is not None else {}
        except Exception:
            src = {}
    for k, v in slo_stats(src).items():
        result.setdefault(k, v)
    payload = getattr(args, "trace_payload", None)
    if payload is not None:
        try:
            from localai_tpu import telemetry

            # backend spans + this (parent) process's rpc/client spans
            events = list(payload.get("spans") or [])
            events += telemetry.chrome_events()
            events.sort(key=lambda e: e.get("ts", 0))
            names = {os.getpid(): "bench"}
            if payload.get("pid"):
                names[payload["pid"]] = "backend"
            with open(args.trace_out, "w") as fh:
                json.dump(telemetry.chrome_trace(events, names), fh)
            note(f"chrome trace ({len(events)} events) -> {args.trace_out}")
        except Exception as e:
            note(f"trace dump failed: {e}")
    print(json.dumps(result))
    return 0


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.trace:
        # env, not in-process flags: serve mode's backend subprocess must
        # inherit them (manager spawn copies os.environ)
        os.environ["LOCALAI_TRACE"] = "1"

    # the chip or an error, for this process and every backend it spawns:
    # with JAX_PLATFORMS unset JAX itself falls back to the CPU when the TPU
    # client cannot start. --cpu is the explicit harness smoke; its numbers
    # are counts and correctness, never speeds.
    on_cpu = args.cpu
    os.environ["JAX_PLATFORMS"] = "cpu" if on_cpu else "tpu"
    from localai_tpu.system.device import configure_compile_cache

    configure_compile_cache()
    size = args.size or ("tiny" if on_cpu else "8b")
    if args.slots is None:
        # int8-KV geometries halve per-slot HBM → double the slot count;
        # dense-KV geometries keep the old footprint. Mirror bench_serve's
        # dtype resolution incl. the CPU float32 override.
        dtype = args.dtype or ("int8" if size == "8b" else "bfloat16")
        if on_cpu:
            dtype = args.dtype or "float32"
        args.slots = 16 if dtype in ("int8", "int4") else 8

    if args.mode == "embed":
        rate = bench_embed(args, size, on_cpu)
        out = {
            "metric": f"embeddings/s (llama-{size}, served Embedding RPC, "
                      f"batch {args.embed_batch} x 24 tok) [BASELINE #3]",
            "value": round(rate, 2), "unit": "embeddings/s",
            "vs_baseline": None, "device": args.device_kind}
        return emit_result(out, args)
    if args.mode == "whisper":
        rtf = bench_whisper(args, on_cpu)
        geom = "tiny-smoke, 5 s" if on_cpu else "whisper-base, 20 s"
        out = {
            "metric": f"whisper RTF ({geom} clip, served "
                      f"AudioTranscription) [BASELINE #4]",
            "value": round(rtf, 2), "unit": "audio-s/s",
            "vs_baseline": None, "device": args.device_kind}
        return emit_result(out, args)
    if args.mode == "tp":
        # single device vs an N-wide TP mesh, SAME workload, ONE process —
        # the mesh twin of --mode paged. On CPU the mesh is virtual
        # (XLA_FLAGS host-platform devices, must be set pre-jax-init).
        n_dev = args.tensor_parallel if args.tensor_parallel > 1 else 4
        if on_cpu:
            ensure_virtual_devices(n_dev)
        import jax

        note("initializing device client...")
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)
        tmp = tempfile.mkdtemp(prefix="bench-ckpt-")
        ckpt = write_synthetic_checkpoint(size, os.path.join(tmp, size))
        os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
        from localai_tpu.engine.loader import load_config
        from localai_tpu.models.llama import max_model_axis

        dtype_probe = args.dtype or ("int8" if size == "8b" else "bfloat16")
        if on_cpu:
            dtype_probe = args.dtype or "float32"
        cfg = load_config(ckpt, dtype=dtype_probe)
        # TP degree: explicit flag, else the widest axis every sharded dim
        # divides into (mirrors the backend's auto-TP)
        tp = args.tensor_parallel or max_model_axis(cfg, len(jax.devices()))
        if tp < 2:
            note(f"geometry shards over no more than {tp} device(s) — "
                 "tp_over_single would be vacuous")
            return 2
        single_tps, single_ttft, context, dtype, _ = bench_engine(
            args, size, on_cpu, tp=0)
        note(f"single device: {single_tps:.1f} tok/s")
        tp_tps, tp_ttft, _, _, stats = bench_engine(args, size, on_cpu, tp=tp)
        note(f"tp 1x{tp}: {tp_tps:.1f} tok/s global "
             f"({tp_tps / max(single_tps, 1e-9):.2f}x single)")
        n_params = param_count(size)
        result = {
            "metric": f"decode tok/s (llama-{size} {dtype}, tp mesh 1x{tp} "
                      f"vs single device, {args.slots} slots, ctx {context})",
            # scoreboard value = per chip, like every other row
            "value": round(tp_tps / tp, 2),
            "unit": "tok/s/chip",
            "vs_baseline": None if on_cpu else round(tp_tps / tp / 1000.0, 4),
            "tp_over_single": round(tp_tps / max(single_tps, 1e-9), 4),
            "mesh": {"data": 1, "model": tp},
            "chips": tp,
            "tok_s_global": round(tp_tps, 2),
            "tok_s_per_chip": round(tp_tps / tp, 2),
            "single_tok_s": round(single_tps, 2),
            "ttft_p50_ms": round(tp_ttft, 2),
            "single_ttft_p50_ms": round(single_ttft, 2),
            "mfu": stats.pop("mfu_cost", None),
            "device": device_kind,
            "params": n_params,
            **stats,
        }
        return emit_result(result, args)
    if args.mode == "longctx":
        import jax

        note("initializing device client...")
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)
        r = bench_longctx(args, size, on_cpu)
        ratio = r["long_tok_s"] / max(r["short_tok_s"], 1e-9)
        result = {
            "metric": f"longctx decode tok/s (llama-{size} {r['dtype']}, "
                      f"ctx {r['long_tokens']} {r['policy']} vs ctx 1024 "
                      f"full KV, 1 slot) [BASELINE #2f]",
            "value": round(r["long_tok_s"], 2),
            "unit": "tok/s",
            "vs_baseline": None,
            "short_tok_s": round(r["short_tok_s"], 2),
            "longctx_over_short": round(ratio, 4),
            "kv_blocks_peak": r["kv_blocks_peak"],
            "kv_evictions": r["kv_evictions"],
            "parity_exact": r["parity_exact"],
            "parity_cold_agreement": round(r["parity_cold_agreement"], 4),
            "cold_blocks": r["cold_blocks"],
            "device": device_kind,
        }
        return emit_result(result, args)
    if args.mode == "session":
        import jax

        note("initializing device client...")
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)
        r = bench_session(args, size, on_cpu)
        result = {
            "metric": f"session turn-2 TTFT ms (llama-{size} {r['dtype']}, "
                      f"{r['session_tokens']}-token conversation, host KV "
                      f"tier {r['budget_bytes'] // (1 << 20)} MB, "
                      f"{r['kv_pages']}-block device pool)",
            "value": round(r["ttft2_host_ms"], 2),
            "unit": "ms",
            "vs_baseline": None,
            "ttft1_ms": round(r["ttft1_ms"], 2),
            "ttft2_warm_ms": round(r["ttft2_warm_ms"], 2),
            "ttft2_reprefill_ms": round(r["ttft2_reprefill_ms"], 2),
            "ttft2_restart_ms": round(r["ttft2_restart_ms"], 2),
            # lower-better gate: host-tier turn-2 TTFT over turn-1 full
            # prefill (re-admission should beat re-running the prefill)
            "turn2_over_turn1_ttft": round(
                r["ttft2_host_ms"] / max(r["ttft1_ms"], 1e-9), 4),
            # higher-better twin: re-prefill baseline over host-tier TTFT
            "readmit_speedup": round(
                r["ttft2_reprefill_ms"] / max(r["ttft2_host_ms"], 1e-9), 4),
            "restart_over_warm_ttft": round(
                r["ttft2_restart_ms"] / max(r["ttft2_warm_ms"], 1e-9), 4),
            "readmitted_blocks": r["readmitted_blocks"],
            "restart_readmitted_blocks": r["restart_readmitted_blocks"],
            # preempt/resume leg (ISSUE 19): TTFT-to-next-token resuming a
            # spill-drained generation via the survivor pool over the
            # re-prefill fallback — higher-better ratio gated in benchdiff
            # (acceptance: resume TTFT <= 0.75x re-prefill, i.e. >= 1.33)
            "ttft_resume_ms": round(r["ttft_resume_ms"], 2),
            "ttft_resume_reprefill_ms": round(
                r["ttft_resume_reprefill_ms"], 2),
            "resume_speedup": round(
                r["ttft_resume_reprefill_ms"]
                / max(r["ttft_resume_ms"], 1e-9), 4),
            "preempt_tokens": r["preempt_tokens"],
            "preempt_spilled_blocks": r["preempt_spilled_blocks"],
            "resume_readmits": r["resume_readmits"],
            "resume_reprefills": r["resume_reprefills"],
            "parity_resume": bool(r["parity_resume"]),
            "parity_resume_reprefill": bool(r["parity_resume_reprefill"]),
            "parity_host": bool(r["parity_host"]),
            "parity_restart": bool(r["parity_restart"]),
            "parity_reprefill": bool(r["parity_reprefill"]),
            "kv_host_bytes_peak": r["kv_host_bytes_peak"],
            "kv_host_budget_bytes": r["budget_bytes"],
            "budget_respected": bool(
                r["kv_host_bytes_peak"] <= r["budget_bytes"]),
            "kv_host_spills": r["kv_host_spills"],
            "kv_host_evictions": r["kv_host_evictions"],
            "device": device_kind,
        }
        return emit_result(result, args)
    if args.mode == "paged":
        import jax

        note("initializing device client...")
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)
        (dense_tps, dense_ttft, toks_per_s, ttft_ms, pages, context,
         dtype, stats) = bench_paged(args, size, on_cpu)
        n_params = param_count(size)
        result = {
            "metric": f"decode tok/s/chip (llama-{size} {dtype}, paged "
                      f"{pages} blocks vs dense, {args.slots} slots, "
                      f"ctx {context})",
            "value": round(toks_per_s, 2),
            "unit": "tok/s",
            "vs_baseline": None if on_cpu else round(toks_per_s / 1000.0, 4),
            "dense_tok_s": round(dense_tps, 2),
            "paged_over_dense": round(toks_per_s / max(dense_tps, 1e-9), 4),
            "mesh": None,
            "chips": 1,
            "tok_s_global": round(toks_per_s, 2),
            "tok_s_per_chip": round(toks_per_s, 2),
            "ttft_p50_ms": round(ttft_ms, 2),
            "dense_ttft_p50_ms": round(dense_ttft, 2),
            "mfu": stats.pop("mfu_cost", None),
            "device": device_kind,
            "params": n_params,
            **stats,
        }
        return emit_result(result, args)
    if args.mode == "serve":
        # the parent process stays JAX-free: the backend subprocess owns the
        # accelerator, exactly like production serving
        toks_per_s, ttft_ms, context, dtype, stats = bench_serve(
            args, size, on_cpu)
        device_kind = args.device_kind
    else:
        if on_cpu and args.tensor_parallel > 1:
            ensure_virtual_devices(args.tensor_parallel)
        import jax

        note("initializing device client...")
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", dev.platform)
        toks_per_s, ttft_ms, context, dtype, stats = bench_engine(
            args, size, on_cpu)

    n_params = param_count(size)
    # a TP run measures GLOBAL tok/s over `chips` devices: the scoreboard
    # value and MFU normalize per chip, and the mesh shape rides the JSON so
    # a TP number can never be silently compared against a single-chip one
    chips = args.tensor_parallel if args.tensor_parallel > 1 else 1

    # BASELINE.md's north star is tok/s/chip for the flagship on a REAL chip:
    # a CPU run is a harness smoke, not a comparable number.
    paged = f", paged {args.kv_pages} blocks" if args.kv_pages else ""
    tp_tag = f", tp 1x{chips}" if chips > 1 else ""
    result = {
        "metric": f"decode tok/s/chip (llama-{size} {dtype}, {args.mode} path, "
                  f"{args.slots} slots, ctx {context}{paged}{tp_tag})",
        "value": round(toks_per_s / chips, 2),
        "unit": "tok/s",
        "vs_baseline": None if on_cpu else round(toks_per_s / chips / 1000.0,
                                                 4),
        "mesh": {"data": 1, "model": chips} if chips > 1 else None,
        "chips": chips,
        "tok_s_global": round(toks_per_s, 2),
        "tok_s_per_chip": round(toks_per_s / chips, 2),
        "ttft_p50_ms": round(ttft_ms, 2),
        "mfu": stats.pop("mfu_cost", None),
        "device": device_kind,
        "params": n_params,
        **stats,
    }
    return emit_result(result, args)


if __name__ == "__main__":
    sys.exit(main())
