"""CLI entrypoint — the `local-ai` role (reference: core/cli/cli.go:8-21).

Subcommands mirror the reference surface: `run` (serve HTTP), `backend` (run
one gRPC backend process), `models` (list/install), `version`; invoking with
no subcommand prints help. Implemented with argparse; flags use the same names
as the reference's kong flags (core/cli/run.go:24-77) where they map 1:1.
"""
from __future__ import annotations

import argparse
import sys


def _add_run(sub):
    p = sub.add_parser("run", help="start the OpenAI-compatible HTTP server")
    p.add_argument("models", nargs="*", help="model names/URIs to preload")
    p.add_argument("--address", default="127.0.0.1:8080", help="bind address")
    p.add_argument("--models-path", default="models", help="model YAML/weights dir")
    p.add_argument("--context-size", type=int, default=None)
    p.add_argument("--threads", type=int, default=None)
    p.add_argument("--api-keys", nargs="*", default=None)
    p.add_argument("--cors", action="store_true")
    p.add_argument("--watchdog-idle-timeout", default=None)
    p.add_argument("--watchdog-busy-timeout", default=None)
    p.add_argument("--single-active-backend", action="store_true")
    p.add_argument("--parallel-requests", type=int, default=8,
                   help="a model's engine slots where its YAML gives no "
                        "`parallel`; its admission gate lets through that "
                        "many requests + max(2, that // 4) ahead of them, "
                        "which wait tokenised in the engine's queue")
    p.add_argument("--tensor-parallel", type=int, default=None,
                   help="shard each model over N chips (Megatron-style TP "
                        "on the 'model' mesh axis; int8 weights shard too). "
                        "A per-model YAML `mesh:` block overrides this; "
                        "default: auto-TP over every divisible device")
    p.add_argument("--backends-path", default=None,
                   help="installed external backends dir")
    p.add_argument("--backend-galleries", default=None,
                   help="comma-separated backend registry index URIs")
    p.add_argument("--galleries", default=None,
                   help="comma-separated gallery index YAMLs (path or URL)")
    p.add_argument("--env-file", default=None,
                   help=".env file to load (default: ./.env, ./.env.local)")
    p.add_argument("--disable-config-watcher", action="store_true",
                   help="do not hot-reload model YAMLs on change")
    # resilience knobs (ISSUE 4) — AppConfig fields, env LOCALAI_<NAME>
    p.add_argument("--request-timeout", type=float, default=None,
                   help="per-request deadline budget in seconds; propagated "
                        "through gRPC into the engine so expired slots are "
                        "evicted (default 600)")
    p.add_argument("--retry-budget", type=int, default=None,
                   help="transparent retries against a respawned backend "
                        "when a request fails before any bytes streamed "
                        "(default 1)")
    p.add_argument("--breaker-threshold", type=int, default=None,
                   help="consecutive backend failures before the circuit "
                        "breaker opens and loads fail fast (default 3)")
    p.add_argument("--breaker-cooldown", type=float, default=None,
                   help="seconds a tripped breaker stays open before a "
                        "half-open probe (default 15)")
    p.add_argument("--queue-depth", type=int, default=None,
                   help="per-model bounded wait queue beyond the in-flight "
                        "limit; excess requests get 429 + Retry-After "
                        "(default 8)")
    p.add_argument("--drain-timeout", type=float, default=None,
                   help="graceful-shutdown hard deadline: SIGTERM and "
                        "/backend/shutdown let in-flight requests finish "
                        "this long while new work gets 503 (default 30)")
    p.add_argument("--preempt-grace", type=float, default=None,
                   help="preemption spill-drain grace in seconds: on a "
                        "preemption notice (backend SIGTERM or "
                        "/backend/preempt) live slots run this long before "
                        "being frozen into resume checkpoints (default 0)")
    # KV lifecycle tier (engine/kvtier.py) — app-wide default; a per-model
    # YAML kv_policy wins
    p.add_argument("--kv-window", type=int, default=None,
                   help="retain only the last N tokens of KV per request "
                        "(attention-sink + sliding-window tier for 32k-128k "
                        "serving); 0/unset = full KV")
    p.add_argument("--kv-sinks", type=int, default=None,
                   help="keep the first N tokens (attention sinks) resident "
                        "alongside --kv-window")
    p.add_argument("--kv-host-bytes", type=int, default=None,
                   help="host-RAM KV spill tier budget in bytes (engine/"
                        "kvhost.py): device blocks evicted by slot reclaim "
                        "or the KV lifecycle tier are kept in host RAM "
                        "(int8 sub-channel) and re-admitted on prefix-cache "
                        "hits instead of re-prefilling; 0/unset disables. "
                        "Per-model YAML kv_host_bytes wins")
    p.add_argument("--trace", action="store_true",
                   help="record request/engine spans (LOCALAI_TRACE=1); "
                        "export via /debug/trace or `util trace`")
    p.add_argument("--log-level", default="info")
    return p


def _add_backend(sub):
    p = sub.add_parser("backend", help="run a single gRPC backend process")
    p.add_argument("--addr", default="127.0.0.1:50051")
    p.add_argument("--backend", default="jax-tpu")
    return p


def _add_federated(sub):
    p = sub.add_parser("federated",
                       help="run a federated load balancer over workers")
    p.add_argument("--address", default="127.0.0.1:9090")
    p.add_argument("--token", default="",
                   help="shared federation token (HMAC-signed requests; "
                        "default $LOCALAI_FEDERATION_TOKEN)")
    p.add_argument("--workers", default="",
                   help="comma-separated worker base URLs")
    p.add_argument("--strategy", default="least_used",
                   choices=["least_used", "random", "round_robin"])
    return p


def _add_tts(sub):
    p = sub.add_parser("tts", help="synthesize speech to a WAV file "
                                   "(reference core/cli/tts.go)")
    p.add_argument("text", help="text to speak")
    p.add_argument("--model", default="default-tts")
    p.add_argument("--voice", default="")
    p.add_argument("--language", default="")
    p.add_argument("--output-file", default="output.wav")
    p.add_argument("--models-path", default="models")
    return p


def _add_soundgeneration(sub):
    p = sub.add_parser("soundgeneration",
                       help="generate audio from a text description "
                            "(reference core/cli/soundgeneration.go)")
    p.add_argument("text", help="description of the sound to generate")
    p.add_argument("--model", default="default-tts")
    p.add_argument("--duration", type=float, default=2.0,
                   help="clip length in seconds")
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--output-file", default="output.wav")
    p.add_argument("--models-path", default="models")
    return p


def cli_soundgeneration(args) -> int:
    manager, handle = _one_shot_handle(args.model, args.models_path, "tts")
    try:
        import os

        dst = os.path.abspath(args.output_file)
        r = handle.client.sound_generation(
            text=args.text, duration=args.duration,
            temperature=args.temperature, dst=dst)
        if not r.success:
            print(f"sound generation failed: {r.message}")
            return 1
        print(dst)
        return 0
    finally:
        manager.stop_all()


def _add_transcript(sub):
    p = sub.add_parser("transcript",
                       help="transcribe an audio file "
                            "(reference core/cli/transcript.go)")
    p.add_argument("filename", help="audio file (16kHz WAV)")
    p.add_argument("--model", default="default-whisper")
    p.add_argument("--language", default="")
    p.add_argument("--translate", action="store_true")
    p.add_argument("--output-format", default="text",
                   choices=["text", "json", "srt"])
    p.add_argument("--models-path", default="models")
    return p


def _one_shot_handle(model: str, models_path: str, default_backend: str):
    """Spawn the backend for a one-shot CLI inference command."""
    from localai_tpu.config import AppConfig, ModelConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager

    import dataclasses

    app = AppConfig(models_path=models_path)
    cfg = ModelConfigLoader(models_path).get(model) if model else None
    if cfg is None:
        cfg = ModelConfig(name=model, backend=default_backend)
    elif not cfg.config_file and cfg.backend == "llm":
        # bare checkpoint dir auto-registered with the generic default —
        # this one-shot command knows the right backend role
        cfg = dataclasses.replace(cfg, backend=default_backend)
    manager = ModelManager(app)
    return manager, manager.load(cfg)


def cli_tts(args) -> int:
    manager, handle = _one_shot_handle(args.model, args.models_path, "tts")
    try:
        import os

        dst = os.path.abspath(args.output_file)
        r = handle.client.tts(text=args.text, voice=args.voice, dst=dst,
                              language=args.language)
        if not r.success:
            print(f"tts failed: {r.message}")
            return 1
        print(dst)
        return 0
    finally:
        manager.stop_all()


def cli_transcript(args) -> int:
    import json as _json
    import os

    manager, handle = _one_shot_handle(args.model, args.models_path,
                                       "whisper")
    try:
        r = handle.client.transcribe(dst=os.path.abspath(args.filename),
                                     language=args.language,
                                     translate=args.translate)
        if args.output_format == "json":
            print(_json.dumps({"text": r.text, "segments": [
                {"id": s.id, "start": s.start / 1e9, "end": s.end / 1e9,
                 "text": s.text} for s in r.segments]}))
        elif args.output_format == "srt":
            def ts(ns):
                s, ms = divmod(int(ns // 1e6), 1000)
                h, rem = divmod(s, 3600)
                m, s = divmod(rem, 60)
                return f"{h:02}:{m:02}:{s:02},{ms:03}"

            for i, seg in enumerate(r.segments, 1):
                print(f"{i}\n{ts(seg.start)} --> {ts(seg.end)}\n{seg.text}\n")
        else:
            print(r.text)
        return 0
    finally:
        manager.stop_all()


def _add_worker(sub):
    p = sub.add_parser(
        "worker",
        help="join a multi-host serving job (reference: worker_llamacpp.go)")
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator host:port (rank 0's host)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--model", required=True, help="model directory (all ranks)")
    p.add_argument("--dtype", default=None)
    p.add_argument("--context-size", type=int, default=None)
    p.add_argument("--parallel", type=int, default=4)
    p.add_argument("--mesh-data", type=int, default=None)
    p.add_argument("--mesh-model", type=int, default=None)
    p.add_argument("--replicate-port", type=int, default=39219,
                   help="rank 0's dispatch-broadcast port")
    p.add_argument("--addr", default="127.0.0.1:50051",
                   help="rank 0's gRPC backend bind address")
    return p


def _add_util(sub):
    p = sub.add_parser("util",
                       help="model utilities (reference: core/cli util cmd)")
    p.add_argument("action", choices=["hf-info", "fits", "trace",
                                      "flightrec", "sched"],
                   help="hf-info: checkpoint geometry + params; "
                            "fits: HBM fit estimate; "
                            "trace: pull a Chrome-trace + the engine "
                            "thread's phase times from a running server; "
                            "flightrec: dump the server's flight recorder "
                            "(recent request timelines + SLO percentiles); "
                            "sched: scheduler X-ray (reason-code counters, "
                            "pack composition, per-variant rooflines)")
    p.add_argument("model", help="checkpoint directory (hf-info/fits) or "
                                 "server address (trace/flightrec/sched)")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--context", type=int, default=2048)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--cache-type", default="")
    p.add_argument("--hbm-gb", type=float, default=None)
    p.add_argument("--out", default="",
                   help="trace: output Chrome-trace file "
                        "(default trace.json); "
                        "flightrec: output dump file (default stdout)")
    p.add_argument("--api-key", default="",
                   help="trace: bearer token for a key-protected server")
    return p


def cli_util_trace(args) -> int:
    """`local-ai util trace <addr>` — fetch /debug/trace into a Chrome-trace
    file (open at chrome://tracing) and print where each model's engine
    thread spent its time (the always-on phase counters of
    /backend/monitor). Spans need a server run with --trace."""
    import json as _json
    import urllib.request

    base = args.model if args.model.startswith("http") \
        else f"http://{args.model}"

    def fetch(path):
        req = urllib.request.Request(base + path)
        if args.api_key:
            req.add_header("Authorization", f"Bearer {args.api_key}")
        with urllib.request.urlopen(req, timeout=30) as r:
            return _json.loads(r.read().decode())

    trace = fetch("/debug/trace")
    out = args.out or "trace.json"
    with open(out, "w") as fh:
        _json.dump(trace, fh)
    n = len(trace.get("traceEvents", []))
    print(f"{out}: {n} events")
    for model, st in (fetch("/backend/monitor") or {}).items():
        phases = {k: v for k, v in (st.get("metrics") or {}).items()
                  if k.startswith(("engine_host_ms__", "engine_wait_ms__"))}
        total = sum(phases.values())
        if not total:
            continue
        print(f"\n{model}: engine thread, {total / 1e3:.1f} s")
        for key, ms in sorted(phases.items(), key=lambda kv: -kv[1]):
            kind, _, phase = key[len("engine_"):].partition("_ms__")
            print(f"  {phase:<9} {kind:<5} {ms / total:>6.1%}  "
                  f"{ms:>11.1f} ms")
    return 0


def cli_util_flightrec(args) -> int:
    """`local-ai util flightrec <addr>` — pull /debug/flightrec +
    /debug/slo from a running server: recent request timelines, engine
    ticks, tripwire/breaker/supervision events, and the current latency
    percentiles. JSON goes to --out (or stdout); a summary to stderr."""
    import json as _json
    import sys as _sys
    import urllib.request

    base = args.model if args.model.startswith("http") \
        else f"http://{args.model}"

    def fetch(path):
        req = urllib.request.Request(base + path)
        if args.api_key:
            req.add_header("Authorization", f"Bearer {args.api_key}")
        with urllib.request.urlopen(req, timeout=30) as r:
            return _json.loads(r.read().decode())

    dump = fetch("/debug/flightrec")
    slo = fetch("/debug/slo")
    payload = {"flightrec": dump, "slo": slo}
    if args.out:
        with open(args.out, "w") as fh:
            _json.dump(payload, fh, indent=1)
        print(f"wrote {args.out}")
    else:
        print(_json.dumps(payload, indent=1))
    for model, rec in (dump.get("models") or {}).items():
        reqs = (rec or {}).get("requests") or []
        events = (rec or {}).get("events") or []
        print(f"{model}: {len(reqs)} recent requests, "
              f"{len(events)} events in the ring", file=_sys.stderr)
    for model, snap in (slo.get("models") or {}).items():
        e2e = (snap or {}).get("e2e") or {}
        if e2e.get("count"):
            print(f"{model}: e2e p50 {e2e.get('p50_ms', 0):.0f} ms  "
                  f"p95 {e2e.get('p95_ms', 0):.0f} ms  "
                  f"p99 {e2e.get('p99_ms', 0):.0f} ms  "
                  f"({e2e['count']} requests)", file=_sys.stderr)
    return 0


def cli_util_sched(args) -> int:
    """`local-ai util sched <addr>` — pull /debug/sched from a running
    server and print the scheduler X-ray: reason-code counters grouped by
    category, pack-composition totals (budget utilization, pad-row
    fraction), per-variant dispatch counts with their cost-analysis
    rooflines, and the most recent ticks. Raw JSON to --out when given."""
    import json as _json
    import sys as _sys
    import urllib.request

    base = args.model if args.model.startswith("http") \
        else f"http://{args.model}"

    req = urllib.request.Request(base + "/debug/sched")
    if args.api_key:
        req.add_header("Authorization", f"Bearer {args.api_key}")
    with urllib.request.urlopen(req, timeout=30) as r:
        payload = _json.loads(r.read().decode())
    if args.out:
        with open(args.out, "w") as fh:
            _json.dump(payload, fh, indent=1)
        print(f"wrote {args.out}")
    registry = payload.get("reason_codes") or {}
    saw_any = False
    for model, snap in (payload.get("models") or {}).items():
        if not snap:
            continue
        saw_any = True
        print(f"{model}: {snap.get('ticks_total', 0)} ticks, "
              f"{snap.get('dispatches_total', 0)} dispatches")
        print(f"  pad rows {snap.get('pad_rows_frac', 0):.1%}")
        reasons = snap.get("reason_counters") or {}
        if reasons:
            width = max(len(c) for c in reasons)
            print("  reason codes:")
            for code, n in sorted(reasons.items(), key=lambda kv: -kv[1]):
                cat = (registry.get(code) or {}).get("category", "?")
                print(f"    {code:<{width}}  x{n:<8d} [{cat}]")
        variants = snap.get("variants") or {}
        roofs = snap.get("rooflines") or {}
        if variants:
            width = max(len(v) for v in variants)
            print("  variants:")
            for name, n in sorted(variants.items(), key=lambda kv: -kv[1]):
                roof = roofs.get(name) or {}
                extra = ""
                if roof:
                    extra = (f"  {roof.get('cost_flops', 0):.3g} flops  "
                             f"{roof.get('cost_bytes', 0):.3g} B  "
                             f"{roof.get('bound', '?')}-bound  "
                             f"mfu≤{roof.get('mfu', 0):.1%}")
                print(f"    {name:<{width}}  x{n:<8d}{extra}")
        kvh = snap.get("kv_host") or {}
        if kvh:
            print(f"  kv host tier: {kvh.get('blocks', 0)} blocks "
                  f"({kvh.get('bytes', 0) / 1e6:.1f} MB, peak "
                  f"{kvh.get('peak_bytes', 0) / 1e6:.1f} MB of "
                  f"{kvh.get('budget_bytes', 0) / 1e6:.1f} MB)  "
                  f"hits {kvh.get('hits', 0)}  "
                  f"spills {kvh.get('spills', 0)}  "
                  f"evictions {kvh.get('evictions', 0)}")
        ticks = snap.get("recent_ticks") or []
        if ticks:
            print(f"  last tick: {_json.dumps(ticks[-1])}", file=_sys.stderr)
    if not saw_any:
        print("no scheduler ledger (run the backend with LOCALAI_SCHED=1)")
    return 0


def cli_util(args) -> int:
    import json as _json

    if args.action == "trace":
        return cli_util_trace(args)
    if args.action == "flightrec":
        return cli_util_flightrec(args)
    if args.action == "sched":
        return cli_util_sched(args)

    from localai_tpu.engine.loader import load_config
    from localai_tpu.system.memory import estimate, param_count

    cfg = load_config(args.model)
    if args.action == "hf-info":
        print(_json.dumps({
            "architecture": "llama-family",
            "hidden_size": cfg.hidden_size,
            "layers": cfg.num_layers,
            "heads": cfg.num_heads,
            "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "intermediate_size": cfg.intermediate_size,
            "vocab_size": cfg.vocab_size,
            "max_position": cfg.max_position,
            "num_experts": cfg.num_experts,
            "rope_scaling": cfg.rope_scaling,
            "parameters": param_count(cfg),
        }, indent=1))
        return 0
    if args.hbm_gb:
        hbm = int(args.hbm_gb * 2**30)
    else:
        # table lookup on the operator's forced capability only — a
        # pre-flight CLI never inits a device client (it would contend for
        # the chip with a running server); without --hbm-gb or a forced
        # capability, `fits` is unknown
        from localai_tpu.system.capabilities import detect_capability
        from localai_tpu.system.memory import hbm_table_bytes

        hbm = hbm_table_bytes(detect_capability())
    est = estimate(cfg, slots=args.slots, context=args.context,
                   dtype=args.dtype, cache_type=args.cache_type,
                   hbm_bytes=hbm, detect_hbm=False)
    print(_json.dumps(est.to_dict(), indent=1))
    return 0


def _add_launcher(sub):
    p = sub.add_parser("launcher",
                       help="interactive server controller "
                            "(reference: cmd/launcher GUI role)")
    p.add_argument("--address", default="127.0.0.1:8080")
    p.add_argument("--models-path", default="models")
    p.add_argument("--autostart", action="store_true")
    return p


def _add_explorer(sub):
    p = sub.add_parser("explorer",
                       help="federation dashboard + network discovery "
                            "(reference: core/cli/explorer.go)")
    p.add_argument("--address", default="127.0.0.1:8509")
    p.add_argument("--pool-database", default="explorer.json")
    p.add_argument("--with-sync", action="store_true",
                   help="poll registered networks in the background")
    p.add_argument("--only-sync", action="store_true",
                   help="run the discovery crawler without the dashboard")
    p.add_argument("--interval", type=float, default=50.0)
    p.add_argument("--threshold", type=int, default=3)
    return p


def _add_models(sub):
    p = sub.add_parser("models", help="list or install models")
    p.add_argument("action", choices=["list", "install"], nargs="?", default="list")
    p.add_argument("name", nargs="?", default=None)
    p.add_argument("--models-path", default="models")
    p.add_argument("--galleries", default=None)
    return p


def _add_backends(sub):
    p = sub.add_parser("backends",
                       help="list, install, or uninstall serving backends "
                            "(reference: core/cli backends cmd)")
    p.add_argument("action", choices=["list", "install", "uninstall"],
                   nargs="?", default="list")
    p.add_argument("name", nargs="?", default=None)
    p.add_argument("--backends-path", default="backends")
    p.add_argument("--backend-galleries", default=None,
                   help="comma-separated backend registry index URIs")
    p.add_argument("--capability", default=None,
                   help="override detected capability for meta resolution")
    return p


def cli_backends(args) -> int:
    from localai_tpu.services.backend_gallery import (
        BackendGallery, delete_backend, install_backend,
        list_system_backends,
    )

    if args.action == "list":
        for b in list_system_backends(args.backends_path):
            kind = "system" if b.get("system") else "installed"
            extra = (f" -> {b['meta_backend_for']}"
                     if b.get("meta_backend_for") else "")
            print(f"{b['name']}\t{kind}{extra}")
        return 0
    if not args.name:
        print("backend name required", file=sys.stderr)
        return 2
    if args.action == "uninstall":
        delete_backend(args.backends_path, args.name)
        print(f"uninstalled {args.name}")
        return 0
    sources = [s.strip() for s in (args.backend_galleries or "").split(",")
               if s.strip()]
    if not sources:
        print("--backend-galleries required for install", file=sys.stderr)
        return 2
    path = install_backend(BackendGallery(sources), args.name,
                           args.backends_path, capability=args.capability)
    print(f"installed {args.name} -> {path}")
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="localai-tpu",
        description="TPU-native OpenAI-compatible inference server",
    )
    sub = parser.add_subparsers(dest="cmd")
    _add_run(sub)
    _add_backend(sub)
    _add_models(sub)
    _add_backends(sub)
    _add_explorer(sub)
    _add_launcher(sub)
    _add_util(sub)
    _add_federated(sub)
    _add_worker(sub)
    _add_tts(sub)
    _add_soundgeneration(sub)
    _add_transcript(sub)
    sub.add_parser("version", help="print version")

    args = parser.parse_args(argv)
    cmd = args.cmd
    if cmd is None:
        parser.print_help()
        return 1

    if cmd == "version":
        from localai_tpu.version import __version__

        print(__version__)
        return 0
    if cmd == "backend":
        from localai_tpu.backend.server import serve_blocking

        return serve_blocking(addr=args.addr, backend=args.backend)
    if cmd == "models":
        from localai_tpu.services.gallery import cli_models

        return cli_models(args)
    if cmd == "backends":
        return cli_backends(args)
    if cmd == "explorer":
        from localai_tpu.explorer import run_explorer

        return run_explorer(args)
    if cmd == "launcher":
        from localai_tpu.launcher import run_launcher

        return run_launcher(args)
    if cmd == "util":
        return cli_util(args)
    if cmd == "federated":
        from localai_tpu.federation import run_federated

        return run_federated(args)
    if cmd == "worker":
        from localai_tpu.core.worker import run_worker

        return run_worker(args)
    if cmd == "tts":
        return cli_tts(args)
    if cmd == "soundgeneration":
        return cli_soundgeneration(args)
    if cmd == "transcript":
        return cli_transcript(args)
    if cmd == "run":
        from localai_tpu.server.http import run_server

        return run_server(args)
    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
