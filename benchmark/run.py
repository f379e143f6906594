#!/usr/bin/env python3
"""One cell, once, through the served path.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

HTTP -> core/manager.py -> gRPC -> backend subprocess -> Engine: the path a
user's client takes. This process never imports JAX (the chip belongs to the
backend); what it says about the device is what the backend reports through
/system and /backend/monitor. See benchmark/README.md for the layout, the
end-of-window rules and the exit codes.

Exit codes: 0 a result line was printed; 1 nothing could be measured (the
reason and the server's last lines are on stderr); 2 the checkout lacks the
program or the arguments name nothing; 3 --cpu-rehearsal (never a result).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import subprocess
import sys
import time

T0 = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

from benchmark.harness import client, json_dir, readers, server, traffic  # noqa: E402
from benchmark.harness.server import BenchFailure  # noqa: E402

TEMPLATE_TOKENS = 4          # bos, t4, t5, t6 around the user's words
LOAD_LIMIT_S = 1100.0        # the first run of a cell compiles everything
SLICE_S = 3.0                # the traced slice of the window
SLICE_AT = (0.4, 0.72)       # where in the window it is taken, and retaken
TRACE_DONE_LIMIT_S = 90.0
TRACE_PARSE_LIMIT_S = 90.0


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_peaks(device_kind: str) -> dict | None:
    for _, d in json_dir(os.path.join(BENCH_DIR, "peaks")):
        if d["device_kind"] == device_kind:
            return d
    return None


def clean_env(rehearsal: bool, cache_dir: str, work: str) -> dict:
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("LOCALAI_")}       # a user's defaults, no more
    env["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    # pinned: with JAX_PLATFORMS unset JAX falls back to the CPU by itself
    env["JAX_PLATFORMS"] = "cpu" if rehearsal else "tpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["BENCH_PYTHON"] = sys.executable
    env["BENCH_TRACE_CTL"] = os.path.join(work, "ctl")
    env["BENCH_TRACE_DIR"] = os.path.join(work, "trace")
    return env


# ------------------------------------------------------------ warm-up, checks

def shape_warmup(port: int, model: str, srv: dict, vocab: int, seed: int) -> dict:
    """Send every shape the window can dispatch before it opens: for each
    prefill bucket, bursts that land in the admission groups of 8, 4, 2 and
    1, and one prompt long enough for a middle and a final chunk. The
    program's own prewarm covers the decode programs and one bucket only."""
    import random

    rng = random.Random(seed ^ 0x5EED)
    ctx, slots = srv["context_size"], srv["parallel"]

    def wave(size: int, n: int) -> list:
        return [traffic.Request(i, 0.0, [rng.randrange(8, vocab)
                                         for _ in range(n)], 6)
                for i in range(size)]

    waves = []       # each is sent at once and awaited whole before the next
    sizes = [s for s in (8, 3, 2, 1) if s <= slots] or [1]
    for bucket in srv["prefill_buckets"]:
        n = min(bucket, ctx - 32) - TEMPLATE_TOKENS - 2
        if n >= 1:
            waves += [wave(size, n) for size in sizes]
    chunk = srv.get("prefill_chunk", 512)
    long_n = min(2 * chunk + 64, ctx - 32)
    if long_n > chunk:
        waves.append(wave(1, long_n))
    t_begin = time.monotonic()
    bad = []
    for reqs in waves:
        recs = asyncio.run(client.run_open_loop(
            port, model, [("warmup", reqs, 0.0)], {}, time.monotonic(),
            time.monotonic() + 900.0, wait_all=True))
        bad += [r.error or f"finish={r.finish} tokens={r.tokens}"
                for r in recs
                if r.error or r.finish != "length" or r.tokens != r.max_tokens]
    if bad:
        raise BenchFailure(f"warm-up requests failed: {bad[:3]}")
    return {"requests": sum(len(w) for w in waves),
            "seconds": time.monotonic() - t_begin}


def greedy_ids(port: int, model: str, seed_words: list) -> list:
    """One explicit greedy request with a prompt under the slot prompt
    cache's 16-token minimum; the text is the ids."""
    r = server.http_json(port, "POST", "/v1/chat/completions", dict(
        model=model, stream=False, temperature=0.0, max_tokens=32,
        ignore_eos=True,
        messages=[{"role": "user", "content": " ".join(seed_words)}]),
        timeout=180.0)
    ch = r["choices"][0]
    words = (ch.get("message") or {}).get("content", "").split()
    if ch.get("finish_reason") != "length" or len(words) != 32:
        raise BenchFailure(f"greedy check: finish={ch.get('finish_reason')!r}, "
                           f"{len(words)} tokens (want 32, 'length')")
    return words


# ------------------------------------------------------------------ the trace

class Slice:
    """The traced slice: touch `start`, wait for the wrapper's `started`,
    sleep, touch `stop`. No counter is sampled around it: /backend/monitor
    takes seconds under load (the backend answers between dispatches), and
    the engine credits its step counter a fused loop at a time, so what the
    slice did is counted from the trace and the generator's own records.
    Never raises: a profiler that does not start is recorded and the slice
    retaken once."""

    def __init__(self, ctl: str, srv: "server.Server"):
        self.ctl, self.srv = ctl, srv
        self.taken = None        # {"c0", "c1", "m0", "m1", "started", ...}
        self.errors: list = []

    def _path(self, name: str) -> str:
        return os.path.join(self.ctl, name)

    async def _wait_file(self, names: tuple, limit: float) -> str | None:
        deadline = time.monotonic() + limit
        while time.monotonic() < deadline:
            for n in names:
                if os.path.exists(self._path(n)):
                    return n
            await asyncio.sleep(0.02)
        return None

    async def take(self) -> None:
        if self.taken is not None:
            return
        for n in ("started", "done", "error"):
            if os.path.exists(self._path(n)):
                os.remove(self._path(n))
        open(self._path("start"), "w").close()
        got = await self._wait_file(("started", "error"), 20.0)
        if got != "started":
            why = (open(self._path("error")).read()[-600:] if got == "error"
                   else "the profiler did not start within 20 s")
            self.errors.append(why)
            if got is None and os.path.exists(self._path("start")):
                os.remove(self._path("start"))
            return
        started = load_json(self._path("started"))
        m0, w0 = time.monotonic(), time.time_ns()
        await asyncio.sleep(SLICE_S)
        m1, w1 = time.monotonic(), time.time_ns()
        open(self._path("stop"), "w").close()
        self.taken = dict(m0=m0, m1=m1, w0=w0, w1=w1, started=started)

    def wait_done(self, limit: float) -> dict | None:
        deadline = time.monotonic() + limit
        while time.monotonic() < deadline:
            if os.path.exists(self._path("done")):
                return load_json(self._path("done"))
            if os.path.exists(self._path("error")):
                self.errors.append(open(self._path("error")).read()[-600:])
                return None
            time.sleep(0.05)
        self.errors.append(f"stop_trace did not return within {limit:.0f} s")
        return None


def parse_trace(work: str, sl: dict, done: dict, records: list,
                end: float, keep: str | None, config: dict) -> dict:
    """Reduce the .xplane.pb in a child process (JAX on the CPU platform,
    after the server is gone). The load generator's spans go with it, moved
    from the monotonic to the unix clock."""
    def spans(xs):
        return [[int(sl["w0"] + (t - sl["m0"]) * 1e9) for t in x] for x in xs]

    req = {
        "window": [sl["started"]["returned_ns"], done["called_ns"]],
        "in_flight": spans(client.in_flight_intervals(records, end)),
        "decoding": spans(client.decoding_intervals(records, end)),
        "wall_started_ns": sl["started"]["returned_ns"],
        "wall_stopped_ns": done["called_ns"],
        "dump_raw": keep, "config": config,
    }
    req_path, out_path = (os.path.join(work, "trace_request.json"),
                          os.path.join(work, "trace_facts.json"))
    with open(req_path, "w") as f:
        json.dump(req, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "xplane.py"),
         os.path.join(work, "trace"), req_path, out_path],
        env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=TRACE_PARSE_LIMIT_S)
    if proc.returncode != 0:
        raise BenchFailure(f"the trace parser exited {proc.returncode}: "
                           f"{proc.stderr[-600:]}")
    return load_json(out_path)


def mean_context(records: list, times: tuple) -> float | None:
    """Mean tokens of context over the requests decoding at each of `times`
    (the slice's start, middle and end, pooled: one instant can fall where
    a request has just ended and the next has no first token yet)."""
    ctxs = []
    for at in times:
        for r in records:
            if r.first is None or r.first > at:
                continue
            if r.done is not None and r.done <= at:
                continue
            so_far = sum(n for t, n in r.token_times if t <= at)
            ctxs.append(r.prompt_tokens + TEMPLATE_TOKENS + so_far)
    return sum(ctxs) / len(ctxs) if ctxs else None


# ----------------------------------------------------------------------- main

def end_to_end_value(name: str, acct: dict, setup_s: float) -> float | None:
    if client.STAT_NAME.fullmatch(name):
        return client.stat(acct, name)
    if name == "tokens_per_s":
        return acct["tokens_in_window"] / acct["window_s"]
    if name == "setup_s":
        return setup_s
    raise BenchFailure(f"BENCHMARK.json names the end-to-end metric {name!r}, "
                       f"which benchmark/run.py cannot compute")


def cell_metrics(entries: list, workload: str) -> list:
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def run(args) -> int:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"no workload {args.workload!r} in BENCHMARK.json "
              f"(have {sorted(cells)})", file=sys.stderr)
        return 2
    if not os.path.isfile(os.path.join(ROOT, "localai_tpu", "cli.py")):
        print("this checkout holds the benchmark and not the program "
              "(no localai_tpu/cli.py): nothing to measure", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    spec = traffic.load_traffic(BENCH_DIR, cell["name"], cell["traffic"])
    rehearsal = args.cpu_rehearsal
    if args.rate is not None:
        if not rehearsal:
            print("--rate is for --cpu-rehearsal: a cell's rate is fixed in "
                  "its traffic file", file=sys.stderr)
            return 2
        spec["rate_rps"] = args.rate
    traced = args.trace == 1
    seconds = float(args.seconds)

    work = server.fresh_dir(os.path.join(ROOT, ".bench_work", cell["name"]))
    os.makedirs(os.path.join(work, "models"))
    os.makedirs(os.path.join(work, "ctl"))
    os.makedirs(os.path.join(work, "trace"))
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    env = clean_env(rehearsal, cache_dir, work)
    model = cell["config"]
    srv_fields = server.write_model(
        os.path.join(work, "models"), model, config,
        backend="llm-traced" if traced else "llm", rehearsal=rehearsal)
    hf = server.hf_config(config, rehearsal)
    vocab = hf["vocab_size"]
    cache_before = server.cache_entries(cache_dir)

    srv = server.Server(
        ROOT, work, model, env, slots=srv_fields["parallel"],
        queue_depth=srv_fields.get("queue_depth", 8),
        backends_path=os.path.join(BENCH_DIR, "backends") if traced else None)
    stopped = None
    try:
        loaded = srv.wait_loaded(LOAD_LIMIT_S)
        dev = loaded["device"]
        say(f"loaded in {loaded['phases']['loaded_s']:.1f} s "
            f"(LoadModel {dev.get('load_seconds')}) on {dev.get('platform')} "
            f"{dev.get('device_kind')!r} x{dev.get('device_count')}, "
            f"tiers {dev.get('tiers')}, compile cache {cache_dir} "
            f"({cache_before} entries before)")
        peaks = None
        if rehearsal:
            if dev.get("platform") != "cpu":
                raise BenchFailure("a rehearsal must run on the CPU, the "
                                   f"backend reports {dev.get('platform')!r}")
        else:
            if dev.get("platform") != "tpu":
                raise BenchFailure(
                    f"the backend serves from {dev.get('platform')!r}, not a TPU")
            peaks = find_peaks(dev.get("device_kind", ""))
            if peaks is None:
                raise BenchFailure(
                    f"device kind {dev.get('device_kind')!r} is not in "
                    f"benchmark/peaks/: no roofline can be stated for it")
            if dev.get("device_count", 0) < cell["chips"]:
                raise BenchFailure(
                    f"the cell asks for {cell['chips']} chip(s), the backend "
                    f"holds {dev.get('device_count')}")

        warm = shape_warmup(srv.port, model, srv_fields, vocab, args.seed)
        say(f"shape warm-up: {warm['requests']} requests in "
            f"{warm['seconds']:.1f} s")
        # the probe's words are fixed: the weights are the program's own
        # (one fixed seed), so every run of a cell must give the same ids
        probe = [f"t{8 + (7919 * (i + 1)) % (vocab - 8)}" for i in range(9)]
        ids_before = greedy_ids(srv.port, model, probe)
        ids_again = greedy_ids(srv.port, model, probe)

        warm_s = float(spec.get("warmup_seconds", 4))
        ctx_tokens = srv_fields["context_size"]
        warm_reqs = traffic.schedule(spec, args.seed ^ 0xA5A5A5, warm_s, vocab,
                                     ctx_tokens, TEMPLATE_TOKENS)
        reqs = traffic.schedule(spec, args.seed, seconds, vocab, ctx_tokens,
                                TEMPLATE_TOKENS)
        target = spec.get("model") or model
        sampling = dict(spec.get("sampling") or {})
        samples: dict = {}
        slice_ = Slice(os.path.join(work, "ctl"), srv)
        t0 = time.monotonic() + 0.25
        start, end = t0 + warm_s, t0 + warm_s + seconds

        def monitor_or_none():
            # a sample that cannot be had costs the per-layer metrics that
            # read it, never the run; it is asked for three times, because
            # the driver refuses a traced line that lacks a metric
            for attempt in (1, 2, 3):
                try:
                    return srv.monitor()
                except (BenchFailure, OSError, ValueError) as e:
                    say(f"/backend/monitor could not be sampled "
                        f"(attempt {attempt} of 3): {e}")
            return None

        async def sample_start():
            loop = asyncio.get_running_loop()
            samples["cache0"] = server.cache_entries(cache_dir)
            samples["c0"] = await loop.run_in_executor(None, monitor_or_none)

        hooks = [(start, sample_start)]
        if traced:
            hooks += [(start + f * seconds, slice_.take) for f in SLICE_AT
                      if f * seconds + SLICE_S + 1.0 < seconds]
            if len(hooks) == 1:
                hooks.append((start + max(0.0, (seconds - SLICE_S) / 2),
                              slice_.take))
        setup_s = start - T0
        say(f"window: {len(reqs)} requests at {spec['rate_rps']} req/s over "
            f"{seconds:.0f} s after {warm_s:.0f} s of the same traffic "
            f"({len(warm_reqs)} requests); set-up {setup_s:.1f} s")
        records = asyncio.run(client.run_open_loop(
            srv.port, target, [("warmup", warm_reqs, 0.0),
                               ("window", reqs, warm_s)],
            sampling, t0, end, hooks=hooks))
        samples["c1"] = monitor_or_none()
        cache_after = server.cache_entries(cache_dir)
        acct = client.account(records, start, end)
        say(f"attempted {acct['attempted']}, finished {acct['finished']}, "
            f"cut {acct['cut']}, failed {acct['failed']} {acct['failures']}, over "
            f"length {acct['over_length']} {acct['over_length_seen']}; "
            f"{acct['ttft_lower_bound']} without a first token (counted with "
            f"their wait so far); {acct['tokens_in_window']} tokens streamed "
            f"in the window; generator lateness median "
            f"{acct['lateness_ms_median']:.2f} ms, max "
            f"{acct['lateness_ms_max']:.2f} ms; compile cache entries "
            f"{cache_before} -> {samples.get("cache0")} at the window's start -> "
            f"{cache_after} at its end")

        # the same request on the same idle engine as before the window: the
        # first call outlasts the dispatch in which the engine drops the
        # requests cut above (a greedy row decoded beside sampled rows runs
        # another variant of the decode program, and on random weights' near
        # ties another rounding picks another token), the second is compared
        try:
            greedy_ids(srv.port, model, probe)
            ids_after = greedy_ids(srv.port, model, probe)
        except (BenchFailure, OSError) as e:
            say(f"greedy check after the window failed: {e}")
            ids_after = None
        final = monitor_or_none() or samples["c1"] or {"device": dev}
        done = None
        if traced and slice_.taken is not None:
            say(f"waiting for stop_trace (limit {TRACE_DONE_LIMIT_S:.0f} s)")
            done = slice_.wait_done(TRACE_DONE_LIMIT_S)
        stopped = srv.stop()
        say(f"server stopped in {stopped['stop_s']:.1f} s "
            f"(leftover processes: {stopped['leftover']})")
    except BaseException:
        if stopped is None:
            tail = srv.log_tail(40)
            srv.stop()
            print("---- last lines of the server's and backend's log ----\n"
                  + tail, file=sys.stderr, flush=True)
        raise

    # ------------------------------------------------------------ the result
    device = {"platform": dev["platform"], "kind": dev["device_kind"],
              "count": dev["device_count"],
              "memory_peak_bytes": max(
                  (d.get("peak_bytes_in_use") or 0)
                  for d in (final["device"].get("devices") or [{}]))}
    correct = (acct["failed"] == 0 and ids_before == ids_again == ids_after
               and not stopped["leftover"] and acct["attempted"] > 0)
    for name, other in (("asked again", ids_again), ("after the window",
                                                     ids_after)):
        if other != ids_before:
            at = next((i for i, (a, b) in enumerate(zip(ids_before, other or []))
                       if a != b), len(other or []))
            say(f"the greedy request gave other ids {name} than at first, "
                f"from token {at}: {ids_before[:at + 2]} / {(other or [])[:at + 2]}")
    out = {"correct": bool(correct), "attempted": acct["attempted"],
           "failed": acct["failed"], "cut": acct["cut"],
           "over_length": acct["over_length"], "metrics": {},
           "device": device, "workload": cell["name"], "seed": args.seed,
           "compile_cache_new_in_window": cache_after - samples.get("cache0", cache_after),
           "extras": client.extras(acct)}

    e2e = {}
    for m in cell_metrics(bench["end_to_end"], cell["name"]):
        v = end_to_end_value(m["name"], acct, setup_s)
        if v is not None:
            e2e[m["name"]] = {"value": v, "unit": m["unit"]}

    if traced:
        facts = None
        if done is not None:
            keep = (os.path.join(ROOT, args.keep_trace)
                    if args.keep_trace else None)
            parsed = parse_trace(work, slice_.taken, done, records, end, keep,
                                 hf)
            facts = parsed["facts"] or None
            say(f"trace: {json.dumps(parsed['summary'])}")
            shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)
        for e in slice_.errors:
            say(f"trace slice: {e}")
        if not rehearsal and (not facts or facts["busy_s"] <= 0):
            raise BenchFailure(
                "the traced run has no device trace to read (profiler errors: "
                f"{slice_.errors or 'none'}; device planes with events: "
                f"{bool(facts)}): a --trace 1 line must carry busy_s > 0")
        sl = slice_.taken
        ctx = {
            "counters": {"window": (
                (samples.get("c0") or {}).get("metrics"),
                (samples.get("c1") or {}).get("metrics"))},
            "records": records, "acct": acct, "window": (start, end),
            "system": dev, "trace": None if rehearsal else facts,
            "slice": None, "config": hf, "serving": srv_fields, "peaks": peaks,
        }
        if sl:
            mc = mean_context(records, (sl["m0"], (sl["m0"] + sl["m1"]) / 2,
                                        sl["m1"]))
            ctx["slice"] = ({"mean_context": mc, "span": (sl["m0"], sl["m1"])}
                            if mc else None)
        layer = readers.load_layer_metrics(BENCH_DIR)
        for m in cell_metrics(bench["per_layer"], cell["name"]):
            spec_m = layer.get(m["name"])
            if spec_m is None or not readers.applies(spec_m, cell["name"]):
                continue
            if rehearsal and spec_m["reader"] in readers.DEVICE_READERS:
                continue
            v = readers.read(spec_m, ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
            else:
                say(f"per-layer metric {m['name']} left out: its reader "
                    f"{spec_m['reader']!r} found nothing to read (counter "
                    f"samples {[k for k in ('c0', 'c1') if samples.get(k)]}, "
                    f"slice {ctx['slice']}, trace classes "
                    f"{sorted((facts or {}).get('class_s', {}))})")
        if ctx.get("notes"):
            say(f"roofline: {json.dumps(ctx['notes'])}")
        if facts and not rehearsal:
            device["busy_s"] = facts["busy_s"]
            device["window_s"] = facts["window_s"]
            out["breakdown"] = {"device_ops": facts["device_ops"],
                                "idle_gaps": facts["idle_gaps"]}
            say(f"device time by class of module: {facts['class_s']}, runs "
                f"{facts['class_runs']}, steps counted {facts['class_steps']}; "
                f"idle by label: {facts['idle_s_by_label']}")
        out["end_to_end_traced"] = e2e
    else:
        out["metrics"] = e2e

    if rehearsal:
        # a rehearsal proves the script, never the system: no `metrics` key,
        # no device number, and an exit code no driver takes for a result
        out["rehearsal_metrics"] = out.pop("metrics")
        out.pop("breakdown", None)
        out["rehearsal"] = True
        print(json.dumps(out), flush=True)
        return 3
    print(json.dumps(out), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny geometry on the CPU: rehearses this script, "
                         "prints no device metric, exits 3")
    ap.add_argument("--rate", type=float, default=None,
                    help="override the traffic file's rate (rehearsals only)")
    ap.add_argument("--keep-trace", default=None,
                    help="also write the trace's device events as JSON here "
                         "(a path inside the checkout)")
    args = ap.parse_args()
    try:
        return run(args)
    except BenchFailure as e:
        print(f"BENCHMARK FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except Exception as e:       # a fault of the harness: say which
        import traceback

        traceback.print_exc()
        print(f"BENCHMARK FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
