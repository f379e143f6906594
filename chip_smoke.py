#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives the main path once through the entry points a user calls:

    python -m localai_tpu.cli run  (HTTP)  →  ModelManager
        →  python -m localai_tpu.backend  (gRPC subprocess)  →  Engine

at the full width and depth of Llama-3.1-8B (32 layers, h 4096, 32/8 heads x
128, vocab 128256), int8 weights, int8 KV, 16 slots, ctx 1024. Weights are the
loader's synthetic ones (made on device from a fixed seed); the tokenizer is
generated here so that token id i is the word "t<i>" — prompts have an exact
length and the generated ids can be read back from the text.

Three server lifetimes run one after another, each ended with SIGTERM and a
wait for every process it started:
  A  defaults (dense KV), cold;
  B  the same again — LoadModel should now hit the compile cache A filled;
  C  the same model with `kv_pages` set (the paged pool the prefix cache,
     kvtier, kvhost and resume all stand on).
A lifetime starting at all is the check that the one before released the chip.

Each lifetime answers /system before any request, then: one streamed
/v1/chat/completions, the same greedy /v1/completions twice (same ids), one
prompt longer than the 512-token prefill chunk, and a concurrent burst over
most of the slots. `ignore_eos` + a fixed `max_tokens` make every token count
exact; an engine error surfaces as a short stream, so counts and
finish_reason are checked, not only HTTP 200.

This process never imports JAX: a chip belongs to one process at a time, and
it belongs to the backend. Everything reported about the device is what the
backend says through the server's /system. The children get JAX_PLATFORMS
pinned, so a TPU that cannot be had is an error, not a quiet CPU run.

    python3 chip_smoke.py                  # needs the chip; exit 0 = proved
    python3 chip_smoke.py --cpu-rehearsal  # tiny model on the CPU: checks the
                                           # script, prints "device": "cpu",
                                           # can never print the pass line

Last line of stdout on success, and only then:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
MODEL = "smoke"

# Llama-3.1-8B as published (config.json of meta-llama/Llama-3.1-8B), context
# cut to what the smoke serves
LLAMA_8B = dict(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336,
    num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
    head_dim=128, max_position_embeddings=8192, rope_theta=500000.0,
    tie_word_embeddings=False)
# the rehearsal's stand-in: same code paths, nothing like the same size
TINY = dict(
    vocab_size=512, hidden_size=128, intermediate_size=256,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=32, max_position_embeddings=2048, tie_word_embeddings=True)

SLOTS, CONTEXT, PREFILL_CHUNK = 16, 1024, 512
DEADLINE_S = 1150          # the whole run, compilation included
T0 = time.monotonic()


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - T0)


# ------------------------------------------------------------- model files

def write_model(models_dir: str, geometry: dict, dtype: str) -> None:
    """config.json (synthetic weights) + a tokenizer where id i is the word
    "t<i>", + the two YAMLs (dense and paged) that serve it."""
    ckpt = os.path.join(models_dir, "ckpt")
    os.makedirs(ckpt)
    with open(os.path.join(ckpt, "config.json"), "w") as f:
        json.dump(dict(geometry, architectures=["LlamaForCausalLM"],
                       rms_norm_eps=1e-5, localai_synthetic=True), f)
    vocab = {f"t{i}": i for i in range(geometry["vocab_size"])}
    with open(os.path.join(ckpt, "tokenizer.json"), "w") as f:
        json.dump({
            "version": "1.0", "truncation": None, "padding": None,
            "added_tokens": [], "normalizer": None,
            "pre_tokenizer": {"type": "WhitespaceSplit"},
            "post_processor": None, "decoder": None,
            "model": {"type": "WordLevel", "vocab": vocab,
                      "unk_token": "t3"},
        }, f)
    with open(os.path.join(ckpt, "tokenizer_config.json"), "w") as f:
        json.dump({
            "bos_token": "t0", "eos_token": "t1", "add_bos_token": True,
            "chat_template": (
                "{{ bos_token }}{% for message in messages %}"
                " t4 {{ message['content'] }} t5{% endfor %} t6"),
        }, f)
    kv = "int8" if dtype == "int8" else ""
    for name, pages in ((MODEL, 0), (MODEL + "-paged",
                                     SLOTS * CONTEXT // 128 + 1)):
        with open(os.path.join(models_dir, f"{name}.yaml"), "w") as f:
            f.write(f"name: {name}\nbackend: llm\n"
                    f"context_size: {CONTEXT}\nparallel: {SLOTS}\n"
                    f"dtype: {dtype}\ncache_type_k: \"{kv}\"\n"
                    f"kv_pages: {pages}\n"
                    f"parameters:\n  model: ckpt\n")


def words(rng: random.Random, n: int, vocab: int) -> str:
    return " ".join(f"t{rng.randrange(8, vocab)}" for _ in range(n))


# -------------------------------------------------------------------- HTTP

def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 300.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     body=json.dumps(body) if body is not None else None,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            raise SmokeFailure(
                f"{method} {path} -> HTTP {resp.status}: {data[:400]!r}")
        return json.loads(data)
    finally:
        conn.close()


def chat_stream(port: int, content: str, max_tokens: int, **sampling):
    """One streamed /v1/chat/completions. Returns the content deltas, the
    finish_reason, the usage tail and the seconds to the first delta."""
    body = dict(model=sampling.pop("model"), stream=True,
                messages=[{"role": "user", "content": content}],
                max_tokens=max_tokens, ignore_eos=True, **sampling)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300.0)
    t0 = time.monotonic()
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"chat stream -> HTTP {resp.status}: "
                               f"{resp.read()[:400]!r}")
        deltas, finish, usage, first = [], None, None, None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            ev = json.loads(line[6:])
            if "error" in ev:
                raise SmokeFailure(f"chat stream error event: {ev['error']}")
            if ev.get("usage"):
                usage = ev["usage"]
            for ch in ev.get("choices") or []:
                text = (ch.get("delta") or {}).get("content")
                if text:
                    if first is None:
                        first = time.monotonic() - t0
                    deltas.append(text)
                if ch.get("finish_reason"):
                    finish = ch["finish_reason"]
        return deltas, finish, usage, first
    finally:
        conn.close()


def expect(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- lifetime

def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


class Lifetime:
    """One server process (and whatever it spawns), from start to reaped."""

    def __init__(self, tag: str, model: str, models_dir: str, env: dict):
        self.tag, self.model = tag, model
        self.port = free_port()
        self.log_path = os.path.join(OUT_DIR, f"server_{tag}.log")
        self.t_start = time.monotonic()
        self.log_f = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "localai_tpu.cli", "run", model,
             "--address", f"127.0.0.1:{self.port}",
             "--models-path", models_dir,
             "--parallel-requests", str(SLOTS), "--disable-config-watcher"],
            cwd=HERE, env=env, stdout=self.log_f, stderr=subprocess.STDOUT,
            start_new_session=True)   # its own group: reaped as a whole
        self.pgid = self.proc.pid

    def _log_has(self, needle: str) -> str | None:
        with open(self.log_path, errors="replace") as f:
            for line in f:
                if needle in line:
                    return line.strip()
        return None

    def _log_tail(self, n: int = 3) -> str:
        with open(self.log_path, errors="replace") as f:
            return " | ".join(ln.strip() for ln in f.readlines()[-n:])

    def wait_loaded(self) -> dict:
        """Poll until HTTP answers, /system answers (before any request),
        the backend is up and the model is loaded. Returns phase seconds and
        the backend's device report."""
        phases: dict = {}
        system_first = None
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"[{self.tag}] server exited rc={self.proc.returncode} "
                    f"before the model loaded: {self._log_tail()}")
            if remaining() <= 0:
                raise SmokeFailure(
                    f"[{self.tag}] out of time waiting for LoadModel "
                    f"(phases so far: {phases})")
            bad = self._log_has("failed to start")
            if bad:
                raise SmokeFailure(f"[{self.tag}] load failed: {bad[-600:]}")
            now = time.monotonic() - self.t_start
            if "backend_spawn_s" not in phases and self._log_has(
                    "serving on port"):
                phases["backend_spawn_s"] = round(now, 1)
            try:
                info = http_json(self.port, "GET", "/system", timeout=5.0)
            except (OSError, http.client.HTTPException):
                time.sleep(0.25)
                continue
            if system_first is None:
                # the control plane answered /system while (or before) the
                # backend takes the chip: it must not have touched JAX, or
                # the load below fails
                system_first = info
                phases["http_ready_s"] = round(now, 1)
            if self.model in info.get("loaded_models", []):
                phases["loaded_s"] = round(now, 1)
                phases["load_model_s"] = round(
                    now - phases.get("backend_spawn_s", 0.0), 1)
                return {"phases": phases, "system_first": system_first,
                        "device": info["backends"][self.model],
                        "capability": info.get("capability")}
            time.sleep(0.25)

    def stop(self) -> dict:
        """SIGTERM the server and wait for every process of its group."""
        t0 = time.monotonic()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            pass
        leftover = False
        deadline = time.monotonic() + 30
        while True:
            try:
                os.killpg(self.pgid, 0)
            except ProcessLookupError:
                break
            if time.monotonic() > deadline:
                leftover = True
                os.killpg(self.pgid, signal.SIGKILL)
                time.sleep(1.0)
                break
            time.sleep(0.2)
        self.proc.wait()
        self.log_f.close()
        return {"stop_s": round(time.monotonic() - t0, 1),
                "server_rc": self.proc.returncode, "leftover": leftover}


def run_requests(lt: Lifetime, vocab: int, burst: int) -> dict:
    """The request mix of one lifetime; raises SmokeFailure on any miss."""
    rng = random.Random(21)
    port, model, tag = lt.port, lt.model, lt.tag
    out: dict = {"requests": 0, "tokens": 0}

    def count(n_req: int, n_tok: int):
        out["requests"] += n_req
        out["tokens"] += n_tok

    # 1. streamed chat: one delta per token, usage tail, finish "length"
    n = 32
    deltas, finish, usage, first = chat_stream(
        port, words(rng, 20, vocab), n, model=model, temperature=0.0)
    expect(len(deltas) == n and finish == "length"
           and (usage or {}).get("completion_tokens") == n,
           f"[{tag}] streamed chat: {len(deltas)} deltas, finish={finish!r},"
           f" usage={usage} (want {n} tokens, 'length')")
    out["first_token_s"] = round(first, 3)
    count(1, n)

    # 2. the same greedy completion twice: same ids (the text IS the ids).
    # The prompt is shorter than the slot prompt cache's 16-token minimum,
    # so both runs are the same computation; a reused prefix is read back
    # from the int8 cache, which rounds differently from the first prefill
    # and, on random weights, flips near-tied argmaxes (seen on the chip).
    def completion(prompt: str, n: int, plen: int) -> str:
        r = http_json(port, "POST", "/v1/completions", dict(
            model=model, prompt=prompt, max_tokens=n, temperature=0.0,
            ignore_eos=True))
        ch = r["choices"][0]
        expect(r["usage"] == {"prompt_tokens": plen, "completion_tokens": n,
                              "total_tokens": plen + n}
               and len(ch["text"].split()) == n
               and ch["finish_reason"] == "length",
               f"[{tag}] completion: usage={r['usage']}, "
               f"{len(ch['text'].split())} ids, finish="
               f"{ch['finish_reason']!r} (want {n}, 'length', prompt {plen})")
        ids = [int(w[1:]) for w in ch["text"].split()]
        expect(all(0 <= i < vocab for i in ids), f"[{tag}] id out of vocab")
        count(1, n)
        return ch["text"]

    prompt = words(rng, 11, vocab)
    expect(completion(prompt, 48, 12) == completion(prompt, 48, 12),
           f"[{tag}] greedy request repeated gave different ids")
    # ... and one long enough to be served from the slot's cached prefix the
    # second time: counts must hold; whether the ids match is recorded only
    prompt = words(rng, 24, vocab)
    out["prefix_reuse_same_ids"] = (
        completion(prompt, 32, 25) == completion(prompt, 32, 25))

    # 3. a prompt longer than the prefill chunk: chunked prefill (extend)
    n, plen = 16, PREFILL_CHUNK + 88
    r = http_json(port, "POST", "/v1/completions", dict(
        model=model, prompt=words(rng, plen, vocab), max_tokens=n,
        temperature=0.0, ignore_eos=True))
    expect(r["usage"] == {"prompt_tokens": plen + 1, "completion_tokens": n,
                          "total_tokens": plen + 1 + n}
           and r["choices"][0]["finish_reason"] == "length",
           f"[{tag}] long prompt: usage={r['usage']} finish="
           f"{r['choices'][0]['finish_reason']!r}")
    count(1, n)

    # 4. a concurrent burst over most of the slots, sampling mixed: batched
    # admission and the fused decode loop
    n = 64
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(burst) as pool:
        futs = [pool.submit(
            chat_stream, port, words(rng, 12 + 9 * i, vocab), n, model=model,
            **(dict(temperature=0.0) if i % 3 == 0 else
               dict(temperature=0.8, top_k=40, seed=100 + i)))
            for i in range(burst)]
        for i, f in enumerate(futs):
            deltas, finish, usage, _ = f.result(timeout=600)
            expect(len(deltas) == n and finish == "length"
                   and (usage or {}).get("completion_tokens") == n,
                   f"[{tag}] burst stream {i}: {len(deltas)} deltas, "
                   f"finish={finish!r}, usage={usage}")
    out["burst"] = {"streams": burst, "tokens_each": n,
                    "wall_s": round(time.monotonic() - t0, 2)}
    count(burst, burst * n)
    return out


def check_device(tag: str, dev: dict, rehearsal: bool, paged: bool) -> None:
    expect(bool(dev), f"[{tag}] the backend gave no device report")
    if rehearsal:
        expect(dev["platform"] == "cpu",
               f"[{tag}] rehearsal expected the CPU, got {dev['platform']}")
        return
    expect(dev["platform"] == "tpu",
           f"[{tag}] backend runs on {dev['platform']!r}, not a TPU")
    tiers = dev.get("tiers") or {}
    if dev.get("mesh") is None:
        want = {"prefill_attention": "pallas", "decode_attention": "pallas"}
        if paged:
            want["decode_kv_write"] = "pallas"
    else:
        # under a mesh attention goes to XLA (GSPMD shards it); the paged
        # write runs the kernel per shard
        want = {"decode_kv_write": "pallas"} if paged else {}
        used = [d for d in dev["devices"] if d.get("bytes_in_use")]
        expect(len(used) == dev["device_count"],
               f"[{tag}] memory in use on {len(used)} of "
               f"{dev['device_count']} devices: {dev['devices']}")
    for op, tier in want.items():
        expect(tiers.get(op) == tier,
               f"[{tag}] {op} is served by {tiers.get(op)!r}, not {tier!r} "
               f"(tiers: {tiers})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny model on the CPU: rehearses this script; "
                         "never prints the pass line, exits 3")
    args = ap.parse_args()
    rehearsal = args.cpu_rehearsal

    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    models_dir = os.path.join(OUT_DIR, "models")
    os.makedirs(models_dir)
    geometry = TINY if rehearsal else LLAMA_8B
    write_model(models_dir, geometry, "float32" if rehearsal else "int8")

    env = dict(os.environ)
    env["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    env.pop("LOCALAI_NO_PREWARM", None)      # prewarm on: what a user gets
    # pinned: with JAX_PLATFORMS unset JAX falls back to the CPU by itself
    # when the TPU client cannot start (no chip, or another process has it)
    env["JAX_PLATFORMS"] = "cpu" if rehearsal else "tpu"
    # where the backend will keep its compile cache (system/device.py)
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        HERE, ".jax_cache")

    summary: dict = {
        "geometry": geometry, "dtype": "float32" if rehearsal else "int8",
        "slots": SLOTS, "context": CONTEXT,
        "compile_cache_dir": cache_dir,
        "compile_cache_placed_by": (
            "JAX_COMPILATION_CACHE_DIR" if env.get(
                "JAX_COMPILATION_CACHE_DIR") else "checkout default"),
        "cache_entries_before": cache_entries(cache_dir),
        "lifetimes": {},
    }
    device = None
    failure = None
    current = None
    try:
        for tag, model in (("A", MODEL), ("B", MODEL),
                           ("C", MODEL + "-paged")):
            log(f"lifetime {tag}: starting server for {model!r}")
            current = Lifetime(tag, model, models_dir, env)
            rec: dict = {"model": model}
            summary["lifetimes"][tag] = rec
            loaded = current.wait_loaded()
            rec.update(loaded["phases"])
            rec["device"] = dev = loaded["device"]
            rec["capability"] = loaded["capability"]
            log(f"lifetime {tag}: loaded in {rec['loaded_s']}s "
                f"(LoadModel {rec['load_model_s']}s: "
                f"{dev.get('load_seconds')}) on "
                f"{dev.get('device_kind')} x{dev.get('device_count')}, "
                f"mesh {dev.get('mesh')}, tiers {dev.get('tiers')}")
            check_device(tag, dev, rehearsal, paged=tag == "C")
            device = device or dev
            rec.update(run_requests(current, geometry["vocab_size"],
                                    burst=SLOTS * 3 // 4))
            log(f"lifetime {tag}: {rec['requests']} requests, "
                f"{rec['tokens']} tokens, all counts exact")
            # the device as the backend sees it after serving
            rec["device_after"] = http_json(
                current.port, "GET", "/backend/monitor",
                timeout=30.0)[model]["device"]
            stopped = current.stop()
            current = None
            rec.update(stopped)
            rec["cache_entries_after"] = cache_entries(cache_dir)
            expect(not stopped["leftover"],
                   f"[{tag}] processes outlived SIGTERM + 30 s and had to "
                   f"be killed")
        a, b = summary["lifetimes"]["A"], summary["lifetimes"]["B"]
        if not rehearsal:    # (the rehearsal's programs compile in under
            #                   the cache's 1 s threshold: nothing to see)
            warm_start = summary["cache_entries_before"] > 0
            expect(a["cache_entries_after"] > summary["cache_entries_before"]
                   or warm_start,
                   "lifetime A wrote no compile-cache entries to "
                   + cache_dir)
            expect(b["load_model_s"] < a["load_model_s"] or warm_start,
                   f"warm LoadModel ({b['load_model_s']}s) not shorter than "
                   f"cold ({a['load_model_s']}s): the compile cache missed")
    except SmokeFailure as e:
        failure = str(e)
    except Exception as e:   # anything else is a failure too, with its name
        failure = f"{type(e).__name__}: {e}"
    finally:
        if current is not None:
            summary["lifetimes"][current.tag].update(current.stop())

    summary["total_s"] = round(time.monotonic() - T0, 1)
    summary["parent_imported_jax"] = "jax" in sys.modules
    if summary["parent_imported_jax"] and failure is None:
        failure = "this process imported jax"
    if device:
        summary["versions"] = {k: device.get(k)
                               for k in ("jax", "jaxlib", "libtpu")}
    summary["failure"] = failure
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    shutil.rmtree(models_dir, ignore_errors=True)

    brief = dict(summary)
    brief.pop("geometry")
    if failure is not None:
        print("SMOKE FAILED: " + failure, file=sys.stderr, flush=True)
        print("summary: " + json.dumps(brief), file=sys.stderr, flush=True)
        return 1
    print("summary: " + json.dumps(brief), flush=True)
    if rehearsal:
        print(json.dumps({"ok": False, "device": "cpu", "rehearsal": True}),
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
