"""The system under test, as a user starts it: `python -m localai_tpu.cli run`
with one model YAML, ended with SIGTERM and a wait for every process of its
group. The life-cycle, the exact-length tokenizer and the YAML are copied
from chip_smoke.py (PR 21), not imported: the yardstick lives here.

Nothing in this file imports JAX: the chip belongs to the backend process.
"""
from __future__ import annotations

import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

META_KEYS = ("source", "reduced", "published", "assumed", "deployment",
             "serving", "rehearsal")


class BenchFailure(Exception):
    """Nothing could be measured: the run ends with a non-zero exit code."""


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def hf_config(config: dict, rehearsal: bool) -> dict:
    """The config.json the backend loads: the configuration file's own keys
    (everything but the benchmark's notes), synthetic weights."""
    hf = {k: v for k, v in config.items() if k not in META_KEYS}
    if rehearsal:
        hf.update(config["rehearsal"]["geometry"])
    hf["localai_synthetic"] = True
    return hf


def serving(config: dict, rehearsal: bool) -> dict:
    s = dict(config["serving"])
    if rehearsal:
        s.update(config["rehearsal"]["serving"])
    return s


def write_model(models_dir: str, name: str, config: dict, backend: str,
                rehearsal: bool) -> dict:
    """Checkpoint directory (config.json with synthetic weights, a tokenizer
    in which token id i is the word "t<i>") and the one YAML that serves it.
    Returns the serving fields in effect."""
    ckpt = os.path.join(models_dir, "ckpt")
    os.makedirs(ckpt)
    hf = hf_config(config, rehearsal)
    with open(os.path.join(ckpt, "config.json"), "w") as f:
        json.dump(hf, f)
    vocab = {f"t{i}": i for i in range(hf["vocab_size"])}
    with open(os.path.join(ckpt, "tokenizer.json"), "w") as f:
        json.dump({
            "version": "1.0", "truncation": None, "padding": None,
            "added_tokens": [], "normalizer": None,
            "pre_tokenizer": {"type": "WhitespaceSplit"},
            "post_processor": None, "decoder": None,
            "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "t3"},
        }, f)
    with open(os.path.join(ckpt, "tokenizer_config.json"), "w") as f:
        json.dump({
            "bos_token": "t0", "eos_token": "t1", "add_bos_token": True,
            "chat_template": (
                "{{ bos_token }}{% for message in messages %}"
                " t4 {{ message['content'] }} t5{% endfor %} t6"),
        }, f)
    s = serving(config, rehearsal)
    buckets = ", ".join(str(b) for b in s["prefill_buckets"])
    with open(os.path.join(models_dir, f"{name}.yaml"), "w") as f:
        f.write(f"name: {name}\nbackend: {backend}\n"
                f"context_size: {s['context_size']}\n"
                f"parallel: {s['parallel']}\n"
                f"dtype: {s['dtype']}\n"
                f"cache_type_k: \"{s['cache_type_k']}\"\n"
                f"kv_pages: {s.get('kv_pages', 0)}\n"
                f"prefill_buckets: [{buckets}]\n"
                f"parameters:\n  model: ckpt\n")
    return s


def http_json(port: int, method: str, path: str, body: dict | None = None,
              timeout: float = 60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     body=json.dumps(body) if body is not None else None,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        if resp.status != 200:
            raise BenchFailure(
                f"{method} {path} -> HTTP {resp.status}: {data[:300]!r}")
        return json.loads(data)
    finally:
        conn.close()


def cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0


class Server:
    """One server process and whatever it spawns, from start to reaped."""

    def __init__(self, root: str, work: str, model: str, env: dict,
                 slots: int, queue_depth: int, backends_path: str | None):
        self.model = model
        self.port = free_port()
        self.log_path = os.path.join(work, "server.log")
        self.t_start = time.monotonic()
        self.log_f = open(self.log_path, "w")
        argv = [sys.executable, "-m", "localai_tpu.cli", "run", model,
                "--address", f"127.0.0.1:{self.port}",
                "--models-path", os.path.join(work, "models"),
                "--parallel-requests", str(slots),
                "--queue-depth", str(queue_depth),
                "--disable-config-watcher"]
        if backends_path:
            argv += ["--backends-path", backends_path]
        self.proc = subprocess.Popen(
            argv, cwd=root, env=env, stdout=self.log_f,
            stderr=subprocess.STDOUT, start_new_session=True)
        self.pgid = self.proc.pid

    def log_has(self, needle: str) -> str | None:
        with open(self.log_path, errors="replace") as f:
            for line in f:
                if needle in line:
                    return line.strip()
        return None

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError as e:
            return f"(no server log: {e})"

    def wait_loaded(self, limit_s: float) -> dict:
        """Until /readyz answers and the model is loaded. Returns the
        backend's own device report (what /system says) and phase times."""
        phases: dict = {}
        deadline = self.t_start + limit_s
        while True:
            now = time.monotonic()
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited rc={self.proc.returncode} before the "
                    f"model loaded")
            if now > deadline:
                raise BenchFailure(
                    f"model not loaded within {limit_s:.0f} s "
                    f"(phases so far: {phases})")
            bad = self.log_has("failed to start")
            if bad:
                raise BenchFailure(f"LoadModel failed: {bad[-500:]}")
            if "backend_up_s" not in phases and self.log_has("serving on port"):
                phases["backend_up_s"] = now - self.t_start
            try:
                if "http_ready_s" not in phases:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=5.0)
                    try:
                        conn.request("GET", "/readyz")
                        ok = conn.getresponse().status == 200
                    finally:
                        conn.close()
                    if not ok:
                        time.sleep(0.25)
                        continue
                    phases["http_ready_s"] = time.monotonic() - self.t_start
                info = http_json(self.port, "GET", "/system", timeout=5.0)
            except (OSError, http.client.HTTPException, BenchFailure):
                time.sleep(0.25)
                continue
            # /system reads the device reports and the list of loaded models
            # one after the other: a model that registers in between is in the
            # list and not yet among the reports (seen once on the chip)
            device = (info.get("backends") or {}).get(self.model)
            if self.model in info.get("loaded_models", []) and device:
                phases["loaded_s"] = time.monotonic() - self.t_start
                return {"phases": phases, "device": device}
            time.sleep(0.25)

    def monitor(self) -> dict:
        """GET /backend/monitor -> this model's entry: the backend's whole
        flat GetMetrics dict and the device as the backend sees it now."""
        entry = http_json(self.port, "GET", "/backend/monitor",
                          timeout=60.0).get(self.model)
        if entry is None:
            raise BenchFailure(f"/backend/monitor does not list {self.model!r}"
                               f": the backend is gone")
        return entry

    def stop(self) -> dict:
        """SIGTERM the server, then wait for every process of its group."""
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
                try:
                    os.killpg(self.pgid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                time.sleep(1.0)
                break
            time.sleep(0.1)
        self.proc.wait()
        self.log_f.close()
        return {"stop_s": time.monotonic() - t0, "leftover": leftover,
                "server_rc": self.proc.returncode}


def fresh_dir(path: str) -> str:
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path
