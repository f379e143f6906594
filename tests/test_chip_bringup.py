"""Bring-up guards (ISSUE 21): nothing between the entry points and the device
may hide which device serves, take the chip from the process that owns it, or
turn a refused compile into a quiet change of tier.

CPU-side checks of the rules `chip_smoke.py` proves on the chip: the compile
cache can be placed from outside, the control plane never imports JAX, a load
that cannot compile its programs fails, and the smoke itself fails — saying
why — when there is no TPU.
"""
import json
import os
import queue
import re
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env: dict | None = None, timeout=120):
    """python -c `code` from the checkout; env values of None drop the
    variable."""
    full = dict(os.environ, PYTHONPATH=ROOT)
    full.update(env or {})
    full = {k: v for k, v in full.items() if v is not None}
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=ROOT,
                          capture_output=True, text=True, timeout=timeout)


# ------------------------------------------------------------ compile cache

def test_compile_cache_env_set_is_untouched(monkeypatch, tmp_path):
    import jax

    from localai_tpu.system.device import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    # whoever launched us placed it: nothing set in code, env as given
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_unset_goes_to_the_fixed_checkout_dir():
    """Fresh process, variable unset: the one fixed git-ignored directory in
    the checkout — exported for children, without importing jax; and picked
    up by a jax that was already imported."""
    r = _run(
        "import os, sys\n"
        "from localai_tpu.system.device import configure_compile_cache\n"
        "d = configure_compile_cache()\n"
        "assert 'jax' not in sys.modules\n"
        "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == d\n"
        "import jax\n"
        "assert jax.config.jax_compilation_cache_dir == d\n"
        "del os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "jax.config.update('jax_compilation_cache_dir', None)\n"
        "assert configure_compile_cache() == d\n"
        "assert jax.config.jax_compilation_cache_dir == d\n"
        "print(d)\n", env={"JAX_COMPILATION_CACHE_DIR": None})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_only_the_helper_sets_a_cache_directory():
    """No source line but system/device.py's helper sets a compile-cache
    directory (reading the variable is fine)."""
    setter = re.compile(
        r"""JAX_COMPILATION_CACHE_DIR["']\]\s*=|jax_compilation_cache_dir["']\s*,""")
    offenders = []
    files = [os.path.join(ROOT, "bench.py"), os.path.join(ROOT, "chip_smoke.py")]
    for top in ("localai_tpu", "tools"):
        for dp, _, fns in os.walk(os.path.join(ROOT, top)):
            files += [os.path.join(dp, f) for f in fns if f.endswith(".py")]
    for path in files:
        if path.endswith(os.path.join("system", "device.py")):
            continue
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if setter.search(line):
                    offenders.append(f"{os.path.relpath(path, ROOT)}:{n}")
    assert offenders == []


# -------------------------------------------------- control plane stays off JAX

def test_system_endpoint_leaves_jax_out_of_sys_modules():
    """GET /system in a fresh process: answered from the loaded backends'
    own reports, with `jax` never imported — a control plane that touched
    JAX would take the chip from every backend it spawns."""
    r = _run("""
import asyncio, json, sys
from aiohttp.test_utils import TestClient, TestServer
from localai_tpu.config import AppConfig, ModelConfigLoader
from localai_tpu.core.manager import ModelManager
from localai_tpu.server.http import API

async def main():
    app = AppConfig(models_path="/nonexistent")
    mgr = ModelManager(app)
    mgr.devices = lambda: {"m": {"platform": "tpu",
                                 "device_kind": "TPU v5 lite",
                                 "device_count": 1}}
    api = API(app, ModelConfigLoader(app.models_path), mgr)
    async with TestClient(TestServer(api.app)) as c:
        resp = await c.get("/system")
        assert resp.status == 200
        print(json.dumps(await resp.json()))

asyncio.run(main())
assert "jax" not in sys.modules, "the control plane imported jax"
""")
    assert r.returncode == 0, r.stderr[-2000:]
    info = json.loads(r.stdout.strip().splitlines()[-1])
    assert info["capability"] == "tpu-v5e" and info["hbm_bytes"] == 16 << 30
    assert info["backends"]["m"]["device_kind"] == "TPU v5 lite"


def test_system_info_without_backends_knows_no_device(monkeypatch):
    from localai_tpu.system import system_info

    monkeypatch.delenv("LOCALAI_FORCE_CAPABILITY", raising=False)
    info = system_info()
    assert info["capability"] == "unknown" and info["backends"] == {}
    assert "hbm_bytes" not in info and "devices" not in info


def test_util_preflight_starts_no_device_client(tmp_path):
    """`util fits` parses the config through the model code (which imports
    jax) but must never build a device client: that is what would contend
    for the chip with a running server."""
    (tmp_path / "config.json").write_text(json.dumps(dict(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=512,
        architectures=["LlamaForCausalLM"])))
    r = _run(f"""
import sys
from localai_tpu.cli import main
assert main(["util", "fits", {str(tmp_path)!r}, "--slots", "4"]) == 0
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "util built a device client"
""", env={"LOCALAI_FORCE_CAPABILITY": ""})
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout)["fits"] is None     # no forced capability


# ------------------------------------------- a load that cannot compile fails

def _synthetic_dir(tmp_path) -> str:
    (tmp_path / "config.json").write_text(json.dumps(dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=256, tie_word_embeddings=True,
        architectures=["LlamaForCausalLM"], rms_norm_eps=1e-5,
        localai_synthetic=True)))
    return str(tmp_path)


def _load(tmp_path, monkeypatch, **env):
    from localai_tpu.backend import pb
    from localai_tpu.backend.llm import LLMServicer

    monkeypatch.setenv("LOCALAI_ALLOW_SYNTHETIC", "1")
    monkeypatch.delenv("LOCALAI_NO_PREWARM", raising=False)   # prewarm ON
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    s = LLMServicer()
    r = s.LoadModel(pb.ModelOptions(
        model=_synthetic_dir(tmp_path), context_size=128, parallel=2,
        dtype="float32", prefill_buckets=[16]), None)
    return s, r


def test_failing_prewarm_fails_loadmodel(tmp_path, monkeypatch):
    from localai_tpu.backend import pb

    s, r = _load(tmp_path, monkeypatch, LOCALAI_FAULT="prewarm_raise")
    assert not r.success and "injected prewarm failure" in r.message
    st = s.Status(None, None)
    assert st.state == pb.StatusResponse.ERROR
    # no half-loaded engine left to answer "already loaded" or hold the chip
    assert s.engine is None and json.loads(st.device_json) == {}


def test_kernel_exception_at_load_fails_load_instead_of_switching_tier(
        tmp_path, monkeypatch):
    """A Pallas kernel that raises while the load compiles its programs (what
    a Mosaic refusal looks like) fails LoadModel with that message. There is
    no probe and no path that turns the exception into XLA attention."""
    s, r = _load(tmp_path, monkeypatch, LOCALAI_FORCE_PALLAS="1",
                 LOCALAI_FAULT="kernel_raise")
    assert not r.success
    assert "injected kernel lowering failure" in r.message
    assert s.engine is None
    import localai_tpu.ops.pallas as P
    from localai_tpu.models import llama

    assert not hasattr(P, "pallas_works")
    src = open(llama.__file__).read()
    assert "pallas_works" not in src and "falling back" not in src


class _FakeEngine:
    """An engine whose loop 'recovered' from a step failure: the warm request
    ends "error" after 3 tokens instead of running to its length."""
    last_error = "XlaRuntimeError: RESOURCE_EXHAUSTED: out of memory"
    ec = types.SimpleNamespace(decode_block=16, sampling_topk_width=64)

    def warmup(self):
        pass

    def submit(self, req):
        q = queue.Queue()
        q.put(types.SimpleNamespace(finished=True, finish_reason="error",
                                    generated_tokens=3))
        return 1, q


def test_prewarm_sees_a_request_the_engine_loop_failed():
    """engine._loop swallows a step failure, fails the active requests and
    restarts; from outside that is a short stream. Prewarm checks what its
    warm requests ended on and names the engine's error."""
    from localai_tpu.backend.llm import LLMServicer

    s = LLMServicer()
    s.engine, s.cfg = _FakeEngine(), types.SimpleNamespace(vocab_size=256)
    with pytest.raises(RuntimeError, match=r"'error' after 3/50 tokens: "
                                           r"XlaRuntimeError: RESOURCE"):
        s._prewarm()


# ----------------------------------------------------------- device report

def test_kernel_tiers_name_the_interpreter(monkeypatch):
    """The device report cannot pass interpret mode off as the Pallas tier."""
    from localai_tpu.models.llama import LlamaConfig, kernel_tiers

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16)
    monkeypatch.delenv("LOCALAI_FORCE_PALLAS", raising=False)
    t = kernel_tiers(cfg, None, paged=True)
    assert set(t.values()) == {"xla"}          # CPU: XLA everywhere
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    t = kernel_tiers(cfg, None, paged=True)
    assert t["prefill_attention"] == t["decode_attention"] \
        == t["decode_kv_write"] == "pallas-interpret"
    assert t["chunk_attention"] == "xla"
    # dense KV has no paged write, and a chunk attends over the blocks its
    # context fills; the tiered read has no kernel yet
    dense = kernel_tiers(cfg, None, paged=False)
    assert dense["decode_kv_write"] == "xla"
    assert dense["chunk_attention"] == "xla-blocks"
    assert kernel_tiers(cfg, None, paged=True,
                        tiered=True)["decode_attention"] == "xla"


def test_device_report_says_what_jax_says():
    import jax

    from localai_tpu.system.device import device_report

    rep = device_report()
    assert rep["platform"] == jax.devices()[0].platform == "cpu"
    assert rep["device_kind"] == jax.devices()[0].device_kind
    assert rep["device_count"] == len(jax.devices()) == len(rep["devices"])
    assert rep["jax"] == jax.__version__


# ------------------------------------------------------------ chip release

def test_terminate_reaps_a_child_that_ignores_sigterm():
    """A backend SIGTERMed mid-LoadModel does not exit by itself; the chip is
    only free once the process is gone, so terminate escalates AND waits."""
    from localai_tpu.core.manager import _terminate

    p = subprocess.Popen([sys.executable, "-c", (
        "import signal, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "print('up', flush=True)\ntime.sleep(120)\n")],
        stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "up"
    _terminate(p, grace=0.5)
    assert p.returncode is not None and p.returncode < 0   # killed, reaped


# --------------------------------------------------------------- bench.py

def test_bench_has_no_probe_or_cpu_fallback():
    import bench

    flags = bench.build_parser().format_help()
    for gone in ("--probe", "--allow-cpu-fallback", "--runs-dir"):
        assert gone not in flags
    assert "--cpu" in flags and "--trace" in flags and "longctx" in flags
    src = open(bench.__file__).read()
    for gone in ("LOCALAI_JAX_PLATFORM", "falling back to CPU", "stale",
                 "ProbeKeepalive"):
        assert gone not in src


# ------------------------------------------------------------ chip_smoke.py

def test_chip_smoke_without_a_tpu_exits_nonzero_and_says_why(tmp_path):
    """No arguments = the chip is required. Its children get JAX_PLATFORMS
    pinned to tpu, so here (no TPU) the backend's load fails instead of
    quietly serving from the CPU; no result line is printed."""
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode not in (0, 3)
    assert "SMOKE FAILED" in r.stderr
    assert "Unable to initialize backend 'tpu'" in r.stderr
    assert '"ok"' not in r.stdout
    assert '"parent_imported_jax": false' in r.stderr


def test_chip_smoke_alone_in_a_directory_exits_nonzero(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "No module named" in r.stderr
