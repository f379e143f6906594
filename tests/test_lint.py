"""localai-lint: per-rule positive/negative snippet coverage + the runtime
tripwires (transfer guard, compile-count guard).

Every static rule gets at least one snippet it MUST catch and one it must
NOT (including a pragma'd case). Two snippets reconstruct shipped bug
classes: the PR 4 watchdog holding the model-map lock across Popen.wait, and
a `.item()` in the decode hot loop.
"""
import textwrap

import numpy as np
import pytest

from tools.lint import Config, run_source

HOT = "localai_tpu/engine/fake_hot.py"     # inside the hot-path scope
COLD = "localai_tpu/server/fake_cold.py"   # outside it


def lint(src: str, path: str = HOT, **cfg):
    return run_source(textwrap.dedent(src), path, Config(**cfg))


def rules_of(violations):
    return [v.rule for v in violations]


# ------------------------------------------------------------ family (a)

def test_item_in_hot_loop_caught():
    """The hot-path `.item()` reconstruction: one stray scalar read per
    decode step stalls the fused pipeline."""
    src = """
    import jax.numpy as jnp

    def decode_loop(fn, state):
        while True:
            tokens, state = fn(state)
            t = tokens[0].item()
            yield t
    """
    vs = lint(src)
    assert "host-sync-item" in rules_of(vs)


def test_item_outside_hot_path_allowed():
    vs = lint("x = arr.item()\n", path=COLD)
    assert "host-sync-item" not in rules_of(vs)


def test_item_pragma_suppresses():
    src = """
    def f(arr):
        return arr.item()  # lint: allow(host-sync-item) — once per request
    """
    assert rules_of(lint(src)) == []


def test_cast_on_device_value_caught_and_host_value_allowed():
    src = """
    import jax.numpy as jnp

    def f(x):
        y = jnp.argmax(x)
        bad = int(y)
        n = int("42")          # host value: fine
        m = int(y.shape[0])    # metadata: fine
        return bad, n, m
    """
    vs = [v for v in lint(src) if v.rule == "host-sync-cast"]
    assert len(vs) == 1


def test_cast_direct_jnp_call_caught():
    src = """
    import jax.numpy as jnp

    def f(logits):
        return float(jnp.max(logits))
    """
    assert "host-sync-cast" in rules_of(lint(src))


def test_asarray_on_device_caught_device_get_allowed():
    src = """
    import jax, numpy as np, jax.numpy as jnp

    def f(x):
        y = jnp.exp(x)
        bad = np.asarray(y)
        good = np.asarray(jax.device_get(y))
        return bad, good
    """
    vs = [v for v in lint(src) if v.rule == "host-sync-asarray"]
    assert len(vs) == 1


def test_asarray_on_host_value_allowed():
    src = """
    import numpy as np

    def f(ids):
        lens = np.asarray([len(i) for i in ids], np.int32)
        return lens
    """
    assert "host-sync-asarray" not in rules_of(lint(src))


def test_block_until_ready_caught_in_hot_path_only():
    src = "import jax\n\ndef f(x):\n    return jax.block_until_ready(x)\n"
    assert "sync-block-until-ready" in rules_of(lint(src))
    assert "sync-block-until-ready" not in rules_of(lint(src, path=COLD))
    assert "sync-block-until-ready" not in rules_of(
        lint(src, path="tools/profile_thing.py"))


def test_traced_branch_caught():
    src = """
    import jax, jax.numpy as jnp

    def step(params, x):
        if x > 0:
            return x + 1
        return x

    step_fn = jax.jit(step)
    """
    vs = [v for v in lint(src) if v.rule == "traced-branch"]
    assert len(vs) == 1
    assert "'x'" in vs[0].message


def test_traced_branch_static_and_meta_allowed():
    src = """
    import jax, jax.numpy as jnp

    def step(params, x, flag, mask=None):
        if flag:                  # static → python bool, fine
            x = x * 2
        if mask is not None:      # identity test, fine
            x = x + mask
        if x.shape[0] > 4:        # metadata, fine
            x = x[:4]
        return x

    step_fn = jax.jit(step, static_argnames=("flag",))
    """
    assert "traced-branch" not in rules_of(lint(src))


def test_jit_arg_retrace_caught_and_wrapped_allowed():
    src = """
    import jax, jax.numpy as jnp

    def f(x):
        return x

    f_fn = jax.jit(f)

    def caller(ids):
        bad = f_fn([1, 2, 3])
        also_bad = f_fn(len(ids))
        good = f_fn(jnp.asarray(ids))
        return bad, also_bad, good
    """
    vs = [v for v in lint(src, path=COLD) if v.rule == "jit-arg-retrace"]
    assert len(vs) == 2


def test_jit_static_kw_not_flagged():
    src = """
    import jax

    def f(x, steps):
        return x

    f_fn = jax.jit(f, static_argnames=("steps",))

    def caller(x, n):
        return f_fn(x, steps=len(str(n)))
    """
    assert "jit-arg-retrace" not in rules_of(lint(src, path=COLD))


def test_shape_from_len_caught():
    src = """
    import jax.numpy as jnp

    def admit(prompt_ids):
        buf = jnp.zeros((1, len(prompt_ids)), jnp.int32)
        fixed = jnp.zeros((1, 64), jnp.int32)   # bucketed: fine
        return buf, fixed
    """
    vs = [v for v in lint(src) if v.rule == "shape-from-len"]
    assert len(vs) == 1


# ------------------------------------------------------------ family (b)

def test_watchdog_lock_across_wait_reconstruction():
    """The PR 4 bug, reconstructed: the seed watchdog reaped backends while
    holding the model-map lock, so every load()/get() froze for up to the
    full Popen.wait timeout."""
    src = """
    import subprocess, threading, time

    class Manager:
        def watchdog_tick(self):
            with self._lock:
                for h in self._models.values():
                    if h.busy:
                        h.proc.terminate()
                        h.proc.wait(timeout=10)
    """
    vs = [v for v in lint(src, path="localai_tpu/core/fake_mgr.py")
          if v.rule == "lock-across-blocking"]
    assert len(vs) == 1
    assert ".wait()" in vs[0].message


def test_lock_then_blocking_outside_allowed():
    src = """
    import time

    class Manager:
        def watchdog_tick(self):
            with self._lock:
                handles = list(self._models.values())
            for h in handles:
                h.proc.wait(timeout=10)
                time.sleep(0.1)
    """
    assert "lock-across-blocking" not in rules_of(
        lint(src, path="localai_tpu/core/fake_mgr.py"))


def test_sleep_and_rpc_under_lock_caught():
    src = """
    import time

    def f(self, cfg):
        with self._model_lock(cfg.name):
            time.sleep(1.0)
            self.client.health(timeout=5.0)
    """
    vs = [v for v in lint(src, path=COLD)
          if v.rule == "lock-across-blocking"]
    assert len(vs) == 2


def test_string_and_path_join_not_flagged():
    src = """
    import os

    def f(self, parts):
        with self._lock:
            a = os.path.join(*parts)
            b = ", ".join(parts)
        return a, b
    """
    assert "lock-across-blocking" not in rules_of(lint(src, path=COLD))


def test_mark_busy_without_finally_caught():
    src = """
    def handler(handle, opts):
        handle.mark_busy()
        r = handle.client.predict(opts)
        handle.mark_idle()
        return r
    """
    vs = [v for v in lint(src, path=COLD)
          if v.rule == "acquire-release-finally"]
    assert len(vs) == 1


def test_mark_busy_with_finally_allowed():
    src = """
    def handler(handle, opts):
        handle.mark_busy()
        try:
            return handle.client.predict(opts)
        finally:
            handle.mark_idle()
    """
    assert "acquire-release-finally" not in rules_of(lint(src, path=COLD))


def test_mark_busy_never_released_caught():
    src = """
    def handler(handle):
        handle.mark_busy()
        return handle.port
    """
    assert "acquire-release-finally" in rules_of(lint(src, path=COLD))


def test_span_begin_cross_function_release_allowed():
    # the engine pattern: span opened at admission, finished at slot release
    # (a different function) — must NOT flag
    src = """
    def admit(self, req):
        self.span = self.tracer.begin("engine.request")

    def release(self, slot):
        self.tracer.finish(self.span)
    """
    assert "acquire-release-finally" not in rules_of(lint(src, path=COLD))


# ------------------------------------------------------------ family (c)

def test_inline_partition_spec_caught():
    src = """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(mesh, x):
        return jax.device_put(x, NamedSharding(mesh, P(None, "model")))
    """
    assert "sharding-spec-source" in rules_of(lint(src, path=COLD))


def test_sourced_and_replicated_specs_allowed():
    src = """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(mesh, x, cfg):
        a = jax.device_put(x, NamedSharding(mesh, kv_cache_spec()))
        b = jax.device_put(x, NamedSharding(mesh, P(None, None)))
        c = jax.device_put(x, safe_sharding(mesh, P("data"), x.shape))
        return a, b, c
    """
    assert "sharding-spec-source" not in rules_of(lint(src, path=COLD))


def test_shard_map_inline_specs_caught():
    src = """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    def wrap(mesh, body):
        return shard_map(body, mesh=mesh, in_specs=(P("model"),),
                         out_specs=P("model"))
    """
    vs = [v for v in lint(src, path=COLD)
          if v.rule == "sharding-spec-source"]
    assert len(vs) >= 1


def test_pb2_direct_import_caught_and_shim_allowed():
    bad = "from localai_tpu.backend import backend_pb2\n"
    assert "pb2-direct-import" in rules_of(lint(bad, path=COLD))
    assert "pb2-direct-import" in rules_of(
        lint("import backend_pb2\n", path=COLD))
    # the shim itself and the generator are exempt
    assert "pb2-direct-import" not in rules_of(
        lint("import backend_pb2\n", path="localai_tpu/backend/pb.py"))
    # google runtime modules are upstream, not ours
    assert "pb2-direct-import" not in rules_of(
        lint("from google.protobuf import descriptor_pb2\n", path=COLD))


def test_unregistered_marker_caught_registered_allowed():
    src = """
    import pytest

    @pytest.mark.slow
    @pytest.mark.made_up_lane
    def test_x():
        pass
    """
    vs = lint(src, path="tests/fake_test.py",
              registered_markers=frozenset({"slow"}))
    marker_vs = [v for v in vs if v.rule == "pytest-marker-registered"]
    assert len(marker_vs) == 1
    assert "made_up_lane" in marker_vs[0].message


def test_repo_markers_all_registered():
    """The live tree's markers must be registered (satellite: marker
    hygiene). Runs the real rule over the real tests/ directory."""
    from tools.lint import run_paths

    vs = run_paths(["tests"], Config(select=("pytest-marker-registered",)))
    assert vs == [], [v.render() for v in vs]


# ------------------------------------------------------------ pragma + CLI

def test_bad_pragma_rule_name_is_itself_flagged():
    src = "x = 1  # lint: allow(no-such-rule)\n"
    assert "bad-pragma" in rules_of(lint(src, path=COLD))


def test_pragma_standalone_covers_next_statement():
    src = """
    import jax, numpy as np, jax.numpy as jnp

    def f(x):
        y = jnp.exp(x)
        # lint: allow(host-sync-asarray) — test reason
        z = np.asarray(
            y)
        return z
    """
    assert "host-sync-asarray" not in rules_of(lint(src))


def test_tree_lints_clean():
    """The acceptance gate, as a test: the shipped tree has zero unsuppressed
    violations. Keeps `python -m tools.lint` green without waiting for CI."""
    from tools.lint import run_paths

    vs = run_paths(["localai_tpu", "tools", "tests"])
    assert vs == [], "\n".join(v.render() for v in vs)


def test_cli_exit_codes(tmp_path):
    import subprocess
    import sys

    bad = tmp_path / "localai_tpu" / "engine"
    bad.mkdir(parents=True)
    (tmp_path / "pyproject.toml").write_text("[project]\nname='x'\n")
    (bad / "hot.py").write_text("def f(a):\n    return a.item()\n")
    import os

    env = dict(os.environ, PYTHONPATH=os.getcwd())
    r = subprocess.run(
        [sys.executable, "-m", "tools.lint", str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=os.getcwd())
    assert r.returncode == 1
    assert "host-sync-item" in r.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--list-rules"],
        capture_output=True, text=True, env=env, cwd=os.getcwd())
    assert r2.returncode == 0 and "lock-across-blocking" in r2.stdout


# ------------------------------------------------------------ tripwires

TINY = dict(vocab_size=96, hidden_size=32, intermediate_size=64,
            num_layers=2, num_heads=2, num_kv_heads=2, head_dim=16,
            max_position=256, dtype="float32")


@pytest.fixture(scope="module")
def tiny_engine_parts():
    import jax

    from localai_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(**TINY)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _drive(eng, reqs):
    """Submit all requests, drive the loop to completion, return finish
    reasons."""
    outs = [eng.submit(r)[1] for r in reqs]
    reasons = []
    for out in outs:
        while True:
            o = out.get(timeout=60)
            if o.finished:
                reasons.append(o.finish_reason)
                break
    return reasons


@pytest.mark.tripwire
def test_decode_compiles_exactly_once_across_mixed_stream(tiny_engine_parts):
    """The compile-count guard (acceptance): a mixed-length request stream
    with uniform sampling knobs compiles the decode step EXACTLY once —
    prefill buckets absorb prompt-length variance, and a second stream of
    fresh lengths compiles NOTHING new anywhere. Extended to the while-loop
    path: after engine warmup() the whole compiled-variant set is CLOSED —
    a full mixed stream (loop dispatches included) adds zero programs."""
    from localai_tpu.engine.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.ops.sampling import SamplingParams
    from localai_tpu.testing.tripwires import (
        CompileCounter, decode_cache_sizes, decode_compile_count,
        jit_cache_size,
    )

    cfg, params = tiny_engine_parts
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16, 64),
        decode_block=1, decode_loop=0, prompt_cache=False))
    eng.start()
    try:
        greedy = SamplingParams(temperature=0.0)
        mixed = [GenRequest(prompt_ids=list(range(1, 1 + n)), params=greedy,
                            max_tokens=m, ignore_eos=True)
                 for n, m in ((5, 6), (13, 4), (40, 8), (22, 3))]
        reasons = _drive(eng, mixed)
        assert all(r == "length" for r in reasons), reasons
        assert decode_compile_count(eng) == 1, decode_cache_sizes(eng)

        # second mixed stream, fresh lengths: ZERO new compilations of any
        # program (admission buckets included — they were warmed above)
        with CompileCounter() as cc:
            more = [GenRequest(prompt_ids=list(range(2, 2 + n)),
                               params=greedy, max_tokens=m, ignore_eos=True)
                    for n, m in ((9, 5), (33, 4))]
            reasons = _drive(eng, more)
        assert all(r == "length" for r in reasons), reasons
        assert cc.total == 0, cc.counts
        assert decode_compile_count(eng) == 1, decode_cache_sizes(eng)
    finally:
        eng.stop()

    # ---- while-loop path: the loop program compiles once per sort-free
    # sampling tier at warmup and NEVER again — a retracing loop body
    # (tracer-dependent shape, unhashed arg) would grow the cache here
    loop_eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16, 64),
        decode_block=4, decode_loop=32, prompt_cache=False))
    loop_eng.warmup()
    warm = decode_compile_count(loop_eng)
    loop_variants = jit_cache_size(loop_eng._decode_loop_fn)
    assert loop_variants >= 1
    loop_eng.start()
    try:
        greedy = SamplingParams(temperature=0.0)
        mixed = [GenRequest(prompt_ids=list(range(1, 1 + n)), params=greedy,
                            max_tokens=m, ignore_eos=True)
                 for n, m in ((5, 6), (13, 4), (40, 8), (22, 3))]
        reasons = _drive(loop_eng, mixed)
        assert all(r == "length" for r in reasons), reasons
        assert decode_compile_count(loop_eng) == warm, \
            decode_cache_sizes(loop_eng)
        with CompileCounter() as cc:
            more = [GenRequest(prompt_ids=list(range(2, 2 + n)),
                               params=greedy, max_tokens=m, ignore_eos=True)
                    for n, m in ((9, 5), (33, 4))]
            reasons = _drive(loop_eng, more)
        assert all(r == "length" for r in reasons), reasons
        assert cc.total == 0, cc.counts
        assert jit_cache_size(loop_eng._decode_loop_fn) == loop_variants, \
            decode_cache_sizes(loop_eng)
    finally:
        loop_eng.stop()


@pytest.mark.tripwire
@pytest.mark.parametrize("kind, ec_kw", [
    ("plain", dict()),
    ("paged", dict(kv_pages=8)),
    ("draft", dict(gamma=2)),
])
def test_decode_fn_attrs_are_the_programs_the_engine_builds(
        tiny_engine_parts, kind, ec_kw):
    """The compile-count guard sums the jit caches DECODE_FN_ATTRS names, so
    it can only miss a program the list does not hold. A decode dispatch is
    what the engine counts as one (a method that adds to
    `decode_dispatches`); the jitted attributes those methods call are the
    list, no more and no fewer, and each is an attribute this engine's
    `_build_jit` set (`_spec_fn` with a draft model only)."""
    import ast
    import inspect

    from localai_tpu.engine import engine as E
    from localai_tpu.testing.tripwires import DECODE_FN_ATTRS, jit_cache_size

    called = set()
    for fn in ast.walk(ast.parse(inspect.getsource(E))):
        if not isinstance(fn, ast.FunctionDef):
            continue
        nodes = list(ast.walk(fn))
        if not any(isinstance(n, ast.AugAssign)
                   and isinstance(n.target, ast.Subscript)
                   and isinstance(n.target.slice, ast.Constant)
                   and n.target.slice.value == "decode_dispatches"
                   for n in nodes):
            continue
        called |= {n.attr for n in nodes if isinstance(n, ast.Attribute)
                   and n.attr.endswith("_fn")}
    assert called == set(DECODE_FN_ATTRS)

    cfg, params = tiny_engine_parts
    eng = E.Engine(cfg, params, None, E.EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prompt_cache=False, **ec_kw),
        draft=(cfg, params) if kind == "draft" else None)
    built = {a for a in DECODE_FN_ATTRS if hasattr(eng, a)}
    assert built == set(DECODE_FN_ATTRS) - (
        set() if kind == "draft" else {"_spec_fn"})
    # each is a jitted program whose cache the guard can read
    assert all(jit_cache_size(getattr(eng, a)) == 0 for a in built)
    # the engine's other jitted attributes are admission, prefill and the
    # cache's upkeep: none a decode dispatch calls (`called` above)
    others = {a for a, v in vars(eng).items()
              if a not in called and hasattr(v, "_cache_size")}
    assert {"_admit_many_fn", "_extend_mid_fn", "_extend_final_fn"} <= others


@pytest.mark.tripwire
def test_decode_dispatch_budget_on_128_token_stream(tiny_engine_parts):
    """The dispatch-count guard (ISSUE 6 satellite): a 128-token single-slot
    stream rides the fused while loop in <= 3 decode dispatches (the ladder
    took 8-16, per-step 128). dispatch_budget raises if the loop stops
    engaging."""
    from localai_tpu.engine.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.ops.sampling import SamplingParams
    from localai_tpu.testing.tripwires import dispatch_budget

    cfg, params = tiny_engine_parts
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=160, prefill_buckets=(16,),
        prompt_cache=False))
    eng.start()
    try:
        with dispatch_budget(eng, max_per_128_tokens=3.0):
            reasons = _drive(eng, [GenRequest(
                prompt_ids=[1, 2, 3, 4, 5],
                params=SamplingParams(temperature=0.0),
                max_tokens=128, ignore_eos=True)])
        assert reasons == ["length"]
        assert eng.metrics["decode_dispatches"] <= 3, eng.metrics
        assert eng.metrics["decode_steps_dispatched"] == 128, eng.metrics
        # and the guard itself has teeth: a budget of 0.5/128 must trip
        with pytest.raises(AssertionError, match="dispatch budget"):
            with dispatch_budget(eng, max_per_128_tokens=0.25):
                _drive(eng, [GenRequest(
                    prompt_ids=[1, 2, 3],
                    params=SamplingParams(temperature=0.0),
                    max_tokens=128, ignore_eos=True)])
    finally:
        eng.stop()


@pytest.mark.tripwire
def test_transfer_guard_clean_on_fused_decode(tiny_engine_parts,
                                              monkeypatch):
    """jax.transfer_guard('disallow') around the fused decode block: the
    shipped dispatch makes NO implicit transfers (every host→device crossing
    is an explicit jnp.asarray/device_put), so a full mixed stream completes
    under the guard — including the fused decode_block path."""
    monkeypatch.setenv("LOCALAI_TRANSFER_GUARD", "disallow")
    from localai_tpu.engine.engine import Engine, EngineConfig, GenRequest
    from localai_tpu.ops.sampling import SamplingParams

    cfg, params = tiny_engine_parts
    # decode_loop=16 covers the single-dispatch while-loop path (ISSUE 6:
    # its per-dispatch uploads — active/remaining/check_eos — must all be
    # explicit); decode_loop=0 covers the scan-block fallback
    for loop in (16, 0):
        eng = Engine(cfg, params, None, EngineConfig(
            max_slots=2, max_context=128, prefill_buckets=(16, 64),
            decode_block=4, decode_loop=loop, prompt_cache=False))
        assert eng._xfer_guard == "disallow"
        eng.start()
        try:
            reqs = [GenRequest(prompt_ids=list(range(1, 1 + n)),
                               params=SamplingParams(temperature=0.0),
                               max_tokens=12, ignore_eos=True)
                    for n in (6, 30)]
            reasons = _drive(eng, reqs)
            assert all(r == "length" for r in reasons), reasons
            assert eng.metrics["tokens_generated"] == 24
        finally:
            eng.stop()


@pytest.mark.tripwire
def test_transfer_guard_has_teeth():
    """Prove the guard actually trips: an implicit numpy→device transfer at
    a jit boundary raises under 'disallow' (this is exactly what a stray
    un-wrapped host array in the decode dispatch would look like)."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.testing.tripwires import transfer_guard

    f = jax.jit(lambda a, b: a + b)
    x = jnp.ones(4)
    f(x, np.ones(4))  # warm: implicit transfer is legal un-guarded
    with transfer_guard("disallow"):
        f(x, x)  # device-resident args: fine
        with pytest.raises(Exception, match="[Dd]isallow"):
            f(x, np.ones(4))
    # and the engine helper is a no-op when the env is unset
    from localai_tpu.testing.tripwires import decode_guard_level

    assert decode_guard_level() in ("", "disallow", "log", "allow",
                                    "log_explicit", "disallow_explicit")
