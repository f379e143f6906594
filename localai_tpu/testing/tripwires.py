"""Runtime tripwires — the dynamic half of tools/lint.

The AST pass (tools/lint) catches the host syncs and recompile hazards it can
see; these two guards catch what it can't:

- transfer guard: `LOCALAI_TRANSFER_GUARD=disallow` makes the engine wrap
  every fused decode dispatch in `jax.transfer_guard("disallow")` — any
  implicit host↔device transfer inside the dispatch (an un-wrapped numpy
  arg, a stray `.item()` on a donated buffer) raises instead of silently
  stalling the pipeline. Explicit transfers (jnp.asarray / device_put /
  device_get) stay legal: the contract is "syncs are spelled out", not
  "no transfers".

- compile-count guard: `decode_compile_count(engine)` sums the jit cache
  sizes of the decode-step programs, and `CompileCounter` counts live XLA
  compilations via jax.log_compiles. A perf PR that makes `decode_step`
  retrace per request (tracer branch, data-dependent shape, unhashed jit
  arg) fails the guard long before anyone reads a profile.

- dispatch-count guard: `dispatch_budget(engine, ...)` asserts the enclosed
  stream keeps the decode-dispatch count within a budget per 128 generated
  tokens. The single-dispatch while-loop makes a 128-token single-slot
  stream ~2 dispatches; a regression back to the scan ladder (8-16) or to
  per-step dispatches (128) trips the guard in a tier-1 test instead of a
  chip profile.

The concurrency sibling lives in `localai_tpu.testing.lockdep`: the same
env-gate pattern (`LOCALAI_LOCKDEP=1` / `record`, raw locks when unset)
arms an acquisition-order tripwire over every lock registered through
`lockdep_lock()` — the dynamic half of `tools/lockdep`, the way these
guards are the dynamic half of `tools/lint`.
"""
from __future__ import annotations

import contextlib
import logging
import math
import os


def decode_guard_level() -> str:
    """The engine's transfer-guard level from LOCALAI_TRANSFER_GUARD
    ("" = disabled; "1" is shorthand for "disallow")."""
    val = os.environ.get("LOCALAI_TRANSFER_GUARD", "").strip()
    if val == "1":
        return "disallow"
    if val in ("", "0"):
        return ""
    return val


def transfer_guard(level: str = "disallow"):
    """Context manager guarding implicit transfers (both directions) —
    nullcontext when level is empty."""
    if not level:
        return contextlib.nullcontext()
    import jax

    return jax.transfer_guard(level)


# the engine attributes holding decode-step jit programs; everything the
# per-token serving path can dispatch (admission/prefill compile per bucket
# by design and are not covered by the exactly-once contract)
DECODE_FN_ATTRS = (
    "_decode_fn", "_decode_nomask_fn", "_decode_fast_fn",
    "_decode_block_fn", "_decode_block_mask_fn", "_decode_loop_fn",
    "_spec_fn",
)


def jit_cache_size(fn) -> int:
    """Compiled-variant count of a jax.jit callable (-1 when the runtime
    doesn't expose it — the guard then degrades to the CompileCounter)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


def decode_cache_sizes(engine) -> dict[str, int]:
    out = {}
    for attr in DECODE_FN_ATTRS:
        fn = getattr(engine, attr, None)
        if fn is not None:
            out[attr] = jit_cache_size(fn)
    return out


def decode_compile_count(engine) -> int:
    """Total decode-step programs compiled by this engine. The regression
    contract (ROADMAP #1): a mixed-length request stream with uniform
    sampling knobs compiles the decode step EXACTLY ONCE — prefill buckets
    absorb length variance; per-knob static variants (fast_width tiers,
    decode_block ladder steps) are deliberate and each counts once."""
    sizes = decode_cache_sizes(engine)
    known = [v for v in sizes.values() if v >= 0]
    return sum(known)


@contextlib.contextmanager
def dispatch_budget(engine, max_per_128_tokens: float = 3.0):
    """Decode-dispatch counter guard: assert the enclosed stream spends no
    more than `max_per_128_tokens` decode dispatches per 128 generated
    tokens (pro-rated, floor 1). Reads the engine's own decode_dispatches /
    tokens_generated counters, so it works across loop, block and spec
    paths without instrumentation."""
    m = engine.metrics
    d0, t0 = m["decode_dispatches"], m["tokens_generated"]
    yield
    dispatches = m["decode_dispatches"] - d0
    tokens = m["tokens_generated"] - t0
    allowed = max(1, math.ceil(tokens / 128.0 * max_per_128_tokens))
    if dispatches > allowed:
        # flight-recorder post-mortem (ISSUE 11): the request timelines in
        # the ring at trip time show WHICH stream regressed to the ladder
        from localai_tpu import telemetry

        rec = telemetry.flightrec()
        rec.record_event("tripwire", guard="dispatch_budget",
                         dispatches=dispatches, tokens=tokens,
                         allowed=allowed)
        rec.auto_dump("tripwire:dispatch_budget")
        raise AssertionError(
            f"decode dispatch budget exceeded: {dispatches} dispatches for "
            f"{tokens} generated tokens (allowed {allowed} at "
            f"{max_per_128_tokens}/128-token) — the fused decode loop is "
            f"not engaging or has regressed to per-step dispatch")


class CompileCounter:
    """Count XLA compilations by function name while the context is open.

    Rides `jax.log_compiles`: the pxla layer logs one
    "Compiling <name> ..." record per backend compile, which a handler on
    the "jax" logger tree tallies. Zero new compilations across a repeat
    stream is the strongest no-retrace assertion available at runtime.
    """

    def __init__(self):
        self.counts: dict[str, int] = {}
        self._handler: logging.Handler | None = None
        self._ctx = None

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def __enter__(self):
        import jax

        counter = self

        class _H(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    name = msg.split()[1]
                    counter.counts[name] = counter.counts.get(name, 0) + 1

        self._handler = _H(level=logging.DEBUG)
        logging.getLogger("jax").addHandler(self._handler)
        self._ctx = jax.log_compiles(True)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        if self._ctx is not None:
            self._ctx.__exit__(*exc)
            self._ctx = None
        if self._handler is not None:
            logging.getLogger("jax").removeHandler(self._handler)
            self._handler = None
        return False
