"""The declared lock hierarchy — the single source of truth for lock order.

Semantics: **lower rank = outer lock = acquired first.**  The analyzer
(and the runtime tripwire's observed-order graph) records an edge
``A -> B`` whenever ``B`` is acquired while ``A`` is held; the edge is
legal iff ``rank(A) < rank(B)`` strictly.  Two locks with equal rank may
never nest in either direction (equal rank means "same level, disjoint").

Lock names come from the ``lockdep_lock("name")`` registration sites in
the package; locks not (yet) created through ``lockdep_lock`` are mapped
to names here via :data:`STATIC_IDS` (keyed by the analyzer's derived
identity ``module.Class.attr`` / ``module.GLOBAL``).  A lock the analyzer
discovers that resolves to neither is an ``unranked-lock`` violation —
that is the "adding a new lock" checklist made mechanical: create it via
``lockdep_lock`` with a name, rank the name below, and the analyzer stays
green.

The rank bands (10s gaps so new locks land between existing ones):

- 0–4      backend-process load locks (outermost: a servicer load wraps
           engine construction, warmup and prewarm end to end)
- 5–9      HTTP bridge
- 10–29    manager supervision: the per-model load lock is the OUTERMOST
           long-held lock in the serving stack — load() holds it across
           the whole spawn/health/admit sequence and takes the map lock,
           handle locks and breaker inside it.  (Note the direction: the
           map lock is INNER — it guards the maps only and is never held
           across spawn/health/RPC, per the PR 4 fix.)
- 30–39    circuit breaker
- 40–49    engine bookkeeping (submit/cancel rid maps, grammar-cache init)
- 50–59    host-KV pool + prefix digest
- 60–69    grammar matcher caches, native build lock
- 70–89    peripheral singletons (stores, explorer, config loader, MCP
           transport, distributed replicator, per-backend load locks)
- 90–99    telemetry + test-harness leaves: these locks are taken deep
           inside everything else and must never acquire anything
           themselves.
"""
from __future__ import annotations

RANKS: dict[str, int] = {
    # backend-process outermost: each servicer's load lock serializes the
    # WHOLE load/warmup/prewarm sequence — it wraps engine construction,
    # grammar precompile, KV pool priming and replicator broadcast, so
    # every in-process lock nests inside it.  (Backend servicers live in
    # separate processes; their load locks never nest with each other.)
    "backend.llm.load": 0,
    "backend.image": 1,
    "backend.hfapi": 2,
    "backend.whisper": 3,
    "backend.detect": 4,

    # HTTP bridge
    "http.mcp": 5,

    # manager supervision (manager.model is per-key: one lock per model
    # name; two model locks must never nest — the analyzer and the runtime
    # tripwire both flag same-class nesting)
    "manager.model": 10,
    "manager.map": 20,
    "manager.handle": 25,

    # resilience
    "breaker": 30,

    # engine
    "engine.submit": 40,
    "engine.grammar": 45,

    # host KV hierarchy
    "kvhost.pool": 50,
    "kvhost.digest": 55,

    # grammar / native toolchain
    "matcher.cache": 60,
    "matcher.tables": 62,
    "native.build": 65,

    # peripheral singletons
    "mcp.transport": 70,
    "stores.local": 72,
    "explorer.db": 74,
    "config.loader": 76,
    "parallel.replicator": 78,

    # telemetry + harness leaves (acquire NOTHING below them)
    "telemetry.tracer_init": 90,
    "telemetry.slo_init": 91,
    "telemetry.slo": 92,
    "telemetry.flightrec_init": 93,
    "telemetry.flightrec": 94,
    "telemetry.sched": 95,
    "telemetry.compiles_init": 96,
    "telemetry.compiles": 96,
    # held for the seconds of a device trace by the one GetTrace handler
    # thread that takes it (try-acquire only: nobody ever waits on it)
    "telemetry.xprof": 97,
    "faults.table": 98,
    "lockdep.graph": 99,
}

# locks not created through lockdep_lock(...) — mapped from the analyzer's
# derived static identity to a hierarchy name.  Migrating a lock to
# lockdep_lock removes its row here (the registration carries the name).
STATIC_IDS: dict[str, str] = {
    "localai_tpu.mcp._StdioTransport._lock": "mcp.transport",
    "localai_tpu.stores.LocalStore._lock": "stores.local",
    "localai_tpu.explorer.Database._lock": "explorer.db",
    "localai_tpu.config.model_config.ModelConfigLoader._lock": "config.loader",
    "localai_tpu.parallel.distributed.Replicator._lock": "parallel.replicator",
    "localai_tpu.backend.llm.LLMServicer._load_lock": "backend.llm.load",
    "localai_tpu.backend.image.ImageServicer._lock": "backend.image",
    "localai_tpu.backend.hfapi.HFApiServicer._lock": "backend.hfapi",
    "localai_tpu.backend.whisper.WhisperServicer._lock": "backend.whisper",
    "localai_tpu.backend.detect.DetectServicer._lock": "backend.detect",
    "localai_tpu.testing.faults._lock": "faults.table",
    "localai_tpu.testing.lockdep._graph_lock": "lockdep.graph",
}

# names marked per-key at registration (a CLASS of locks, one per dict
# key): nesting two locks of the class is an ABBA hazard even though the
# instances differ.  lockdep_lock(per_key=True) marks these dynamically;
# this set is the static mirror.
PER_KEY: frozenset[str] = frozenset({"manager.model"})


def rank_of(name: str) -> int | None:
    return RANKS.get(name)
