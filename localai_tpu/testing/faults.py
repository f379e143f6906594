"""Deterministic fault injection for the chaos harness (ISSUE 4).

Activated by the `LOCALAI_FAULT` environment variable — a comma-separated
list of fault specs, each `kind[:arg[:limit[:target]]]`:

- `kind`: injection point name. Wired points:
    `spawn_crash`   backend process exits immediately at startup (the
                    free_port TOCTOU / dead-child shape; arg = exit code)
    `slow_start`    backend sleeps `arg` seconds before serving health
    `prewarm_raise` the llm backend's LoadModel prewarm raises — the load
                    must fail, not report READY
    `kernel_raise`  a Pallas kernel wrapper raises at trace time (a kernel
                    the device refuses to lower) — the load must fail with
                    that message, not switch tier
    `unavailable`   Predict/PredictStream aborts with gRPC UNAVAILABLE
    `deadline`      Predict/PredictStream aborts with DEADLINE_EXCEEDED
    `stall_stream`  PredictStream sleeps `arg` seconds after its first chunk
    `preempt`       backend raises SIGTERM against itself after the first
                    emitted token of a stream — the preemption-notice
                    fast-path (ISSUE 19): the engine spill-drains live slots
                    into ResumeTokens before the process stops (arg = grace
                    seconds the drain lets slots keep running)
    `kill9_middecode`  backend SIGKILLs itself at the `arg`-th emitted token
                    of a stream (default 1) — ungraceful death mid-decode:
                    no drain, no checkpoint; the HTTP bridge must resume
                    from its own accumulated stream state
- `arg`: float parameter (seconds / exit code); default 0.
- `limit`: inject at most N times; empty = unlimited. Counting is shared
  across processes when `LOCALAI_FAULT_DIR` points at a directory (one
  marker file per injection, O_EXCL-raced so concurrent processes never
  double-count a slot); otherwise per-process.
- `target`: only inject in processes whose `LOCALAI_FAULT_MODEL` matches
  (the ModelManager stamps each backend spawn with its model name); empty
  = every process. This is what lets one chaos test crash model A's
  backend while model B serves normally.

Example: `LOCALAI_FAULT=slow_start:3::slowpoke,unavailable:0:1:tiny`
injects a 3 s startup stall into every `slowpoke` backend and exactly one
UNAVAILABLE abort into `tiny`'s generation path.

The whole module is read-only over os.environ at call time — no setup, no
registration; a subprocess inherits the spec through its environment.
"""
from __future__ import annotations

import os
import threading

_lock = threading.Lock()
_local_counts: dict[str, int] = {}


def _specs() -> list[tuple[str, float, int | None, str]]:
    raw = os.environ.get("LOCALAI_FAULT", "")
    if not raw:
        return []
    out = []
    for entry in raw.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = (entry.split(":") + ["", "", ""])[:4]
        kind, arg, limit, target = parts
        try:
            farg = float(arg) if arg else 0.0
        except ValueError:
            farg = 0.0
        try:
            nlimit = int(limit) if limit else None
        except ValueError:
            nlimit = None
        out.append((kind, farg, nlimit, target))
    return out


def _take_slot(kind: str, target: str, limit: int | None) -> bool:
    """Claim one injection slot for a (kind, target) entry; False once
    `limit` is spent. Each spec entry counts independently — two models'
    stall_stream faults never steal each other's slots. Shared-count mode
    (LOCALAI_FAULT_DIR) survives process boundaries."""
    if limit is None:
        return True
    key = f"{kind}@{target}" if target else kind
    fault_dir = os.environ.get("LOCALAI_FAULT_DIR", "")
    if fault_dir and os.path.isdir(fault_dir):
        n = 0
        while n < limit:
            try:
                fd = os.open(os.path.join(fault_dir, f"{key}.{n}"),
                             os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.close(fd)
                return True
            except FileExistsError:
                n += 1
        return False
    with _lock:
        used = _local_counts.get(key, 0)
        if used >= limit:
            return False
        _local_counts[key] = used + 1
        return True


def fire(kind: str) -> float | None:
    """Should fault `kind` inject right now? Returns its arg (consuming one
    count) when yes, None when no. Fast path: env unset → one dict miss."""
    if not os.environ.get("LOCALAI_FAULT"):
        return None
    me = os.environ.get("LOCALAI_FAULT_MODEL", "")
    for k, arg, limit, target in _specs():
        if k != kind:
            continue
        if target and target != me:
            continue
        if not _take_slot(kind, target, limit):
            continue
        import sys

        print(f"[fault] {kind} arg={arg} target={target or '*'} "
              f"pid={os.getpid()} firing", file=sys.stderr, flush=True)
        return arg
    return None
