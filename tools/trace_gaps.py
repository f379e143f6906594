"""What the engine thread was doing while the device sat idle, from ONE trace.

    python -m tools.trace_gaps <trace dir | file.xplane.pb | recorded.json>

The trace is one taken by `GET /debug/xprof` (or any `jax.profiler` trace of
the backend process, host tracer level >= 1): it holds the device's ops and
the engine's `engine.<phase>` TraceAnnotations (telemetry.PhaseClock) on the
profiler's own clock, so no clock is guessed. Printed, and returned by
`reduce` as a dict:

- per engine phase: its time in the trace, and the device-idle time under it
  (where no op ran on the device while the thread was in that phase);
- the ten longest device gaps, each with the phase that covers most of it
  and the rows the engine held as that tick's decode dispatch was enqueued
  (active / in prefill / free, and the queue's length: arguments of the
  annotation opened after it, there in a `GET /debug/xprof` trace);
- per program (XLA module) its calls, mean time a call, and the mean of
  those rows over the ticks its calls started under. A program runs on the
  device up to a dispatch after the tick that enqueued it, so read the rows
  beside a program as "what the engine held while it ran";
- per phase, the host events the profiler recorded on the engine's thread
  inside it (jitted calls, transfers, waits), by inclusive time: what the
  thread was doing there;
- device time by named scope (`jax.named_scope` in models/llama.py and
  ops/sampling.py: attention, mlp, experts/router, experts/expert_einsums,
  lm_head, sampling, cache_update), own time: an op that holds others (a
  while loop and its body's fusions are events of one line) counts only
  what its children leave;
- `unix_offset_us`: the annotations' `unix_us` minus their trace time, which
  places ring spans (`/debug/trace`, unix microseconds) on the trace's clock.

The interval arithmetic is benchmark/harness/tracefacts.py's. Reading an
.xplane.pb needs `jax.profiler.ProfileData`; run with JAX_PLATFORMS=cpu, it
touches no device. A recorded .json (the form `load_xplane` returns) needs
no JAX.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness.tracefacts import (  # noqa: E402
    DEVICE_PLANE, MODULE_LINE, OP_LINE, clip, complement, intersect, merge,
    module_base, op_short, total,
)

PHASE = re.compile(r"^engine\.(dispatch|admit|emit|kv|device|idle)$")
SCOPES = ("expert_einsums", "router", "dispatch", "shared", "latent_in",
          "latent_out", "experts",
          "attention", "mlp", "lm_head", "sampling", "cache_update")
# Pallas kernels are traced under no scope (a scope would change their
# compile-cache key, models/llama.py): they are told by their own jit name
KERNELS = {"kda_decode": "attention/linear/kda_decode",
           "kda_chunk": "attention/linear/kda_chunk",
           "ssd_decode": "attention/ssm/ssd_decode",
           "mla_decode": "attention/latent/mla_decode",
           "mla_chunk": "attention/latent/chunk_kernel",
           "grouped_matmul": "experts/expert_einsums",
           "ragged_decode": "attention",
           "flash_prefill": "attention", "paged_scatter_append": "cache_update"}
LAYER_KINDS = ("window", "full", "linear", "latent", "ssm")
# what a linear layer's mixer is made of (models/kv.py StateKV), and a latent
# layer's attention (models/llama.py _latent_qk, models/kv.py LatentKV)
LINEAR_PARTS = ("conv", "kda_chunk", "kda_decode")
# (`chunk_kernel`: the scope round a chunk's kernel, ops/pallas/mla.py
# mla_chunk, which expands the rows itself: PR 46)
LATENT_PARTS = ("q_lora", "kv_lora", "absorb", "expand", "chunk_kernel")
# a state-space layer's mixer (models/llama.py _ssm_mixer, models/kv.py SsmKV)
SSM_PARTS = ("in_proj", "ssd_chunk", "ssd_decode", "gated_norm", "out_proj")
TOP = 10
# what an annotation says the engine held as its tick's dispatch was enqueued
ROWS = {"active": "rows_active", "prefill": "rows_prefill",
        "free": "rows_free", "queued": "queued"}


def _varint(buf: bytes, i: int) -> tuple:
    v = shift = 0
    while True:
        c = buf[i]
        i += 1
        v |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return v, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: enough of
    the wire format to reach what ProfileData does not show."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, wire, v


def op_paths(path: str) -> dict:
    """{op event name: its `tf_op`}, e.g. 'jit(_loop)/while/body/experts/
    router/top_k:', of the device planes. The path, with the named scopes,
    is a stat of the event's METADATA, which ProfileData does not expose, so
    it is read off the file (tsl xplane.proto: XSpace.planes=1; XPlane name=2
    event_metadata=4 stat_metadata=5; XEventMetadata name=2 stats=5; XStat
    metadata_id=1 str_value=5 ref_value=7; XStatMetadata name=2)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((v for f, _, v in parts if f == 2), b"").decode()
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for f, _, entry in parts:
            if f == 5:
                e = {k: v for k, _, v in _fields(entry)}
                meta = {k: v for k, _, v in _fields(e.get(2, b""))}
                stat_names[e.get(1)] = meta.get(2, b"").decode()
        for f, _, entry in parts:
            if f != 4:
                continue
            e = {k: v for k, _, v in _fields(entry)}
            meta = list(_fields(e.get(2, b"")))
            op = next((v for k, _, v in meta if k == 2), b"").decode()
            for k, _, stat in meta:
                if k != 5:
                    continue
                st = {a: b for a, _, b in _fields(stat)}
                if stat_names.get(st.get(1)) == "tf_op":
                    v = st.get(5) or stat_names.get(st.get(7), "")
                    out[op] = v.decode() if isinstance(v, bytes) else v
    return out


def load_xplane(path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns, {stat: value}], ...]}]}]}: the device planes' op and module
    lines and every host line that holds an engine.<phase> annotation (the
    engine's thread, with everything else the profiler recorded on it)."""
    from jax.profiler import ProfileData

    paths = op_paths(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_dev and line.name not in (OP_LINE, MODULE_LINE):
                continue
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                       {k: v for k, v in ev.stats
                        if isinstance(v, (str, int, float))}]
                      for ev in line.events]
            if is_dev and line.name == MODULE_LINE:
                events = [e[:3] + [{}] for e in events]
            elif is_dev:    # the op's path; its own stats are not read
                for e in events:
                    e[3] = {"tf_op": paths[e[0]]} if e[0] in paths else {}
            if is_dev or any(PHASE.match(e[0]) for e in events):
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load(path: str) -> dict:
    if os.path.isdir(path):
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = max(files, key=os.path.getmtime)
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    return load_xplane(path)


def scope_of(name: str, stats: dict) -> str:
    """The innermost known named scope in the op's metadata path
    (`jit(_loop)/.../experts/router/dot_general`), wherever the trace keeps
    it: a string stat or the event's own name."""
    for text in [v for v in stats.values() if isinstance(v, str)] + [name]:
        parts = text.split("/")
        for scope in SCOPES:          # inner scopes are listed first
            if scope in parts:
                if scope in ("router", "expert_einsums"):
                    return "experts/" + scope
                if scope in ("dispatch", "shared", "latent_in",
                             "latent_out"):
                    # the routed expert layer's own (models/llama.py
                    # _moe_routed); the bare words mean nothing elsewhere
                    if "experts" in parts:
                        return "experts/" + scope
                    continue
                # a model with window and full layers traces its attention
                # under attention/<kind> (models/llama.py _attn_scope)
                kind = parts[parts.index(scope) + 1:][:1]
                if scope == "attention" and kind and kind[0] in LAYER_KINDS:
                    # (a kernel under the scope goes by its jit name)
                    inner = [p for p in (
                        q[4:-1] if q.startswith("jit(") and q.endswith(")")
                        else q for q in parts)
                        if p in LINEAR_PARTS + LATENT_PARTS + SSM_PARTS]
                    return "/".join(["attention", kind[0]] + inner[:1])
                return scope
        for part in parts:
            if part.startswith("jit("):
                for kernel, scope in KERNELS.items():
                    if part[4:].startswith(kernel):
                        return scope
    return "unscoped"


def reduce(raw: dict) -> dict:
    phases, ops, host, modules = [], [], [], []
    for plane in raw.get("planes", []):
        dev = bool(DEVICE_PLANE.match(plane["name"]))
        for line in plane["lines"]:
            if dev:
                if line["name"] == OP_LINE:
                    ops.extend(line["events"])
                elif line["name"] == MODULE_LINE:
                    modules.extend(line["events"])
                continue
            for ev in line["events"]:
                (phases if PHASE.match(ev[0]) else host).append(ev)
    if not ops:
        raise SystemExit("the trace holds no device op")
    if not phases:
        raise SystemExit("the trace holds no engine.<phase> annotation: was "
                         "it taken from the backend process, host tracer "
                         "level 1 or more?")
    # two windows: the device's whole span for its time by scope, and for
    # everything said about phases the part of it the engine's annotations
    # cover (on a TPU v5e the host tracer held about one second of a
    # three-second trace, the device tracer all of it)
    phases.sort(key=lambda e: e[1])
    dev_lo = min(e[1] for e in ops)
    dev_hi = max(e[1] + e[2] for e in ops)
    dev_busy = merge([[e[1], e[1] + e[2]] for e in ops])
    lo = max(dev_lo, phases[0][1])
    hi = min(dev_hi, max(e[1] + e[2] for e in phases))
    if hi <= lo:
        raise SystemExit("the device's ops and the engine's phases do not "
                         "overlap in this trace")
    busy = clip(dev_busy, lo, hi)
    idle = complement(busy, lo, hi)

    by_phase: dict = {}
    offsets = []
    for name, start, dur, stats in phases:
        by_phase.setdefault(name[len("engine."):], []).append(
            [start, start + dur])
        if "unix_us" in stats:
            offsets.append(int(stats["unix_us"]) - start / 1e3)
    rows = {}
    for phase, spans in by_phase.items():
        spans = merge(spans)
        rows[phase] = {
            "s": total(clip(spans, lo, hi)) / 1e9,
            "device_idle_s": total(intersect(clip(spans, lo, hi), idle)) / 1e9,
            "spans": len(spans)}
    covered = merge([s for spans in by_phase.values() for s in spans])

    # the rows of a tick's dispatch, by tick, and the tick open at a time (a
    # tick lasts from its first annotation to the next tick's)
    tick_rows = {e[3]["tick"]: {k: e[3][v] for k, v in ROWS.items()}
                 for e in phases if ROWS["active"] in e[3]}
    starts = [e[1] for e in phases]

    def rows_at(t: float) -> dict | None:
        i = bisect.bisect_right(starts, t) - 1
        return tick_rows.get(phases[i][3].get("tick")) if i >= 0 else None

    gaps = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:TOP]:
        under = {phase: total(intersect(merge(spans), [[a, b]]))
                 for phase, spans in by_phase.items()}
        phase = max(under, key=under.get) if any(under.values()) else "none"
        gaps.append({"phase": phase, "ms": (b - a) / 1e6,
                     "at_ms": (a - lo) / 1e6, "rows": rows_at(a)})

    programs: dict = {}
    for name, start, dur, _ in modules:
        p = programs.setdefault(module_base(name), {"calls": 0, "ns": 0,
                                                    "rows": []})
        p["calls"] += 1
        p["ns"] += dur
        held = rows_at(start)
        if held is not None:
            p["rows"].append(held)

    # what the engine's thread did inside each phase: the other host events
    # of its line, each under the phase that holds its start
    under: dict = {}
    for name, start, dur, _ in host:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < phases[i][1] + phases[i][2]:
            d = under.setdefault(phases[i][0][len("engine."):], {})
            d[name] = d.get(name, 0) + dur

    scope_ns: dict = {}
    top_ops: dict = {}
    stack: list = []                     # [end, scope, op, own_ns]

    def close(item):
        scope_ns[item[1]] = scope_ns.get(item[1], 0.0) + max(item[3], 0.0)
        key = (item[1], item[2])
        top_ops[key] = top_ops.get(key, 0.0) + max(item[3], 0.0)

    for name, start, dur, stats in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][3] -= dur
        stack.append([start + dur, scope_of(name, stats), op_short(name),
                      float(dur)])
    while stack:
        close(stack.pop())

    return {
        "device_s": (dev_hi - dev_lo) / 1e9,
        "device_busy_s": total(dev_busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "busy_s": total(busy) / 1e9,
        "idle_share": 1.0 - total(busy) / (hi - lo),
        "phases": rows,
        # the phases tile the engine thread's time: what their span holds
        # beyond their sum is annotations the trace lost (one that was open
        # when the trace started or stopped is not in it)
        "phases_span_s": (max(e[1] + e[2] for e in phases)
                          - phases[0][1]) / 1e9,
        "phases_sum_s": sum(e[2] for e in phases) / 1e9,
        "idle_outside_any_phase_s": total(intersect(
            idle, complement(clip(covered, lo, hi), lo, hi))) / 1e9,
        "gaps": gaps,
        "programs": [{
            "program": name, "calls": p["calls"],
            "ms": p["ns"] / p["calls"] / 1e6,
            "rows": {k: sum(r[k] for r in p["rows"]) / len(p["rows"])
                     for k in ROWS} if p["rows"] else None}
            for name, p in sorted(programs.items(),
                                  key=lambda kv: -kv[1]["ns"])],
        "host_under": {phase: [[n, ns / 1e9] for n, ns in sorted(
            d.items(), key=lambda kv: -kv[1])[:5]]
            for phase, d in under.items()},
        "scope_s": {k: v / 1e9 for k, v in sorted(
            scope_ns.items(), key=lambda kv: -kv[1])},
        "top_ops": [[scope, op, ns / 1e9] for (scope, op), ns in sorted(
            top_ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "unix_offset_us": (sorted(offsets)[len(offsets) // 2]
                           if offsets else None),
        "ticks": len({e[3].get("tick") for e in phases}),
    }


def _rows(rows: dict | None) -> str:
    if rows is None:
        return "rows not in the trace"
    return ("rows {active:.3g} active / {prefill:.3g} prefill / {free:.3g} "
            "free, {queued:.3g} queued".format(**rows))


def render(facts: dict) -> str:
    out = [f"device: busy {facts['device_busy_s']:.4f} s of "
           f"{facts['device_s']:.4f} s traced; under the engine's "
           f"annotations: busy {facts['busy_s']:.4f} s of "
           f"{facts['window_s']:.4f} s, idle {facts['idle_share']:.3%}, "
           f"{facts['ticks']} engine ticks"]
    out.append("engine phase        time s   device idle under it s")
    for phase, r in sorted(facts["phases"].items(),
                           key=lambda kv: -kv[1]["s"]):
        out.append(f"  {phase:<12} {r['s']:>11.4f}   {r['device_idle_s']:.6f}")
    out.append(f"  (idle outside any phase: "
               f"{facts['idle_outside_any_phase_s']:.6f} s; the phases sum "
               f"to {facts['phases_sum_s']:.4f} s over a span of "
               f"{facts['phases_span_s']:.4f} s)")
    out.append("longest device gaps (ms, at ms from the first op, phase, "
               "the rows of its tick's dispatch)")
    for g in facts["gaps"]:
        out.append(f"  {g['ms']:>10.4f}  {g['at_ms']:>12.3f}  "
                   f"{g['phase']:<9} {_rows(g['rows'])}")
    out.append("programs (calls, mean ms a call, mean rows of the ticks "
               "their calls started under)")
    for p in facts["programs"]:
        out.append(f"  {p['program']:<24} {p['calls']:>5}  {p['ms']:>10.4f}  "
                   f"{_rows(p['rows'])}")
    out.append("host events on the engine's thread inside a phase "
               "(inclusive s)")
    for phase, rows in facts["host_under"].items():
        for name, s in rows:
            out.append(f"  {phase:<9} {s:>10.4f}  {name[:80]}")
    out.append("device time by named scope (own time, the whole trace)")
    for scope, s in facts["scope_s"].items():
        out.append(f"  {scope:<24} {s:>10.4f} s  "
                   f"{s / facts['device_busy_s']:.1%}")
    out.append("top ops")
    for scope, op, s in facts["top_ops"]:
        out.append(f"  {s:>9.4f} s  {scope:<22} {op}")
    if facts["unix_offset_us"] is not None:
        out.append(f"unix_us = trace_us + {facts['unix_offset_us']:.0f}")
    return "\n".join(out)


def main(argv: list) -> int:
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    facts = reduce(load(argv[0]))
    print(render(facts))
    print(json.dumps(facts))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
