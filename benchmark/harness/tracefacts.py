"""From a device trace to numbers: busy union, idle share, time per class of
XLA module, the operations that took most time, the longest idle gaps and
what the load generator knew about each. Pure Python over plain lists, so it
is checked on the small recorded trace in benchmark/recorded/ and never
needs JAX. benchmark/harness/xplane.py turns an .xplane.pb into the input.

Input (`raw`): {"planes": [{"name": "/device:TPU:0", "lines": [
    {"name": "XLA Modules", "events": [[name, start_ns, dur_ns], ...]},
    {"name": "XLA Ops", "events": [...]}]}]}
All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import fnmatch
import os
import re

from benchmark.harness import json_dir

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def load_program_classes(bench_dir: str) -> dict:
    """{class: [module name patterns]} from benchmark/programs/*.json — one
    file per class, so a PR that adds or renames a program adds a file."""
    out: dict = {}
    for d in _program_files(bench_dir):
        out.setdefault(d["class"], []).extend(d["module_patterns"])
    return out


def load_step_markers(bench_dir: str) -> dict:
    """{class: [(op name patterns, events per step)]}: how to count the steps
    a class of module executed from the trace alone. The engine credits its
    step counter when a fused loop of up to 64 steps is consumed, far too
    coarse for a slice of a few seconds; an op that runs a known number of
    times a step (the decode attention kernel: once a layer) is exact."""
    out: dict = {}
    for d in _program_files(bench_dir):
        m = d.get("step_marker")
        if m:
            out.setdefault(d["class"], []).append(
                (m["op_patterns"], m["events_per_step"]))
    return out


def _program_files(bench_dir: str) -> list:
    return [d for _, d in json_dir(os.path.join(bench_dir, "programs"))]


def op_short(name: str) -> str:
    """A TPU trace names an op by its whole HLO line, '%copy.175 = s8[32,...]
    {layout} copy(...)': keep 'copy.175 copy s8[32,...]'."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name.lstrip("%")[:96]
    shape = re.search(r"[a-z]+[0-9]*\[[0-9,]*\]", rest)
    kind = re.search(r"(?:^|[\s)}])([a-z][\w\-]*)\(", rest)
    return " ".join(x for x in (head.lstrip("%"), kind and kind.group(1),
                                shape and shape.group(0)) if x)[:96]


def module_base(name: str) -> str:
    """'jit__loop(1234567)' -> 'jit__loop' (the trace appends a run id)."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def classify(module: str, classes: dict) -> str:
    base = module_base(module)
    for cls, patterns in classes.items():
        if any(fnmatch.fnmatchcase(base, p) for p in patterns):
            return cls
    return "other"


def merge(spans: list) -> list:
    out: list = []
    for a, b in sorted(spans):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(spans: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in spans
            if min(b, hi) > max(a, lo)]


def total(spans: list) -> float:
    return float(sum(b - a for a, b in spans))


def intersect(xs: list, ys: list) -> list:
    """Intersection of two merged, sorted span lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(spans: list, lo: float, hi: float) -> list:
    out, at = [], lo
    for a, b in spans:
        if a > at:
            out.append([at, a])
        at = max(at, b)
    if hi > at:
        out.append([at, hi])
    return out


def _contains(spans: list, starts: list, t: float) -> bool:
    """Is t inside one of the merged `spans` (`starts` are their starts)?"""
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and spans[i][0] <= t < spans[i][1]


def device_planes(raw: dict) -> list:
    return [p for p in raw.get("planes", []) if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> list:
    for ln in plane.get("lines", []):
        if ln["name"] == name:
            return ln["events"]
    return []


def reduce(raw: dict, classes: dict, window: tuple | None = None,
           in_flight: list | None = None, decoding: list | None = None,
           top: int = 10, markers: dict | None = None,
           config: dict | None = None) -> dict:
    """The facts of one traced slice. `window` is (from_ns, to_ns) on the
    trace's clock (default: the span of the device events). `in_flight` and
    `decoding` are merged span lists on the same clock from the load
    generator. `markers` (load_step_markers) and `config` (the sizes an
    events-per-step may name) give `class_steps`, the steps a class of module
    ran as counted from its ops. Returns {} when no device plane has an
    event."""
    planes = device_planes(raw)
    per_plane = []
    for p in planes:
        ops = _line(p, OP_LINE) or _line(p, MODULE_LINE)
        if ops:
            per_plane.append((p, ops))
    if not per_plane:
        return {}
    lo = min(e[1] for _, ops in per_plane for e in ops)
    hi = max(e[1] + e[2] for _, ops in per_plane for e in ops)
    if window is not None and window[1] > window[0]:
        w_lo, w_hi = float(window[0]), float(window[1])
        if w_hi <= lo or w_lo >= hi:
            # the events do not lie in the window we were given: the clocks
            # do not agree, so take the events' own span
            w_lo, w_hi = lo, hi
    else:
        w_lo, w_hi = lo, hi
    window_ns = w_hi - w_lo

    busy_ns, idle_share_in_flight, class_ns, op_ns = [], [], {}, {}
    class_runs: dict = {}
    marker_events: dict = {}
    gaps_all = []
    in_flight_w = clip(in_flight, w_lo, w_hi) if in_flight is not None else None
    decoding_w = clip(decoding, w_lo, w_hi) if decoding is not None else []
    in_flight_at = [a for a, _ in in_flight_w or []]
    decoding_at = [a for a, _ in decoding_w]
    for p, ops in per_plane:
        busy = clip(merge([[e[1], e[1] + e[2]] for e in ops]), w_lo, w_hi)
        busy_ns.append(total(busy))
        if in_flight_w is not None and total(in_flight_w) > 0:
            idle_share_in_flight.append(
                1.0 - total(intersect(busy, in_flight_w)) / total(in_flight_w))
        mods = sorted(_line(p, MODULE_LINE), key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for m in mods:
            a, b = max(m[1], w_lo), min(m[1] + m[2], w_hi)
            if b > a:
                cls = classify(m[0], classes)
                class_ns[cls] = class_ns.get(cls, 0.0) + (b - a)
                class_runs[cls] = class_runs.get(cls, 0) + 1
        if _line(p, OP_LINE):
            # an op that holds others (a while loop and its body's fusions
            # are all events of this line) counts only its own time
            stack: list = []          # [end, key, self_ns]

            def close(item):
                op_ns[item[1]] = op_ns.get(item[1], 0.0) + max(item[2], 0.0)

            for e in sorted(ops, key=lambda e: (e[1], -e[2])):
                a, b = max(e[1], w_lo), min(e[1] + e[2], w_hi)
                if b <= a:
                    continue
                while stack and stack[-1][0] <= a:
                    close(stack.pop())
                if stack:
                    stack[-1][2] -= b - a
                i = bisect.bisect_right(starts, e[1]) - 1
                mod = (module_base(mods[i][0])
                       if i >= 0 and e[1] < mods[i][1] + mods[i][2] else "-")
                short = op_short(e[0])
                if e[1] >= w_lo:
                    cls = classify(mod, classes)
                    for k, (pats, _) in enumerate((markers or {}).get(cls, [])):
                        if any(fnmatch.fnmatchcase(short, p) for p in pats):
                            marker_events[(cls, k)] = marker_events.get(
                                (cls, k), 0) + 1
                stack.append([b, f"{mod}/{short}", b - a])
            while stack:
                close(stack.pop())
        for a, b in complement(busy, w_lo, w_hi):
            mid = (a + b) / 2.0
            if in_flight_w is None:
                label = "unlabelled"
            elif not _contains(in_flight_w, in_flight_at, mid):
                label = "no-request-in-flight"
            elif _contains(decoding_w, decoding_at, mid):
                label = "decoding"
            else:
                label = "requests-queued-none-decoding"
            gaps_all.append((b - a, label))
    n = len(per_plane)
    class_steps: dict = {}
    for (cls, k), count in marker_events.items():
        per = (markers or {})[cls][k][1]
        per = (config or {}).get(per) if isinstance(per, str) else per
        if per:
            class_steps[cls] = class_steps.get(cls, 0.0) + count / per / n
    # the same op name inside a while loop is one entry: sum, then rank
    device_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps_all.sort(key=lambda g: -g[0])
    idle_by_label: dict = {}
    for d, label in gaps_all:
        idle_by_label[label] = idle_by_label.get(label, 0.0) + d
    return {
        "chips": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "idle_share": 1.0 - (sum(busy_ns) / n) / window_ns,
        "idle_share_in_flight": (sum(idle_share_in_flight)
                                 / len(idle_share_in_flight)
                                 if idle_share_in_flight else None),
        "in_flight_s": (total(in_flight_w) / 1e9
                        if in_flight_w is not None else None),
        "class_s": {k: v / n / 1e9 for k, v in class_ns.items()},
        "class_runs": {k: v / n for k, v in class_runs.items()},
        "class_steps": class_steps,
        "device_ops": [[k, v / n / 1e9] for k, v in device_ops],
        "idle_gaps": [[label, d / 1e9] for d, label in gaps_all[:top]],
        "idle_s_by_label": {k: v / n / 1e9 for k, v in idle_by_label.items()},
    }
