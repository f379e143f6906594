"""Child process of a traced run: read the profiler's .xplane.pb with
jax.profiler.ProfileData, reduce it with tracefacts.reduce and write the
facts as JSON. It runs in a process of its own so that a parser crash or its
memory cannot take the run down, and so that the harness itself never
imports JAX. It is started with JAX_PLATFORMS=cpu and touches no device.

    python benchmark/harness/xplane.py <trace dir> <request.json> <out.json>

request.json: {"window": [from_ns, to_ns] | null, "in_flight": [[a, b], ...],
"decoding": [...], "wall_started_ns": n, "wall_stopped_ns": n}; the spans are
unix nanoseconds and are shifted onto the trace's clock here.
"""
from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import tracefacts  # noqa: E402


def find_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def to_raw(path: str, keep_lines: tuple | None = None) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = bool(tracefacts.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_dev and keep_lines and line.name not in keep_lines:
                lines.append({"name": line.name, "events": [], "skipped": True})
                continue
            if not is_dev:
                # host threads: only how many events, the reduction reads none
                lines.append({"name": line.name, "events": [],
                              "count": sum(1 for _ in line.events)})
                continue
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def main(argv: list) -> int:
    trace_dir, req_path, out_path = argv
    with open(req_path) as f:
        req = json.load(f)
    path = find_xplane(trace_dir)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {trace_dir}")
    raw = to_raw(path, keep_lines=(tracefacts.MODULE_LINE, tracefacts.OP_LINE))
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    classes = tracefacts.load_program_classes(bench_dir)
    summary = {"xplane_bytes": os.path.getsize(path), "planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": len(ln["events"]) or ln.get("count", 0)}
            for ln in p["lines"]]} for p in raw["planes"]]}
    dev_events = [e for p in tracefacts.device_planes(raw)
                  for ln in p["lines"] for e in ln["events"]]
    shift = 0
    if dev_events and req.get("wall_started_ns"):
        first = min(e[1] for e in dev_events)
        ws, we = req["wall_started_ns"], req["wall_stopped_ns"]
        if not (ws - 5e9 <= first <= we + 5e9):
            # the trace's clock is not the unix clock: pin its first device
            # event to the moment the profiler reported it had started
            shift = first - ws
        summary["clock"] = {"first_device_event_ns": first,
                            "wall_started_ns": ws, "shift_ns": shift}

    def moved(spans):
        return None if spans is None else [[a + shift, b + shift]
                                           for a, b in spans]

    window = req.get("window")
    facts = tracefacts.reduce(
        raw, classes, markers=tracefacts.load_step_markers(bench_dir),
        config=req.get("config"),
        window=None if not window else (window[0] + shift, window[1] + shift),
        in_flight=moved(req.get("in_flight")),
        decoding=moved(req.get("decoding")))
    if req.get("dump_raw"):
        # a head of every device line, small enough to bring home and to keep
        # as a recorded trace: the first events of each line
        head = {"planes": [{"name": p["name"], "lines": [
            {"name": ln["name"], "events": ln["events"][:int(req.get(
                "dump_events", 4000))]} for ln in p["lines"]]}
            for p in tracefacts.device_planes(raw)]}
        with open(req["dump_raw"], "w") as f:
            json.dump(head, f)
    counts: dict = {}
    for p in tracefacts.device_planes(raw):
        for ln in p["lines"]:
            if ln["name"] == tracefacts.OP_LINE:
                for e in ln["events"]:
                    k = tracefacts.op_short(e[0])
                    counts[k] = counts.get(k, 0) + 1
    summary["op_event_counts"] = sorted(counts.items(), key=lambda kv: -kv[1])[:14]
    with open(out_path, "w") as f:
        json.dump({"facts": facts, "summary": summary}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
