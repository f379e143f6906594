"""Readers of per-layer metrics. A metric is a data file,
benchmark/layer_metrics/<name>.json, that names one of the readers below and
its arguments; a reader takes the run's context and returns a number, or None
when it finds nothing to read (the metric is then left out of the line).

The context (`ctx`) of a run:
  counters   {"window": (before, after)}: flat GetMetrics dicts from
             GET /backend/monitor at the window's ends
  records    the load generator's Records
  acct       client.account(...) of the window
  window     (start, end) on the monotonic clock
  system     the backend's device report from /system
  trace      tracefacts.reduce(...) of the slice, or None
  slice      {"mean_context": tokens, "span": (from, to)} from the load
             generator, on the monotonic clock, or None
  config, serving, peaks
"""
from __future__ import annotations

import fnmatch
import os

from benchmark.harness import client as _client
from benchmark.harness import json_dir
from benchmark.harness import roofline as _roofline


def load_layer_metrics(bench_dir: str) -> dict:
    out = {}
    for fn, d in json_dir(os.path.join(bench_dir, "layer_metrics")):
        if d["name"] + ".json" != fn:
            raise ValueError(f"layer_metrics/{fn} names {d['name']!r}")
        out[d["name"]] = d
    return out


def applies(metric: dict, workload: str) -> bool:
    return any(fnmatch.fnmatchcase(workload, p)
               for p in metric.get("cells", ["*"]))


def _delta(ctx: dict, pattern: str) -> float | None:
    """What the counters matching `pattern` gained over the window."""
    before, after = ctx["counters"]["window"]
    if before is None or after is None:      # a sample could not be had
        return None
    keys = [k for k in after if fnmatch.fnmatchcase(k, pattern)]
    if not keys:
        return None
    return float(sum(after[k] - before.get(k, 0.0) for k in keys))


def hist_mean(ctx: dict, hist: str) -> float | None:
    """Mean of an SLO histogram over the window, ms: delta sum / delta count
    over every decode path. The buckets are 2-2.5x apart and are not read."""
    s = _delta(ctx, f"hist_{hist}__*__sum")
    n = _delta(ctx, f"hist_{hist}__*__count")
    if s is None or not n:
        return None
    return s / n * 1e3


def counter_ratio(ctx: dict, num: str, den: str) -> float | None:
    a, b = _delta(ctx, num), _delta(ctx, den)
    if a is None or not b:
        return None
    return a / b


def client_stat(ctx: dict, stat: str) -> float | None:
    """A statistic of the load generator's own records: <what>_p<q>_ms."""
    if not _client.STAT_NAME.fullmatch(stat):
        raise ValueError(f"unknown client statistic {stat!r}")
    return _client.stat(ctx["acct"], stat)


def bridge(ctx: dict) -> float | None:
    """Mean client-side time from send to first token, minus the engine's own
    mean time to first token, over the requests whose first token came inside
    the window (the engine observes its histogram at the first token)."""
    start, end = ctx["window"]
    mine = [(r.first - r.sent) * 1e3 for r in ctx["records"]
            if r.first is not None and start <= r.first < end]
    engine = hist_mean(ctx, "ttft")
    if not mine or engine is None:
        return None
    return sum(mine) / len(mine) - engine


def system_load(ctx: dict) -> float | None:
    secs = (ctx["system"] or {}).get("load_seconds")
    if not secs:
        return None
    return float(sum(secs.values()))


def trace_module_time(ctx: dict, cls: str, per: str) -> float | None:
    """Device time of the XLA modules of one class in the traced slice, in
    ms, over the steps the class ran as counted from the trace itself
    (`trace-steps`; programs/<class>.json: step_marker). A count taken on
    another clock (the client's first tokens in the slice) was tried for
    prefill and went: a slice of a few seconds need not hold one."""
    tr = ctx.get("trace")
    if not tr or cls not in tr["class_s"]:
        return None
    if per != "trace-steps":
        raise ValueError(f"trace-module-time cannot count per {per!r}")
    n = tr.get("class_steps", {}).get(cls)
    if not n:
        return None
    return tr["class_s"][cls] / n * 1e3


def trace_class_share(ctx: dict, cls: str) -> float | None:
    """Share of the traced slice in which an XLA module of one class was
    running on the device, in %: the class's module time, clipped to the
    slice, over the slice's length (modules of one chip do not overlap, so
    it cannot pass 100). A class that did not run in the slice reads 0:
    that is a reading, so the metric is in every traced line."""
    tr = ctx.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return tr["class_s"].get(cls, 0.0) / tr["window_s"] * 100.0


def trace_idle(ctx: dict, over: str) -> float | None:
    tr = ctx.get("trace")
    if not tr:
        return None
    v = tr["idle_share_in_flight"] if over == "in-flight" else tr["idle_share"]
    return None if v is None else v * 100.0


def roofline_share(ctx: dict, cls: str = "decode") -> float | None:
    """Least time for one decode step at the window's mean batch and the
    slice's mean context (benchmark/harness/roofline.py) over the measured
    device time a step."""
    step_ms = trace_module_time(ctx, cls, "trace-steps")
    batch = counter_ratio(ctx, "tokens_generated", "decode_steps_dispatched")
    sl = ctx.get("slice")
    if not step_ms or not batch or not sl or not ctx.get("peaks"):
        return None
    cost = _roofline.decode_step_cost(ctx["config"], ctx["serving"], batch,
                                      sl["mean_context"])
    least = _roofline.least_step_seconds(cost, ctx["peaks"])
    ctx.setdefault("notes", {})["roofline"] = dict(
        least, batch=batch, context=sl["mean_context"], step_ms=step_ms)
    return least["seconds"] * 1e3 / step_ms * 100.0


READERS = {
    "hist-mean": hist_mean,
    "counter-ratio": counter_ratio,
    "client": client_stat,
    "client-minus-hist": bridge,
    "system-load": system_load,
    "trace-module-time": trace_module_time,
    "trace-class-share": trace_class_share,
    "trace-idle": trace_idle,
    "roofline": roofline_share,
}

# a reader whose number comes off the device: never printed by a rehearsal
DEVICE_READERS = ("trace-module-time", "trace-class-share", "trace-idle",
                  "roofline")


def read(metric: dict, ctx: dict) -> float | None:
    fn = READERS.get(metric["reader"])
    if fn is None:
        raise ValueError(f"layer metric {metric['name']!r} names the reader "
                         f"{metric['reader']!r}; have {sorted(READERS)}")
    return fn(ctx, **metric.get("args", {}))
