"""The one general traffic generator: a traffic file of parameters and a seed
give a schedule of requests. Nothing here knows a cell by name.

A traffic file (benchmark/traffic/<name>.json) may `extend` another; a cell
looks for `traffic/<workload>.json` first and `traffic/<traffic>.json` after
it, so a cell's rate lives in a file of its own and a later PR adds a cell by
adding a file.

One draw. The set of lengths and the set of gaps are the distribution's
quantile grid, the same for every seed; `--seed` orders them, pairs prompts
with outputs and draws the words. Lengths and gaps are dealt in blocks of
BLOCK consecutive arrivals with about equal sums, so any stretch of the
window offers about the same work whatever the seed: what differs from seed
to seed is the order, never the load. (A plain shuffle of 127 gaps put 41 to
53 arrivals into a window's first 20 s, and the tokens a run delivered
followed that count: PERF.md section 6. In blocks of 4 it is 49 to 54.)
The gaps are exponential, but their sum over a few arrivals is steadier than
a Poisson process's.

Fields read (all optional but the lengths and the rate):
  rate_rps            offered requests per second; gaps are exponential,
                      their mean 1 / rate
  prompt_tokens,
  output_tokens       {"dist": "lognormal", median, sigma, min, max}
                      | {"dist": "fixed", "value": n}
                      | {"dist": "mixture", "parts": [{"weight": w, ...}, ...]}
  burst               {"size_min", "size_max", "within_s"}: arrivals come in
                      bursts of that many inside that span, same mean rate
  shared_prefix_tokens  every prompt starts with the same n tokens
  sessions            {"turns_min", "turns_max", "think_s"}: a request is the
                      first turn of a session; later turns resend the
                      conversation so far plus a new user turn
  sampling            fields copied into every request body (none by default:
                      the server's own defaults)
  model               name of the model to address (default: the cell's)
  warmup_seconds      how much of the same traffic runs before the window
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import random
from statistics import NormalDist


BLOCK = 4      # arrivals to a block of balanced lengths, or gaps


@dataclasses.dataclass
class Request:
    index: int
    due_s: float              # seconds after the schedule's start
    prompt_ids: list          # user-content token ids (the text is "t<i> ...")
    max_tokens: int
    session: int = -1         # session id, -1 = none
    turn: int = 0

    @property
    def content(self) -> str:
        return " ".join(f"t{i}" for i in self.prompt_ids)


def load_traffic(bench_dir: str, workload: str, traffic: str) -> dict:
    """The cell's traffic parameters: `traffic/<workload>.json` if there is
    one, else `traffic/<traffic>.json`, each merged over what it extends."""
    tdir = os.path.join(bench_dir, "traffic")

    def read(name: str, seen: tuple) -> dict:
        if name in seen:
            raise ValueError(f"traffic files extend each other in a loop: {seen}")
        path = os.path.join(tdir, name + ".json")
        with open(path) as f:
            spec = json.load(f)
        base = spec.pop("extends", None)
        if base:
            merged = read(base, seen + (name,))
            merged.update(spec)
            return merged
        return spec

    for name in (workload, traffic):
        if os.path.isfile(os.path.join(tdir, name + ".json")):
            spec = read(name, ())
            spec["file"] = f"traffic/{name}.json"
            return spec
    raise FileNotFoundError(
        f"no traffic file for {workload!r}: neither traffic/{workload}.json "
        f"nor traffic/{traffic}.json")


# ------------------------------------------------------------------ lengths

def _quantile(dist: dict, u: float) -> float:
    kind = dist.get("dist", "lognormal")
    if kind == "fixed":
        return float(dist["value"])
    if kind == "lognormal":
        z = NormalDist().inv_cdf(min(max(u, 1e-9), 1 - 1e-9))
        return float(dist["median"]) * math.exp(float(dist["sigma"]) * z)
    if kind == "mixture":
        parts = dist["parts"]
        total = sum(float(p["weight"]) for p in parts)
        acc = 0.0
        for p in parts:
            w = float(p["weight"]) / total
            if u <= acc + w or p is parts[-1]:
                return _quantile(p, (u - acc) / w if w else 0.5)
            acc += w
    raise ValueError(f"unknown length distribution {kind!r}")


def _clip(dist: dict, x: float) -> int:
    lo = int(dist.get("min", 1))
    hi = int(dist.get("max", 1 << 30))
    return max(lo, min(hi, int(round(x))))


def balanced_order(values: list, rng: random.Random) -> list:
    """`values` in an order the seed picks, dealt in blocks of about BLOCK
    whose sums are about equal: the largest value left goes to the block
    with the smallest sum so far. Blocks and the order inside each are
    shuffled, so a long request has its short ones around it, somewhere."""
    m = max(1, -(-len(values) // BLOCK))
    cap = -(-len(values) // m)
    blocks, sums = [[] for _ in range(m)], [0.0] * m
    for v in sorted(values, reverse=True):
        b = min((i for i in range(m) if len(blocks[i]) < cap),
                key=sums.__getitem__)
        blocks[b].append(v)
        sums[b] += v
    for blk in blocks:
        rng.shuffle(blk)
    rng.shuffle(blocks)
    return [v for blk in blocks for v in blk]


def lengths(dist: dict, n: int, rng: random.Random) -> list:
    """The quantile grid (i + 0.5) / n of `dist`, clipped, in balanced order:
    every seed offers the same lengths."""
    grid = [_clip(dist, _quantile(dist, (i + 0.5) / n)) for i in range(n)]
    return balanced_order(grid, rng)


def gaps(n: int, span: float, rng: random.Random) -> list:
    """n exponential gaps that fill `span`: the quantile grid in balanced
    order, so a long gap has its short ones around it and no seed puts its
    arrivals early or late."""
    out = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span / sum(out)
    return balanced_order([g * scale for g in out], rng)


# ----------------------------------------------------------------- schedule

def schedule(spec: dict, seed: int, seconds: float, vocab: int, context: int,
             template_tokens: int = 8) -> list:
    """Requests due in [0, seconds), sorted by due time. The same seed gives
    the same list; another seed gives the same lengths and gaps in another
    order, with other words."""
    rate = float(spec["rate_rps"])
    if rate <= 0 or seconds <= 0:
        return []
    rng = random.Random(int(seed) & 0xFFFFFFFFFFFF)
    burst = spec.get("burst")
    n = max(1, int(round(rate * seconds)))

    if burst:
        # bursts of size_min..size_max requests inside within_s, burst starts
        # spaced so the mean rate stays `rate`
        mean_size = (burst["size_min"] + burst["size_max"]) / 2.0
        n_bursts = max(1, int(round(n / mean_size)))
        starts, t = [], 0.0
        for g in gaps(n_bursts, seconds, rng):
            t += g
            starts.append(t - g / 2.0)
        due = []
        for s in starts:
            k = rng.randint(burst["size_min"], burst["size_max"])
            due.extend(s + rng.random() * burst["within_s"] for _ in range(k))
        due = sorted(d for d in due if d < seconds)
    else:
        due, t = [], 0.0
        for g in gaps(n, seconds, rng):
            # an arrival sits in the middle of its gap, so the first is not
            # always at 0 and the last never beyond the window
            due.append(t + g / 2.0)
            t += g
        due = [d for d in due if d < seconds]
    n = len(due)

    p_len = lengths(spec["prompt_tokens"], n, rng)
    o_len = lengths(spec["output_tokens"], n, rng)
    shared_n = int(spec.get("shared_prefix_tokens") or 0)
    shared = [rng.randrange(8, vocab) for _ in range(shared_n)]
    room = context - template_tokens - 2
    out = []
    for i, d in enumerate(due):
        o = min(o_len[i], room - 16)
        p = max(1, min(p_len[i], room - o))
        ids = (shared + [rng.randrange(8, vocab) for _ in range(p)])[:max(p, 1)]
        out.append(Request(index=i, due_s=d, prompt_ids=ids, max_tokens=o))

    sess = spec.get("sessions")
    if sess:
        # each request opens a session; its later turns are due think_s after
        # the one before and resend what was said plus a new user turn. The
        # answers are not known ahead, so a turn repeats the earlier prompts
        extra = []
        for r in out:
            r.session = r.index
            turns = rng.randint(sess["turns_min"], sess["turns_max"])
            ids, t = list(r.prompt_ids), r.due_s
            for k in range(1, turns):
                t += float(sess["think_s"])
                ids = ids + [rng.randrange(8, vocab)
                             for _ in range(max(8, len(r.prompt_ids) // 4))]
                if t >= seconds or len(ids) + r.max_tokens > room:
                    break
                extra.append(Request(index=-1, due_s=t, prompt_ids=list(ids),
                                     max_tokens=r.max_tokens,
                                     session=r.index, turn=k))
        out = sorted(out + extra, key=lambda r: r.due_s)
        for i, r in enumerate(out):
            r.index = i
    return out
