"""End-of-window accounting on a fake server that stalls: cut, attempted,
failed, lower-bound TTFT, tokens inside the window."""
import asyncio
import json
import time

import pytest
from aiohttp import web

from benchmark.harness import client, traffic


async def _chat(request):
    body = await request.json()
    words = body["messages"][0]["content"].split()
    kind, n = words[0], body["max_tokens"]
    assert body["stream"] is True and body["ignore_eos"] is True
    assert "temperature" not in body        # the server's own defaults
    if kind == "t500":
        return web.json_response({"error": "boom"}, status=500)
    resp = web.StreamResponse(headers={"Content-Type": "text/event-stream"})
    await resp.prepare(request)

    async def send(obj):
        await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

    if kind == "t404":                       # never a first token
        await asyncio.sleep(3600)
    emit = n - 2 if kind == "t300" else n    # t300: a short stream
    if kind == "t700":                       # one token too many
        emit = n + 1
    for i in range(emit):
        if kind == "t800":                   # chunks end inside a word
            await send({"choices": [{"delta": {"content": " t"}}]})
            await send({"choices": [{"delta": {"content": f"{i + 8}"}}]})
            continue
        await send({"choices": [{"delta": {"content": f" t{i + 8}"}}]})
        if kind == "t200" and i == 2:        # stalls after three tokens
            await asyncio.sleep(3600)
        await asyncio.sleep(0.01)
    if kind == "t600":
        await send({"error": {"message": "engine fell over"}})
    else:
        await send({"choices": [{"delta": {}, "finish_reason": "length"}],
                    "usage": {"completion_tokens": emit}})
    await resp.write(b"data: [DONE]\n\n")
    return resp


def _req(i, due, kind, n=5):
    return traffic.Request(i, due, [kind, 9, 9], n)


def _run(window_reqs, warm_reqs=(), seconds=1.0):
    async def go():
        app = web.Application()
        app.router.add_post("/v1/chat/completions", _chat)
        runner = web.AppRunner(app, handler_cancellation=True,
                               shutdown_timeout=0.2)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        fired = []

        async def hook():
            fired.append(time.monotonic())

        t0 = time.monotonic() + 0.05
        start, end = t0 + 0.3, t0 + 0.3 + seconds
        try:
            recs = await client.run_open_loop(
                port, "m", [("warmup", list(warm_reqs), 0.0),
                            ("window", list(window_reqs), 0.3)],
                {}, t0, end, hooks=[(start + 0.1, hook)])
        finally:
            await runner.cleanup()
        return recs, start, end, fired

    return asyncio.run(go())


# Request.content renders ids as "t<i>": the first id picks the behaviour
def test_accounting_with_a_stalled_backlog():
    window = [_req(0, 0.0, 100), _req(1, 0.1, 200), _req(2, 0.2, 404),
              _req(3, 0.3, 100, n=8), _req(4, 5.0, 100)]   # the last is due after the end
    t_begin = time.monotonic()
    recs, start, end, fired = _run(window, warm_reqs=[_req(0, 0.0, 100, n=40)])
    assert time.monotonic() - t_begin < 3.0      # nothing waited for the stall
    acct = client.account(recs, start, end)
    assert acct["attempted"] == 4                # due inside the window
    assert acct["failed"] == 0 and acct["failures"] == []
    assert acct["cut"] == 2 and acct["finished"] == 2
    by = {r.index: r for r in recs if r.phase == "window"}
    stalled, never = by[2], by[3]                # index 0 is the warm-up's
    assert stalled.cut and stalled.tokens == 3 and stalled.first is not None
    assert never.cut and never.first is None
    # the request without a first token counts with its wait so far
    assert acct["ttft_lower_bound"] == 1
    assert len(acct["ttft_ms"]) == 4
    assert max(acct["ttft_ms"]) == pytest.approx(
        (end - never.due) * 1e3, abs=1.0)
    assert max(acct["ttft_ms"]) > 700
    # the stalled one gives its real first-token time and a TPOT
    assert min(acct["ttft_ms"]) < 200
    assert len(acct["tpot_ms"]) == 3
    # tokens inside the window: 5 + 3 + 8, plus what the warm-up request
    # streamed after the window opened
    warm = next(r for r in recs if r.phase == "warmup")
    warm_inside = sum(n for t, n in warm.token_times if start <= t < end)
    assert 0 < warm_inside < 40
    assert acct["tokens_in_window"] == 16 + warm_inside
    assert len(fired) == 1 and fired[0] >= start + 0.1
    assert acct["lateness_ms_max"] < 100


@pytest.mark.parametrize("kind,why", [(500, "HTTP 500"), (300, "tokens=3"),
                                      (600, "error event")])
def test_what_counts_as_failed(kind, why):
    recs, start, end, _ = _run([_req(0, 0.0, kind), _req(1, 0.05, 100)])
    acct = client.account(recs, start, end)
    assert acct["attempted"] == 2 and acct["failed"] == 1 and acct["cut"] == 0
    assert why in acct["failures"][0][1]
    assert len(acct["ttft_ms"]) == 1             # a failed request gives no latency


def test_percentile_and_spans():
    assert client.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert client.percentile(list(range(101)), 0.95) == pytest.approx(95)
    assert client.merge([[0, 2], [1, 3], [5, 6], [6, 6]]) == [[0, 3], [5, 6]]
    assert client.stat({"ttft_ms": [1, 2, 3], "tpot_ms": []}, "ttft_p50_ms") == 2
    assert client.stat({"ttft_ms": [1], "tpot_ms": []}, "tpot_p90_ms") is None


def test_one_token_too_many_is_reported_and_not_failed():
    recs, start, end, _ = _run([_req(0, 0.0, 700), _req(1, 0.05, 100)])
    acct = client.account(recs, start, end)
    assert acct["failed"] == 0 and acct["finished"] == 2
    assert acct["over_length"] == 1
    assert acct["over_length_seen"] == [(0, 1, 6)]      # the server's usage too
    assert client.extras(acct)["over_length"] == 1
    assert acct["tokens_in_window"] == 10        # the sixth token is not work


def test_a_word_cut_by_a_chunk_counts_once():
    recs, start, end, _ = _run([_req(0, 0.0, 800)])
    acct = client.account(recs, start, end)
    assert recs[0].tokens == 5 and acct["failed"] == 0
    assert acct["over_length"] == 0 and acct["tokens_in_window"] == 5
