"""How many streams are open against a model's backend at once is decided by
the model's admission gate (`slots` = `parallel`, `limit` = `slots` +
max(2, slots // 4): a few requests ahead of the engine's slots) and by
nothing else: each permit has a pump thread of the gate's own, and the
loop's default executor is left to the short blocking calls.

No engine, no gRPC: a fake handle whose `predict_stream` keeps the iterator
contract of `backend/client.py` (an iterator of `pb.Reply` with `cancel()`),
driven through `API._admit` and `API._stream_rpc`. Every test runs under a
time limit of its own (`_run`), and every wait of a fake is bounded, so a tree
that cannot open the streams fails here in seconds and leaves no thread
behind.
"""
import asyncio
import concurrent.futures
import queue
import threading
import time
from collections import Counter

import grpc
import pytest

from localai_tpu.backend import pb
from localai_tpu.config import AppConfig, ModelConfig
from localai_tpu.server.http import API

LIMIT_S = 10.0      # a test's own time limit
WAIT_S = 6.0        # the longest a fake waits: under the limit, so the
                    # threads of a failing run end before the test does


class FakeCall:
    """One PredictStream call: `gate()` once before the first reply (what
    the test makes the open stream wait for), then `chunks` replies, the
    last one finished. After `cancel()` the next read raises, as gRPC's
    does."""

    def __init__(self, gate, chunks: int):
        self._gate, self._left, self._first = gate, chunks, True
        self.cancelled = threading.Event()
        self.thread = ""

    def __iter__(self):
        return self

    def __next__(self):
        self.thread = threading.current_thread().name
        if self._first:
            self._first = False
            self._gate(self)
        if self.cancelled.is_set():
            raise grpc.RpcError("cancelled")
        if not self._left:
            raise StopIteration
        self._left -= 1
        return pb.Reply(message=b"t ", token_ids=[7], tokens=1,
                        finish_reason="" if self._left else "length")

    def cancel(self):
        self.cancelled.set()


class FakeHandle:
    def __init__(self, gate, chunks: int):
        self.client = self
        self.calls: list[FakeCall] = []
        self._gate, self._chunks = gate, chunks

    def predict_stream(self, **opts):
        self.calls.append(FakeCall(self._gate, self._chunks))
        return self.calls[-1]

    def mark_busy(self):
        pass

    def mark_idle(self):
        pass


class FakeManager:
    def __init__(self, handle):
        self.handle = handle
        self.events = Counter()

    def load(self, cfg):
        return self.handle

    def stop_all(self):
        pass


def _api(parallel: int, gate=lambda call: None, chunks: int = 2,
         depth: int = 64):
    handle = FakeHandle(gate, chunks)
    api = API(AppConfig(queue_depth=depth), None, FakeManager(handle))
    return api, ModelConfig(name="m", backend="llm", parallel=parallel), handle


async def _stream(api, cfg) -> int:
    n = 0
    async with api._admit(cfg):
        async for reply in api._stream_rpc(cfg, {}):
            n += len(reply.token_ids)
    return n


def _run(main, default_threads: int = 2):
    """Run `main()` under the test's time limit, on a loop whose default
    executor has `default_threads` threads: what a one-core machine gives
    the short blocking calls, and what the streams used to share."""
    async def limited():
        pool = concurrent.futures.ThreadPoolExecutor(
            default_threads, thread_name_prefix="default")
        asyncio.get_running_loop().set_default_executor(pool)
        try:
            return await asyncio.wait_for(main(), LIMIT_S)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)

    return asyncio.run(limited())


def _pump_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.name.startswith("pump-")]


async def _close(api):
    api._draining = True        # nothing to drain: no backend, no request
    await api._on_shutdown(api.app)


@pytest.mark.parametrize("parallel", [4, 40])
def test_parallel_streams_are_open_at_once(parallel):
    """Every stream waits, on its pump thread, until `parallel` of them are
    open: with streams on a pool smaller than `parallel` this never ends."""
    barrier = threading.Barrier(parallel, timeout=WAIT_S)
    api, cfg, handle = _api(parallel, gate=lambda call: barrier.wait())

    async def main():
        try:
            return await asyncio.gather(
                *(_stream(api, cfg) for _ in range(parallel)))
        finally:
            await _close(api)

    assert _run(main) == [2] * parallel
    assert not barrier.broken
    threads = {c.thread for c in handle.calls}
    assert len(threads) == parallel
    assert all(t.startswith("pump-m") for t in threads)


def test_a_unary_call_returns_while_parallel_streams_are_open():
    """The default executor is left to the short blocking calls: with every
    permit's stream open, `asyncio.to_thread` answers at once."""
    parallel = 8
    release = threading.Event()
    opened = threading.Semaphore(0)

    def hold(call):
        opened.release()
        release.wait(WAIT_S)

    api, cfg, _ = _api(parallel, gate=hold)

    async def main():
        streams = [asyncio.create_task(_stream(api, cfg))
                   for _ in range(parallel)]
        try:
            for _ in range(parallel):
                assert await asyncio.to_thread(opened.acquire, True, WAIT_S)
            assert api._gate(cfg).streams_open == parallel
            t0 = time.monotonic()
            assert await asyncio.wait_for(
                asyncio.to_thread(lambda: 7), 2.0) == 7
            return time.monotonic() - t0
        finally:
            release.set()
            assert await asyncio.gather(*streams) == [2] * parallel
            await _close(api)

    assert _run(main) < 1.0


def test_a_stream_the_client_closes_frees_its_thread_and_cancels_its_call():
    """A stream closed after its first chunk (what a client's disconnect
    does) must cancel its RPC and give its pump thread back: the next
    stream runs on that thread, not on one more."""
    def until_cancelled(call):
        if len(handle.calls) == 1:
            return
        # the second stream got the one thread: the first let go of it
        assert handle.calls[0].cancelled.is_set()

    api, cfg, handle = _api(1, gate=until_cancelled, chunks=1000)

    async def main():
        try:
            async with api._admit(cfg):
                stream = api._stream_rpc(cfg, {})
                reply = await stream.__anext__()
                assert list(reply.token_ids) == [7]
                assert api._gate(cfg).streams_open == 1
                await stream.aclose()
            assert handle.calls[0].cancelled.is_set()
            assert api._gate(cfg).streams_open == 0
            handle._chunks = 3
            assert await _stream(api, cfg) == 3
            assert handle.calls[1].thread == handle.calls[0].thread
        finally:
            await _close(api)

    _run(main)


def test_shutdown_leaves_no_pump_thread():
    # another file's API may have left its pumps in this worker's process
    # (`--dist loadfile` runs several files in one): they are not this one's
    others = set(_pump_threads())
    api, cfg, _ = _api(6)

    def mine():
        return [t for t in _pump_threads() if t not in others]

    async def main():
        await asyncio.gather(*(_stream(api, cfg) for _ in range(6)))
        assert mine()
        await _close(api)

    _run(main)
    for t in mine():
        t.join(WAIT_S)
    assert not [t.name for t in mine() if t.is_alive()]


def test_stream_start_is_observed_once_a_stream_and_streams_open_returns():
    """`hist_stream_start` (permit -> pump thread running) has one
    observation a stream, 0 included, a retried stream's too; `streams_open`
    is the gate's count of open streams and ends at 0. Both ride the model's
    metrics under the flat keys the backend's use."""
    api, cfg, handle = _api(4)
    real = handle.predict_stream
    failed = []

    def flaky(**opts):
        call = real(**opts)
        if not failed:          # the first RPC dies before any chunk
            failed.append(call)
            call._gate = lambda c: c.cancel()
        return call

    handle.predict_stream = flaky
    api.manager.classify_failure = lambda h, e: (True, e)

    async def main():
        try:
            assert api._gate_metrics("m") == {}     # no gate yet
            assert await asyncio.gather(
                *(_stream(api, cfg) for _ in range(9))) == [2] * 9
            return api._gate_metrics("m")
        finally:
            await _close(api)

    m = _run(main)
    assert len(handle.calls) == 10 and api.manager.events[("m", "stream_retry")] == 1
    assert m["hist_stream_start__all__count"] == 9
    assert 0 <= m["hist_stream_start__all__sum"] < 9 * LIMIT_S
    assert m["hist_gate_wait__all__count"] == 9
    assert m["streams_open"] == 0


# ------------------------------------------------- a permit's life (ISSUE 37)

TAIL = ("hist_reply_to_release__all__", "hist_permit_hold__all__")


def _calls_are(handle, cls):
    """Make the handle's next streams calls of `cls` (a FakeCall)."""
    def predict_stream(**opts):
        handle.calls.append(cls(handle._gate, handle._chunks))
        return handle.calls[-1]

    handle.predict_stream = predict_stream


def test_permit_tail_and_hold_are_observed_once_a_finished_stream():
    """`hist_reply_to_release` (the pump thread reads the stream's end ->
    the permit is released) and `hist_permit_hold` (granted -> released):
    one observation each per stream that ran to the backend's finished
    reply, under the flat keys the backend's metrics use, there at 0 from
    the gate's first use."""
    api, cfg, _ = _api(4, chunks=3)

    async def main():
        try:
            api._gate(cfg)
            zero = api._gate_metrics("m")
            assert await asyncio.gather(
                *(_stream(api, cfg) for _ in range(6))) == [3] * 6
            return zero, api._gate_metrics("m")
        finally:
            await _close(api)

    zero, m = _run(main)
    for base in TAIL:
        assert zero[base + "count"] == 0 and zero[base + "sum"] == 0.0
        assert m[base + "count"] == 6
    assert m["hist_gate_wait__all__count"] == 6
    # the tail lies inside the hold, for every stream
    assert 0 <= m[TAIL[0] + "sum"] <= m[TAIL[1] + "sum"] < 6 * LIMIT_S


@pytest.mark.parametrize("how", ["client closes", "backend says cancelled",
                                 "stream dies", "nothing streamed"])
def test_a_stream_that_did_not_finish_observes_no_tail_and_no_hold(how):
    """A permit handed to a client that has gone, a stream the backend
    ended as cancelled, one that died, a request that streamed nothing:
    none is a measure of a request's life, and the gate's queue at a
    window's end is full of them."""
    api, cfg, handle = _api(2, chunks=4)

    class Tagged(FakeCall):
        def __next__(self):
            reply = super().__next__()
            if how == "backend says cancelled" and reply.finish_reason:
                reply.finish_reason = "cancelled"
            if how == "stream dies" and reply.finish_reason:
                raise grpc.RpcError("backend died")
            return reply

    _calls_are(handle, Tagged)
    api.manager.classify_failure = lambda h, e: (False, e)

    async def main():
        try:
            async with api._admit(cfg):
                if how == "nothing streamed":
                    return api._gate_metrics("m")
                stream = api._stream_rpc(cfg, {})
                try:
                    reply = await stream.__anext__()
                    assert list(reply.token_ids) == [7]
                    if how != "client closes":
                        async for reply in stream:
                            pass
                except grpc.RpcError:
                    assert how == "stream dies"
                finally:
                    await stream.aclose()
            return api._gate_metrics("m")
        finally:
            await _close(api)

    m = _run(main)
    assert m["hist_gate_wait__all__count"] == 1
    for base in TAIL:
        assert m[base + "count"] == 0 and m[base + "sum"] == 0.0, (how, base)


def test_permit_hold_is_at_least_the_sum_of_its_stages_on_a_stubbed_backend():
    """One request on a stub that times its own part (the first read of the
    stream -> its finished reply handed over) as the backend's spans do:
    `permit_hold` >= `stream_start` + the backend's part +
    `reply_to_release`. What is left is the two crossings between the
    processes, which no single clock times."""
    spans = []

    class Timed(FakeCall):
        def __next__(self):
            if self._first:
                self.t0 = time.monotonic()
                time.sleep(0.03)        # queue, prefill, decode
            reply = super().__next__()
            if reply.finish_reason:
                spans.append(time.monotonic() - self.t0)
            return reply

    api, cfg, handle = _api(1, chunks=3)
    _calls_are(handle, Timed)

    async def main():
        try:
            assert await _stream(api, cfg) == 3
            return api._gate_metrics("m")
        finally:
            await _close(api)

    m = _run(main)
    assert len(spans) == 1 and spans[0] >= 0.03
    assert m["hist_permit_hold__all__count"] == 1
    stages = (m["hist_stream_start__all__sum"] + spans[0]
              + m["hist_reply_to_release__all__sum"])
    assert m["hist_permit_hold__all__sum"] >= stages
    assert m["hist_permit_hold__all__sum"] - stages < 1.0


# ------------------------------------- ahead of the slots (ISSUE 39)

def _held_streams(parallel: int, n: int, depth: int = 64):
    """`n` streams started one after another against a gate of `parallel`
    slots, each held open on its pump thread until `release(k)` lets `k` of
    them run to their end."""
    go, opened = queue.SimpleQueue(), queue.SimpleQueue()

    def hold(call):
        opened.put(call)
        try:
            go.get(timeout=WAIT_S)
        except queue.Empty:
            pass

    def release(k: int):
        for _ in range(k):
            go.put(None)

    api, cfg, handle = _api(parallel, gate=hold, depth=depth)

    async def start():
        tasks = []
        for _ in range(n):
            tasks.append(asyncio.create_task(_stream(api, cfg)))
            await asyncio.sleep(0)          # in arrival order at the gate
        return tasks

    async def opened_are(k: int):
        for _ in range(k):
            assert await asyncio.to_thread(opened.get, True, WAIT_S)
        await asyncio.sleep(0.05)           # one more would have shown
        assert opened.empty()

    return api, cfg, handle, release, start, opened_are


@pytest.mark.parametrize("parallel, limit", [(1, 3), (4, 6), (8, 10),
                                             (32, 40), (48, 60)])
def test_the_gate_grants_slots_plus_ahead(parallel, limit):
    """`slots` is the model's `parallel`; `limit` = `slots` +
    max(2, slots // 4), derived, and the semaphore and the pump pool go by
    `limit`."""
    api, cfg, _ = _api(parallel)

    async def main():
        try:
            gate = api._gate(cfg)
            return gate.slots, gate.limit, gate.pumps._max_workers
        finally:
            await _close(api)

    assert _run(main) == (parallel, limit, limit)


def test_six_permits_are_out_for_four_slots_and_the_seventh_waits():
    """With `slots` 4 the gate has 6 permits out, each with its stream open
    on a pump thread of its own (the pool runs `limit` streams at once), and
    the 7th request waits at the gate until one is handed back."""
    api, cfg, handle, release, start, opened_are = _held_streams(4, 7)

    async def main():
        tasks = await start()
        try:
            await opened_are(6)
            gate = api._gate(cfg)
            seen = (gate.out, gate.streams_open, gate.waiting,
                    gate.sem.locked(), len(handle.calls),
                    len({c.thread for c in handle.calls}))
            release(1)
            await opened_are(1)             # the 7th, on the permit freed
            assert (gate.out, gate.waiting) == (6, 0)
            release(7)
            assert await asyncio.gather(*tasks) == [2] * 7
            return seen, (gate.out, gate.waiting), api._gate_metrics("m")
        finally:
            release(7)
            await _close(api)

    seen, after, m = _run(main)
    assert seen == (6, 6, 1, True, 6, 6)
    assert after == (0, 0)
    assert m["gate_grants"] == 7
    assert m["hist_gate_wait__all__count"] == 7


def test_grants_ahead_counts_the_grants_made_at_or_above_slots():
    """Of 9 requests that arrive at once on 4 slots, the first 4 are
    granted with fewer than `slots` permits out and the next 2 with 4 and
    5 out; each of the three that waited is granted as one permit comes
    back, with 5 out. Requests one after another never see `slots` permits
    out and add none."""
    api, cfg, _, release, start, opened_are = _held_streams(4, 9)

    async def main():
        tasks = await start()
        try:
            await opened_are(6)
            first = api._gate_metrics("m")
            for _ in range(3):
                release(1)
                await opened_are(1)
            burst = api._gate_metrics("m")
            release(9)
            assert await asyncio.gather(*tasks) == [2] * 9
            release(5)
            for _ in range(5):
                assert await _stream(api, cfg) == 2
            return first, burst, api._gate_metrics("m")
        finally:
            release(9)
            await _close(api)

    first, burst, after = _run(main)
    assert (first["gate_grants"], first["gate_grants_ahead"]) == (6, 2)
    assert (burst["gate_grants"], burst["gate_grants_ahead"]) == (9, 5)
    assert (after["gate_grants"], after["gate_grants_ahead"]) == (14, 5)


def test_sequential_requests_are_never_ahead():
    api, cfg, _ = _api(4)

    async def main():
        try:
            for _ in range(8):
                assert await _stream(api, cfg) == 2
            return api._gate_metrics("m")
        finally:
            await _close(api)

    m = _run(main)
    assert (m["gate_grants"], m["gate_grants_ahead"]) == (8, 0)


def test_the_gate_sheds_at_limit_plus_depth_and_names_both_numbers():
    """The shed rule keeps its form: full semaphore and `depth` waiters.
    With 4 slots and a depth of 3 that is the 10th request (6 + 3 stand),
    not the 8th, and the 429 says how many are in flight for how many
    slots."""
    from localai_tpu.core import resilience

    api, cfg, _, release, start, opened_are = _held_streams(4, 9, depth=3)

    async def main():
        tasks = await start()
        try:
            await opened_are(6)
            gate = api._gate(cfg)
            assert (gate.out, gate.waiting) == (6, 3)
            with pytest.raises(resilience.RequestShed) as shed:
                async with api._admit(cfg):
                    pass
            release(9)
            assert await asyncio.gather(*tasks) == [2] * 9
            return str(shed.value), api._gate_metrics("m")
        finally:
            release(9)
            await _close(api)

    text, m = _run(main)
    assert "6 in flight for 4 slots, 3 queued" in text
    assert m["gate_grants"] == 9            # the shed request was no grant


def test_the_gates_counters_ride_backend_monitor():
    """`gate.metrics()` carries the two counters and the two gauges into the
    model's metrics in `/backend/monitor`, beside `streams_open` and over
    the backend's own keys."""
    import json
    import types

    api, cfg, handle = _api(4)
    handle.busy = False
    handle.status = lambda: types.SimpleNamespace(
        state=1, memory=types.SimpleNamespace(total=0), device_json="{}")
    handle.metrics = lambda: {"tokens_generated": 3.0}
    api.manager.loaded = lambda: ["m"]
    api.manager.get = lambda name: handle

    async def main():
        try:
            assert await asyncio.gather(
                *(_stream(api, cfg) for _ in range(6))) == [2] * 6
            reply = await api._backend_monitor(None)
            return json.loads(reply.body)["m"]["metrics"]
        finally:
            await _close(api)

    m = _run(main, default_threads=4)
    assert m["tokens_generated"] == 3.0
    assert m["gate_slots"] == 4 and m["gate_limit"] == 6
    assert m["gate_grants"] == 6 and 0 <= m["gate_grants_ahead"] <= 2
    assert m["streams_open"] == 0
