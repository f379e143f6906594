"""Where the rows go (ISSUE 37): every consumed decode dispatch's max_slots
rows, times the steps the device ran, put down to one of five states, from
inside the engine. Tiny engine on the CPU, stepped by hand: counts and
identities, never a speed.

The identity, on every decode path: the five `decode_row_steps__*` sum to
max_slots x `decode_steps_consumed`, and `decode_row_steps__live` is the
tokens decode dispatches emitted (all of `tokens_generated`, but a
speculative engine's first token, which an admission samples).
"""
import numpy as np
import pytest

from fixtures import tiny_checkpoint

STATES = ("live", "spent", "prefill", "free_queued", "free_starved")
SLOTS = 4
GREEDY = dict(temperature=0.0)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return tiny_checkpoint(tmp_path_factory)


def _engine(ckpt, draft=None, **ec_kw):
    from localai_tpu.engine import (
        Engine, EngineConfig, Tokenizer, load_config, load_params,
    )

    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    kw = dict(max_slots=SLOTS, max_context=128, prefill_buckets=(32, 64),
              prefill_chunk=64)
    kw.update(ec_kw)
    if draft:
        draft = (cfg, params)
    return Engine(cfg, params, tok, EngineConfig(**kw), draft=draft), tok


def _submit(eng, tok, i, max_tokens=8, words=1, **kw):
    from localai_tpu.engine import GenRequest

    text = " ".join([f"request number {i} says"] * words)
    return eng.submit(GenRequest(prompt_ids=tok.encode(text),
                                 max_tokens=max_tokens, ignore_eos=True,
                                 **kw))


def _rows(eng) -> dict:
    return {s: eng.metrics[f"decode_row_steps__{s}"] for s in STATES}


def _holds(eng, first_tokens_elsewhere: int = 0):
    """The two identities, as they stand now (any tick boundary)."""
    m, rows = eng.metrics, _rows(eng)
    assert all(v >= 0 for v in rows.values()), rows
    assert sum(rows.values()) == SLOTS * m["decode_steps_consumed"], rows
    assert rows["live"] == m["tokens_generated"] - first_tokens_elsewhere


def _drain(eng, first_tokens_elsewhere: int = 0):
    while eng.step():
        # at every tick boundary, not only once all is consumed
        m = eng.metrics
        assert sum(_rows(eng).values()) == SLOTS * m["decode_steps_consumed"]
    _holds(eng, first_tokens_elsewhere)


def test_counters_exist_at_zero_and_survive_warmup(ckpt):
    eng, _ = _engine(ckpt, decode_loop=8)
    assert _rows(eng) == dict.fromkeys(STATES, 0)
    eng.warmup()                       # consumed by nobody: credited nowhere
    assert _rows(eng) == dict.fromkeys(STATES, 0)


@pytest.mark.parametrize("path, ec_kw, want_variant", [
    ("dense single step", dict(decode_loop=0, decode_block=1), "decode"),
    ("fused block", dict(decode_loop=0, decode_block=4), "decode_block4"),
    ("fused loop", dict(decode_loop=8), "loop"),
])
def test_identity_on_the_dense_paths(ckpt, path, ec_kw, want_variant):
    """Three requests of different lengths on four slots: one slot is free
    all through, the short requests finish inside a block or loop (its later
    steps are spent) and one dispatch before their last consume (a whole
    pipelined dispatch is spent)."""
    eng, tok = _engine(ckpt, **ec_kw)
    for i, n in enumerate((5, 11, 20)):
        _submit(eng, tok, i, max_tokens=n)
    _drain(eng)
    m, rows = eng.metrics, _rows(eng)
    assert m["tokens_generated"] == 36 == rows["live"]
    assert any(k.startswith(f"sched_variant__{want_variant}")
               for k in eng._sched.flat()), eng._sched.flat()
    assert rows["prefill"] == rows["free_queued"] == 0
    # the empty fourth slot, every step, with nothing queued to fill it
    assert rows["free_starved"] >= m["decode_steps_consumed"]
    assert rows["spent"] > 0


@pytest.mark.parametrize("case, ec_kw, params, by_blocks", [
    # a width of 1 puts the tiny vocabulary above width x 128 lanes
    ("by blocks, single step",
     dict(sampling_topk_width=1, decode_loop=0, decode_block=1), GREEDY, True),
    ("by blocks, fused block",
     dict(sampling_topk_width=1, decode_loop=0, decode_block=4), GREEDY, True),
    ("by blocks, fused loop",
     dict(sampling_topk_width=1, decode_loop=8), GREEDY, True),
    ("the plain call at width 64", dict(decode_loop=8), GREEDY, False),
    ("the full sort", dict(sampling_topk_width=1, decode_loop=8),
     dict(temperature=0.8, top_k=0, seed=7), False),
])
def test_steps_whose_top_k_went_by_blocks_are_counted(ckpt, case, ec_kw,
                                                      params, by_blocks):
    """`decode_steps__topk_blocks` is `decode_steps_consumed` on a run whose
    every dispatch sampled on the sort-free path at a vocabulary above
    width x 128 (sampling.topk_by_blocks), and stays 0 on the plain call and
    on the full sort; greedy tokens are the plain call's either way."""
    from localai_tpu.ops.sampling import SamplingParams, topk_by_blocks

    def run(**kw):
        eng, tok = _engine(ckpt, **kw)
        qs = [_submit(eng, tok, i, max_tokens=n,
                      params=SamplingParams(**params))[1]
              for i, n in enumerate((5, 11))]
        _drain(eng)
        return eng, [[o.token_id for o in q.queue] for q in qs]

    eng, tokens = run(**ec_kw)
    m = eng.metrics
    width = eng.ec.sampling_topk_width
    assert topk_by_blocks(eng.cfg.vocab_size, width) == (width == 1)
    assert m["decode_steps_consumed"] > 0
    assert m["decode_steps__topk_blocks"] == (
        m["decode_steps_consumed"] if by_blocks else 0)
    if by_blocks:
        assert tokens == run(decode_loop=8)[1]


def test_loop_with_an_early_finish_spends_the_rows_steps_after_it(ckpt):
    """One 8-step loop over a row that wants 3 tokens and a row that wants
    8: the device runs 8 steps, the short row gives 3 and spends 5."""
    eng, tok = _engine(ckpt, decode_loop=8, pipeline=False)
    _submit(eng, tok, 0, max_tokens=3)
    _submit(eng, tok, 1, max_tokens=8)
    eng.step()                                  # both admitted
    before, steps = _rows(eng), eng.metrics["decode_steps_consumed"]
    eng.step()                                  # the one loop, consumed
    steps = eng.metrics["decode_steps_consumed"] - steps
    got = {s: v - before[s] for s, v in _rows(eng).items()}
    assert steps == 8
    assert got == {"live": 11, "spent": 5, "prefill": 0, "free_queued": 0,
                   "free_starved": 2 * 8}
    _drain(eng)


def test_pipelined_dispatch_after_the_finish_is_spent_whole(ckpt):
    """Dispatch N+1 is enqueued before N is consumed: a request whose last
    token is in N rides N+1 as an active row that gives nothing."""
    eng, tok = _engine(ckpt, decode_loop=0, decode_block=1)
    _submit(eng, tok, 0, max_tokens=3)
    _drain(eng)
    m, rows = eng.metrics, _rows(eng)
    assert rows["live"] == 3
    # one row, three tokens, and the step in flight when the third came
    assert m["decode_steps_consumed"] == 4 and rows["spent"] == 1
    assert rows["free_starved"] == 3 * 4


def test_cancelled_request_stops_being_live(ckpt):
    eng, tok = _engine(ckpt, decode_loop=0, decode_block=4)
    rid, _ = _submit(eng, tok, 0, max_tokens=60)
    _submit(eng, tok, 1, max_tokens=12)
    for _ in range(3):
        eng.step()
    eng.cancel(rid)
    _drain(eng)
    rows = _rows(eng)
    assert rows["live"] == eng.metrics["tokens_generated"] < 72
    assert rows["spent"] > 0          # the rest of the block it was cut in


def test_prefill_rows_while_a_long_prompt_is_chunked(ckpt):
    """A prompt of several chunks holds its slot, not prefilled, while the
    other row decodes: those steps are `prefill`, not free and not spent."""
    eng, tok = _engine(ckpt, decode_loop=8, prefill_chunk=16,
                       prefill_buckets=(16,), admit_per_tick=1)
    _submit(eng, tok, 0, max_tokens=30)
    eng.step()
    eng.step()
    _submit(eng, tok, 1, max_tokens=4, words=4)      # five chunks of 16
    _drain(eng)
    rows = _rows(eng)
    assert rows["prefill"] >= 2
    assert rows["live"] == 34


def test_free_rows_split_by_whether_a_request_was_queued(ckpt):
    """A free slot beside a running request: with the queue empty at
    dispatch its steps are starved; with a request waiting in the queue as
    the dispatch is enqueued (admission follows dispatch in a tick) they
    are queued."""
    eng, tok = _engine(ckpt, max_slots=2, decode_loop=8)

    def tick():
        before, steps = _rows(eng), eng.metrics["decode_steps_consumed"]
        eng.step()
        return ({s: v - before[s] for s, v in _rows(eng).items()},
                eng.metrics["decode_steps_consumed"] - steps)

    _submit(eng, tok, 0, max_tokens=40)
    eng.step()                       # admitted
    eng.step()                       # loop 1 in flight, nothing consumed yet
    assert eng._pending is not None
    got, steps = tick()              # loop 2 enqueued, loop 1 consumed
    assert steps == 8 and got["free_starved"] == 8 and not got["free_queued"]
    # a request arrives behind the running loop: the next dispatch is
    # enqueued with it still queued, and is cut to one step for it
    _submit(eng, tok, 1, max_tokens=6)
    got, steps = tick()              # dispatch 3 (1 step), admit; consume 2
    assert steps == 8 and got["free_starved"] == 8     # loop 2: as enqueued
    got, steps = tick()              # consume dispatch 3
    assert steps == 1
    assert got["free_queued"] == 1 and got["free_starved"] == 0
    while eng.step():
        pass
    # both slots full from there on: no free row of either kind
    n = 2 * eng.metrics["decode_steps_consumed"]
    assert sum(_rows(eng).values()) == n
    assert eng.metrics["decode_row_steps__free_queued"] == 1
    assert eng.metrics["decode_row_steps__live"] == 46


def test_a_request_queued_before_a_row_frees_takes_it_on_the_next_tick(ckpt):
    """What a gate that admits ahead of the slots gives the engine (ISSUE
    39): two requests stand in the queue while both rows decode. When a row
    finishes, the first of them has the row by the end of the next tick and
    the second still waits (FIFO); the second takes the next row that
    finishes. While a request stands queued a free row reads `free_queued`,
    never `free_starved`."""
    eng, tok = _engine(ckpt, max_slots=2, decode_loop=8)
    a, _ = _submit(eng, tok, 0, max_tokens=10)
    b, _ = _submit(eng, tok, 1, max_tokens=30)
    eng.step()                       # both admitted
    c, _ = _submit(eng, tok, 2, max_tokens=20)
    d, _ = _submit(eng, tok, 3, max_tokens=6)

    def held():
        return {s.request_id for s in eng._slots if s is not None}

    assert held() == {a, b} and eng._queue.qsize() == 2
    ticks, left_at, joined_at = 0, {}, {}
    while len(joined_at) < 2:
        assert eng.step()
        ticks += 1
        now = held()
        for rid in (a, b):
            if rid not in now:
                left_at.setdefault(rid, ticks)
        for rid in (c, d):
            if rid in now:
                joined_at.setdefault(rid, ticks)
    # in arrival order, each at most a tick after the row it took was freed
    assert left_at[a] <= joined_at[c] <= left_at[a] + 1
    assert left_at[b] <= joined_at[d] <= left_at[b] + 1
    assert joined_at[c] < joined_at[d]
    # every dispatch enqueued so far had a request queued behind it
    rows = _rows(eng)
    assert rows["free_starved"] == 0, rows
    assert rows["free_queued"] >= 1, rows
    while eng.step():
        pass
    assert eng.metrics["decode_row_steps__live"] == 66
    assert eng.metrics["requests_admitted"] == 4


def test_dispatch_record_carries_the_rows(ckpt):
    """The tick ledger's record of a dispatch holds the states it was
    enqueued with; they are not summed there (sched_pack__* count once a
    dispatch, whatever its steps)."""
    eng, tok = _engine(ckpt, decode_loop=8)
    _submit(eng, tok, 0, max_tokens=10)
    _drain(eng)
    packs = [p for t in eng._sched.snapshot()["recent_ticks"]
             for p in t["packs"]]
    assert packs
    for p in packs:
        assert (p["rows_active"] + p["rows_prefill"] + p["rows_free_queued"]
                + p["rows_free_starved"]) == SLOTS
        assert p["pad_rows"] == SLOTS - p["decode_rows"]
    assert not any("rows_free" in k or "rows_active" in k
                   for k in eng._sched.flat())


@pytest.mark.parametrize("path, ec_kw, want_variant", [
    ("dense single step", dict(decode_loop=0, decode_block=1), "decode"),
    ("fused block", dict(decode_loop=0, decode_block=4), "decode_block4"),
    ("fused loop", dict(decode_loop=8), "loop"),
])
def test_identity_on_a_paged_engine(ckpt, path, ec_kw, want_variant):
    """The same sum on the block pool (`kv_pages`): a long prompt's chunks
    ride ticks of their own beside the decode dispatches, so its row is in
    prefill while the others decode, and the states still add up to
    max_slots x the steps consumed at every tick boundary."""
    eng, tok = _engine(ckpt, kv_pages=10, prompt_cache=False,
                       prefill_chunk=16, prefill_buckets=(16,), **ec_kw)
    assert eng._paged
    _submit(eng, tok, 0, max_tokens=14)
    eng.step()
    eng.step()
    _submit(eng, tok, 1, max_tokens=5, words=4)
    _submit(eng, tok, 2, max_tokens=9)
    _drain(eng)
    m, rows = eng.metrics, _rows(eng)
    assert m["tokens_generated"] == 28 == rows["live"]
    assert rows["prefill"] > 0, rows
    assert any(k.startswith(f"sched_variant__{want_variant}")
               for k in eng._sched.flat()), eng._sched.flat()


def test_identity_on_a_speculative_engine(ckpt):
    """A draft+verify dispatch counts gamma + 1 steps; an active row's
    rejected drafts are spent, and each request's first token comes from
    its admission, not from a decode dispatch."""
    eng, tok = _engine(ckpt, draft=True, gamma=2)
    for i, n in enumerate((7, 12)):
        _submit(eng, tok, i, max_tokens=n)
    _drain(eng, first_tokens_elsewhere=2)
    m, rows = eng.metrics, _rows(eng)
    assert m["tokens_generated"] == 19 and rows["live"] == 17
    assert m["decode_steps_consumed"] % 3 == 0


def test_a_scrape_is_taken_between_two_consumes(ckpt):
    """While a dispatch's tokens are emitted `tokens_generated` runs ahead
    of `live`: metrics_snapshot retakes a copy from inside that stretch, and
    serves what it has once its patience is over (a failed tick may leave
    the stretch open)."""
    import threading
    import time

    eng, tok = _engine(ckpt, decode_loop=8)
    _submit(eng, tok, 0, max_tokens=10)
    _drain(eng)
    assert eng._consume_seq % 2 == 0
    assert eng.metrics_snapshot(0.0) == eng.metrics
    eng._credit_consumed(1)                    # a consume opens the stretch
    eng.metrics["tokens_generated"] += 1       # ... and emits a token
    assert eng._consume_seq % 2 == 1
    t0 = time.monotonic()
    torn = eng.metrics_snapshot(0.02)          # nobody closes it: served
    assert time.monotonic() - t0 >= 0.02
    assert torn["decode_row_steps__live"] == torn["tokens_generated"] - 1
    threading.Timer(0.02, eng._credit_live).start()
    whole = eng.metrics_snapshot(5.0)          # closed meanwhile: retaken
    assert time.monotonic() - t0 < 4.0
    assert whole["decode_row_steps__live"] == whole["tokens_generated"]
    assert sum(whole[f"decode_row_steps__{s}"] for s in STATES) == \
        SLOTS * whole["decode_steps_consumed"]


def test_annotation_after_a_dispatch_carries_rows_and_queue(ckpt, monkeypatch):
    """While GET /debug/xprof traces, the first annotation opened after a
    decode dispatch's snapshot says what the engine held as the dispatch was
    enqueued, once; with no trace running nothing is computed or said."""
    from localai_tpu.telemetry import trace

    monkeypatch.setattr(trace, "_XPROF_ON", True)
    seen = []

    class Spy:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    eng, tok = _engine(ckpt, decode_loop=8)
    eng._phases._annotation = Spy
    _submit(eng, tok, 0, max_tokens=6)
    _submit(eng, tok, 1, max_tokens=6)
    eng.step()                       # admits both: no dispatch, no rows
    assert seen and not any("rows_active" in kw for _, kw in seen)
    eng.step()                       # the first dispatch
    held = [(name, kw) for name, kw in seen if "rows_active" in kw]
    assert len(held) == 1
    name, kw = held[0]
    assert name != "engine.dispatch" and kw["tick"] == 2
    assert (kw["rows_active"], kw["rows_prefill"], kw["rows_free"],
            kw["queued"]) == (2, 0, SLOTS - 2, 0)
    monkeypatch.setattr(trace, "_XPROF_ON", False)
    del seen[:]
    while eng.step():
        pass
    assert seen and all(set(kw) <= {"tick", "unix_us"} for _, kw in seen)


def test_trace_gaps_prints_rows_beside_gaps_and_programs():
    """tools/trace_gaps.py on the recorded fixture: the tick's rows beside
    each idle gap and each program that started under it."""
    import os

    from tools import trace_gaps

    here = os.path.dirname(os.path.abspath(__file__))
    facts = trace_gaps.reduce(trace_gaps.load(
        os.path.join(here, "data", "trace_gaps_small.json")))
    want = {"active": 30, "prefill": 1, "free": 1, "queued": 3}
    assert [g["rows"] for g in facts["gaps"]] == [want, want]
    progs = {p["program"]: p for p in facts["programs"]}
    assert set(progs) == {"jit__admit_many", "jit__loop"}
    assert progs["jit__loop"]["calls"] == 1
    assert progs["jit__loop"]["rows"] == {k: float(v)
                                          for k, v in want.items()}
    text = trace_gaps.render(facts)
    assert "rows 30 active / 1 prefill / 1 free, 3 queued" in text
    assert "jit__loop" in text
    assert np.isclose(progs["jit__loop"]["ms"], 6000 / 1e6)
