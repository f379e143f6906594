"""Preemption-safe serving suite (ISSUE 19) — ResumeToken round-trips, the
HostKVPool in-flight spill claims (pin semantics + threaded spill/evict race),
the HTTP bridge's resume-lane selection, and the full checkpoint/resume flows:
engine-level spill-drain parity across engine death (greedy, sampled via the
persisted RNG key, tiny-pool re-prefill fallback, second preempt mid-resume)
and chaos E2E through the HTTP->gRPC->engine stack (`preempt` SIGTERM notice,
`kill9_middecode` ungraceful death with/without the host KV tier, the
deterministic-replay fallback, and drain-during-preempt never hanging a
stream).

Unit pieces run in tier-1; the engine-driving flows carry `slow` + `preempt`
and the process-spawning chaos scenarios carry `slow` + `resilience`, matching
the CI lane split in test_resilience.py.
"""
import json
import threading
import time

import numpy as np
import pytest
import requests
import yaml

from fixtures import tiny_checkpoint
from test_resilience import _free_port, _read_until_content, _serve, _sse_events

# ------------------------------------------------------- ResumeToken units


def test_resume_token_roundtrip():
    from localai_tpu.engine.resume import ResumeToken

    tok = ResumeToken(prompt_ids=[1, 2, 3], emitted=[4, 5], key=[7, 9],
                      sent_chars=11, chain=["ab12", "cd34"],
                      deadline_left=2.5, request_id="req-1", model="m")
    assert tok.generated == 2                      # auto-filled from emitted
    assert tok.resume_prompt == [1, 2, 3, 4, 5]
    back = ResumeToken.from_json(tok.to_json())
    assert back == tok
    assert back.payload() == {"emitted": 2, "key": [7, 9], "sent_chars": 11}


def test_resume_token_minimal_dict_and_defaults():
    from localai_tpu.engine.resume import ResumeToken

    tok = ResumeToken.from_dict({"prompt_ids": [1], "emitted": []})
    assert tok.key is None and tok.chain == [] and tok.generated == 0
    assert tok.deadline_left == 0.0 and tok.model == ""
    assert tok.payload() == {"emitted": 0, "key": None, "sent_chars": 0}
    # a caller-trimmed emitted list keeps its explicit generated count
    t2 = ResumeToken(prompt_ids=[1], emitted=[2], generated=5)
    assert t2.generated == 5


def test_resume_token_rejects_unknown_version():
    from localai_tpu.engine.resume import ResumeToken

    with pytest.raises(ValueError, match="version"):
        ResumeToken.from_dict({"v": 2, "prompt_ids": [], "emitted": []})


# ------------------------------------------- pool spill claims (ISSUE 19)


def _blk(seed: int = 0):
    from localai_tpu.engine.kvhost import HostKVBlock

    r = np.random.default_rng(seed)
    return HostKVBlock(
        kq=r.integers(-128, 127, (1, 1, 4, 2)).astype(np.int8),
        ks=r.random((1, 1, 1, 4)).astype(np.float32),
        vq=r.integers(-128, 127, (1, 1, 4, 2)).astype(np.int8),
        vs=r.random((1, 1, 1, 4)).astype(np.float32),
    )


BLK_BYTES = _blk().nbytes        # 48


def _h(i: int) -> bytes:
    return i.to_bytes(16, "big")


def test_spill_claim_refuses_zero_budget_and_dups():
    from localai_tpu.engine.kvhost import HostKVPool

    dead = HostKVPool(budget_bytes=0)
    assert not dead.begin_spill(_h(1)) and dead.stats()["rejects"] == 1
    pool = HostKVPool(budget_bytes=1 << 20)
    pool.put(_h(1), _blk(1))
    assert not pool.begin_spill(_h(1))       # already resident
    assert pool.begin_spill(_h(2))
    assert not pool.begin_spill(_h(2))       # identical spill in flight
    assert pool.stats()["pending_spills"] == 1
    pool.end_spill(_h(2), _blk(2))
    assert pool.contains(_h(2)) and pool.stats()["pending_spills"] == 0


def test_spill_claim_pins_chain_against_eviction():
    """The ISSUE 19 spill/evict race: an open spill batch pins every
    resident block of its group, so LRU pressure victimizes newcomers
    instead of freeing a chain head whose in-flight tail would be useless
    without it."""
    from localai_tpu.engine.kvhost import HostKVPool

    pool = HostKVPool(budget_bytes=3 * BLK_BYTES)
    g = _h(100)
    pool.put(_h(1), _blk(1), group=g)
    pool.put(_h(2), _blk(2), group=g)
    assert pool.begin_spill(_h(3), group=g)        # pins h1+h2
    pool.put(_h(4), _blk(4), group=_h(200))        # budget now full
    pool.put(_h(5), _blk(5), group=_h(200))        # overflow: g is LRU...
    # ...but its blocks are pinned — the newcomer loses instead
    assert pool.contains(_h(1)) and pool.contains(_h(2))
    assert not pool.contains(_h(5))
    # landing the claimed tail closes the batch, unpins the chain, and
    # settles any eviction the pins deferred (tail-first inside the group)
    pool.end_spill(_h(3), _blk(3))
    st = pool.stats()
    assert st["pending_spills"] == 0
    assert st["bytes"] <= 3 * BLK_BYTES
    assert pool.contains(_h(1))                    # chain head survives


def test_spill_claim_abandon_and_unclaimed_end():
    from localai_tpu.engine.kvhost import HostKVPool

    pool = HostKVPool(budget_bytes=1 << 20)
    assert pool.begin_spill(_h(1))
    assert pool.end_spill(_h(1), None) == 0        # abandoned D2H copy
    assert not pool.contains(_h(1))
    assert pool.stats()["pending_spills"] == 0
    # ending a never-claimed hash degrades to plain put / no-op
    pool.end_spill(_h(2), _blk(2))
    assert pool.contains(_h(2))
    assert pool.end_spill(_h(3), None) == 0
    assert not pool.contains(_h(3))


def test_spill_evict_race_threaded_stress():
    """Spiller vs evictor hammering one pool: no deadlock, no exception,
    and the books balance afterwards — budget respected, no claim or pin
    leaked, used_bytes equal to the sum of resident blocks."""
    from localai_tpu.engine.kvhost import HostKVPool

    pool = HostKVPool(budget_bytes=8 * BLK_BYTES)
    errs = []

    def spiller():
        try:
            for i in range(200):
                h, g = _h(1000 + i), _h(5000 + i // 4)
                if pool.begin_spill(h, group=g):
                    pool.end_spill(h, _blk(i) if i % 5 else None)
        except Exception as e:          # pragma: no cover - failure path
            errs.append(e)

    def churner():
        try:
            for i in range(200):
                pool.put(_h(2000 + i), _blk(i), group=_h(6000 + i // 3))
                pool.get(_h(1000 + i))
        except Exception as e:          # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=spiller),
               threading.Thread(target=spiller),
               threading.Thread(target=churner)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "spill/evict stress deadlocked"
    assert not errs, errs
    st = pool.stats()
    assert st["pending_spills"] == 0
    assert st["bytes"] <= 8 * BLK_BYTES
    with pool._lock:
        assert sum(e.block.nbytes for e in pool._entries.values()) \
            == pool.used_bytes
        assert all(e.pins == 0 for e in pool._entries.values()), \
            "an open spill batch leaked pins"


# ------------------------------------------------- taxonomy / fault specs


def test_preempt_reason_codes_registered():
    from localai_tpu.telemetry.sched import REASON_CODES, reason_category

    assert reason_category("preempt_spill") == "kv"
    for code in ("resume_readmit", "resume_reprefill"):
        assert code in REASON_CODES
        assert reason_category(code) == "admission"


def test_fault_kinds_preempt_and_kill9(monkeypatch):
    from localai_tpu.testing import faults

    monkeypatch.setenv("LOCALAI_FAULT",
                       "preempt:2.5:1:gt,kill9_middecode:3::kt")
    monkeypatch.delenv("LOCALAI_FAULT_DIR", raising=False)
    monkeypatch.setattr(faults, "_local_counts", {})
    monkeypatch.setenv("LOCALAI_FAULT_MODEL", "gt")
    assert faults.fire("preempt") == 2.5           # arg = grace seconds
    assert faults.fire("preempt") is None          # limit 1 spent
    assert faults.fire("kill9_middecode") is None  # scoped to kt
    monkeypatch.setenv("LOCALAI_FAULT_MODEL", "kt")
    assert faults.fire("kill9_middecode") == 3.0   # unlimited
    assert faults.fire("kill9_middecode") == 3.0


# ------------------------------------------------- bridge resume lanes


def _api(**app_kw):
    from localai_tpu.config import AppConfig
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    app_cfg = AppConfig(**app_kw)
    return API(app_cfg, None, ModelManager(app_cfg))


def _mcfg(**kw):
    from localai_tpu.config import ModelConfig

    return ModelConfig(name="m", backend="llm", parallel=1, **kw)


def test_resume_opts_graceful_checkpoint_lane():
    from localai_tpu.engine.resume import ResumeToken

    api = _api()
    ckpt = ResumeToken(prompt_ids=[1, 2], emitted=[3, 4], key=[5, 6],
                       sent_chars=7, chain=["ab"], model="m").to_dict()
    opts = {"prompt_ids": [1, 2], "tokens": 16, "temperature": 0.0,
            "prompt": "x", "messages_json": "[]", "tools_json": "[]"}
    got = api._resume_opts(_mcfg(), opts, [1, 2], [3], 1, ckpt)
    assert got is not None
    ropts, mode, suppress, base = got
    assert mode == "resume" and suppress == [] and base == 2
    assert ropts["prompt_ids"] == [1, 2, 3, 4]     # engine-authoritative
    back = ResumeToken.from_json(ropts["resume_json"])
    assert back.key == [5, 6] and back.chain == ["ab"]
    # template/tool inputs must not be re-expanded on the resume leg
    for dead in ("prompt", "messages_json", "tools_json"):
        assert dead not in ropts


def test_resume_opts_synthesized_lane_needs_host_tier():
    api = _api()
    opts = {"prompt_ids": [1, 2], "tokens": 16, "temperature": 0.9}
    # pool enabled (model-level budget): bridge synthesizes the token
    got = api._resume_opts(_mcfg(kv_host_bytes=1 << 20), opts,
                           [1, 2], [7, 8, 9], 5, None)
    assert got is not None
    ropts, mode, suppress, base = got
    assert mode == "resume" and base == 3 and suppress == []
    assert ropts["prompt_ids"] == [1, 2, 7, 8, 9]
    tok = json.loads(ropts["resume_json"])
    assert tok["key"] is None and tok["chain"] == []   # died with the pool
    assert tok["sent_chars"] == 5
    # sampled + no pool anywhere: no lane — PR 4 terminal-error contract
    assert api._resume_opts(_mcfg(), opts, [1, 2], [7], 3, None) is None
    # nothing streamed yet → plain retry path, not a resume
    assert api._resume_opts(_mcfg(kv_host_bytes=1), opts,
                            [1, 2], [], 0, None) is None


def test_resume_opts_replay_lane_and_exclusions():
    api = _api()
    det = {"prompt_ids": [1, 2], "tokens": 16, "temperature": 0.0}
    got = api._resume_opts(_mcfg(), det, [1, 2], [5, 6, 7, 8, 9, 10], 9, None)
    assert got is not None
    ropts, mode, suppress, base = got
    assert mode == "replay"
    assert base == 2 and suppress == [7, 8, 9, 10]  # 4-token verify tail
    assert ropts["prompt_ids"] == [1, 2, 5, 6]
    assert ropts["tokens"] == 14                    # 16 - 2 folded
    # tools / stop strings / multimodal never replay
    assert api._resume_opts(_mcfg(), dict(det, tools_json="[{}]"),
                            [1], [5], 1, None) is None
    assert api._resume_opts(_mcfg(), dict(det, stop_prompts=["x"]),
                            [1], [5], 1, None) is None
    assert api._resume_opts(_mcfg(kv_host_bytes=1), dict(det, images=["i"]),
                            [1], [5], 1, None) is None


# --------------------------------------------------------- engine-level

TINY = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position=512, dtype="float32")


@pytest.fixture(scope="module")
def tiny_parts():
    import jax

    from localai_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(**TINY)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _mk(tiny_parts, kvhost=None, kv_host_bytes=0, loop=8, block=4):
    from localai_tpu.engine.engine import Engine, EngineConfig

    cfg, params = tiny_parts
    return Engine(cfg, params, None, EngineConfig(
        max_slots=2, max_context=512, prefill_buckets=(64,),
        prefill_chunk=64, kv_pages=6, prompt_cache=True,
        decode_loop=loop, decode_block=block,
        cache_type="int8", kv_host_bytes=kv_host_bytes), kvhost=kvhost)


def _run(eng, ids, n, params_=None, resume=None):
    from localai_tpu.engine.engine import GenRequest
    from localai_tpu.ops.sampling import SamplingParams

    rid, out = eng.submit(GenRequest(
        prompt_ids=list(ids), max_tokens=n,
        params=params_ or SamplingParams(temperature=0.0),
        ignore_eos=True, resume=resume))
    toks = []
    while True:
        eng.step()
        while not out.empty():
            so = out.get()
            if so.token_id >= 0:
                toks.append(so.token_id)
            if so.finished:
                while eng.step():
                    pass
                return toks


def _run_until_preempt(eng, ids, n, k, params_=None, resume=None):
    """Step until >=k tokens observed, then spill-drain; returns
    (emitted-so-far, resume manifest)."""
    from localai_tpu.engine.engine import GenRequest
    from localai_tpu.ops.sampling import SamplingParams

    rid, out = eng.submit(GenRequest(
        prompt_ids=list(ids), max_tokens=n,
        params=params_ or SamplingParams(temperature=0.0),
        ignore_eos=True, resume=resume))
    toks = []
    while len(toks) < k:
        eng.step()
        while not out.empty():
            so = out.get()
            if so.token_id >= 0:
                toks.append(so.token_id)
            assert not so.finished, "finished before the preempt landed"
    man = eng.preempt()
    term = None
    while not out.empty():
        so = out.get()
        if so.token_id >= 0:
            toks.append(so.token_id)
        if so.finished:
            term = so
    assert term is not None and term.finish_reason == "preempted"
    assert term.resume is not None
    return toks, man


PROMPT = np.random.default_rng(7).integers(1, 127, 200).tolist()
N = 48


@pytest.mark.slow
@pytest.mark.preempt
def test_greedy_parity_across_engine_death(tiny_parts):
    from localai_tpu.engine.resume import ResumeToken

    ref = _run(_mk(tiny_parts), PROMPT, N)
    eng = _mk(tiny_parts, kv_host_bytes=1 << 26)
    got, man = _run_until_preempt(eng, PROMPT, N, 10)
    assert eng.metrics["preempts"] == 1
    assert eng.metrics["preempt_spilled_blocks"] > 0
    tok = ResumeToken.from_dict(man[0])
    assert tok.emitted == got
    assert tok.chain, "a 200-token prompt must spill full KV blocks"
    assert tok.key is None                         # greedy: no RNG state
    # the engine object dies; only the host pool survives the "process"
    fresh = _mk(tiny_parts, kvhost=eng._kvhost)
    rest = _run(fresh, tok.resume_prompt, N - tok.generated,
                resume=tok.payload())
    assert got + rest == ref, "greedy resume diverged from the unbroken run"
    assert fresh.metrics["resume_readmits"] == 1
    assert fresh.metrics["resume_reprefills"] == 0


@pytest.mark.slow
@pytest.mark.preempt
def test_sampled_parity_via_persisted_rng_key(tiny_parts):
    from localai_tpu.engine.resume import ResumeToken
    from localai_tpu.ops.sampling import SamplingParams

    sp = SamplingParams(temperature=0.9, top_k=40, seed=123)
    ref = _run(_mk(tiny_parts), PROMPT, N, params_=sp)
    eng = _mk(tiny_parts, kv_host_bytes=1 << 26)
    got, man = _run_until_preempt(eng, PROMPT, N, 10, params_=sp)
    tok = ResumeToken.from_dict(man[0])
    assert tok.key is not None, "sampled checkpoint must carry the RNG key"
    fresh = _mk(tiny_parts, kvhost=eng._kvhost)
    rest = _run(fresh, tok.resume_prompt, N - tok.generated, params_=sp,
                resume=tok.payload())
    assert got + rest == ref, "sampled resume diverged (RNG key not restored)"


@pytest.mark.slow
@pytest.mark.preempt
def test_tiny_pool_falls_back_to_reprefill(tiny_parts):
    from localai_tpu.engine.resume import ResumeToken

    ref = _run(_mk(tiny_parts), PROMPT, N)
    eng = _mk(tiny_parts, kv_host_bytes=64)        # can't hold one block
    got, man = _run_until_preempt(eng, PROMPT, N, 10)
    tok = ResumeToken.from_dict(man[0])
    fresh = _mk(tiny_parts)                        # and no pool at all
    rest = _run(fresh, tok.resume_prompt, N - tok.generated,
                resume=tok.payload())
    assert got + rest == ref, "re-prefill fallback diverged"
    assert fresh.metrics["resume_reprefills"] == 1
    assert fresh.metrics["resume_readmits"] == 0


@pytest.mark.slow
@pytest.mark.preempt
def test_second_preempt_during_resume_folds_base(tiny_parts):
    """Drain interaction: a resume run preempted AGAIN must checkpoint
    against the ORIGINAL prompt boundary (resume_base folding), not the
    prompt+emitted resubmission, so a third engine still resumes cleanly."""
    from localai_tpu.engine.resume import ResumeToken

    ref = _run(_mk(tiny_parts), PROMPT, N)
    eng1 = _mk(tiny_parts, kv_host_bytes=1 << 26)
    got1, man1 = _run_until_preempt(eng1, PROMPT, N, 10)
    tok1 = ResumeToken.from_dict(man1[0])
    # short fused bursts so the second preempt lands well before max_tokens
    eng2 = _mk(tiny_parts, kvhost=eng1._kvhost, kv_host_bytes=1 << 26,
               loop=4, block=2)
    got2, man2 = _run_until_preempt(eng2, tok1.resume_prompt,
                                    N - tok1.generated, 4,
                                    resume=tok1.payload())
    tok2 = ResumeToken.from_dict(man2[0])
    assert tok2.prompt_ids == PROMPT, "resume_base folding lost the boundary"
    assert tok2.emitted == got1 + got2
    eng3 = _mk(tiny_parts, kvhost=eng2._kvhost)
    rest = _run(eng3, tok2.resume_prompt, N - tok2.generated,
                resume=tok2.payload())
    assert got1 + got2 + rest == ref, "double-preempt resume diverged"


# --------------------------------------------------- chaos: HTTP stack

_FAULTS = ",".join([
    "preempt:0:1:gtiny",           # SIGTERM notice after gtiny's first token
    "kill9_middecode:2:1:ktiny",   # SIGKILL at ktiny's 2nd emitted token
    "kill9_middecode:2:1:ntiny",   # ditto, model without the host KV tier
    "kill9_middecode:2:1:rtiny",   # ditto, greedy → deterministic replay
    "stall_stream:1.5:1:ptiny",    # holds a stream open for the drain race
])


@pytest.fixture(scope="module")
def preempt_faultenv(tmp_path_factory):
    import os

    fault_dir = str(tmp_path_factory.mktemp("faults-preempt"))
    old = {k: os.environ.get(k)
           for k in ("LOCALAI_FAULT", "LOCALAI_FAULT_DIR")}
    os.environ["LOCALAI_FAULT"] = _FAULTS
    os.environ["LOCALAI_FAULT_DIR"] = fault_dir
    yield fault_dir
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _write_kv_model(models, name, ckpt, kv_host_bytes=0):
    # 512-token context + 256-token generations below: the preempt SIGTERM
    # fires after the FIRST emitted token, so the generation must outlast
    # the signal→spill-drain latency or the stream finishes before the
    # freeze and nothing is left to resume
    (models / f"{name}.yaml").write_text(yaml.safe_dump({
        "name": name,
        "backend": "llm",
        "context_size": 512,
        "parallel": 2,
        "dtype": "float32",
        "prefill_buckets": [32, 64],
        "kv_pages": 8,
        "kv_host_bytes": kv_host_bytes,
        "parameters": {"model": ckpt, "temperature": 0.0, "max_tokens": 8},
    }))


@pytest.fixture(scope="module")
def pstack(tmp_path_factory, preempt_faultenv):
    import os

    from localai_tpu.config import AppConfig

    ckpt = tiny_checkpoint(tmp_path_factory, max_position=512)
    models = tmp_path_factory.mktemp("models-preempt")
    for name in ("gtiny", "ktiny", "ptiny"):
        _write_kv_model(models, name, ckpt, kv_host_bytes=1 << 26)
    for name in ("ntiny", "rtiny"):
        _write_kv_model(models, name, ckpt)
    os.environ["JAX_PLATFORMS"] = "cpu"
    app_cfg = AppConfig(
        address=f"127.0.0.1:{_free_port()}", models_path=str(models),
        parallel_requests=2, retry_budget=1, spawn_retries=1,
        spawn_timeout=60.0, drain_timeout=10.0)
    base, manager, api, stop = _serve(app_cfg, models)
    yield base, manager, api
    stop()


def _pchat(base, model, n=256, stream=True, temperature=None, timeout=300):
    body = {
        "model": model,
        "messages": [{"role": "user", "content": "the quick brown"}],
        "max_tokens": n,
        "stream": stream,
    }
    if temperature is not None:
        body["temperature"] = temperature
    return requests.post(base + "/v1/chat/completions", json=body,
                         stream=stream, timeout=timeout)


def _delta_text(events):
    return "".join(
        e["choices"][0].get("delta", {}).get("content") or ""
        for e in events
        if isinstance(e, dict) and e.get("choices"))


def _assert_uninterrupted(events):
    assert events and events[-1] == "DONE", f"stream did not finish: {events}"
    errors = [e for e in events if isinstance(e, dict) and "error" in e]
    assert not errors, f"resume leaked an error event: {errors}"
    finals = [e for e in events if isinstance(e, dict) and e.get("choices")
              and e["choices"][0].get("finish_reason")]
    assert finals, "stream ended without finish_reason"


@pytest.mark.slow
@pytest.mark.resilience
def test_graceful_preempt_one_uninterrupted_stream(pstack):
    """SIGTERM preemption notice mid-stream: the dying backend spill-drains
    a full ResumeToken, the bridge re-issues it on the respawned backend,
    and the client sees ONE clean stream whose text byte-matches an
    unbroken run — with localai_resume_total{outcome="ok"} to prove the
    checkpoint lane (not a silent full retry) carried it."""
    base, manager, _ = pstack
    events = _sse_events(_pchat(base, "gtiny", timeout=(30, 300)))
    _assert_uninterrupted(events)
    text = _delta_text(events)
    assert text, "no content reached the client"
    # fault limit 1 is consumed: this reference run is unbroken, greedy
    ref = _pchat(base, "gtiny", stream=False)
    assert ref.status_code == 200, ref.text
    assert text == ref.json()["choices"][0]["message"]["content"], \
        "resumed stream text diverged from the unbroken run"
    m = requests.get(base + "/metrics", timeout=30)
    assert b'localai_resume_total{model="gtiny",outcome="ok"}' in m.content


@pytest.mark.slow
@pytest.mark.resilience
def test_kill9_middecode_with_host_tier_resumes(pstack):
    """kill -9 at the 2nd emitted token, host KV tier enabled: no drain ran
    and no checkpoint exists — the bridge synthesizes a ResumeToken from
    its own accumulated stream state and the client still sees one
    uninterrupted, byte-exact stream."""
    base, manager, _ = pstack
    events = _sse_events(_pchat(base, "ktiny", timeout=(30, 300)))
    _assert_uninterrupted(events)
    text = _delta_text(events)
    ref = _pchat(base, "ktiny", stream=False)
    assert ref.status_code == 200, ref.text
    assert text == ref.json()["choices"][0]["message"]["content"]
    m = requests.get(base + "/metrics", timeout=30)
    assert b'localai_resume_total{model="ktiny",outcome="ok"}' in m.content


@pytest.mark.slow
@pytest.mark.resilience
def test_kill9_no_pool_sampled_keeps_terminal_error_contract(pstack):
    """Resume disabled (no host tier) and non-deterministic sampling: no
    lane applies, so the PR 4 contract holds — a clean terminal SSE error
    event and [DONE], never a hung connection."""
    base, _, _ = pstack
    events = _sse_events(_pchat(base, "ntiny", temperature=0.9,
                                timeout=(30, 300)))
    assert events and events[-1] == "DONE", f"hung/severed stream: {events}"
    errors = [e for e in events if isinstance(e, dict) and "error" in e]
    assert errors, f"expected a terminal SSE error event, got {events}"
    assert errors[-1]["error"]["code"] in (502, 503)


@pytest.mark.slow
@pytest.mark.resilience
def test_kill9_no_pool_greedy_deterministic_replay(pstack):
    """Resume disabled but the request is temperature-0: the replay lane
    re-prefills prompt+emitted minus a verification tail and the stream
    completes seamlessly, counted as outcome="replay"."""
    base, _, _ = pstack
    events = _sse_events(_pchat(base, "rtiny", timeout=(30, 300)))
    _assert_uninterrupted(events)
    text = _delta_text(events)
    ref = _pchat(base, "rtiny", stream=False)
    assert ref.status_code == 200, ref.text
    assert text == ref.json()["choices"][0]["message"]["content"]
    m = requests.get(base + "/metrics", timeout=30)
    assert b'localai_resume_total{model="rtiny",outcome="replay"}' in m.content


@pytest.mark.slow
@pytest.mark.resilience
def test_preempt_endpoint_then_drain_never_hangs_stream(pstack):
    """/backend/preempt validation plus the drain interaction: a preempt
    fired into a live stream, immediately followed by a full drain, must
    still terminate the client stream with [DONE] — resumed or failed,
    but never wedged. Runs last in this module: the drain stops the stack."""
    base, manager, _ = pstack
    r = requests.post(base + "/backend/preempt", json={}, timeout=30)
    assert r.status_code == 400                      # model is required

    s = _pchat(base, "ptiny", timeout=(30, 120))
    it = s.iter_lines()
    assert _read_until_content(it)       # stream live; stall holds it ~1.5 s
    done = {}

    def preempt():
        done["p"] = requests.post(base + "/backend/preempt",
                                  json={"model": "ptiny"}, timeout=60)

    def shutdown():
        done["s"] = requests.post(base + "/backend/shutdown", json={},
                                  timeout=120)

    tp = threading.Thread(target=preempt)
    tp.start()
    time.sleep(0.3)
    ts = threading.Thread(target=shutdown)
    ts.start()
    tail = []
    for line in it:                      # MUST terminate, resumed or not
        if line.startswith(b"data: "):
            payload = line[6:]
            tail.append("DONE" if payload == b"[DONE]"
                        else json.loads(payload))
    assert tail and tail[-1] == "DONE", f"drain+preempt hung the stream: {tail}"
    tp.join(timeout=60)
    ts.join(timeout=120)
    assert done["p"].status_code == 200
    assert done["s"].status_code == 200 and done["s"].json()["success"]
