"""End-to-end engine tests against a real (locally built) HF checkpoint.

This is the round-2 "one real model talks" milestone (VERDICT next-round #1):
checkpoint loading parity with HF, greedy decode parity with HF generate, and
concurrent streaming with per-request sampling params.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from localai_tpu.engine import (
    Engine, EngineConfig, GenRequest, Tokenizer, load_config, load_params,
)
from localai_tpu.functions.grammars import JSON_GRAMMAR
from localai_tpu.models.llama import forward_train
from localai_tpu.ops.sampling import SamplingParams

from fixtures import tiny_checkpoint


@pytest.fixture(scope="session")
def ckpt(tmp_path_factory):
    return tiny_checkpoint(tmp_path_factory)


@pytest.fixture(scope="session")
def loaded(ckpt):
    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    return cfg, params, tok


def _hf_model(ckpt):
    import torch
    from transformers import LlamaForCausalLM

    m = LlamaForCausalLM.from_pretrained(ckpt, torch_dtype=torch.float32)
    m.eval()
    return m


def test_config_parsed(loaded):
    cfg, _, tok = loaded
    assert cfg.num_kv_heads == 2 and cfg.num_layers == 2
    assert cfg.vocab_size == tok.vocab_size


def test_logits_parity_with_hf(ckpt, loaded):
    """Our forward on loaded safetensors == HF forward on the same weights."""
    import torch

    cfg, params, tok = loaded
    ids = tok.encode("the quick brown fox jumps over the lazy dog")
    hf = _hf_model(ckpt)
    with torch.no_grad():
        ref = hf(torch.tensor([ids])).logits[0].numpy()
    ours = np.asarray(forward_train(params, cfg, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_greedy_generate_matches_hf(ckpt, loaded):
    import torch

    cfg, params, tok = loaded
    prompt = tok.encode("hello world")
    n_new = 12

    hf = _hf_model(ckpt)
    with torch.no_grad():
        out = hf.generate(
            torch.tensor([prompt]), max_new_tokens=n_new, do_sample=False,
            eos_token_id=None, pad_token_id=0,
            # explicit mask: generate() would otherwise infer one from
            # pad_token_id and mask out our BOS (id 0)
            attention_mask=torch.ones((1, len(prompt)), dtype=torch.long),
        )[0].tolist()
    ref_new = out[len(prompt):]

    eng = Engine(cfg, params, tok, EngineConfig(max_slots=2, max_context=128,
                                                prefill_buckets=(32, 128)))
    req = GenRequest(prompt_ids=prompt, params=SamplingParams(temperature=0.0),
                     max_tokens=n_new, ignore_eos=True)
    toks = [o.token_id for o in eng.generate(req)]
    assert toks == ref_new


def test_concurrent_streams_with_different_sampling(loaded):
    """2+ requests in flight with different sampling params stream to
    completion and produce the prompt-conditioned text deterministically
    for the greedy one."""
    cfg, params, tok = loaded
    eng = Engine(cfg, params, tok, EngineConfig(max_slots=3, max_context=128,
                                                prefill_buckets=(32,)))
    reqs = [
        GenRequest(tok.encode("pack my box"), SamplingParams(temperature=0.0),
                   max_tokens=8, ignore_eos=True),
        GenRequest(tok.encode("sphinx of black"),
                   SamplingParams(temperature=0.9, top_k=20, seed=7),
                   max_tokens=8, ignore_eos=True),
        GenRequest(tok.encode("hello"),
                   SamplingParams(temperature=0.7, top_p=0.9, seed=3),
                   max_tokens=8, ignore_eos=True),
    ]
    outs = [eng.submit(r) for r in reqs]
    # drive the loop manually until all finish
    for _ in range(200):
        if not eng.step():
            break
    results = {}
    for rid, q in outs:
        text, n = "", 0
        while not q.empty():
            o = q.get()
            text += o.text
            n = o.generated_tokens
            if o.finished:
                results[rid] = (text, n, o.finish_reason)
    assert len(results) == 3
    for text, n, reason in results.values():
        assert n == 8 and reason == "length"

    # greedy request must reproduce the single-request greedy output
    solo = Engine(cfg, params, tok, EngineConfig(max_slots=1, max_context=128,
                                                 prefill_buckets=(32,)))
    ref = solo.generate_text(reqs[0])
    assert results[outs[0][0]][0] == ref


def test_burst_admission_matches_sequential(loaded):
    """A burst of simultaneous submissions rides the batched-admission path
    (_flush_admits: one prefill device call per (bucket, heavy) group, padded
    by repetition) and must emit token streams identical to admitting each
    request alone — per-request RNG is keyed on the request, not the path."""
    cfg, params, tok = loaded
    prompts = ["pack my box", "sphinx of black", "hello", "the quick brown",
               "jump over"]
    # mixed groups: 3 light seeded + 1 greedy light + 1 heavy (penalty)
    reqs = [
        GenRequest(tok.encode(p), SamplingParams(temperature=0.8, top_k=20,
                                                 seed=11 + i),
                   max_tokens=6, ignore_eos=True)
        for i, p in enumerate(prompts[:3])
    ] + [
        GenRequest(tok.encode(prompts[3]), SamplingParams(temperature=0.0),
                   max_tokens=6, ignore_eos=True),
        GenRequest(tok.encode(prompts[4]),
                   SamplingParams(temperature=0.0, repeat_penalty=3.0),
                   max_tokens=6, ignore_eos=True),
    ]

    def run_burst():
        eng = Engine(cfg, params, tok,
                     EngineConfig(max_slots=8, max_context=128,
                                  prefill_buckets=(32,)))
        outs = [eng.submit(r) for r in reqs]
        for _ in range(300):
            if not eng.step():
                break
        toks = {}
        for rid, q in outs:
            seq = []
            while not q.empty():
                seq.append(q.get().token_id)
            toks[rid] = seq
        return [toks[rid] for rid, _ in outs]

    def run_sequential():
        res = []
        for r in reqs:
            eng = Engine(cfg, params, tok,
                         EngineConfig(max_slots=1, max_context=128,
                                      prefill_buckets=(32,)))
            res.append([o.token_id for o in eng.generate(r)])
        return res

    burst, seq = run_burst(), run_sequential()
    assert burst == seq


def test_wide_topk_rides_escalated_fast_path(loaded):
    """A top_k above the base sort-free width but under 8x of it samples on
    the escalated window — identical tokens to the full-sort path, and the
    batch never falls back to full [B, V] sorting."""
    cfg, params, tok = loaded
    prompt = tok.encode("pack my box with five")

    def run(width):
        eng = Engine(cfg, params, tok, EngineConfig(
            max_slots=2, max_context=128, prefill_buckets=(32,),
            sampling_topk_width=width))
        # top_k=50 > 8 (base) but <= 64 (8x tier) when width=8
        req = GenRequest(list(prompt),
                         SamplingParams(temperature=0.9, top_k=50, seed=21),
                         max_tokens=10, ignore_eos=True)
        seen = {"w": []}
        orig = eng._dev_decode

        def spy(active, mask_host=None, fast_width=None):
            seen["w"].append(fast_width)
            return orig(active, mask_host, fast_width)

        eng._dev_decode = spy
        toks = [o.token_id for o in eng.generate(req)]
        return toks, seen["w"]

    full_toks, full_w = run(0)        # width 0 disables the fast path
    fast_toks, fast_w = run(8)
    assert full_toks == fast_toks
    assert all(w is None for w in full_w)
    assert all(w == 64 for w in fast_w)   # escalated 8x tier, never full


def test_stop_sequence_truncates(loaded):
    cfg, params, tok = loaded
    eng = Engine(cfg, params, tok, EngineConfig(max_slots=1, max_context=128,
                                                prefill_buckets=(32,)))
    # run greedy once to find a substring the model actually emits
    base = eng.generate_text(GenRequest(
        tok.encode("the quick"), SamplingParams(temperature=0.0),
        max_tokens=10, ignore_eos=True))
    assert len(base) > 3
    stop = base[2:5]
    eng2 = Engine(cfg, params, tok, EngineConfig(max_slots=1, max_context=128,
                                                 prefill_buckets=(32,)))
    outs = list(eng2.generate(GenRequest(
        tok.encode("the quick"), SamplingParams(temperature=0.0),
        max_tokens=10, ignore_eos=True, stop=(stop,))))
    text = "".join(o.text for o in outs)
    assert stop not in text
    assert outs[-1].finish_reason == "stop"
    assert text == base[:base.find(stop)]


def test_penalties_affect_output(loaded):
    """repeat penalty must change sampling behavior (token_counts is live)."""
    cfg, params, tok = loaded
    ec = EngineConfig(max_slots=1, max_context=128, prefill_buckets=(32,))
    prompt = tok.encode("hello world hello world hello")

    def run(rp):
        eng = Engine(cfg, params, tok, ec)
        return [o.token_id for o in eng.generate(GenRequest(
            prompt, SamplingParams(temperature=0.0, repeat_penalty=rp),
            max_tokens=10, ignore_eos=True))]

    assert run(1.0) != run(5.0)


def test_chat_template(loaded):
    _, _, tok = loaded
    text = tok.apply_chat_template(
        [{"role": "user", "content": "hi"}], add_generation_prompt=True
    )
    assert "<|user|>" in text and text.endswith("<|assistant|>\n")
    ids = tok.encode_chat([{"role": "user", "content": "hi"}])
    assert ids[0] == tok.bos_id


def test_incremental_detok_utf8(loaded):
    """Multi-byte characters split across tokens must never emit U+FFFD."""
    _, _, tok = loaded
    s = "café 東京 über"
    ids = tok.encode(s, add_bos=False)
    dec = tok.stream_decoder()
    text = "".join(dec.push(i) for i in ids)
    assert "�" not in text
    assert text == tok.decode(ids)


def test_bad_grammar_fails_request_not_engine(loaded):
    """Client-reachable admission failures must reject that request only and
    leave the engine serving others (advisor finding: an admission exception
    bricked the whole engine). Two layers: malformed GBNF raises ValueError at
    submit() (→ gRPC INVALID_ARGUMENT); anything slipping to admission time is
    converted to a terminal finish_reason=error StepOutput."""
    cfg, params, tok = loaded
    eng = Engine(cfg, params, tok, EngineConfig(max_slots=2, max_context=128,
                                                prefill_buckets=(32,)))
    bad = GenRequest(tok.encode("hello"), SamplingParams(temperature=0.0),
                     max_tokens=4, ignore_eos=True,
                     grammar="root ::= (")
    with pytest.raises(ValueError, match="grammar"):
        eng.submit(bad)

    # admission-time failure (defensive layer): force the matcher compile to
    # blow up only inside _admit_one
    ok = GenRequest(tok.encode("hi"), SamplingParams(temperature=0.0),
                    max_tokens=4, ignore_eos=True, grammar=JSON_GRAMMAR)
    good = GenRequest(tok.encode("hello"), SamplingParams(temperature=0.0),
                      max_tokens=4, ignore_eos=True)
    _, bad_q = eng.submit(ok)
    orig = eng._matcher_for
    eng._matcher_for = lambda g: (_ for _ in ()).throw(ValueError("boom"))
    _, good_q = eng.submit(good)
    for _ in range(50):
        if not eng.step():
            break
    eng._matcher_for = orig
    o = bad_q.get_nowait()
    assert o.finished and o.finish_reason == "error"
    outs = []
    while not good_q.empty():
        outs.append(good_q.get_nowait())
    assert outs and outs[-1].finished and outs[-1].finish_reason == "length"
    assert not eng._dead


def _drain(q):
    text, reason = "", None
    while True:
        o = q.get(timeout=60)
        text += o.text
        if o.finished:
            return text, o.finish_reason


def test_chunked_prefill_matches_single_shot(loaded):
    """A prompt longer than every prefill bucket is admitted via chunked
    extend() ticks; its greedy continuation must be identical to single-shot
    prefill of the same prompt in a large-bucket engine."""
    cfg, params, tok = loaded
    prompt = (tok.encode("the quick brown fox jumps over the lazy dog") * 8)[:70]
    req = lambda: GenRequest(list(prompt), SamplingParams(temperature=0.0),
                             max_tokens=8, ignore_eos=True)
    big = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=256, prefill_buckets=(128,),
        prefill_chunk=128))
    ref = big.generate_text(req())
    small = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=256, prefill_buckets=(32,),
        prefill_chunk=32))
    assert len(prompt) > 32  # really exercises the chunked path
    got = small.generate_text(req())
    assert got == ref and len(ref) > 0


def test_chunked_prefill_interleaved_with_decode(loaded):
    """While one stream decodes, a long prompt prefills chunk-by-chunk in the
    gaps; both outputs must equal their solo greedy runs (no KV corruption
    from the concurrent decode writes)."""
    cfg, params, tok = loaded
    long_prompt = (tok.encode("pack my box with five dozen jugs") * 10)[:80]
    short = GenRequest(tok.encode("hello world"),
                       SamplingParams(temperature=0.0),
                       max_tokens=24, ignore_eos=True)
    longr = GenRequest(list(long_prompt), SamplingParams(temperature=0.0),
                       max_tokens=8, ignore_eos=True)
    ec = EngineConfig(max_slots=2, max_context=256, prefill_buckets=(32,),
                      prefill_chunk=32)
    solo = Engine(cfg, params, tok, ec)
    ref_short = solo.generate_text(GenRequest(short.prompt_ids, short.params,
                                              max_tokens=24, ignore_eos=True))
    ref_long = solo.generate_text(GenRequest(longr.prompt_ids, longr.params,
                                             max_tokens=8, ignore_eos=True))
    eng = Engine(cfg, params, tok, ec)
    _, q_short = eng.submit(GenRequest(short.prompt_ids, short.params,
                                       max_tokens=24, ignore_eos=True))
    # let the short stream get going, then admit the long prompt mid-decode
    for _ in range(3):
        eng.step()
    _, q_long = eng.submit(GenRequest(longr.prompt_ids, longr.params,
                                      max_tokens=8, ignore_eos=True))
    for _ in range(200):
        if not eng.step():
            break
    t_short, r_short = _drain(q_short)
    t_long, r_long = _drain(q_long)
    assert (t_short, r_short) == (ref_short, "length")
    assert (t_long, r_long) == (ref_long, "length")


def test_pipeline_matches_sync_mode(loaded):
    """Pipelined dispatch (one step in flight) must not change outputs vs the
    synchronous loop for mixed seeded-sampling concurrent requests."""
    cfg, params, tok = loaded

    def run(pipeline: bool):
        eng = Engine(cfg, params, tok, EngineConfig(
            max_slots=3, max_context=128, prefill_buckets=(32,),
            pipeline=pipeline))
        reqs = [
            GenRequest(tok.encode("pack my box"),
                       SamplingParams(temperature=0.0), max_tokens=8,
                       ignore_eos=True),
            GenRequest(tok.encode("sphinx of black"),
                       SamplingParams(temperature=0.9, top_k=20, seed=7),
                       max_tokens=8, ignore_eos=True),
            GenRequest(tok.encode("hello"),
                       SamplingParams(temperature=0.7, top_p=0.9, seed=3),
                       max_tokens=8, ignore_eos=True),
        ]
        outs = [eng.submit(r) for r in reqs]
        for _ in range(200):
            if not eng.step():
                break
        return [_drain(q) for _, q in outs]

    assert run(True) == run(False)


def test_context_shift_rotation_unit():
    """cache_shift mechanics: a K row written at position p must, after the
    shift, equal the same raw vector roped at position p-discard; V rows move
    verbatim; sink rows stay; lengths drops by discard."""
    import jax

    from localai_tpu.models.llama import LlamaConfig, cache_shift
    from localai_tpu.ops.rope import apply_rope, rope_table

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, intermediate_size=32,
                      num_layers=2, num_heads=2, num_kv_heads=2, head_dim=8,
                      max_position=64, dtype="float32")
    L, B, KVH, T, D = 2, 2, 2, 32, 8
    keep, discard, length = 3, 10, 30
    cos, sin = rope_table(cfg.rope, T)
    raw = jax.random.normal(jax.random.PRNGKey(0), (L, B, KVH, T, D))
    positions = jnp.arange(T)[None, :].repeat(L * B * KVH, 0).reshape(
        L, B, KVH, T)
    # roped[l,b,h,p] = R(p)·raw  (apply_rope wants [..., seq, heads, dim])
    roped = apply_rope(raw.transpose(0, 1, 3, 2, 4).reshape(L * B, T, KVH, D),
                       cos, sin, jnp.arange(T)[None, :].repeat(L * B, 0))
    kc = roped.reshape(L, B, T, KVH, D).transpose(0, 1, 3, 2, 4)
    vc = jax.random.normal(jax.random.PRNGKey(1), (L, B, KVH, T, D))
    lengths = jnp.array([length, 5], jnp.int32)

    kc2, vc2, lengths2 = cache_shift(cfg, kc, vc, lengths, 0,
                                     keep=keep, discard=discard)
    assert int(lengths2[0]) == length - discard
    assert int(lengths2[1]) == 5           # other slot untouched
    np.testing.assert_allclose(np.asarray(kc2[:, 1]), np.asarray(kc[:, 1]))
    # sink rows unchanged
    np.testing.assert_allclose(np.asarray(kc2[:, 0, :, :keep]),
                               np.asarray(kc[:, 0, :, :keep]), rtol=1e-6)
    # moved V rows verbatim
    np.testing.assert_allclose(
        np.asarray(vc2[:, 0, :, keep:length - discard]),
        np.asarray(vc[:, 0, :, keep + discard:length]), rtol=1e-6)
    # moved K rows = raw re-roped at the new position
    expect = apply_rope(
        raw.transpose(0, 1, 3, 2, 4).reshape(L * B, T, KVH, D),
        cos, sin,
        (jnp.arange(T) - discard)[None, :].repeat(L * B, 0) % T,
    ).reshape(L, B, T, KVH, D).transpose(0, 1, 3, 2, 4)
    np.testing.assert_allclose(
        np.asarray(kc2[:, 0, :, keep:length - discard]),
        np.asarray(expect[:, 0, :, keep + discard:length]),
        rtol=1e-4, atol=1e-5)


def test_context_shift_generation_crosses_limit(loaded):
    """A context_shift request keeps generating past the context cap (bounded
    memory) and ends with finish_reason=length from max_tokens — while a
    non-shift request dies at the cap."""
    cfg, params, tok = loaded
    ctx = 48
    prompt = tok.encode("the quick brown fox jumps over")
    n = len(prompt)

    def run(shift):
        eng = Engine(cfg, params, tok, EngineConfig(
            max_slots=2, max_context=ctx, prefill_buckets=(32,)))
        req = GenRequest(list(prompt), SamplingParams(temperature=0.0),
                         max_tokens=3 * ctx, ignore_eos=True,
                         context_shift=shift)
        _, out = eng.submit(req)
        outs = []
        for _ in range(4000):
            if not eng.step():
                break
        while not out.empty():
            outs.append(out.get())
        return outs

    plain = run(False)
    assert plain[-1].finish_reason == "length"
    assert plain[-1].generated_tokens <= ctx - n  # capped by the context

    shifted = run(True)
    assert shifted[-1].finish_reason == "length"
    assert shifted[-1].generated_tokens == 3 * ctx  # sailed past the cap
    assert all(o.token_id >= 0 for o in shifted)


def test_engine_self_restart_after_fatal_step(loaded):
    """A fatal device error in step() fails the in-flight streams, but the
    engine rebuilds its device state (weights are never donated) and keeps
    serving — the in-process analog of the manager reaping + respawning a
    dead backend, without reloading weights."""
    cfg, params, tok = loaded
    eng = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=64, prefill_buckets=(16,),
        prefill_chunk=16, max_restarts=1))
    fired = {"n": 0}
    orig_admit = eng._admit_many_fn

    def boom(*a, **kw):
        if fired["n"] == 0:
            fired["n"] += 1
            raise RuntimeError("injected device fault")
        return orig_admit(*a, **kw)

    eng._admit_many_fn = boom
    eng.start()
    try:
        _, q = eng.submit(GenRequest([1, 2, 3], SamplingParams(
            temperature=0.0), max_tokens=4, ignore_eos=True))
        o = q.get(timeout=60)
        while not o.finished:
            o = q.get(timeout=60)
        assert o.finish_reason == "error"

        # engine recovered: the next request serves normally
        _, q2 = eng.submit(GenRequest([1, 2, 3], SamplingParams(
            temperature=0.0), max_tokens=4, ignore_eos=True))
        toks = []
        while True:
            o = q2.get(timeout=60)
            toks.append(o.token_id)
            if o.finished:
                break
        assert o.finish_reason == "length" and len(toks) == 4

        # a second fault exceeds max_restarts=1: engine goes dead for good
        fired["n"] = 0
        _, q3 = eng.submit(GenRequest([1, 2, 3], SamplingParams(
            temperature=0.0), max_tokens=4, ignore_eos=True))
        o = q3.get(timeout=60)
        while not o.finished:
            o = q3.get(timeout=60)
        assert o.finish_reason == "error"
        import time as _t

        for _ in range(100):          # loop thread flips _dead shortly after
            if eng._dead:
                break
            _t.sleep(0.05)
        with pytest.raises(RuntimeError, match="terminated"):
            eng.submit(GenRequest([1, 2, 3], SamplingParams(), max_tokens=2))
    finally:
        eng.stop()


def test_a_tick_that_never_ends_fails_its_requests_with_a_message(
        loaded, monkeypatch):
    """The device does not answer (a program that hangs: the engine thread
    sits in its tick for ever): past the limit the engine says so, ends, and
    the request gets a terminal output instead of silence."""
    import threading

    from localai_tpu.engine import engine as engine_mod

    cfg, params, tok = loaded
    monkeypatch.setattr(engine_mod, "TICK_LIMIT_S", 0.3)
    eng = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=64, prefill_buckets=(16,),
        prefill_chunk=16))
    hung, release = threading.Event(), threading.Event()

    def step():
        hung.set()
        release.wait(30)
        return False

    monkeypatch.setattr(eng, "step", step)
    eng.start()
    try:
        assert hung.wait(10)
        _, q = eng.submit(GenRequest([1, 2, 3], SamplingParams(
            temperature=0.0), max_tokens=4, ignore_eos=True))
        o = q.get(timeout=10)
        assert o.finished and o.finish_reason == "error"
        assert "has not ended after" in eng.last_error
        with pytest.raises(RuntimeError, match="terminated"):
            eng.submit(GenRequest([1, 2, 3], SamplingParams(), max_tokens=2))
    finally:
        release.set()
        eng.stop()
