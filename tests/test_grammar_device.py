"""Device-side grammar tables (ISSUE 12): dense automaton tables vs the
host matcher, and the engine path that consumes them: the fused decode
loop. A speculative engine refuses a grammar at submit.

Table-unit cases run in tier-1; the engine parity sweeps are slow-marked
and run standalone via `-m grammar`.
"""
import numpy as np
import pytest

from fixtures import tiny_checkpoint
from localai_tpu.functions.grammars import JSON_GRAMMAR, json_schema_grammar
from localai_tpu.functions.matcher import CompiledGrammar, GrammarCache

pytestmark = pytest.mark.grammar


# ------------------------------------------------------------ table units

VOCAB = ['{', '}', '"', 'a', 'b', ':', ',', ' ', '0', '1', 'x']


def _bits_of(mask_u32, nbytes):
    return mask_u32.view(np.uint8)[:nbytes]


def test_table_matches_matcher_walk():
    """Every (state, token) the matcher can walk agrees with the dense
    table: same allowed-token mask at each step, and trans[] lands in a
    state whose mask equals the matcher's mask after accept."""
    g = CompiledGrammar('root ::= "a" [01]+ ("x" | "b")?', VOCAB)
    tbl = g.table(64)
    assert tbl is not None and tbl.n_states >= 2
    s = g.state()
    st = 0
    for tok in [VOCAB.index('a'), VOCAB.index('0'), VOCAB.index('1'),
                VOCAB.index('x')]:
        assert np.array_equal(_bits_of(tbl.masks[st], g.nbytes),
                              s.mask_bits())
        assert tbl.trans[st, tok] >= 0
        assert s.accept(tok)
        st = tbl.trans[st, tok]
    assert tbl.accepting[st]
    # masked-off tokens have no transition anywhere the mask bit is 0
    for state in range(tbl.n_states):
        bits = _bits_of(tbl.masks[state], g.nbytes)
        for t in range(len(VOCAB)):
            allowed = bits[t >> 3] >> (t & 7) & 1
            assert (tbl.trans[state, t] >= 0) == bool(allowed)


def test_table_accepting_tracks_matcher_done():
    g = CompiledGrammar('root ::= "a" "b"', VOCAB)
    tbl = g.table(16)
    s = g.state()
    st = 0
    assert not tbl.accepting[st]
    for tok in (VOCAB.index('a'), VOCAB.index('b')):
        st = tbl.trans[st, tok]
        s.accept(tok)
    assert s.done and tbl.accepting[st]


def test_table_overflow_returns_none():
    """Unbounded-nesting grammars never close their token-reachable state
    set — table() reports None and the engine keeps those on the per-token
    host matcher path instead of shipping a truncated automaton."""
    g = CompiledGrammar('root ::= "b" | "a" root "x"', VOCAB)
    assert g.table(64) is None
    # a closing grammar still overflows when the cap is below its state
    # count — same None contract, memoized per cap
    h = CompiledGrammar('root ::= "a" [01]+ ("x" | "b")?', VOCAB)
    assert h.table(1) is None
    assert h.table(64) is not None
    assert h.table(1) is None  # memo keeps per-cap answers separate


def test_table_memoized_per_cap():
    g = CompiledGrammar('root ::= "a" "b"', VOCAB)
    t1 = g.table(16)
    assert g.table(16) is t1  # double-checked insert returns the cached one


# ------------------------------------------------------------ engine paths

SCHEMA = {"type": "object",
          "properties": {"a": {"type": "integer"},
                         "b": {"type": "string"}},
          "required": ["a", "b"]}


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    from localai_tpu.engine import Tokenizer, load_config, load_params

    ckpt = tiny_checkpoint(tmp_path_factory)
    cfg = load_config(ckpt, dtype="float32")
    params = load_params(ckpt, cfg)
    tok = Tokenizer.from_dir(ckpt)
    return cfg, params, tok


def _drain(eng, reqs, steps=2000):
    outs = [eng.submit(r) for r in reqs]
    for _ in range(steps):
        if not eng.step():
            break
    res = []
    for _, q in outs:
        ids, reason = [], None
        while not q.empty():
            o = q.get_nowait()
            if o.token_id >= 0:
                ids.append(o.token_id)
            if o.finished:
                reason = o.finish_reason
        res.append((ids, reason))
    return res


def _greq(tok, temp=0.0, seed=5, n=24, g=None):
    from localai_tpu.engine import GenRequest
    from localai_tpu.ops.sampling import SamplingParams

    return GenRequest(tok.encode("emit json:"),
                      SamplingParams(temperature=temp, seed=seed),
                      max_tokens=n,
                      grammar=g or json_schema_grammar(SCHEMA))


def _preq(tok, n=10):
    from localai_tpu.engine import GenRequest
    from localai_tpu.ops.sampling import SamplingParams

    return GenRequest(tok.encode("the quick brown fox"),
                      SamplingParams(temperature=0.0),
                      max_tokens=n, ignore_eos=True)


def _assert_conformant(tok, gbnf, ids):
    m = GrammarCache(tok).get(gbnf).state()
    for t in ids:
        if tok.eos_ids and t in tok.eos_ids:
            return
        assert m.accept(t), f"illegal token {t} ({tok.decode([t])!r})"


@pytest.mark.slow
@pytest.mark.parametrize("temp", [0.0, 0.9])
def test_loop_grammar_parity_vs_host_masking(loaded, temp):
    """A table-backed grammar slot rides the single-dispatch while loop and
    emits the SAME stream as the host-masked per-step reference (greedy and
    sampled — the loop's device mask gather + state advance is bit-exact
    against mask_bits)."""
    from localai_tpu.engine import Engine, EngineConfig

    cfg, params, tok = loaded
    e_tab = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prompt_cache=False))
    # decode_block=1: every host-masked step samples under a FRESH mask
    # (the fused-block rollback path re-keys the sampler on a stale-mask
    # miss — a different, equally-valid stream)
    e_host = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prompt_cache=False, grammar_table_states=0, decode_block=1,
        decode_loop=0))
    a = _drain(e_tab, [_greq(tok, temp)])
    b = _drain(e_host, [_greq(tok, temp)])
    assert a == b, (temp, a, b)
    assert e_tab.metrics.get("grammar_table_states", 0) > 0
    # the table engine must NOT have fallen back to per-token dispatches
    assert e_tab.metrics["decode_dispatches"] < \
        e_host.metrics["decode_dispatches"] / 4


@pytest.mark.parametrize("what, named", [
    ("grammar", "grammar-constrained decoding is not served with a draft"),
    ("multimodal", "multimodal prompts are not served with a draft"),
], ids=["grammar", "multimodal"])
def test_a_draft_engine_refuses_at_submit(loaded, what, named):
    """A speculative engine has no grammar lane in its verify program and
    its draft ingests token ids only: both are refused by name at submit,
    nothing is enqueued, and the next plain request is served."""
    from localai_tpu.engine import Engine, EngineConfig

    cfg, params, tok = loaded
    eng = Engine(cfg, params, tok, EngineConfig(
        max_slots=2, max_context=128, prefill_buckets=(16,),
        prompt_cache=False, gamma=2), draft=(cfg, params))
    if what == "grammar":
        bad = _greq(tok)
    else:
        bad = _preq(tok)
        bad.mm_embeds = np.zeros((2, cfg.hidden_size), np.float32)
        bad.mm_positions = np.arange(1, 3)
    with pytest.raises(ValueError, match=named):
        eng.submit(bad)
    assert eng._queue.empty()
    (ids, reason), = _drain(eng, [_preq(tok, 6)])
    assert len(ids) == 6 and reason == "length"
    assert eng.metrics["tokens_by_path__spec"] == 6
