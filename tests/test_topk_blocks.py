"""Sampling's top-k by blocks (ISSUE 41): `ops/sampling._top_k` gives
`lax.top_k`'s values and order to the bit, in two stages where the row is
wider than width x 128 lanes and in the plain call where it is not. Counts
and equalities on the CPU, never a speed (`tools/topk_bench.py` on the chip).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops import sampling
from localai_tpu.ops.sampling import NEG_INF, TOPK_BLOCK, SamplerState

VOCABS = (200192, 153600, 98304, 65537, 8193)   # 65537, 8193: a ragged block
WIDTHS = (64, 512)
ROWS = ("random", "ties across a block boundary", "bfloat16-rounded",
        "all equal", "mostly NEG_INF", "signed zeros and infinities")


def _rows(kind: str, v: int, width: int) -> np.ndarray:
    rng = np.random.default_rng(v + width)
    x = rng.standard_normal((2, v)).astype(np.float32) * 3
    if kind == "ties across a block boundary":
        # the row's maximum on both sides of every 7th boundary, more
        # of them than the width: the lower index has to win each tie
        edge = np.arange(TOPK_BLOCK, v, 7 * TOPK_BLOCK)
        x[0, edge] = x[0, edge - 1] = 50.0
        x[1, edge - 1] = x[1, np.minimum(edge + TOPK_BLOCK, v - 1)] = 50.0
    elif kind == "bfloat16-rounded":
        x = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    elif kind == "all equal":
        x[0], x[1] = 0.0, NEG_INF
    elif kind == "mostly NEG_INF":
        keep = rng.random((2, v)) < (width // 3) / v   # fewer than the width
        keep[1, -3:] = True
        x = np.where(keep, x, np.float32(NEG_INF))
    elif kind == "signed zeros and infinities":
        x = np.where(rng.random((2, v)) < 0.5, -0.0, 0.0).astype(np.float32)
        x[1, rng.integers(0, v, 40)] = -np.inf
        x[1, rng.integers(0, v, 5)] = np.inf
    return x


@functools.lru_cache(maxsize=None)
def _programs(width: int):
    return (jax.jit(lambda l: jax.lax.top_k(l, width)),
            jax.jit(lambda l: sampling._top_k(l, width)))


@pytest.mark.parametrize("kind", ROWS)
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("v", VOCABS)
def test_values_and_order_are_lax_top_ks_to_the_bit(v, width, kind):
    one, served = _programs(width)
    x = jnp.asarray(_rows(kind, v, width))
    (vals, order), (want_vals, want_order) = served(x), one(x)
    assert order.dtype == want_order.dtype and vals.dtype == want_vals.dtype
    np.testing.assert_array_equal(np.asarray(order), np.asarray(want_order))
    np.testing.assert_array_equal(np.asarray(vals).view(np.uint32),
                                  np.asarray(want_vals).view(np.uint32))


def _top_k_operands(jaxpr) -> list[int]:
    """The lanes of every top_k's operand, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            found.append(eqn.invars[0].aval.shape[-1])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_top_k_operands(sub))
    return found


@pytest.mark.parametrize("v, width", [
    (8192, 64), (32000, 512), (65536, 512), (4096, 64), (96, 64), (512, 512),
    (8193, 64), (32000, 64), (65537, 512), (200192, 64), (200192, 512),
])
def test_the_form_follows_the_shape(v, width):
    """At or under width x 128 lanes the program holds the one `top_k` over
    the row; above it no `top_k` reads a whole row."""
    lanes = _top_k_operands(jax.make_jaxpr(
        lambda l: sampling._top_k(l, width))(
            jax.ShapeDtypeStruct((2, v), jnp.float32)).jaxpr)
    if sampling.topk_by_blocks(v, width):
        assert v > width * TOPK_BLOCK
        assert lanes == [-(-v // TOPK_BLOCK), width * TOPK_BLOCK]
    else:
        assert lanes == [v]


def test_sample_draws_what_the_one_stage_call_draws(monkeypatch):
    """`sample(..., topk_width=64)` over a 200 k vocabulary: the tokens, the
    carried keys and the log-probabilities of a batch of greedy, top-k, top-p
    and min-p rows are those of the same function over `lax.top_k` itself
    (the parent's program), pinned here from that call."""
    v, width = 200192, 64
    rows = [dict(greedy=True), dict(top_k=40), dict(top_k=1),
            dict(top_k=64, top_p=0.9), dict(top_p=0.5, temperature=1.3),
            dict(top_k=40, min_p=0.05), dict(min_p=0.2, top_p=0.95)]
    state = SamplerState.init(len(rows), v)
    cols = {f.name: np.array(getattr(state, f.name))
            for f in dataclasses.fields(state)}
    for i, row in enumerate(rows):
        cols["key"][i] = np.asarray(jax.random.key_data(
            jax.random.PRNGKey(1000 + i)))
        for name, value in row.items():
            cols[name][i] = value
    cols["logit_bias"][3, 77] = 9.0
    cols["token_counts"][5, :4096] = 1
    cols["repeat_penalty"][5] = 1.2
    state = SamplerState(**{k: jnp.asarray(a) for k, a in cols.items()})
    logits = jnp.asarray(_rows("bfloat16-rounded", v, width)[:1].repeat(
        len(rows), 0) * np.linspace(0.1, 0.4, len(rows))[:, None]
    ).astype(jnp.float32)

    def draw():
        fn = jax.jit(lambda l, s: sampling.sample(l, s, topk_width=width))
        tokens, keys, logprobs = [], [], []
        s = state
        for _ in range(3):                     # the keys carried on
            t, k, lp = fn(logits, s)
            s = dataclasses.replace(s, key=k)
            tokens.append(np.asarray(t))
            keys.append(np.asarray(k))
            logprobs.append(np.asarray(lp))
        return tokens, keys, logprobs

    got = draw()
    monkeypatch.setattr(sampling, "_top_k", jax.lax.top_k)
    want = draw()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.stack(g), np.stack(w))
    assert len({int(t) for t in np.stack(got[0])[:, 4]}) > 1   # rows do draw
