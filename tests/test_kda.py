"""ops/kda.py and ops/pallas/kda.py: the chunkwise gated delta rule against
the token-by-token scan, and the decode kernel (Pallas interpreter) against
its XLA twin."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops import kda


def _inputs(b, s, h, dk, dv, seed=0, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    # decays from 0.999 down to 0.05 a token (strong: to 0.002)
    lo = -7.0, (1.8 if strong else 1.1)
    g = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, dk), minval=lo[0],
                                    maxval=lo[1]))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("sub_chunks", [1, 2, 7])
@pytest.mark.parametrize("carried", [False, True])
def test_chunk_form_equals_the_token_scan(sub_chunks, carried):
    s = sub_chunks * kda.SUB - (5 if sub_chunks == 7 else 0)   # one ragged
    q, k, v, g, beta, state = _inputs(2, s, 3, 16, 24, seed=sub_chunks)
    if not carried:
        state = jnp.zeros_like(state)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, state)
    got_o, got_s = kda.kda_chunk(q, k, v, g, beta, state)
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


def test_chunk_form_under_strong_decay():
    """alpha down to 0.002 a token: e^G and e^-G about the sub-chunk's
    middle stay inside float32."""
    q, k, v, g, beta, state = _inputs(1, 128, 2, 16, 16, seed=5, strong=True)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, state)
    got_o, got_s = kda.kda_chunk(q, k, v, g, beta, state)
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(got_o - want_o).max()) < 2e-5
    assert float(jnp.abs(got_s - want_s).max()) < 2e-5


def test_padding_past_a_rows_end_changes_nothing():
    q, k, v, g, beta, state = _inputs(2, 100, 2, 16, 16, seed=3)
    n = jnp.array([37, 100])
    o, s = kda.kda_chunk(q, k, v, g, beta, state, n_valid=n)
    cut = lambda a: a[:1, :37]  # noqa: E731
    want_o, want_s = kda.kda_recurrent(cut(q), cut(k), cut(v), cut(g),
                                       cut(beta), state[:1])
    assert float(jnp.abs(o[0, :37] - want_o[0]).max()) < 2e-5
    assert float(jnp.abs(s[0] - want_s[0]).max()) < 2e-5


def test_step_is_one_token_of_the_scan():
    q, k, v, g, beta, state = _inputs(3, 1, 2, 16, 16, seed=4)
    o, s = kda.kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
    want_o, want_s = kda.kda_recurrent(q, k, v, g, beta, state)
    assert np.allclose(o, want_o[:, 0], atol=1e-6)
    assert np.allclose(s, want_s, atol=1e-6)


def test_short_conv_is_causal_and_carries_its_tail():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 10, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    whole, _ = kda.short_conv(jnp.zeros((2, 3, 6)), x, w)
    first, xx = kda.short_conv(jnp.zeros((2, 3, 6)), x[:, :6], w)
    second, _ = kda.short_conv(xx[:, -3:], x[:, 6:], w)
    assert np.allclose(jnp.concatenate([first, second], 1), whole, atol=1e-6)
    by_hand = sum(w[:, i] * (x[:, 5 - 3 + i]) for i in range(4))
    assert np.allclose(whole[:, 5], by_hand, atol=1e-6)


ACTIVE = ([1, 0, 1, 1, 0], [0, 0, 1, 0, 0], [0, 0, 0, 0, 0], [1, 1, 1, 1, 1],
          [0, 1, 0, 0, 1])


@pytest.mark.parametrize("active", ACTIVE, ids=lambda a: "".join(map(str, a)))
@pytest.mark.parametrize("layer", [0, 2])
def test_decode_kernel_against_its_twin(monkeypatch, active, layer):
    """Rows that do not decode hold NaN in their state: the kernel leaves
    them as they are (it moves nothing of theirs), gives zeros for them, and
    touches no other layer of the stack."""
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    from localai_tpu.ops.pallas.kda import kda_decode

    b, h, dk, dv, layers = 5, 32, 16, 128, 3
    q, k, v, g, beta, _ = _inputs(b, 1, h, dk, dv, seed=7)
    q, k, v, g, beta = (a[:, 0] for a in (q, k, v, g, beta))
    stack = jax.random.normal(jax.random.PRNGKey(9), (layers, b, h, dk, dv))
    live = jnp.asarray(active, bool)
    planted = stack.at[layer].set(
        jnp.where(live[:, None, None, None], stack[layer], jnp.nan))
    o, out = kda_decode(q, k, v, g, beta, planted, layer, live)
    want_o, want_s = kda.kda_step(q, k, v, g, beta, stack[layer])
    m = np.asarray(live)
    assert np.allclose(o[m], want_o[m], atol=1e-4)
    assert np.allclose(out[layer][m], want_s[m], atol=1e-4)
    assert bool((o[~m] == 0).all())
    assert bool(jnp.isnan(out[layer][~m]).all())
    for other in set(range(layers)) - {layer}:
        assert bool((out[other] == stack[other]).all())


def test_decode_kernel_refuses_a_shape_it_cannot_tile(monkeypatch):
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    from localai_tpu.ops.pallas.kda import kda_decode

    z = jnp.zeros
    with pytest.raises(ValueError, match="do not tile"):
        kda_decode(z((1, 4, 16)), z((1, 4, 16)), z((1, 4, 32)), z((1, 4, 16)),
                   z((1, 4)), z((1, 1, 4, 16, 32)), 0, jnp.ones((1,), bool))
