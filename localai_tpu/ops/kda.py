"""The gated delta rule with per-channel decay (a linear-attention layer's
mixer), in XLA: the chunkwise form prefill and extend run, the one-token
step decode runs off the TPU, and the token-by-token scan they are tested
against.

Per head, with a state S [Dk, Dv] in float32, a token's log-decay g [Dk]
(<= 0), its write strength beta, and q, k [Dk], v [Dv]:

    S' = diag(exp(g)) S
    S  = S' + beta k (v - S'^T k)^T       = (I - beta k k^T) diag(alpha) S + beta k v^T
    o  = S^T q

Shapes: q, k, g [B, S, H, Dk]; v [B, S, H, Dv]; beta [B, S, H]; state
[B, H, Dk, Dv]. Everything is computed in float32 (`_HI`: a TPU's default
matrix product would round the operands to bfloat16, and the state passes
through every chunk of a prompt).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
SUB = 64        # tokens a sub-chunk: one (I + tril)^-1 transform each


def short_conv(tail, u, w):
    """Causal depthwise convolution over time, kernel K, no bias: tail
    [B, K-1, C] are the K-1 inputs before u [B, S, C]; w [C, K], w[:, K-1]
    on the current token. Returns (y [B, S, C], the inputs [B, K-1+S, C])."""
    taps = w.shape[-1]
    xx = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    s = u.shape[1]
    y = sum(xx[:, i:i + s] * w[:, i].astype(u.dtype) for i in range(taps))
    return y, xx


def kda_step(q, k, v, g, beta, state):
    """One token a row: q, k, g [B, H, Dk], v [B, H, Dv], beta [B, H],
    state [B, H, Dk, Dv] -> (o [B, H, Dv], state)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    s = state * jnp.exp(g)[..., None]
    u = jnp.einsum("bhk,bhkv->bhv", k, s, precision=_HI)
    d = beta[..., None] * (v - u)
    s = s + k[..., None] * d[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, s, precision=_HI), s


def kda_recurrent(q, k, v, g, beta, state):
    """The recurrence a token at a time (lax.scan over S): the chunkwise
    form's twin in tests, never the served prefill."""
    def step(s, xs):
        o, s = kda_step(*xs, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def kda_chunk(q, k, v, g, beta, state, n_valid=None, sub: int = SUB):
    """The chunkwise (WY / UT transform) form over sub-chunks of `sub`
    tokens, taking and returning the state. With G the log-decay summed
    inside a sub-chunk and u_i = beta_i (v_i - S'_i^T k_i) a token's
    pseudo-value:

        (I + diag(beta) stril(A)) U = diag(beta) (V - (K e^G) S_0),
            A_ij = (k_i e^G_i) . (k_j e^-G_j)
        O   = (Q e^G) S_0 + tril(B) U,   B_ij = (q_i e^G_i) . (k_j e^-G_j)
        S_C = diag(e^G_C) S_0 + (K e^(G_C - G))^T U

    The transform is solved once a sub-chunk for [beta K e^G | beta V]; the
    pass over sub-chunks (lax.scan, the state its carry) then costs four
    products each. e^G and e^-G are taken about the sub-chunk's middle, so
    that neither leaves float32's range at any decay a model draws.
    n_valid [B]: tokens from there on are padding and leave the state as
    it was (their outputs mean nothing)."""
    f32 = jnp.float32
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    if n_valid is not None:
        live = jnp.arange(s)[None, :] < n_valid[:, None]
        g = jnp.where(live[..., None, None], g, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
    pad = -s % sub
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // sub

    def split(a):       # [B, S, H, D] -> [N, B, H, C, D]
        return a.reshape(b, n, sub, h, -1).transpose(1, 0, 3, 2, 4)

    q, k, v, g = split(q), split(k), split(v), split(g)
    beta = split(beta[..., None])                        # [N, B, H, C, 1]
    gc = jnp.cumsum(g, axis=-2)
    mid = gc[..., sub // 2:sub // 2 + 1, :]
    k_den = k * jnp.exp(mid - gc)
    ii = jnp.arange(sub)
    a_mat = jnp.einsum("...ik,...jk->...ij", k * jnp.exp(gc - mid), k_den,
                       precision=_HI)
    b_mat = jnp.einsum("...ik,...jk->...ij", q * jnp.exp(gc - mid), k_den,
                       precision=_HI)
    lower = jnp.where(ii[:, None] > ii[None, :], beta * a_mat, 0.0)
    b_mat = jnp.where(ii[:, None] >= ii[None, :], b_mat, 0.0)
    decay = jnp.exp(gc)
    rhs = jnp.concatenate([beta * k * decay, beta * v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        lower + jnp.eye(sub, dtype=f32), rhs, lower=True, unit_diagonal=True)
    w, uv = solved[..., :dk], solved[..., dk:]
    last = gc[..., -1:, :]
    k_out = k * jnp.exp(last - gc)

    def step(s0, xs):
        w, uv, qd, b_mat, k_out, last = xs
        u = uv - jnp.einsum("bhck,bhkv->bhcv", w, s0, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", qd, s0, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", b_mat, u, precision=_HI))
        s1 = (s0 * jnp.exp(last)[..., 0, :, None]
              + jnp.einsum("bhck,bhcv->bhkv", k_out, u, precision=_HI))
        return s1, o

    state, o = jax.lax.scan(step, state.astype(f32),
                            (w, uv, q * decay, b_mat, k_out, last))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, s + pad, h, dv)
    return o[:, :s], state
