"""A Mamba-2 state-space layer's mixer (SSD: a scalar decay a head over a
matrix state), in XLA: the chunked form a prompt and a prompt chunk run, the
one-token step decode runs off the TPU, and the token-by-token scan they are
tested against.

Per head, with a state S [P, N] in float32 (P the head's channels, N the
state size), a token's step dt > 0, the head's A < 0, x [P], and the B, C [N]
of the head's group:

    S = exp(dt A) S + dt x B^T
    y = S C                      (the caller adds D x)

Shapes: x [B, S, H, P]; dt [B, S, H]; a [H]; bm, cm [B, S, G, N] (head h
reads group h // (H / G)); state [B, H, P, N]. Everything is computed in
float32 (`_HI`: a TPU's default matrix product would round the operands to
bfloat16, and the state passes through every chunk of a prompt).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
CHUNK = 128     # tokens a chunk of the chunked form (the config's chunk_size)


def causal_conv(tail, u, w, bias):
    """Causal depthwise convolution over time with a bias, kernel K: tail
    [B, K-1, C] are the K-1 inputs before u [B, S, C]; w [C, K], w[:, K-1]
    on the current token; bias [C]. Returns (y [B, S, C] float32 before the
    activation, the inputs [B, K-1+S, C])."""
    f32 = jnp.float32
    taps = w.shape[-1]
    xx = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    s = u.shape[1]
    y = sum(xx[:, i:i + s].astype(f32) * w[:, i].astype(f32)
            for i in range(taps))
    return y + bias.astype(f32), xx


def _by_head(g, heads: int):
    """bm or cm [..., G, N] -> [..., H, N]: a head reads its group's."""
    return jnp.repeat(g, heads // g.shape[-2], axis=-2)


def ssd_step(x, dt, a, bm, cm, state):
    """One token a row: x [B, H, P], dt [B, H], a [H], bm, cm [B, G, N],
    state [B, H, P, N] -> (y [B, H, P], state)."""
    f32 = jnp.float32
    x, dt, bm, cm = (v.astype(f32) for v in (x, dt, bm, cm))
    h = x.shape[1]
    decay = jnp.exp(dt * a.astype(f32))                        # [B, H]
    s = (state * decay[..., None, None]
         + (dt[..., None] * x)[..., None] * _by_head(bm, h)[:, :, None, :])
    return jnp.einsum("bhpn,bhn->bhp", s, _by_head(cm, h), precision=_HI), s


def ssd_recurrent(x, dt, a, bm, cm, state):
    """The recurrence a token at a time (lax.scan over S): the chunked
    form's twin in tests, never the served prefill."""
    def step(s, xs):
        y, s = ssd_step(*xs[:2], a, *xs[2:], s)
        return s, y

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm))
    state, y = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(y, 0, 1), state


def ssd_chunk(x, dt, a, bm, cm, state, n_valid=None, chunk: int = CHUNK):
    """The chunked form over chunks of `chunk` tokens, taking and returning
    the state. With l_i the log-decay dt_i A summed from the chunk's start
    through token i (<= 0, falling) and S_0 the state before the chunk:

        Y_i = exp(l_i) S_0 C_i + sum_{j <= i} exp(l_i - l_j) (C_i . B_j) dt_j x_j
        S_Q = exp(l_Q) S_0 + sum_j exp(l_Q - l_j) dt_j x_j B_j^T

    matrix products inside a chunk (C B^T a group, the masked decay matrix
    times dt x a head), the state carried between chunks (lax.scan) in
    float32. Every exponent is of a number <= 0. n_valid [B]: tokens from
    there on are padding and leave the state as it was (their outputs mean
    nothing)."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = bm.shape[-2:]
    x, dt, bm, cm = (v.astype(f32) for v in (x, dt, bm, cm))
    if n_valid is not None:
        live = jnp.arange(s)[None, :] < n_valid[:, None]
        dt = jnp.where(live[..., None], dt, 0.0)
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, bm, cm = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                     for v in (x, bm, cm))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (s + pad) // q

    def split(v):       # [B, S, ...] -> [NC, B, Q, ...]
        return jnp.moveaxis(v.reshape(b, nc, q, *v.shape[2:]), 1, 0)

    la = jnp.cumsum(split(dt * a.astype(f32)), axis=2)          # [NC,B,Q,H]
    dx = split(dt[..., None] * x)                               # [NC,B,Q,H,P]
    bm, cm = split(bm), split(cm)                               # [NC,B,Q,G,N]
    ii = jnp.arange(q)
    causal = (ii[:, None] >= ii[None, :])[None, :, :, None]     # [1,Q,Q,1]

    def step(s0, xs):
        la, dx, bm, cm = xs
        # the groups' C_i . B_j, then the heads' decay from j to i over it
        cb = jnp.einsum("bign,bjgn->bijg", cm, bm, precision=_HI)
        seg = la[:, :, None, :] - la[:, None, :, :]             # [B,Q,Q,H]
        mix = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
        mix = mix * jnp.repeat(cb, h // g, axis=-1)
        y = jnp.einsum("bijh,bjhp->bihp", mix, dx, precision=_HI)
        ch = _by_head(cm, h)                                    # [B,Q,H,N]
        y = y + jnp.exp(la)[..., None] * jnp.einsum(
            "bhpn,bihn->bihp", s0, ch, precision=_HI)
        out = jnp.exp(la[:, -1:, :] - la)                       # [B,Q,H]
        s1 = (s0 * jnp.exp(la[:, -1, :])[..., None, None]
              + jnp.einsum("bjhp,bjhn->bhpn", dx * out[..., None],
                           _by_head(bm, h), precision=_HI))
        return s1, y

    state, y = jax.lax.scan(step, state.astype(f32), (la, dx, bm, cm))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, h, p)
    return y[:, :s], state
