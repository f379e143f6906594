"""Operations and bytes of the two kernels a Mamba-2 state-space layer adds
(SSD: a scalar decay a head over a matrix state), from shapes alone, and the
least time a chip could take for a call. Kept with the benchmark so that no
PR that claims a gain can change the yardstick. Pure Python.

A head's state is [P, N] float32 (P the head's channels, N the state size);
G groups share B and C [N]; the causal convolution runs over C_conv = H P +
2 G N channels with K taps.

`ssd_decode`, one call a state-space layer a decode step, over `rows` live
rows of `heads` heads: reads and writes each live row's state once (2 x 4 P N
bytes a head); reads x [P], dt and the head's decay (float32), B and C [N] a
group, writes y [P]; decays the state (P N multiplies), adds the rank-one
update dt x B^T (2 P N) and reads it out against C (2 P N): 5 P N operations
a head. The convolution's tail (K - 1 inputs of C_conv channels, in
`tail_bytes` each) is read and written once a row beside it, and its K taps
cost 2 K C_conv operations. The vector unit does all of it: the call is
bound by the bandwidth at every shape (5 operations for 8 bytes of state).

`ssd_chunk`, one call a state-space layer a prompt chunk, over `tokens`
tokens of `heads` heads in chunks of Q tokens. A chunk of one head:
  the groups' C B^T             2 Q Q N a GROUP (shared by H / G heads)
  the masked decay matrix       Q Q exponentials and multiplies: 2 Q Q
  its product with dt x         2 Q Q P
  the state's part of y         C S_0: 2 Q P N
  the new state                 (dt x e^(l_Q - l))^T B: 2 Q P N; the decay
                                of S_0: P N
Bytes: x in and y out [P], dt a head, B and C a group for every token
(float32), and the state read and written ONCE a call: the chunked form
keeps it on the chip between chunks, which is what it is for. An
implementation that writes the [Q, Q] matrices to memory moves more; that
shows as a lower share, as it should. Whether the products run at float32
(several passes of the matrix unit) or bfloat16 is the implementation's
choice; the peak used is the bfloat16 one, the chip's best.
"""
from __future__ import annotations

from benchmark.harness.roofline_kda import (  # noqa: F401  (the same rule)
    least_seconds, roofline_share,
)

F32 = 4.0


def conv_channels(heads: int, p: int, groups: int, n: int) -> int:
    return heads * p + 2 * groups * n


def ssd_decode_cost(rows: float, heads: int, p: int, n: int, groups: int,
                    taps: int = 4, tail_bytes: float = 2.0) -> dict:
    c = conv_channels(heads, p, groups, n)
    state = rows * heads * 2 * p * n * F32
    vectors = rows * (heads * (2 * p + 2) + 2 * groups * n) * F32
    tail = rows * 2 * (taps - 1) * c * tail_bytes
    return {"ops": rows * (heads * 5.0 * p * n + 2.0 * taps * c),
            "bytes": state + vectors + tail,
            "state_bytes": state, "tail_bytes": tail}


def ssd_chunk_cost(tokens: int, heads: int, p: int, n: int, groups: int,
                   chunk: int = 128, rows: int = 1) -> dict:
    """One call over `rows` sequences of `tokens` tokens each."""
    q = min(chunk, tokens)
    nc = -(-tokens // q)
    scores = groups * 2.0 * q * q * n
    intra = heads * (2.0 * q * q + 2.0 * q * q * p)
    state_pass = heads * (2 * 2.0 * q * p * n + p * n)
    per_token_bytes = (heads * (2 * p + 1) + 2 * groups * n) * F32
    return {"ops": rows * nc * (scores + intra + state_pass),
            "bytes": rows * (tokens * per_token_bytes
                             + heads * 2 * p * n * F32),
            "chunks": nc,
            "ops_by_part": {"scores": rows * nc * scores,
                            "intra": rows * nc * intra,
                            "state_pass": rows * nc * state_pass}}
