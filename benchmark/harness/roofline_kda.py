"""Operations and bytes of the two kernels a linear-attention layer (gated
delta rule with per-channel decay) adds, from shapes alone, and the least
time a chip could take for a call. Kept with the benchmark so that no PR
that claims a gain can change the yardstick. Pure Python.

A head's state is [Dk, Dv] float32.

`kda_decode`, one call a linear layer a decode step, over `rows` live rows
of `heads` heads: reads and writes each live row's state once (2 x 4 Dk Dv
bytes a head), reads q, k, g [Dk], v [Dv] and beta, writes o [Dv] (float32);
decays the state (Dk Dv multiplies), reads it against k and q (2 x 2 Dk Dv),
and adds the rank-one update (2 Dk Dv): 7 Dk Dv operations a head. The
vector unit does them, not the matrix unit: the kernel is bound by the
bandwidth at every shape (7 operations for 8 bytes of state).

`kda_chunk`, one call a linear layer a prefill chunk, over `tokens` tokens
of `heads` heads in sub-chunks of C tokens. A sub-chunk of one head:
  the intra-chunk products      A = (K e^G)(K e^-G)^T, B = (Q e^G)(K e^-G)^T
                                2 x 2 C C Dk
  the transform                 (I + tril)^-1 [beta K e^G | beta V] by
                                substitution: C C (Dk + Dv)
  the pass over the state       W S, (Q e^G) S: 2 x 2 C Dk Dv; tril(B) U:
                                2 C C Dv; (K e^(G_C - G))^T U: 2 C Dk Dv;
                                the decay of S: Dk Dv
Bytes: q, k, g, v, beta in and o out for every token (float32), and the
state read and written ONCE a call: the chunkwise form keeps it on the chip
between sub-chunks, which is what it is for. An implementation that writes
the [C, C] matrices or the solved [C, Dk + Dv] to memory moves more; that
shows as a lower share, as it should. Whether the products run at float32
(several passes of the matrix unit) or bfloat16 is the implementation's
choice; the peak used is the bfloat16 one, the chip's best.
"""
from __future__ import annotations

F32 = 4.0


def kda_decode_cost(rows: float, heads: int, dk: int, dv: int) -> dict:
    per_head_bytes = (2 * dk * dv + 3 * dk + 2 * dv + 1) * F32
    return {"ops": rows * heads * 7.0 * dk * dv,
            "bytes": rows * heads * per_head_bytes,
            "state_bytes": rows * heads * 2 * dk * dv * F32}


def kda_chunk_cost(tokens: int, heads: int, dk: int, dv: int,
                   sub: int = 64, rows: int = 1) -> dict:
    """One call over `rows` sequences of `tokens` tokens each."""
    n = -(-tokens // sub)
    intra = 2 * 2.0 * sub * sub * dk
    transform = 1.0 * sub * sub * (dk + dv)
    state_pass = (2 * 2.0 * sub * dk * dv + 2.0 * sub * sub * dv
                  + 2.0 * sub * dk * dv + dk * dv)
    per_token_bytes = (3 * dk + 2 * dv + 1) * F32
    return {"ops": rows * heads * n * (intra + transform + state_pass),
            "bytes": rows * heads * (tokens * per_token_bytes
                                     + 2 * dk * dv * F32),
            "sub_chunks": n,
            "ops_by_part": {"intra": rows * heads * n * intra,
                            "transform": rows * heads * n * transform,
                            "state_pass": rows * heads * n * state_pass}}


def least_seconds(cost: dict, peaks: dict) -> dict:
    """The roofline of one call: the larger of bytes over bandwidth and
    operations over the matrix unit's bfloat16 peak."""
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    t_ops = cost["ops"] / peaks["bf16_flops"]
    return {"seconds": max(t_bytes, t_ops),
            "bound": "bandwidth" if t_bytes >= t_ops else "compute",
            "bytes_s": t_bytes, "ops_s": t_ops}


def roofline_share(cost: dict, peaks: dict, measured_s: float) -> float:
    """Per cent of the roofline a call that took `measured_s` reached."""
    return 100.0 * least_seconds(cost, peaks)["seconds"] / measured_s
