"""The KV cache's formats — one class each, one view per forward.

A forward of models/llama.py is given K and V as the engine holds them
(`k_cache`, `v_cache`, a block pool's `table`, the lifecycle tier's `kvt`) and
calls `view` once; from then on its layer body talks to the view, and nothing
outside this module knows how K and V are laid out. A view is a trace-time
object: the arrays one layer touches (`k`, `v`) and what its format needs to
address them. It knows how a window of K/V is WRITTEN (`append`: decode's one
token a row; `write`: a prompt or a chunk), how a decode query READS it
(`decode`: Pallas kernel or XLA twin, int8 or not, per KV-head shard under a
mesh), which rows a chunk's queries attend over (`attend_window`), and how it
rides the layer scan (`carried`).

    DenseKV   the [L, B, KVH, T, D] stack, slot-contiguous
    RingKV    a WINDOW layer's stack: position p lives in row p mod R
    PagedKV   the block pool [L, NB, KVH, BS, D] behind a block table
    TieredKV  the pool under a sink_window policy (engine/kvtier.py)
    StateKV   a LINEAR layer's recurrent state and short-convolution tail
    SsmKV     an SSM (Mamba-2) layer's state and convolution tail
    LatentKV  a LATENT layer's stack of latent rows, one buffer, no heads
    NoKV      nothing is kept (hidden_states)

A model with several kinds of layer gets a tuple of views, one per place in
its period of layer kinds. A new kind of cache is a new class here, not a
branch in every forward.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.ops.attention import (
    block_span, mha_decode, mha_decode_masked, mha_extend, mha_extend_blocks,
    mha_extend_tiered, mha_prefill, mha_prefill_tiered,
)
from localai_tpu.ops.kvcache import (
    SCALE_TILE, QuantKV, cache_scatter, dequant,
)
from localai_tpu.ops.paged import (
    BLOCK, paged_view, resident_block_positions, resident_row_positions,
    ring_block_map,
)
from localai_tpu.parallel.mesh import current_mesh, seq_axis_size

FULL, WINDOW, LINEAR = "full", "window", "linear"   # LlamaConfig.layer_types
LATENT = "latent"
SSM = "ssm"             # a Mamba-2 state-space mixer (ops/ssd.py)
# a layer that is a feed-forward part ALONE (an expert layer without a mixer,
# in a model whose other layers are a mixer alone): no cache place
EXPERTS = "experts"
# rows of a full-length dense cache a chunk's attention visits at a time
# (DenseKV.attend_window): whole scale tiles, and it divides every served T
CHUNK_BLOCK = 512


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PeriodKV:
    """K (or V) of a model with several kinds of layer, as the engine holds
    it: one cache per place in the period of layer kinds, `slots[j]` of
    [L/period, B, KVH, T_j, D] (dense or QuantKV) — T_j the served context
    for a FULL layer, the ring for a WINDOW one; after them one place of
    [1, B, KVH, T_j, D] for each layer that runs before the scanned periods
    (cfg.cache_kinds names every place's kind). A LINEAR layer's place
    holds its state [L/period, B, H, Dk, Dv] float32 in the K tree and its
    convolution tail [L/period, B, K-1, C] in the V tree (StateKV). `view`
    makes a DenseKV, a RingKV or a StateKV of each; the layer scan CARRIES
    them, and place j of period i writes and reads slots[j][i] where it
    lies."""
    slots: tuple


# --------------------------------------------------------- which kernels

def _pallas(unless_forced: bool) -> bool:
    """LOCALAI_FORCE_PALLAS=1 forces Pallas (interpreter off-TPU — tests);
    LOCALAI_NO_PALLAS=1 is the one deliberate way to XLA on a TPU. Nothing
    else chooses: a kernel Mosaic refuses fails the compile that uses it
    (LoadModel's warmup) with its own message."""
    if os.environ.get("LOCALAI_FORCE_PALLAS") == "1":
        return True
    return (unless_forced and os.environ.get("LOCALAI_NO_PALLAS") != "1"
            and jax.default_backend() == "tpu")


def _pallas_attention(mesh) -> bool:
    """Whether attention runs on the Pallas kernels: on TPU without a mesh
    (a mesh sends attention to XLA so GSPMD shards the einsums)."""
    return _pallas(mesh is None)


def _pallas_paged_scatter(num_kv_heads: int) -> bool:
    """Whether a block pool's decode write uses the Pallas kernel
    (ops/pallas/paged_scatter.py) instead of XLA. Under a mesh the pool
    shards its KV-head axis on 'model' and the kernel runs per-shard via
    shard_map (the *_sharded twins) — usable iff the KV-head count divides
    the TP axis; otherwise the XLA tier handles the (unevenly shardable)
    pool."""
    mesh = current_mesh()
    if mesh is not None:
        tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if num_kv_heads % int(tp):
            return False
    return _pallas(True)


def prompt_attention(cache=None, rows=None):
    """Self-attention over a prompt whose K/V are in hand (prefill,
    hidden_states, a pipeline stage): `f(q, k, v, lengths, sliding_window=)`.
    Pallas flash attention on a single-chip TPU; under a mesh with a 'seq'
    axis the ppermute ring (parallel/ring_attention.py); else the XLA
    reference, which GSPMD shards. A tiered cache masks per slot instead
    (`rows`: the slot of each prompt)."""
    if isinstance(cache, TieredKV):
        return cache.prompt_attention(rows)
    mesh = current_mesh()
    if _pallas_attention(mesh):
        from localai_tpu.ops.pallas import flash_prefill

        return flash_prefill
    if seq_axis_size(mesh) > 1:
        from localai_tpu.parallel.ring_attention import ring_prefill

        return partial(ring_prefill, mesh=mesh)
    return mha_prefill


def _ring_back(newest, ring: int):
    """[B, R]: how many positions behind `newest` [B] the entry in each row
    of a ring of R rows is (row p mod R holds position p)."""
    return jnp.mod(newest[:, None] - jnp.arange(ring)[None, :], ring)


# ------------------------------------------------------------- the views

@dataclasses.dataclass
class NoKV:
    """No cache: K and V live for one attention call. Also the formats' base:
    the arrays one layer touches, and the layers' attention window."""
    k: object = None
    v: object = None
    window: int | None = None   # None: full attention
    layer: object = None        # index into a stack; None: k, v are one layer's
    active: object = None       # [B] bool: the rows decoding this step

    carried = False     # True: k/v are the scan's carry, addressed by `layer`
    cold = ()           # per-layer arrays scanned beside k/v, read only
    table = None
    ring = False

    def at(self, k, v, layer=None, cold=()):
        """This view over one layer's arrays (carried: the stack and the
        layer's index in it)."""
        return dataclasses.replace(self, k=k, v=v, layer=layer)

    def of_layer(self, lp):
        """This view for the layer whose weights are `lp`: a view that
        needs none of them is itself (LatentKV takes the up-projection)."""
        return self

    def self_attend(self, fn, q, k, v, lengths):
        """A prompt's self-attention by `fn` (prompt_attention) over the
        K and V the layer handed to `attend`."""
        return fn(q, k, v, lengths, sliding_window=self.window)

    quant = property(lambda self: isinstance(self.k, QuantKV))

    def _pools(self):
        """k and v as a Pallas kernel takes them: int8 bodies and scales."""
        if self.quant:
            return self.k.q, self.k.s, self.v.q, self.v.s
        return self.k, self.v

    def _repack(self, out):
        k, v = (QuantKV(*out[:2]), QuantKV(*out[2:])) if self.quant else out
        return dataclasses.replace(self, k=k, v=v)

    def _set(self, idx, k, v, unique):
        """Scatter window K/V [B, S, KVH, D] at idx = (lead..., :, token)."""
        def put(cache, x):
            if self.quant:
                return cache_scatter(cache, idx, x, unique)
            return cache.at[idx].set(x, unique_indices=unique)

        return dataclasses.replace(self, k=put(self.k, k.transpose(0, 2, 1, 3)),
                                   v=put(self.v, v.transpose(0, 2, 1, 3)))

    def decode(self, q, lengths):
        """q [B, 1, H, D] over `lengths` [B] entries, the token just written
        among them: the Pallas kernel streams the cache where it lies (by
        layer index, through the table); the XLA twin dequantizes in its dots.
        The kernel is told which rows decode: a slot that finished, or that a
        chunked prefill is filling beside this step, keeps its length, and at
        length 0 the kernel neither fetches nor multiplies its context (zeros
        out: a row the host masks anyway)."""
        if _pallas_attention(current_mesh()):
            fn = _kernel("ragged_decode", self.quant, sharded=False)
            if self.active is not None:
                lengths = jnp.where(self.active, lengths, 0)
            return fn(q, *self._pools(), lengths, sliding_window=self.window,
                      table=self.table, ring=self.ring, layer=self.layer)
        return self.decode_xla(q, lengths)

    def decode_xla(self, q, lengths):
        kc, vc = self._gather()
        return mha_decode(q, dequant(kc), dequant(vc), lengths,
                          sliding_window=self.window)

    def attend_window(self, q, positions, start, rows, gathered):
        """A chunk's queries (its K/V already written at `positions`, from
        `start` [B]) over their slots' rows; gathered=False: row i is slot i."""
        kr, vr = self._gather(rows, gathered)
        return mha_extend(q, dequant(kr), dequant(vr), positions,
                          sliding_window=self.window)


class DenseKV(NoKV):
    """The dense stack [L, B, KVH, T, D], head-major (ops/kvcache.py for the
    int8 twin): slot b's position p is row (b, :, p). It rides the layer
    scan as the CARRY and every touch names the layer — one scatter into the
    stack, a kernel whose index maps take the layer, a gather of the rows a
    chunk attends over — so nothing is sliced out, put back or copied (on a
    v5e the xs/ys form cost a decode step 3.6 ms of 20 on Mixtral-8x7B at 6
    layers and 12.5 of 37 on Mellum2; PERF.md)."""
    carried = True

    @jax.named_scope("cache_update")
    def write(self, k, v, rows, positions, *, unique=True, **_):
        """Window K/V [B, S, KVH, D] to (layer, rows[b], :, positions[b, s]).
        Padding lands past the slot's length, where the next real token
        overwrites it: `end` and `last` are not needed. unique=True asserts
        the scatter rows never collide and keeps XLA on the in-place scatter
        path; a caller passes False when collisions are REAL: batched
        admission pads groups by repeating a plan (engine._flush_admits) —
        don't lie to the compiler there (per-request, not per-token)."""
        kvh = self.k.shape[-3]
        idx = (rows[:, None, None], jnp.arange(kvh)[None, :, None],
               positions[:, None, :])
        if self.layer is not None:
            idx = (self.layer, *idx)
        return self._set(idx, k, v, unique)

    def append(self, k, v, lengths, positions):
        """Decode's write, one token a row at positions [B, 1] = lengths.
        Each row owns its slot row, so an inactive row aims at T-1 (never
        readable — the engine terminates at max_context-2): a decode step
        can run beside a chunked prefill into an inactive slot."""
        if self.active is not None:
            positions = jnp.where(self.active[:, None], positions,
                                  self.k.shape[-2] - 1)
        return self.write(k, v, jnp.arange(k.shape[0]), positions)

    def _gather(self, rows=None, gathered=False):
        """This layer's [B, KVH, T, D] of the stack: every slot's, or `rows`'."""
        idx = tuple(i for i in (self.layer, rows if gathered else None)
                    if i is not None)
        return (self.k[idx], self.v[idx]) if idx else (self.k, self.v)

    def _block(self, a, rows, first, size):
        """[B, KVH, size, D'] of a stack's array `a`: rows first .. first +
        size of (layer, slot, :) for every slot, or for `rows`, in ONE slice
        of the stack (slicing the layer out first copies it, PERF.md)."""
        lead = () if self.layer is None else (self.layer,)

        def cut(slot, n):
            out = jax.lax.dynamic_slice(
                a, (*lead, slot, 0, first, 0),
                (*(1,) * len(lead), n, a.shape[-3], size, a.shape[-1]))
            return out.reshape(out.shape[len(lead):])

        if rows is None:
            return cut(0, a.shape[-4])
        return jax.vmap(lambda slot: cut(slot, 1)[0])(rows)

    def attend_window(self, q, positions, start, rows, gathered):
        """NoKV's, in work proportional to the context the chunk has and
        not to the row's capacity: blocks of CHUNK_BLOCK rows, read and
        dequantised where they lie, up to the one that holds the newest
        position (ops/attention.mha_extend_blocks). A mesh with a sequence
        axis keeps the reference."""
        if seq_axis_size(current_mesh()) > 1:
            return NoKV.attend_window(self, q, positions, start, rows,
                                      gathered)
        t = self.k.shape[-2]
        block = min(CHUNK_BLOCK, t)
        rows = rows if gathered else None

        def fetch(first):
            def of(c):
                if not self.quant:
                    return self._block(c, rows, first, block)
                s = self._block(c.s, rows, first // SCALE_TILE,
                                block // SCALE_TILE)
                return dequant(QuantKV(self._block(c.q, rows, first, block),
                                       s))
            return of(self.k), of(self.v)

        return mha_extend_blocks(q, fetch, self.k.shape[-3], t, positions,
                                 start, block=block,
                                 sliding_window=self.window)


def chunk_rows(t: int, window, start: int, s: int) -> int:
    """The rows of a T-row dense cache row DenseKV.attend_window visits for
    a window of s tokens from `start`: the host's count (the engine's
    chunk_ctx_tokens__attended), by the arithmetic the device loops by."""
    block = min(CHUNK_BLOCK, t)
    first, end = block_span(np.int64(start - window + 1 if window else 0),
                            np.int64(start + s - 1), t, block)
    return min(int(end - first) * block, t)


@dataclasses.dataclass
class RingKV(DenseKV):
    """A WINDOW layer's stack: R = T rows a slot, position p in row p mod R,
    R the window plus one prefill chunk (llama.ring_len). A ring has no
    spare row to take a write that must not land (an inactive decode row, a
    prompt's padding, what a prompt longer than the ring has before its
    tail): such an entry is aimed at row R — out of bounds, which a scatter
    drops."""
    full_len: int | None = None     # T of the model's FULL layers
    ring = True

    def _keep(self, k, v, rows, positions, keep, unique=True):
        size = self.k.shape[-2]
        return DenseKV.write(self, k, v, rows,
                             jnp.where(keep, positions % size, size),
                             unique=unique)

    def write(self, k, v, rows, positions, *, end=None, last=None,
              unique=True, **_):
        """end [B] (a prompt, from position 0): the ring takes the prompt's
        own tokens only, and of a prompt longer than the ring its tail.
        last [B] (a final chunk): the window's entries after `last` are
        padding and are not written — nothing in a ring is out of the way."""
        b, s = positions.shape
        if end is not None:
            keep = ((positions < end[:, None])
                    & (positions >= end[:, None] - self.k.shape[-2]))
        else:
            keep = (jnp.ones((b, s), bool) if last is None
                    else jnp.arange(s)[None, :] <= last[:, None])
        return self._keep(k, v, rows, positions, keep, unique)

    def append(self, k, v, lengths, positions):
        # an inactive row's write is dropped
        keep = (jnp.ones((k.shape[0], 1), bool) if self.active is None
                else self.active[:, None])
        return self._keep(k, v, jnp.arange(k.shape[0]), positions, keep)

    def decode_xla(self, q, lengths):
        # the rows in the window: at most window - 1 behind the newest
        kc, vc = self._gather()
        mask = (_ring_back(lengths - 1, kc.shape[2])
                < jnp.minimum(lengths, self.window)[:, None])
        return mha_decode_masked(q, dequant(kc), dequant(vc), mask)

    def attend_window(self, q, positions, start, rows, gathered):
        b, s = positions.shape
        size = self.k.shape[-2]
        # the chunk's writes wrap; every query must still find the
        # window - 1 tokens before it, which the chunk's own newest writes
        # overwrite unless the ring holds window + chunk
        if size < (self.full_len or size) and size < self.window + s:
            raise ValueError(
                f"a ring of {size} tokens cannot take a window of "
                f"{self.window} behind a chunk of {s}")
        kr, vr = self._gather(rows, gathered)
        newest = start + s - 1
        kv_pos = newest[:, None] - _ring_back(newest, kr.shape[2])
        return mha_extend_tiered(
            q, dequant(kr), dequant(vr), positions, kv_pos, kv_pos >= 0,
            jnp.zeros((b,), jnp.int32),
            jnp.full((b,), self.window, jnp.int32))


@dataclasses.dataclass
class _SlotState(NoKV):
    """What the caches of a recurrent layer share (StateKV, SsmKV): `k` a
    state a slot and `v` the short convolution's last inputs, both carried
    by the layer scan and put back whole, a slot at a time."""
    carried = True

    @jax.named_scope("cache_update")
    def _put(self, rows, state, tail):
        return dataclasses.replace(
            self,
            k=self.k.at[self.layer, rows].set(state, unique_indices=False),
            v=self.v.at[self.layer, rows].set(tail.astype(self.v.dtype),
                                              unique_indices=False))

    def _resume(self, rows, start, dtype):
        """The state and the tail a window from `start` [B] goes on from:
        the slots', or zeros where start is 0 (ADMISSION RESETS THE STATE
        HERE, on the device, whatever the last tenant left)."""
        fresh = (start == 0)
        state = jnp.where(fresh[:, None, None, None], 0.0,
                          self.k[self.layer, rows])
        tail = jnp.where(fresh[:, None, None], 0,
                         self.v[self.layer, rows]).astype(dtype)
        return state, tail


@dataclasses.dataclass
class StateKV(_SlotState):
    """A LINEAR (gated delta rule) layer's cache: no keys and values but a
    recurrent STATE, `k` = [L, B, H, Dk, Dv] float32, and the last K-1
    inputs of the short convolution, `v` = [L, B, K-1, C] (C = the q, k and
    v channels side by side, before the convolution). A token rewrites the
    whole state, so nothing is addressed by position:

    prompt  a prompt from position 0 (prefill): from a zero state; the
            slot gets the state after its last real token and that token's
            K-1 predecessors (padding past a row's end changes nothing).
    chunk   a window from `start` (extend): from the slot's state, or from
            zero where start is 0 — ADMISSION RESETS THE STATE HERE, on the
            device, whatever the last tenant left; `n` [B] real tokens.
    step    decode's token: conv, update and readout of the rows decoding
            (`active`); an inactive row's state and tail are untouched.

    The mathematics is ops/kda.py's; on a TPU `step` is one Pallas kernel a
    layer (ops/pallas/kda.py: a live row's state read once, written once,
    in place in the carried stack), and `prompt` and `chunk` run the
    chunkwise form in one kernel a layer too (kda_chunk there: the state a
    value, nothing aliased). No state is kept per position, so a
    prefix of a slot's tokens cannot be lent to the next tenant (the engine
    reuses none), and nothing can be rolled back."""
    heads: int = 0

    def _qkv(self, y, unit_qk=True):
        """The convolution's output [B, S, C] -> q, k, v [B, S, H, D]:
        SiLU, then q and k L2-normalised a head (q also scaled D^-1/2;
        not `unit_qk`: the chunk's kernel does both itself)."""
        b, s, _ = y.shape
        q, k, v = jnp.split(jax.nn.silu(y.astype(jnp.float32)), 3, axis=-1)
        q, k, v = (a.reshape(b, s, self.heads, -1) for a in (q, k, v))
        if not unit_qk:
            return q, k, v

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        return unit(q) * q.shape[-1] ** -0.5, unit(k), v

    def _mix(self, u, conv, g, beta, tail, state, n):
        from localai_tpu.ops.kda import kda_chunk, short_conv

        # the chunkwise form: one kernel on a TPU without a mesh (the state
        # a value in and out: _resume and _put stay XLA's), else its twin
        kernel = _pallas_attention(current_mesh())
        with jax.named_scope("conv"):
            y, xx = short_conv(tail, u, conv)
            q, k, v = self._qkv(y, unit_qk=not kernel)
        with jax.named_scope("kda_chunk"):
            if kernel:
                from localai_tpu.ops.pallas import kda

                o, state = kda.kda_chunk(q, k, v, g, beta, state, n_valid=n,
                                         unit_qk=True)
            else:
                o, state = kda_chunk(q, k, v, g, beta, state, n_valid=n)
        if n is None:
            return o, state, xx[:, -tail.shape[1]:]
        # the K-1 inputs ending at the row's last real token: token t is
        # xx[t + K-1]
        idx = n[:, None] + jnp.arange(tail.shape[1])[None, :]
        return o, state, jnp.take_along_axis(xx, idx[..., None], axis=1)

    def _zeros(self, u, taps):
        b, _, c = u.shape
        dv = c // (3 * self.heads)
        return (jnp.zeros((b, self.heads, dv, dv), jnp.float32),
                jnp.zeros((b, taps - 1, c), u.dtype))

    def prompt(self, u, conv, g, beta, rows, lengths):
        state, tail = self._zeros(u, conv.shape[-1])
        o, state, tail = self._mix(u, conv, g, beta, tail, state, lengths)
        return o, (self if self.k is None else self._put(rows, state, tail))

    def chunk(self, u, conv, g, beta, rows, start, n):
        state, tail = self._resume(rows, start, u.dtype)
        o, state, tail = self._mix(u, conv, g, beta, tail, state, n)
        return o, self._put(rows, state, tail)

    def step(self, u, conv, g, beta):
        from localai_tpu.ops.kda import kda_step, short_conv

        b = u.shape[0]
        active = (jnp.ones((b,), bool) if self.active is None
                  else self.active)
        with jax.named_scope("conv"):
            old = self.v[self.layer]
            y, xx = short_conv(old, u, conv)
            q, k, v = (a[:, 0] for a in self._qkv(y))
            tail = jnp.where(active[:, None, None],
                             xx[:, 1:].astype(old.dtype), old)
            tails = self.v.at[self.layer].set(tail)
        if _pallas_attention(current_mesh()):
            from localai_tpu.ops.pallas.kda import kda_decode

            o, states = kda_decode(q, k, v, g[:, 0], beta[:, 0], self.k,
                                   self.layer, active)
        else:
            with jax.named_scope("kda_decode"):
                old = self.k[self.layer]
                o, new = kda_step(q, k, v, g[:, 0], beta[:, 0], old)
                states = self.k.at[self.layer].set(
                    jnp.where(active[:, None, None, None], new, old))
        return o[:, None], dataclasses.replace(self, k=states, v=tails)


@dataclasses.dataclass
class SsmKV(_SlotState):
    """An SSM (Mamba-2) layer's cache: a recurrent STATE a head, `k` =
    [L, B, H, P, N] float32 (P the head's channels, N the state size), and
    the last K-1 inputs of the causal convolution, `v` = [L, B, K-1, C]
    (C = the x, B and C channels side by side, before the convolution).
    `of_layer` hands it the layer's small leaves (conv, conv_bias, dt_bias,
    A_log, D); the forwards give it xBC [B, S, C] and the raw dt [B, S, H]
    of W_in and get y [B, S, H, P] float32 (D x included) back. As StateKV:

    prompt  from position 0, from a zero state; padding past a row's end
            changes nothing.
    chunk   a window from `start`: from the slot's state, or from zero
            where start is 0 (ADMISSION RESETS THE STATE HERE, on the
            device); `n` [B] real tokens.
    step    decode's token, of the rows decoding (`active`); an inactive
            row's state and tail are untouched.

    The mathematics is ops/ssd.py's: the chunked form for a prompt or a
    chunk, and on a TPU `step` is one Pallas kernel a layer
    (ops/pallas/ssd.py: a live row's state read once, written once, in place
    in the carried stack). Nothing is kept per position: no prefix is lent,
    nothing is rolled back."""
    heads: int = 0
    groups: int = 0
    state: int = 0          # N
    chunk_size: int = 128
    lp: object = None       # the layer's weights (of_layer)

    def of_layer(self, lp):
        return dataclasses.replace(self, lp=lp)

    def _split(self, y, dt):
        """The convolution's output [B, S, C] float32 and the raw dt ->
        x [B, S, H, P], bm, cm [B, S, G, N], dt = softplus(dt + dt_bias)
        [B, S, H], a = -exp(A_log) [H]."""
        f32 = jnp.float32
        b, s, c = y.shape
        gn = self.groups * self.state
        y = jax.nn.silu(y)
        x = y[..., :c - 2 * gn].reshape(b, s, self.heads, -1)
        bm = y[..., c - 2 * gn:c - gn].reshape(b, s, self.groups, self.state)
        cm = y[..., c - gn:].reshape(b, s, self.groups, self.state)
        dt = jax.nn.softplus(dt.astype(f32) + self.lp["dt_bias"].astype(f32))
        return x, bm, cm, dt, -jnp.exp(self.lp["A_log"].astype(f32))

    def _skip(self, y, x):
        return y + self.lp["D"].astype(jnp.float32)[:, None] * x

    def _mix(self, u, dt, tail, state, n):
        from localai_tpu.ops.ssd import causal_conv, ssd_chunk

        with jax.named_scope("conv"):
            y, xx = causal_conv(tail, u, self.lp["conv"],
                                self.lp["conv_bias"])
            x, bm, cm, dt, a = self._split(y, dt)
        with jax.named_scope("ssd_chunk"):
            y, state = ssd_chunk(x, dt, a, bm, cm, state, n_valid=n,
                                 chunk=self.chunk_size)
            y = self._skip(y, x)
        if n is None:
            return y, state, xx[:, -tail.shape[1]:]
        # the K-1 inputs ending at the row's last real token: token t is
        # xx[t + K-1]
        idx = n[:, None] + jnp.arange(tail.shape[1])[None, :]
        return y, state, jnp.take_along_axis(xx, idx[..., None], axis=1)

    def _zeros(self, u):
        b, _, c = u.shape
        p = (c - 2 * self.groups * self.state) // self.heads
        return (jnp.zeros((b, self.heads, p, self.state), jnp.float32),
                jnp.zeros((b, self.lp["conv"].shape[-1] - 1, c), u.dtype))

    def prompt(self, u, dt, rows, lengths):
        state, tail = self._zeros(u)
        y, state, tail = self._mix(u, dt, tail, state, lengths)
        return y, (self if self.k is None else self._put(rows, state, tail))

    def chunk(self, u, dt, rows, start, n):
        state, tail = self._resume(rows, start, u.dtype)
        y, state, tail = self._mix(u, dt, tail, state, n)
        return y, self._put(rows, state, tail)

    def step(self, u, dt):
        from localai_tpu.ops.ssd import causal_conv, ssd_step

        b = u.shape[0]
        active = (jnp.ones((b,), bool) if self.active is None
                  else self.active)
        with jax.named_scope("conv"):
            old = self.v[self.layer]
            y, xx = causal_conv(old, u, self.lp["conv"],
                                self.lp["conv_bias"])
            x, bm, cm, dt, a = (v if v.ndim == 1 else v[:, 0]
                                for v in self._split(y, dt))
            tail = jnp.where(active[:, None, None],
                             xx[:, 1:].astype(old.dtype), old)
            tails = self.v.at[self.layer].set(tail)
        if _pallas_attention(current_mesh()):
            from localai_tpu.ops.pallas.ssd import ssd_decode

            y, states = ssd_decode(x, dt, a, bm, cm, self.k, self.layer,
                                   active)
        else:
            with jax.named_scope("ssd_decode"):
                old = self.k[self.layer]
                y, new = ssd_step(x, dt, a, bm, cm, old)
                states = self.k.at[self.layer].set(
                    jnp.where(active[:, None, None, None], new, old))
        return self._skip(y, x)[:, None], dataclasses.replace(
            self, k=states, v=tails)


def latent_row_width(rank: int, rope: int) -> int:
    """Columns of a latent layer's cache row: the latent and the position
    key side by side, padded with zeros to whole 128-lane tiles. A row of
    576 is laid out token-minor by the compiler (576 is no multiple of 128,
    the token axis is) and then copied whole, 2.5 GB at the served size, for
    every call of a kernel that reads rows (PERF.md section 6, PR 40)."""
    return -(-(rank + rope) // 128) * 128


@dataclasses.dataclass
class LatentKV(NoKV):
    """A LATENT (latent attention, MLA) layer's cache: ONE buffer, `k` =
    [L, B, T, W], slot b's position p in row (b, p): the normalised latent
    c (R columns), the rotated position key k_pe every head shares (P), and
    zeros up to W (latent_row_width). No heads axis and no V: `v` is None
    (an empty place of the V tree); a head's keys and values are made of a
    row by the layer's up-projection `w_kvb` (ops/mla.py), which the view is
    given a layer (`of_layer`). The layer hands `attend` q [B, S, H, N + P]
    and, as k, the rows to cache [B, S, R + P]; v is None.

    decode         absorbed: the query takes W_UK in, the kernel
                   (ops/pallas/mla.py; XLA twin ops/mla.mla_decode_xla)
                   reads each block of rows once, as keys and as values of
                   all H heads, and the output goes through W_UV;
    attend_window  expanding: each CHUNK_BLOCK of rows up to the context the
                   chunk has is put through W_kvb once and attended by
                   heads of N + P / V: on one chip in the kernel
                   ops/pallas/mla.py: mla_chunk, a block's scores and the
                   expanded rows never leaving VMEM; its twin
                   (attend_window_xla: ops/attention.mha_extend_blocks) on
                   a CPU, under a mesh and where the tests compare. At
                   H 128 a chunk of S tokens costs S x 278.5 k operations a
                   cached row absorbed, 33.6 M + S x 81.9 k expanding: the
                   expanding form wins from 171 tokens a chunk, at every
                   context (measured: tools/mla_kernel_bench.py, PERF.md);
    self_attend    a prompt from position 0: its own rows, expanded."""
    heads: int = 0      # H
    nope: int = 0       # N: a head's key columns made of the latent
    rope: int = 0       # P: the position key's
    rank: int = 0       # R: the latent's
    vdim: int = 0       # V: a head's value columns
    w_kvb: object = None
    carried = True

    scale = property(lambda self: (self.nope + self.rope) ** -0.5)

    def of_layer(self, lp):
        return dataclasses.replace(self, w_kvb=lp["wkv_b"])

    def _expand(self, rows):
        from localai_tpu.ops import mla

        with jax.named_scope("expand"):
            return mla.expand(rows[..., :self.rank + self.rope], self.w_kvb,
                              self.heads, self.nope, self.rank)

    def self_attend(self, fn, q, k, v, lengths):
        kx, vx = (a.transpose(0, 2, 1, 3) for a in self._expand(k))
        # `fn` takes values as wide as the keys: zeros beside them
        vx = jnp.pad(vx, ((0, 0),) * 3 + ((0, kx.shape[-1] - self.vdim),))
        return fn(q, kx, vx, lengths, sliding_window=None)[..., :self.vdim]

    @jax.named_scope("cache_update")
    def write(self, k, v, rows, positions, *, unique=True, **_):
        """Rows [B, S, R + P] to (layer, rows[b], positions[b, s]); padding
        lands past the slot's length, as in DenseKV.write."""
        row = jnp.pad(k, ((0, 0), (0, 0),
                          (0, self.k.shape[-1] - k.shape[-1])))
        return dataclasses.replace(self, k=self.k.at[
            self.layer, rows[:, None], positions].set(
                row.astype(self.k.dtype), unique_indices=unique))

    def append(self, k, v, lengths, positions):
        """Decode's write; an inactive row aims at T - 1 (DenseKV.append)."""
        if self.active is not None:
            positions = jnp.where(self.active[:, None], positions,
                                  self.k.shape[-2] - 1)
        return self.write(k, v, jnp.arange(k.shape[0]), positions)

    def _absorbed(self, q, decode):
        """q [B, 1, H, N + P] through W_UK, the heads' sums of latents
        `decode` makes of it [B, H, R] through W_UV -> [B, 1, H, V]."""
        from localai_tpu.ops import mla

        with jax.named_scope("absorb"):
            q = mla.absorb(q, self.w_kvb, self.nope)[:, 0]
            q = jnp.pad(q, ((0, 0), (0, 0),
                            (0, self.k.shape[-1] - q.shape[-1])))
        o = decode(q)
        with jax.named_scope("absorb"):
            return mla.unabsorb(o[:, None], self.w_kvb, self.nope)

    def decode(self, q, lengths):
        if not _pallas_attention(current_mesh()):
            return self.decode_xla(q, lengths)
        from localai_tpu.ops.pallas.mla import mla_decode

        if self.active is not None:
            lengths = jnp.where(self.active, lengths, 0)
        return self._absorbed(q, lambda q: mla_decode(
            q, self.k, lengths, self.layer, rank=self.rank,
            scale=self.scale))

    def decode_xla(self, q, lengths):
        from localai_tpu.ops import mla

        return self._absorbed(q, lambda q: mla.mla_decode_xla(
            q, self.k[self.layer], lengths, self.rank, self.scale))

    def attend_window(self, q, positions, start, rows, gathered):
        """The chunk's queries (positions = start + 0 .. S - 1, as `extend`
        hands them) over their slots' rows, block by block up to the
        chunk's context: the kernel (ops/pallas/mla.py: mla_chunk) where
        decode takes one, else its twin, the XLA block loop."""
        if _pallas_attention(current_mesh()):
            from localai_tpu.ops.pallas.mla import mla_chunk

            with jax.named_scope("chunk_kernel"):
                return mla_chunk(
                    q, self.k, self.w_kvb, start,
                    rows if gathered else jnp.arange(q.shape[0]), self.layer,
                    rank=self.rank, nope=self.nope, scale=self.scale,
                    block=CHUNK_BLOCK)
        return self.attend_window_xla(q, positions, start, rows, gathered)

    def attend_window_xla(self, q, positions, start, rows, gathered):
        t, width = self.k.shape[-2:]
        block = min(CHUNK_BLOCK, t)

        def cut(slot, n, first):
            return jax.lax.dynamic_slice(
                self.k, (self.layer, slot, first, 0),
                (1, n, block, width))[0]

        def fetch(first):
            return self._expand(
                jax.vmap(lambda slot: cut(slot, 1, first)[0])(rows)
                if gathered else cut(0, self.k.shape[1], first))

        return mha_extend_blocks(q, fetch, self.heads, t, positions, start,
                                 block=block, scale=self.scale,
                                 v_dim=self.vdim)


def _kernel(name: str, quant: bool, sharded: bool = True):
    """ops/pallas's `name` for this cache: `name_q8` takes int8 bodies and
    scales; `name[_q8]_sharded` is the shard_map twin, per KV-head shard of a
    pool under a mesh (pallas_call has no GSPMD partitioning rule — without
    it the partitioner would all-gather the whole pool)."""
    from localai_tpu.ops import pallas

    mesh = current_mesh() if sharded else None
    fn = getattr(pallas, name + "_q8" * quant + "_sharded" * (mesh is not None))
    return fn if mesh is None else partial(fn, mesh)


@dataclasses.dataclass
class PagedKV(NoKV):
    """The block pool [L, NB, KVH, BS, D] behind a block table [B, MAXB]
    (ops/paged.py): (slot, position) resolves to (table[slot, pos // BS], :,
    pos % BS); physical block 0 is the TRASH block. One layer's pool rides
    the scan a step, as xs and ys: the Pallas kernels alias a layer's pool,
    so XLA slices it out of the stack and writes it back (moving the pool
    into the carry is a perf_opt with a paged cell — PERF.md §7.5).

    redirect [B] bool: rows flagged True write to the trash block at offset
    (row*S + s) % BLOCK instead of through their table — inactive slots in
    decode (S=1) and in the spec-verify window (S=gamma+1). Routing by
    PHYSICAL block keeps the garbage out of every real block (a slot's own
    table can map its last virtual block to a RETAINED warm-prefix block);
    the per-(row, s) offsets keep the scatter collision-free only while
    B*S <= BLOCK, so `write` drops the uniqueness assertion beyond that (the
    engine warns at init — engine._build_jit)."""
    table: object = None
    redirect: object = None
    cold: tuple = ()        # (k, v) of the tier's cold pool (TieredKV)
    sb = rw = None          # the tier's ring map (TieredKV)

    def at(self, k, v, layer=None, cold=()):
        return dataclasses.replace(self, k=k, v=v, cold=tuple(cold))

    kernels = property(lambda self: _pallas_paged_scatter(self.k.shape[-3]))

    def _resident(self, raw, rows):
        """Raw (virtual) block index -> table column."""
        return raw

    @jax.named_scope("cache_update")
    def _scatter(self, k, v, rows, positions, unique):
        kvh = self.k.shape[-3]
        raw = self._resident(positions // BLOCK, rows)
        pb = self.table[rows[:, None], raw]                # [B, S] physical
        off = positions % BLOCK
        if self.redirect is not None:
            # distinct per-(row, window-pos) trash offsets: collision-free
            # (and so assertable-unique) as long as B*S <= BLOCK
            s = positions.shape[1]
            tr_off = (rows[:, None] * s + jnp.arange(s)[None, :]) % BLOCK
            pb = jnp.where(self.redirect[:, None], 0, pb)
            off = jnp.where(self.redirect[:, None], tr_off, off)
        idx = (pb[:, None, :], jnp.arange(kvh)[None, :, None],
               off[:, None, :])
        return self._set(idx, k, v, unique)

    def write(self, k, v, rows, positions, *, unique=True, full_window=True,
              **_):
        """The XLA scatter through the table. unique (see DenseKV.write)
        also needs every position inside the slot's allocation: a window
        that is (mid prefill chunks — full_window=True) never collides; a
        FINAL chunk's padded tail resolves to shared TRASH offsets with
        different values — a genuine collision, so the assertion would be a
        lie there. A redirect gets distinct trash offsets, so it stays
        unique while B*S fits one block. Without the assertion the table-
        gathered indices are unprovably unique and the layer scan
        re-materializes the whole pool (O(pool) per call)."""
        b, s = positions.shape
        redirected = self.redirect is not None
        return self._scatter(
            k, v, rows, positions,
            unique and (full_window or redirected)
            and (not redirected or b * s <= BLOCK))

    def append(self, k, v, lengths, positions):
        """Decode's write. Pallas tier: a scatter-append DMA kernel (O(slots)
        traffic, provably in place; inactive rows go to the trash block in
        the kernel) instead of an XLA scatter through gathered physical
        indices — the scatter XLA de-optimizes into a full-pool copy inside
        the fused decode block (VERDICT Weak #2). XLA tier: decode rows
        target distinct slots and redirected rows distinct trash offsets, so
        the scatter is unique while the batch fits one block."""
        if self.kernels:
            fn = _kernel("paged_scatter_append", self.quant)
            return self._repack(fn(*self._pools(), k[:, 0], v[:, 0], lengths,
                                   self.table, self.active,
                                   sb=self.sb, rw=self.rw))
        b = k.shape[0]
        return self._scatter(k, v, jnp.arange(b), positions, b <= BLOCK)

    def _gather(self, rows=None, gathered=True):
        # reference tier: the virtual cache is materialized per layer via
        # gather (the Pallas kernels stream through the table)
        if rows is None:
            return paged_view(self.k, self.table), paged_view(self.v, self.table)
        return (paged_view(self.k, self.table[rows]),
                paged_view(self.v, self.table[rows]))


@dataclasses.dataclass
class TieredKV(PagedKV):
    """The pool under the KV lifecycle tier (engine/kvtier.py). kvt holds
    per-slot residency arrays {"sb": [B], "rw": [B], "sinks", "window", ...}:
    raw block indices are ring-mapped (ops/paged.ring_block_map) before the
    table lookup, so a windowed slot's writes reuse its O(window) ring
    columns in place. Full-policy slots carry the identity sentinel — same
    program, no recompile across policy mixes. Uniqueness survives the
    mapping: the ring's wrap period (rw*BLOCK tokens) exceeds any single
    write window by construction (kvtier.ring_blocks margins). A chunk's
    padded tail lands in ring margin columns (never the live window —
    ring_blocks reserves a full prefill chunk of margin) at positions >
    every real query, so the kv_pos <= q_pos mask hides it until real
    tokens overwrite those rows.

    With quantize_cold ("cold_tab" in kvt) the blocks that left the window
    are demoted to an int8 cold pool, not dropped: the cold pools (per-layer,
    like k/v) ride the scan as extra READ-ONLY xs — the demote copy is a
    separate host-driven jit (engine._demote_fn), so ys stays (k, v)."""
    kvt: dict | None = None

    sb = property(lambda self: self.kvt["sb"])
    rw = property(lambda self: self.kvt["rw"])
    demotes = property(lambda self: "cold_tab" in self.kvt)

    def _resident(self, raw, rows):
        per_row = raw.ndim > rows.ndim      # [B, S] blocks of [B] rows
        sb, rw = (a[rows][:, None] if per_row else a[rows]
                  for a in (self.sb, self.rw))
        return ring_block_map(raw, sb, rw)

    def prompt_attention(self, rows):
        """First-chunk self-attention under the per-slot sink+window
        retention mask. quantize_cold slots keep full causal coverage
        (exited content is demoted, not dropped), so the window term is
        lifted to a sentinel there."""
        sinks = self.kvt["sinks"][rows]
        window = self.kvt["window"][rows]
        if self.demotes:
            window = jnp.full_like(window, jnp.int32(1 << 30))
        return (lambda q, k, v, lengths, sliding_window=None:
                mha_prefill_tiered(q, k, v, lengths, sinks, window))

    def _resident_kv(self, table_rows, sb, rw, length, ctab):
        """Materialize the RESIDENT (ring-mapped) cache view: the per-slot
        table gather [B, MAXB*BS] plus explicit true positions and row
        validity, optionally concatenated with the dequantized cold tier.

        table_rows [B, MAXB]; sb/rw/length [B] (already row-indexed by the
        caller). ctab [B, MAXB_FULL] (quantize_cold): cold block per raw
        virtual block, 0 = not demoted. Demoted blocks drop out of the hot
        view (their ring column may already hold a newer generation's rows)
        and are read from the cold pool at their true positions instead.
        Returns (k [B, KVH, T, D], v, pos [B, T], ok [B, T]) — `ok` covers
        residency + freshness (+ demotion state); retention masking
        (window/sinks) is the attention caller's layer."""
        maxb = table_rows.shape[1]
        kr, vr = paged_view(self.k, table_rows), paged_view(self.v, table_rows)
        pos, ok = resident_row_positions(maxb, sb, rw, length)
        k, v = dequant(kr), dequant(vr)
        if ctab is not None:
            ck, cv = self.cold
            b = pos.shape[0]
            mb_full = ctab.shape[1]
            raw, _ = resident_block_positions(maxb, sb, rw, length)
            demoted = ctab != 0                                # [B, MAXB_FULL]
            hot_dem = jnp.take_along_axis(
                demoted, jnp.clip(raw, 0, mb_full - 1), axis=1)
            hot_dem = hot_dem & (raw >= 0) & (raw < mb_full)   # [B, MAXB]
            keep = jnp.broadcast_to(~hot_dem[:, :, None],
                                    (b, maxb, BLOCK)).reshape(b, maxb * BLOCK)
            ok = ok & keep
            ckr = paged_view(ck, ctab)
            cvr = paged_view(cv, ctab)
            posc = jnp.arange(mb_full * BLOCK, dtype=jnp.int32)[None, :]
            okc = jnp.broadcast_to(
                demoted[:, :, None],
                (b, mb_full, BLOCK)).reshape(b, mb_full * BLOCK)
            okc = okc & (posc < length[:, None])
            k = jnp.concatenate([k, dequant(ckr).astype(k.dtype)], axis=2)
            v = jnp.concatenate([v, dequant(cvr).astype(v.dtype)], axis=2)
            pos = jnp.concatenate(
                [pos, jnp.broadcast_to(posc, (b, mb_full * BLOCK))], axis=1)
            ok = jnp.concatenate([ok, okc], axis=1)
        return k, v, pos, ok

    def decode(self, q, lengths):
        # the ring-position/tier-map read rides the XLA path for now — the
        # Pallas decode kernel has no per-slot ring-geometry scalar prefetch
        # yet (the WRITE side is kernel-native: paged_scatter's targets are
        # ring-mapped before the DMA kernel). TODO(kvtier): teach
        # _decode_kernel the ring map + per-block dtype tier.
        return self.decode_xla(q, lengths)

    def decode_xla(self, q, lengths):
        """The gather covers only the RESIDENT ring view (O(sinks+window)
        rows for windowed slots, identity for full-policy slots in the same
        program) and the mask derives from true ring positions; with
        quantize_cold the exited-window blocks attend from the int8 cold
        tier instead of being dropped."""
        kvt = self.kvt
        k, v, pos, ok = self._resident_kv(
            self.table, kvt["sb"], kvt["rw"], lengths,
            kvt["cold_tab"] if self.demotes else None)
        if self.demotes:
            mask = ok  # demotion state decides hot vs cold; nothing evicted
        else:
            mask = ok & ((pos >= (lengths - kvt["window"])[:, None])
                         | (pos < kvt["sinks"][:, None]))
        return mha_decode_masked(q, k, v, mask)

    def attend_window(self, q, positions, start, rows, gathered):
        kvt = self.kvt
        kr, vr, kv_pos, kv_ok = self._resident_kv(
            self.table[rows], kvt["sb"][rows], kvt["rw"][rows],
            start + positions.shape[1],
            kvt["cold_tab"][rows] if self.demotes else None)
        return mha_extend_tiered(
            q, kr, vr, positions, kv_pos, kv_ok, kvt["sinks"][rows],
            kvt["window"][rows], drop_window=not self.demotes)


# ----------------------------------------------------- building a view

def no_mixed(cfg, what: str):
    if cfg.layer_types is not None:
        raise NotImplementedError(
            f"{what} does not take a model with window and full layers, or "
            "linear, latent or state-space ones (layer_types): it knows one "
            "cache of keys and values per layer stack")


def view(cfg, k_cache, v_cache, table=None, kvt=None, *, pool=False,
         active=None, redirect=None):
    """The view of the cache a forward was given — the one place its format
    is told from the arguments (a model with layer_types: one per place of
    the cache, cfg.cache_kinds: the period's places, then a leading layer's
    each). active [B] bool (decode): the rows decoding this step, the
    others' writes must land nowhere readable; redirect [B] bool (extend
    over a pool): rows whose whole window goes to the trash block."""
    pool = pool or table is not None or kvt is not None
    if pool or redirect is not None:
        no_mixed(cfg, "a paged, tiered or redirected cache")
    window = cfg.sliding_window
    if pool:
        if active is not None:
            # inactive rows write to the trash block — never through their
            # own table, whose last virtual block can be a RETAINED
            # warm-prefix block
            redirect = ~active
        cls, tier = PagedKV, {}
        if kvt is not None:
            cold = (kvt["cold_k"], kvt["cold_v"]) if "cold_tab" in kvt else ()
            cls, tier = TieredKV, dict(kvt=kvt, cold=cold)
        return cls(k_cache, v_cache, window, active=active, table=table,
                   redirect=redirect, **tier)
    if k_cache is None and cfg.period is None:
        return NoKV(window=window)

    def latent(k=None):
        return LatentKV(k, None, active=active, heads=cfg.num_heads,
                        nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                        rank=cfg.kv_lora_rank, vdim=cfg.v_head_dim)

    def ssm(k=None, v=None):
        return SsmKV(k, v, active=active, heads=cfg.ssm_heads,
                     groups=cfg.ssm_groups, state=cfg.ssm_state,
                     chunk_size=cfg.ssm_chunk)

    if k_cache is None:
        return tuple(StateKV(heads=cfg.linear_heads) if kind == LINEAR
                     else latent() if kind == LATENT
                     else ssm() if kind == SSM
                     else NoKV(window=window if kind == WINDOW else None)
                     for kind in cfg.cache_kinds)
    if cfg.layer_types is None:
        return DenseKV(k_cache, v_cache, window, active=active)
    full_len = max((k.shape[-2] for k, kind
                    in zip(k_cache.slots, cfg.cache_kinds) if kind == FULL),
                   default=None)

    def one(k, v, kind):
        if kind == LINEAR:
            return StateKV(k, v, active=active, heads=cfg.linear_heads)
        if kind == LATENT:
            return latent(k)
        if kind == SSM:
            return ssm(k, v)
        if kind == WINDOW:
            return RingKV(k, v, window, active=active, full_len=full_len)
        return DenseKV(k, v, None, active=active)

    return tuple(one(k, v, kind) for k, v, kind
                 in zip(k_cache.slots, v_cache.slots, cfg.cache_kinds))


def _decode_dq(q, kc, vc, lengths, sliding_window=None, table=None,
               kvt=None, ck=None, cv=None, ring=False, layer=None):
    """XLA decode attention over a (possibly quantized) cache, by keyword:
    every view's `decode_xla` behind one signature — the reference the
    Pallas kernels are tested against. Dequant is fused into the consuming
    dots by XLA; quantized caches still halve HBM capacity on this path.
    layer: kc/vc are [L, ...] stacks; ck/cv: this layer's cold pools."""
    if kvt is not None:
        cache = TieredKV(kc, vc, table=table, kvt=kvt, cold=(ck, cv))
    elif ring:
        cache = RingKV(kc, vc, sliding_window, layer)
    elif table is not None:
        cache = PagedKV(kc, vc, sliding_window, layer, table=table)
    else:
        cache = DenseKV(kc, vc, sliding_window, layer)
    return cache.decode_xla(q, lengths)
