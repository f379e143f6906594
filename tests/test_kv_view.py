"""The KV cache's views (models/kv.py), one format at a time: K/V written
through a view at known positions — a prompt with padding, full chunks, a
final chunk with a padded tail, decode appends with an inactive row — then
the view's decode read against a float32 attention over exactly the values
written (for int8, the values the quantizer kept). What must not land (an
inactive row's append, padding in a ring) is not in the reference, so a
write that lands where it is readable fails the comparison; a stack's other
layer must stay untouched."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import kv
from localai_tpu.models.llama import (
    FULL, WINDOW, LlamaConfig, init_kv_cache,
)
from localai_tpu.ops.kvcache import quantize_tokens
from localai_tpu.ops.paged import BLOCK, init_paged

B, H, KVH, D, L, LAYER = 3, 4, 2, 16, 2, 1
T, CHUNK, W = 1024, 128, 64
BASE = dict(vocab_size=32, hidden_size=H * D, intermediate_size=32,
            num_layers=L, num_heads=H, num_kv_heads=KVH, head_dim=D,
            max_position=T, dtype="float32")
ONE = LlamaConfig(**BASE)
WIN = LlamaConfig(**BASE, sliding_window=W)
MIXED = LlamaConfig(**{**BASE, "num_layers": 2 * L}, sliding_window=W,
                    layer_types=(WINDOW, FULL) * L)


def _pool(ctype, maxb):
    k, v = init_paged(L, 1 + B * maxb, KVH, D, jnp.float32, ctype)
    table = np.random.default_rng(3).permutation(B * maxb) + 1
    return k, v, jnp.asarray(table.reshape(B, maxb), jnp.int32)


def _tier(sb, rw, sinks, window):
    return {k: jnp.full((B,), x, jnp.int32) for k, x in
            dict(sb=sb, rw=rw, sinks=sinks, window=window).items()}


# name -> (cfg, cache type, build() -> (k, v, table, kvt), retention)
# retention (sinks, window): which positions a query at length n may see
FORMATS = {
    "dense": (ONE, "", None, (0, T)),
    "dense-int8": (ONE, "int8", None, (0, T)),
    "dense-window": (WIN, "", None, (0, W)),
    "ring": (MIXED, "", None, (0, W)),
    "ring-int8": (MIXED, "int8", None, (0, W)),
    "paged": (ONE, "", lambda c: (*_pool(c, T // BLOCK), None), (0, T)),
    "paged-int8": (ONE, "int8", lambda c: (*_pool(c, T // BLOCK), None),
                   (0, T)),
    # full policy: the identity sentinel sb >= table width
    "tiered-full": (ONE, "", lambda c: (
        *_pool(c, T // BLOCK), _tier(T // BLOCK, 1, T, T)), (0, T)),
    # sink_window(sinks=128, window=128): one sink block and a ring of
    # kvtier.ring_blocks(128, CHUNK) = 4 columns, which 641 tokens wrap
    "tiered-sink_window": (ONE, "", lambda c: (
        *_pool(c, 5), _tier(1, 4, 128, 128)), (128, 128)),
}


class _Written:
    """What each slot holds, position by position, as float32."""

    def __init__(self, quant):
        self.quant, self.at = quant, [dict() for _ in range(B)]

    def put(self, slot, pos, k, v):
        if self.quant:
            k, v = (np.asarray(q, np.float32) * np.asarray(s)[..., None]
                    for q, s in (quantize_tokens(k), quantize_tokens(v)))
        self.at[slot][int(pos)] = (np.asarray(k, np.float32),
                                   np.asarray(v, np.float32))

    def attend(self, q, lengths, sinks, window):
        out = np.zeros((B, 1, H, D), np.float32)
        for b, n in enumerate(np.asarray(lengths)):
            seen = [p for p in range(n) if p >= n - window or p < sinks]
            k = np.stack([self.at[b][p][0] for p in seen])     # [T, KVH, D]
            v = np.stack([self.at[b][p][1] for p in seen])
            qg = np.asarray(q[b, 0], np.float32).reshape(KVH, H // KVH, D)
            logits = np.einsum("kgd,tkd->kgt", qg, k) * D ** -0.5
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[b, 0] = np.einsum("kgt,tkd->kgd", p, v).reshape(H, D)
        return out


@pytest.mark.parametrize("pallas", [False, True], ids=["xla", "pallas"])
@pytest.mark.parametrize("name", FORMATS)
def test_view_writes_and_decode_read(name, pallas, monkeypatch):
    cfg, ctype, pool, (sinks, window) = FORMATS[name]
    if pallas:
        monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    monkeypatch.delenv("LOCALAI_NO_PALLAS", raising=False)
    if pool is None:
        k, v = init_kv_cache(cfg, B, T, jnp.float32, ctype, CHUNK)
        table = kvt = None
    else:
        k, v, table, kvt = pool(ctype)
    place = lambda c: c.slots[0] if cfg.layer_types else c  # noqa: E731
    layer = lambda a, i: jax.tree_util.tree_map(lambda x: x[i], a)  # noqa
    untouched = jax.tree_util.tree_map(np.asarray,
                                       layer((place(k), place(v)), 0))
    held = _Written(bool(ctype))
    rng = np.random.default_rng(0)
    state = {"k": k, "v": v, "n": np.zeros(B, np.int64)}

    def view(active=None):
        """The view a forward would build, at layer LAYER (of a model with
        layer_types: its WINDOW place, the first of the period)."""
        cache = kv.view(cfg, state["k"], state["v"], table, kvt,
                        active=active)
        cache = cache[0] if cfg.layer_types else cache
        if cache.carried:
            return cache.at(cache.k, cache.v, LAYER)
        return cache.at(layer(cache.k, LAYER), layer(cache.v, LAYER))

    def keep(cache):
        for key, new in (("k", cache.k), ("v", cache.v)):
            if not cache.carried:
                new = jax.tree_util.tree_map(
                    lambda a, n: a.at[LAYER].set(n), state[key], new)
            if cfg.layer_types:
                new = kv.PeriodKV((new, *state[key].slots[1:]))
            state[key] = new

    def fresh(s):
        return (jnp.asarray(rng.standard_normal((B, s, KVH, D)), jnp.float32),
                jnp.asarray(rng.standard_normal((B, s, KVH, D)), jnp.float32))

    def window_write(s, real, **kw):
        """Write [B, s] at each slot's length; row b's first real[b] entries
        are its tokens, the rest padding."""
        kn, vn = fresh(s)
        rows = jnp.asarray([2, 0, 1]) if "end" in kw else jnp.arange(B)
        start = state["n"][np.asarray(rows)]
        positions = jnp.asarray(start[:, None] + np.arange(s)[None, :])
        keep(view().write(kn, vn, rows, positions, **kw))
        for i, slot in enumerate(np.asarray(rows)):
            for j in range(real[i]):
                held.put(slot, start[i] + j, kn[i, j], vn[i, j])
            state["n"][slot] += real[i]

    def check():
        q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
        lengths = jnp.asarray(state["n"], jnp.int32)
        got = view().decode(q, lengths)
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            held.attend(q, state["n"], sinks, window),
            rtol=0, atol=3e-2 if ctype else 2e-5)

    # a prompt a slot, padded, through a permuted slot map (rows may repeat
    # in admission, so the write is not asserted unique). Longer than a ring:
    # only its tail may land, and row 2's padding would wrap onto its tokens
    real = [300, 3 * CHUNK, 37]
    window_write(3 * CHUNK, real, end=jnp.asarray(real), unique=False)
    check()
    for _ in range(3):          # full chunks: past a tier's wrap
        window_write(CHUNK, [CHUNK] * B, full_window=True)
    check()
    # a final chunk: 16 entries, the tail after `last` is padding
    last = [5, 0, 9]
    window_write(16, [n + 1 for n in last], last=jnp.asarray(last))
    check()
    # decode appends; an inactive row's write must land nowhere readable,
    # wherever its stale length points (here: at a token it holds)
    for active in ([True, False, True], [True, True, True], None):
        kn, vn = fresh(1)
        act = None if active is None else jnp.asarray(active)
        lengths = jnp.asarray(state["n"], jnp.int32)
        if act is not None:
            lengths = jnp.where(act, lengths, lengths - 2)
        keep(view(act).append(kn, vn, lengths, lengths[:, None]))
        for b in range(B):
            if active is None or active[b]:
                held.put(b, state["n"][b], kn[b, 0], vn[b, 0])
                state["n"][b] += 1
        check()
    assert state["n"].max() > 640       # the wraps were really crossed
    for was, now in zip(jax.tree_util.tree_leaves(untouched),
                        jax.tree_util.tree_leaves(layer(
                            (place(state["k"]), place(state["v"])), 0))):
        np.testing.assert_array_equal(was, now)


def test_view_refuses_a_pool_under_two_kinds_of_layer():
    with pytest.raises(NotImplementedError, match="window and full layers"):
        kv.view(MIXED, None, None, table=jnp.zeros((B, 2), jnp.int32))
    with pytest.raises(NotImplementedError, match="window and full layers"):
        kv.view(MIXED, None, None, pool=True)
