"""A decode step over a dense cache leaves the cache where it is: the layer
scan carries the stacked cache, the new token is one scatter into the stack
at (layer, row, head, position), and the decode kernel reads its layer out
of the stack through its index maps (models/kv.DenseKV, llama._scan_layers).

- a jaxpr proof, in the manner of tests/test_paged_fast_path.py: outside a
  kernel nothing slices, gathers or puts back a layer's cache or more, and
  no scan takes or gives a cache as xs or ys;
- the one new way for the carry form to go wrong, a write aimed at the wrong
  layer: an inactive row beside live ones keeps every layer of its cache bit
  for bit over several steps, and a live row changes in every layer, at the
  positions written and nowhere else.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    FULL, WINDOW, LlamaConfig, PeriodKV, decode_step, extend, init_kv_cache,
    init_params, prefill, rope_tables,
)
from localai_tpu.ops.kvcache import QuantKV

SHAPES = {
    "one-kind": dict(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_layers=3,
        num_heads=4, num_kv_heads=2, head_dim=16, max_position=1024),
    "period": dict(
        vocab_size=96, hidden_size=32, intermediate_size=64, num_layers=8,
        num_heads=4, num_kv_heads=2, head_dim=16, max_position=1024,
        sliding_window=8, layer_types=(WINDOW, WINDOW, WINDOW, FULL) * 2),
}


def _config(shape: str, dtype="float32", **over) -> LlamaConfig:
    return LlamaConfig(**{**SHAPES[shape], "dtype": dtype, **over})


def _stacks(cache):
    """The [L', B, KVH, T, D] value arrays of a K or V cache of any form."""
    slots = cache.slots if isinstance(cache, PeriodKV) else (cache,)
    return [s.q if isinstance(s, QuantKV) else s for s in slots]


# --------------------------------------------------------- jaxpr inspection

def _moves(jaxpr, layer_elems):
    """What moves a layer's cache or more outside a kernel: (primitive,
    shape) of every value of at least `layer_elems` elements that is a
    scan's xs or ys, or that anything but a scan or a scatter produces
    (a slice, a gather, an update-slice, a copy, a reshape: all of them)."""
    hits = []

    def big(v):
        aval = getattr(v, "aval", None)
        return aval is not None and getattr(aval, "size", 0) >= layer_elems

    def visit(jx):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                continue                  # a kernel reads the stack in place
            if name == "scan":
                nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
                for v in (list(eqn.invars[nc + nk:])
                          + list(eqn.outvars[nk:])):
                    if big(v):
                        hits.append(("scan xs/ys", tuple(v.aval.shape)))
            elif not name.startswith("scatter") and name != "pjit":
                hits.extend((name, tuple(v.aval.shape))
                            for v in eqn.outvars if big(v))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else [p]):
                    sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr → Jaxpr
                    if hasattr(sub, "eqns"):
                        visit(sub)
    visit(jaxpr.jaxpr)
    return hits


CASES = {
    # name: (program, shape, cache_type, paged, does the detector fire)
    "decode-one-kind-int8": ("decode", "one-kind", "int8", False, False),
    "decode-one-kind-bf16": ("decode", "one-kind", "", False, False),
    "decode-period-int8": ("decode", "period", "int8", False, False),
    # the xs/ys form a paged decode keeps: the detector is not vacuous
    "decode-paged-xla-keeps-xs-ys": ("decode", "one-kind", "", True, True),
    # a prefill chunk and an admission, one row of the batch each
    "extend-one-kind-int8": ("extend", "one-kind", "int8", False, False),
    "extend-period-int8": ("extend", "period", "int8", False, False),
    "prefill-one-kind-bf16": ("prefill", "one-kind", "", False, False),
    "prefill-period-int8": ("prefill", "period", "int8", False, False),
}


@pytest.mark.parametrize("case", list(CASES))
def test_dense_cache_is_not_moved(case, monkeypatch):
    program, shape, cache_type, paged, fires = CASES[case]
    if paged:
        monkeypatch.setenv("LOCALAI_NO_PALLAS", "1")
        monkeypatch.delenv("LOCALAI_FORCE_PALLAS", raising=False)
    else:
        monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
        monkeypatch.delenv("LOCALAI_NO_PALLAS", raising=False)
    # a window of 200 behind a chunk of 56: rings of 256 rows
    cfg = _config(shape, "float32" if cache_type else "bfloat16",
                  **({"sliding_window": 200} if shape == "period" else {}))
    B, T, S = 4, 512, 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    cos, sin = rope_tables(cfg, T)
    table = None
    if paged:
        from localai_tpu.ops.paged import BLOCK, init_paged

        kc, vc = init_paged(cfg.num_layers, 1 + B * T // BLOCK,
                            cfg.num_kv_heads, cfg.head_dim, cfg.jdtype)
        table = jnp.zeros((B, T // BLOCK), jnp.int32)
    else:
        kc, vc = init_kv_cache(cfg, B, T, cache_type=cache_type,
                               prefill_chunk=56)
    one = jnp.array([2], jnp.int32)
    run = {
        "decode": lambda kc, vc: decode_step(
            params, cfg, jnp.ones((B,), jnp.int32),
            jnp.full((B,), 5, jnp.int32), cos, sin, kc, vc,
            jnp.ones((B,), bool), table),
        "extend": lambda kc, vc: extend(
            params, cfg, jnp.ones((1, S), jnp.int32), jnp.array([300]), cos,
            sin, kc, vc, slot_map=one, with_logits=False, full_window=True),
        "prefill": lambda kc, vc: prefill(
            params, cfg, jnp.ones((1, S), jnp.int32), jnp.array([S - 2]),
            cos, sin, kc, vc, one),
    }[program]
    jaxpr = jax.make_jaxpr(run)(kc, vc)
    # one layer of the smallest cache (a window layer's ring)
    layer_elems = min(a[0].size for a in _stacks(kc))
    # every weight is smaller, or the proof would trip over the weights
    assert max(a.size for a in jax.tree_util.tree_leaves(params)) \
        < layer_elems
    hits = _moves(jaxpr, layer_elems)
    if fires:
        assert ("scan xs/ys", tuple(kc.shape)) in hits, hits
    else:
        assert not hits, f"a dense {program} moves its cache: {hits}"


# ------------------------------------------------------- the right layer

@pytest.mark.parametrize("cache_type", ["", "int8"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_inactive_row_beside_live_rows_keeps_every_layer(shape, cache_type):
    cfg = _config(shape)
    B, T, steps, start = 3, 256, 4, jnp.array([40, 17, 9], jnp.int32)
    active = jnp.array([True, False, True])
    params = init_params(cfg, jax.random.PRNGKey(1))
    cos, sin = rope_tables(cfg, T)
    kc, vc = init_kv_cache(cfg, B, T, cache_type=cache_type,
                           prefill_chunk=8)

    seeds = itertools.count()

    def fill(a):
        r = np.random.default_rng(next(seeds))
        if a.dtype == jnp.int8:
            return jnp.asarray(r.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(r.standard_normal(a.shape), a.dtype)

    kc, vc = jax.tree_util.tree_map(fill, (kc, vc))
    before = [np.asarray(a) for c in (kc, vc) for a in _stacks(c)]
    step = jax.jit(lambda tok, n, kc, vc: decode_step(
        params, cfg, tok, n, cos, sin, kc, vc, active=active))
    lengths = start
    for i in range(steps):
        _, kc, vc = step(jnp.array([3 + i, 4, 5 + i], jnp.int32), lengths,
                         kc, vc)
        lengths = lengths + active
    after = [np.asarray(a) for c in (kc, vc) for a in _stacks(c)]
    for was, now in zip(before, after):
        size = was.shape[3]
        ring = size < T
        # a full cache takes an inactive row's write in its last row, which
        # nothing reads; a ring drops it
        keep = size if ring else size - 1
        assert np.array_equal(was[:, 1, :, :keep], now[:, 1, :, :keep])
        for row in (0, 2):
            at = (int(start[row]) + np.arange(steps)) % size
            changed = (was[:, row] != now[:, row]).any(axis=(1, 3))  # [L, T]
            assert changed[:, at].all(), "a layer missed its write"
            changed[:, at] = False
            assert not changed.any(), "a write landed beside its position"
