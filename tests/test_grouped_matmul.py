"""The grouped product kernel (ops/pallas/grouped_matmul.py) in the
interpreter: its grid ends at the tiles in use (a traced first bound), the
tiles in use equal the XLA tile loop's whatever `used` is, and what stands
in the rows past them (the kernel does not write them) reaches no token of
_grouped_experts' result."""
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import llama
from localai_tpu.models.llama import LlamaConfig, _moe_routed
from localai_tpu.ops.pallas import grouped_matmul as gm
from localai_tpu.ops.quant import quantize
from tools.moe_layer_bench import _grid

TILES, TM, K, N, HELD, LAYERS = 14, 8, 256, 384, 5, 2


def _operands(int8, seed=0):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.standard_normal((TILES, TM, K)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((LAYERS, HELD, K, N)) * K ** -0.5,
                    jnp.float32)
    # groups of 1 to 3 tiles, the last expert's to the end of the layout
    tile_e = jnp.asarray(np.minimum(np.arange(TILES) // 3, HELD - 1),
                         jnp.int32)
    if int8:
        q = quantize(w)
        return a, q["q"], q["s"], tile_e
    return a, w.astype(jnp.bfloat16), None, tile_e


def _loop(a, body, scale, tile_e, used, layer):
    """The kernel's twin, as _grouped_experts' loop has it."""
    out = np.zeros((TILES, TM, N), np.float32)
    for t in range(used):
        e = int(tile_e[t])
        y = jnp.dot(a[t], body[layer, e].astype(a.dtype),
                    preferred_element_type=jnp.float32)
        if scale is not None:
            y = y * scale[layer, e]
        out[t] = np.asarray(y.astype(a.dtype), np.float32)
    return out


@pytest.mark.parametrize("used", [0, 1, TILES // 7, TILES])
@pytest.mark.parametrize("blocks", [None, (128, 128)],
                         ids=["one block", "split blocks"])
@pytest.mark.parametrize("int8", [True, False], ids=["int8", "bfloat16"])
def test_the_tiles_in_use_are_the_xla_loops(int8, blocks, used):
    """One block of int8 is the `keep` form (the matrix converted once for
    the tiles that share it); the rows past `used` are not compared: the
    interpreter leaves NaN there, the chip what the buffer held."""
    a, body, scale, tile_e = _operands(int8)
    got = np.asarray(gm.grouped_matmul(
        a, body, scale, tile_e, jnp.int32(used), jnp.int32(1),
        blocks=blocks), np.float32)
    want = _loop(a, body, scale, tile_e, used, 1)
    assert got.shape == want.shape
    np.testing.assert_allclose(got[:used], want[:used], atol=0.02, rtol=0.02)
    # with no tile in use the kernel still multiplies one (a grid of one
    # step): finite, and nobody's
    assert np.isfinite(got[:max(used, 1)]).all()


@pytest.mark.parametrize("mosaic", [False, True],
                         ids=["the interpreter", "for the chip"])
@pytest.mark.parametrize("int8", [True, False], ids=["int8", "bfloat16"])
def test_the_grid_has_one_traced_bound_and_it_is_the_first(int8, mosaic,
                                                           monkeypatch):
    """The same call for the chip (not the interpreter: what Mosaic is
    handed) and under the interpreter: (used, output blocks, inner blocks),
    no static count of tiles anywhere in it."""
    if mosaic:
        monkeypatch.setattr(gm, "_interpret", lambda: False)
    a, body, scale, tile_e = _operands(int8)
    grid = _grid(
        lambda *ops: gm.grouped_matmul.__wrapped__(
            *ops, jnp.int32(3), jnp.int32(0), blocks=(128, 128)),
        a, body, scale, tile_e)
    assert grid == [None, N // 128, K // 128]     # None: a traced bound


def _share_layer(relu2, int8, seed=0):
    h, width, held = 32, 16, 4
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(  # noqa: E731
        rng.standard_normal(s) * s[-2] ** -0.5, jnp.float32)
    lp = {"moe_gate": w(h, 16), "moe_w1": w(held, h, width),
          "moe_w2": w(held, width, h)}
    if not relu2:
        lp["moe_w3"] = w(held, h, width)
    if int8:
        lp = {k: quantize(v) if k.startswith("moe_w") else v
              for k, v in lp.items()}
    cfg = LlamaConfig(
        vocab_size=64, hidden_size=h, intermediate_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=8, num_experts=held,
        experts_per_tok=4, moe_intermediate_size=width, router_experts=16,
        first_expert=4, expert_act="relu2" if relu2 else "silu",
        dtype="float32")
    x = jnp.asarray(rng.standard_normal((1, 96, h)), jnp.float32)
    return x, lp, cfg


@pytest.mark.parametrize("poison", [float("nan"), 1e30, -1e30, float("inf")],
                         ids=["nan", "1e30", "-1e30", "inf"])
@pytest.mark.parametrize("relu2", [False, True], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("int8", [False, True], ids=["float32", "int8"])
def test_what_stands_past_the_tiles_in_use_reaches_no_token(
        int8, relu2, poison, monkeypatch):
    """A share of 4 of 16 experts: about a quarter of the static tiles are
    in use. Every product's rows past `used` filled with zeros (what the
    kernel wrote before PR 53), then with NaN, inf and +-1e30 (what a buffer
    nobody wrote may hold): the layer's result is the same to the bit."""
    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    x, lp, cfg = _share_layer(relu2, int8)
    kernel = gm.grouped_matmul
    seen = []

    def filled(value):
        def product(a, body, scale, tile_e, used, layer):
            out = kernel(a, body, scale, tile_e, used, layer)
            past = jnp.arange(out.shape[0])[:, None, None] >= used
            seen.append((int(used), out.shape[0]))
            return jnp.where(past, jnp.asarray(value, out.dtype), out)
        return product

    monkeypatch.setattr(gm, "grouped_matmul", filled(0.0))
    want = np.asarray(_moe_routed(x, lp, cfg))
    assert seen and all(0 < used < tiles // 2 for used, tiles in seen)
    monkeypatch.setattr(gm, "grouped_matmul", filled(poison))
    got = np.asarray(_moe_routed(x, lp, cfg))
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got, want)
    # and as the kernel leaves them (the interpreter: NaN)
    monkeypatch.setattr(gm, "grouped_matmul", kernel)
    np.testing.assert_array_equal(np.asarray(_moe_routed(x, lp, cfg)), want)
    assert llama.expert_form(cfg, 96) == llama.ROUTED
