"""The kernels of Trinity's cell, compiled at their real sizes for a v5e that
is described and not attached (the TPU's compiler is installed here; nothing
runs): what the interpreter cannot say, whether Mosaic takes the shapes. The
decode attention kernel at 48 query heads over 8 KV heads (a group of 6, the
first that is no power of two) over a ring of 4096 + 512 tokens and over a
16384-token cache, int8; the routed expert layer at 3072 x 3072, 32 held of
256, top-4, for a chunk and for a decode step's rows; a chunk's attention
over the long-document cell's cache row (XLA: what it holds). The topology is
described inside a fixture (one process at a time may load the TPU's
library, and a worker imports every test file). PR 42: Nemotron-3-Super's
cell: the same kernels at a group of 16 over 2 KV heads, the state-space
decode kernel, the latent relu^2 share."""
import os

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels as a TPU backend would trace them: not the interpreter."""
    # every kernel module, imported BEFORE the patch: one first imported
    # under it would bind the patched `_interpret` for the process's life
    from localai_tpu.ops.pallas import (  # noqa: F401
        flash_attention, grouped_matmul, kda, mla, ssd)

    monkeypatch.setenv("LOCALAI_FORCE_PALLAS", "1")
    for mod in (flash_attention, grouped_matmul):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(fn, *shapes):
    with jax.default_matmul_precision(None):    # conftest asks for float32
        return jax.jit(fn).lower(*shapes).compile()


@pytest.mark.parametrize("t,kw", [
    (16384, {}), (4608, dict(sliding_window=4096, ring=True))],
    ids=["a full layer's cache", "a window layer's ring"])
def test_decode_kernel_at_a_group_of_six(one_chip, mosaic, t, kw):
    from localai_tpu.ops.pallas import ragged_decode_q8

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    b, kvh, group, d, layers = 32, 8, 6, 128, 4
    body = shape((layers, b, kvh, t, d), jnp.int8)
    scale = shape((layers, b, kvh, t // 128, 128), jnp.float32)
    out = _compile(
        lambda q, kq, ks, vq, vs, lengths, layer: ragged_decode_q8(
            q, kq, ks, vq, vs, lengths, layer=layer, **kw),
        shape((b, 1, kvh * group, d), jnp.bfloat16), body, scale, body,
        scale, shape((b,), jnp.int32), shape((), jnp.int32))
    assert out.output_shardings is not None


def test_decode_and_prompt_kernels_at_a_group_of_sixteen(one_chip, mosaic):
    """Nemotron-3-Super's attention layers (PR 42): 32 query heads over 2 KV
    heads, the widest group served; the int8 decode kernel over the cell's
    12288-token cache with its scales at 2 heads, and a 512-token prompt's
    flash attention."""
    from localai_tpu.ops.pallas import flash_prefill, ragged_decode_q8

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    b, kvh, group, d, layers, t = 32, 2, 16, 128, 2, 12288
    body = shape((layers, b, kvh, t, d), jnp.int8)
    scale = shape((layers, b, kvh, t // 128, 128), jnp.float32)
    out = _compile(
        lambda q, kq, ks, vq, vs, lengths, layer: ragged_decode_q8(
            q, kq, ks, vq, vs, lengths, layer=layer),
        shape((b, 1, kvh * group, d), jnp.bfloat16), body, scale, body,
        scale, shape((b,), jnp.int32), shape((), jnp.int32))
    assert out.output_shardings is not None
    out = _compile(
        flash_prefill, shape((8, 512, kvh * group, d), jnp.bfloat16),
        shape((8, 512, kvh, d), jnp.bfloat16),
        shape((8, 512, kvh, d), jnp.bfloat16), shape((8,), jnp.int32))
    assert out.output_shardings is not None


def test_state_space_decode_kernel_at_the_cells_shape(one_chip, mosaic,
                                                      monkeypatch):
    """ssd_decode at 32 rows of 128 heads x 64 x 128 float32 in a stack of 2
    layers: Mosaic takes the blocks (32 heads: 1 MiB of state in, 1 MiB
    out), and the state is updated in place when the caller gives it away."""
    from localai_tpu.ops.pallas import ssd

    monkeypatch.setattr(ssd, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    b, h, p, n, g, layers = 32, 128, 64, 128, 8, 2
    with jax.default_matmul_precision(None):
        out = jax.jit(ssd.ssd_decode.__wrapped__, donate_argnums=(5,)).lower(
            shape((b, h, p)), shape((b, h)), shape((h,)), shape((b, g, n)),
            shape((b, g, n)), shape((layers, b, h, p, n)),
            shape((), jnp.int32), shape((b,), jnp.bool_)).compile()
    text = out.as_text()
    assert "tpu_custom_call" in text
    # no copy of the 268 MB stack beside the kernel
    assert out.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("rows", [(1, 512), (32, 1)],
                         ids=["a chunk", "a decode step"])
def test_latent_relu2_share_at_the_cells_widths(one_chip, mosaic, rows):
    """Nemotron-3-Super's expert layer (PR 42): 128 held of a router 512
    wide, top-22, relu^2 experts of 2688 in a latent of 1024, a relu^2
    shared expert of 5376 over 4096."""
    from localai_tpu.models.llama import LlamaConfig, _InStack, _moe_routed

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def int8(dims):
        return {"q": shape(dims, jnp.int8),
                "s": shape(dims[:-2] + (1, dims[-1]), jnp.float32)}

    h, lat, wide, shared, held, routers = 4096, 1024, 2688, 5376, 128, 512
    cfg = LlamaConfig(hidden_size=h, num_experts=held, experts_per_tok=22,
                      moe_intermediate_size=wide, router_experts=routers,
                      shared_expert_width=shared, routed_scale=5.0,
                      router_sigmoid=True, router_bias=True,
                      expert_act="relu2", moe_latent=lat)
    rest = {"moe_gate": shape((h, routers), jnp.float32),
            "moe_bias": shape((routers,), jnp.float32),
            "w_lat_in": int8((h, lat)), "w_lat_out": int8((lat, h)),
            "ws_up": int8((h, shared)), "ws_down": int8((shared, h))}
    stacks = {"moe_w1": int8((2, held, lat, wide)),
              "moe_w2": int8((2, held, wide, lat))}
    out = _compile(
        lambda x, rest, stacks: _moe_routed(
            x, {**rest, **{n: _InStack(w, 1) for n, w in stacks.items()}},
            cfg),
        shape((*rows, h), jnp.bfloat16), rest, stacks)
    assert out.output_shardings is not None
    # the way into the tiles and back holds no [tokens, k, rows] array
    assert out.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("rows", [(1, 512), (32, 1)],
                         ids=["a chunk", "a decode step"])
def test_routed_share_at_the_cells_widths(one_chip, mosaic, rows):
    from localai_tpu.models.llama import LlamaConfig, _InStack, _moe_routed

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def int8(dims):
        return {"q": shape(dims, jnp.int8),
                "s": shape(dims[:-2] + (1, dims[-1]), jnp.float32)}

    h, held, routers = 3072, 32, 256
    cfg = LlamaConfig(hidden_size=h, num_experts=held, experts_per_tok=4,
                      moe_intermediate_size=h, router_experts=routers,
                      shared_expert_width=h, routed_scale=2.448,
                      router_sigmoid=True, router_bias=True)
    rest = {"moe_gate": shape((h, routers), jnp.float32),
            "moe_bias": shape((routers,), jnp.float32),
            "ws_gate": int8((h, h)), "ws_up": int8((h, h)),
            "ws_down": int8((h, h))}
    stacks = {n: int8((4, held, h, h)) for n in ("moe_w1", "moe_w2",
                                                  "moe_w3")}
    out = _compile(
        lambda x, rest, stacks: _moe_routed(
            x, {**rest, **{n: _InStack(w, 1) for n, w in stacks.items()}},
            cfg),
        shape((*rows, h), jnp.bfloat16), rest, stacks)
    assert out.output_shardings is not None


@pytest.mark.parametrize("tiles,tm,k,n,held", [
    (192, 128, 2304, 896, 64), (127, 64, 896, 2304, 64),
    (479, 128, 1024, 2688, 128), (271, 16, 7680, 2048, 16),
    (294, 16, 1280, 4096, 40), (16, 128, 4096, 14336, 8)],
    ids=["Mellum2 up at [4, 512] (one block, kept)", "Mellum2 down",
         "Nemotron up at [8, 256] (one block, kept)", "openPangu up",
         "Solar-Open2 down", "Mixtral up"])
def test_grouped_product_with_a_traced_grid_bound(one_chip, mosaic, tiles,
                                                  tm, k, n, held):
    """PR 53: the grid's first bound is the traced count of tiles in use;
    Mosaic takes it with the scalar prefetch, the accumulator and, where
    the int8 matrix is one block, the scratch it is converted into once."""
    from localai_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    out = _compile(
        grouped_matmul.__wrapped__, shape((tiles, tm, k), jnp.bfloat16),
        shape((2, held, k, n), jnp.int8),
        shape((2, held, 1, n), jnp.float32), shape((tiles,), jnp.int32),
        shape((), jnp.int32), shape((), jnp.int32))
    assert "tpu_custom_call" in out.as_text()


def test_a_chunks_attention_holds_no_score_array_of_the_whole_row(one_chip):
    """The long-document cell's chunk (512 queries of 64 heads over 8 KV
    heads, one gathered row of an int8 stack of 32 x 16384) compiled as it
    is served: the blockwise loop's temporaries are a few blocks' worth,
    where the whole-row form holds the scores [8, 8, 512, 16384] (1.1 GB
    in bfloat16) and a dequantised row."""
    from localai_tpu.models import kv
    from localai_tpu.ops.kvcache import QuantKV

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    layers, b, kvh, group, t, d, s = 2, 32, 8, 8, 16384, 128, 512
    stack = QuantKV(shape((layers, b, kvh, t, d), jnp.int8),
                    shape((layers, b, kvh, t // 128, 128), jnp.float32))

    def attend(form):
        def call(q, k, v, start, rows, layer):
            view = kv.DenseKV(k, v, None, layer=layer)
            positions = start[:, None] + jnp.arange(s)[None, :]
            return form(view, q, positions, start, rows, True)

        return _compile(
            call, shape((1, s, kvh * group, d), jnp.bfloat16), stack, stack,
            shape((1,), jnp.int32), shape((1,), jnp.int32),
            shape((), jnp.int32)).memory_analysis().temp_size_in_bytes

    scores = kvh * group * s * t * 2        # as XLA keeps them: bfloat16
    assert attend(kv.NoKV.attend_window) > scores
    served = attend(kv.DenseKV.attend_window)
    assert served < scores // 16, served


def test_a_latent_chunks_kernel_at_the_cells_shape(one_chip, mosaic,
                                                   monkeypatch):
    """openPangu's chunk (PR 46): 512 queries of 128 heads of 192 / 128 over
    one gathered row of a bfloat16 latent stack of 32 x 12288 x 640 (rank
    512 + 64), int8 W_kvb, as kv.LatentKV.attend_window serves it on one
    chip: Mosaic takes mla_chunk's blocks under the VMEM it asks for, and
    the program holds no expanded rows and no scores beside the kernel (the
    XLA loop's block: 134 MB of float32 scores and probabilities)."""
    from localai_tpu.models import kv
    from localai_tpu.ops.pallas import mla

    monkeypatch.setattr(mla, "_interpret", lambda: False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    layers, b, t, s, h, r, p, n, v = 2, 32, 12288, 512, 128, 512, 64, 128, 128
    assert mla.mla_chunk_vmem_bytes(512, s, mla._CHUNK_HEADS, 640, r, n, v,
                                    True) < 64 << 20

    def call(q, cache, wq, ws, start, rows, layer):
        view = kv.LatentKV(cache, None, layer=layer, heads=h, nope=n, rope=p,
                           rank=r, vdim=v, w_kvb={"q": wq, "s": ws})
        positions = start[:, None] + jnp.arange(s)[None, :]
        return view.attend_window(q, positions, start, rows, True)

    out = _compile(
        call, shape((1, s, h, n + p), jnp.bfloat16),
        shape((layers, b, t, kv.latent_row_width(r, p)), jnp.bfloat16),
        shape((r, h * (n + v)), jnp.int8), shape((1, h * (n + v)),
                                                 jnp.float32),
        shape((1,), jnp.int32), shape((1,), jnp.int32), shape((), jnp.int32))
    assert "tpu_custom_call" in out.as_text()
    assert out.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows,tokens", [(1, 512), (4, 512)],
                         ids=["a chunk", "an admission group"])
def test_the_state_chunks_kernel_at_the_cells_shape(one_chip, mosaic,
                                                    monkeypatch, rows,
                                                    tokens):
    """Solar-Open2's chunk (PR 51): 512 tokens of 64 heads x 128 x 128
    through kda_chunk as kv.StateKV._mix serves it on one chip (the state a
    value in and a value out): Mosaic takes the blocks (a pair of heads of the
    whole chunk) under the VMEM it asks for, the arrays are read as they lie (the
    program holds no copy of one beside the kernel), and nothing is
    aliased."""
    from localai_tpu.ops.pallas import kda

    monkeypatch.setattr(kda, "_interpret", lambda: False)

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    h, d = 64, 128
    assert kda.kda_chunk_vmem_bytes(tokens, kda.CHUNK_HEADS, d, d) < 64 << 20
    # [B, S, H D]: as the projections' products and the convolution leave
    # them, seen as [B, S, H, D]
    wide = shape((rows, tokens, h * d))
    heads = lambda a: a.reshape(rows, tokens, h, d)  # noqa: E731
    out = _compile(
        lambda q, k, v, g, beta, state, n: kda.kda_chunk(
            heads(q), heads(k), heads(v), heads(g), beta, state, n_valid=n,
            unit_qk=True)[0].reshape(rows, tokens, h * d),
        wide, wide, wide, wide, shape((rows, tokens, h)),
        shape((rows, h, d, d)), shape((rows,), jnp.int32))
    text = out.as_text()
    assert "tpu_custom_call" in text
    assert "output_to_operand_aliasing" not in text
    # the new state, which this program drops: no 17 MB a row of a copy
    assert out.memory_analysis().temp_size_in_bytes < rows * (8 << 20)
