import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models.llama import (
    LlamaConfig, init_params, init_kv_cache, prefill, decode_step, extend,
    forward_train, param_specs,
)
from localai_tpu.ops.rope import rope_table

TINY = LlamaConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16, max_position=128,
    dtype="float32",
)


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(TINY, jax.random.PRNGKey(0))


def test_forward_train_shape(tiny_params):
    tokens = jnp.arange(12).reshape(2, 6) % TINY.vocab_size
    logits = forward_train(tiny_params, TINY, tokens)
    assert logits.shape == (2, 6, TINY.vocab_size)
    assert jnp.isfinite(logits).all()


def test_prefill_decode_matches_forward(tiny_params):
    """Greedy decode via cache must match argmax of the full forward pass."""
    cfg = TINY
    B, S, T = 2, 5, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    lengths = jnp.array([S, 3], jnp.int32)
    cos, sin = rope_table(cfg.rope, T)
    kc, vc = init_kv_cache(cfg, 4, T)
    slot_map = jnp.array([0, 2], jnp.int32)

    logits, kc, vc = prefill(tiny_params, cfg, tokens, lengths, cos, sin, kc, vc, slot_map)
    assert logits.shape == (B, cfg.vocab_size)

    # row 0: compare against full forward on the same sequence
    full = forward_train(tiny_params, cfg, tokens[:1])
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full[0, S - 1]), rtol=2e-4, atol=2e-4
    )

    # decode one step for slot 0 and slot 2; compare with forward on seq+tok
    next_tok = jnp.argmax(logits, -1).astype(jnp.int32)
    slot_tokens = jnp.zeros((4,), jnp.int32).at[slot_map].set(next_tok)
    slot_lengths = jnp.zeros((4,), jnp.int32).at[slot_map].set(lengths)
    dlogits, kc, vc = decode_step(tiny_params, cfg, slot_tokens, slot_lengths,
                                  cos, sin, kc, vc)
    seq = jnp.concatenate([tokens[:1], next_tok[:1][None]], axis=1)
    full2 = forward_train(tiny_params, cfg, seq)
    np.testing.assert_allclose(
        np.asarray(dlogits[0]), np.asarray(full2[0, S]), rtol=2e-4, atol=2e-4
    )


def test_extend_matches_decode_chain(tiny_params):
    """extend() over a window == sequential decode_step calls."""
    cfg, T = TINY, 64
    cos, sin = rope_table(cfg.rope, T)
    prompt = jnp.array([[3, 14, 15, 9, 2]], jnp.int32)
    n = prompt.shape[1]

    kc, vc = init_kv_cache(cfg, 1, T)
    _, kc, vc = prefill(tiny_params, cfg, prompt, jnp.array([n]), cos, sin,
                        kc, vc, jnp.array([0]))
    window = jnp.array([[7, 21, 4]], jnp.int32)
    elogits, _, _ = extend(tiny_params, cfg, window, jnp.array([n]),
                           cos, sin, kc, vc)

    # sequential reference
    seq_logits = []
    for i in range(3):
        dl, kc, vc = decode_step(tiny_params, cfg, window[:, i],
                                 jnp.array([n + i]), cos, sin, kc, vc)
        seq_logits.append(np.asarray(dl[0]))
    np.testing.assert_allclose(np.asarray(elogits[0]), np.stack(seq_logits),
                               rtol=2e-4, atol=2e-4)


def test_param_specs_tree_matches_params(tiny_params):
    specs = param_specs(TINY)
    flat_p = jax.tree_util.tree_structure(tiny_params)
    flat_s = jax.tree_util.tree_structure(specs)
    assert flat_p == flat_s


def test_gqa_and_bias_variant():
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_layers=1, num_heads=4, num_kv_heads=1, head_dim=8,
                      qkv_bias=True, tie_embeddings=True, dtype="float32")
    p = init_params(cfg, jax.random.PRNGKey(2))
    assert "lm_head" not in p and "bq" in p["layers"]
    logits = forward_train(p, cfg, jnp.zeros((1, 4), jnp.int32))
    assert logits.shape == (1, 4, 64)
