"""The programs the engine serves the OLDER models with are the ones it
served them with before Trinity (PR 35): a one-kind model with experts
(Mixtral), window and full layers with a RoPE each (Mellum2), linear layers
beside gated NoPE layers with a share of the experts (Solar-Open2), each
loaded from its config.json's keys with synthetic int8 weights and an int8
cache, as the benchmark's cells serve them. For batched admission
(`_admit_many`), a middle prefill chunk (`extend`), the single decode step
and the fused decode loop, the jaxpr the engine's own call traces is hashed
and compared with the hash the same code gave on the parent commit (8aabf74;
`python tests/test_served_programs_unchanged.py` prints the table, run with
PYTHONPATH at a checkout). A kernel's compile-cache key, and with it the old
cells' `setup_s`, rides on these programs: a change that has to move one
replaces its hash here and says so in PERF.md. PR 36 moved the six `extend`
hashes on purpose (a chunk's attention visits the blocks its context fills,
kv.DenseKV.attend_window); the other eighteen are 8aabf74's still.

The hashes are of this container's JAX (0.9.0); another version prints
other jaxprs, and the table is then made again on both commits.

PR 46: the three newer models beside them (NEWER: a small Trinity, a small
Nemotron-3-Super, a small openPangu-Ultra-MoE, each with a share of its
experts as its cell serves it; openPangu's cache bfloat16, the one a latent
layer has), hashed the same way on PR 46's parent (f4aa4f7) and held to it,
but ONE: openPangu's `extend` with the kernels on, which PR 46 moved on
purpose (a chunk's attention over latents in ops/pallas/mla.py: mla_chunk;
PARENT_OF_46 keeps the parent's hash beside it). On the XLA twin that
program is the parent's too.

PR 51: Solar-Open2's `_admit_many` and `extend` with the kernels on moved on
purpose (a prompt's and a chunk's gated delta rule in ops/pallas/kda.py:
kda_chunk, which kv.StateKV._mix takes where `step` takes kda_decode;
PARENT_OF_51 keeps the parent's two hashes). Its two decode programs, its
four programs on the XLA twin, and all forty of the other five models are
the parent's still.

PR 53: every program with the kernels on that holds the routed expert layer
moved on purpose (ops/pallas/grouped_matmul.py: the grid's first bound is
the traced count of tiles in use, and the kernel lost its `live` predicates):
`_admit_many` and `extend` of all six models, and the two decode programs of
the four that hold a share of their experts (Mixtral's and Mellum2's decode
steps take the dense form). PARENT_OF_53 keeps the parent's twenty hashes
(d05db0e); the four decode programs that hold no such product and all
twenty-four programs on the XLA twin are the parent's still.
"""
import hashlib
import json
import os
import re

import numpy as np
import pytest

MODELS = {
    "mixtral": dict(
        vocab_size=96, hidden_size=64, intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=1024, rms_norm_eps=1e-5, rope_theta=1e6,
        num_local_experts=4, num_experts_per_tok=2, sliding_window=None,
        architectures=["MixtralForCausalLM"], tie_word_embeddings=False),
    "mellum2": dict(
        vocab_size=96, hidden_size=64, intermediate_size=48,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=1024, rms_norm_eps=1e-6,
        sliding_window=8, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, norm_topk_prob=True,
        layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
        mlp_layer_types=["sparse"] * 8,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 16, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        model_type="mellum", tie_word_embeddings=False),
    "solar-open2": dict(
        model_type="solar_open2", hidden_size=64, num_hidden_layers=8,
        num_attention_heads=4, head_dim=16, num_key_value_heads=2,
        vocab_size=128, intermediate_size=128, moe_intermediate_size=32,
        rms_norm_eps=1e-5, max_position_embeddings=4096,
        # (a state of 128 x 128 a head: the least the decode kernel tiles)
        linear_attn_config=dict(short_conv_kernel_size=4, head_dim=128,
                                num_heads=4, num_kv_heads=None),
        gqa_layers=[0, 4], use_rope=False, use_gqa_gate=True,
        kda_use_full_proj=False, kda_allow_neg_eigval=True,
        n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=1, num_experts_per_tok=4,
        first_k_dense_replace=0, tie_word_embeddings=False,
        localai_expert_share=dict(router_experts=16, first_expert=4)),
}
NEWER = {
    "trinity": dict(
        model_type="afmoe", vocab_size=96, hidden_size=48,
        intermediate_size=64, moe_intermediate_size=24,
        num_hidden_layers=10, num_attention_heads=6, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=1024, rms_norm_eps=1e-5,
        rope_theta=10000, rope_scaling=None, sliding_window=8,
        layer_types=["sliding_attention", "full_attention"]
        + (["sliding_attention"] * 3 + ["full_attention"]) * 2,
        num_dense_layers=2, num_experts=8, num_experts_per_tok=2,
        num_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.448, n_group=1, topk_group=1, num_expert_groups=1,
        num_limited_groups=1, mup_enabled=True, tie_word_embeddings=False,
        localai_expert_share=dict(router_experts=16, first_expert=4)),
    "nemotron3": dict(
        model_type="nemotron_h", vocab_size=96, hidden_size=48,
        intermediate_size=24, moe_intermediate_size=24,
        num_hidden_layers=14, hybrid_override_pattern="*EMEMEM*EMEMEM",
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        # (a state of N 128: the least the decode kernel tiles)
        ssm_state_size=128,
        conv_kernel=4, chunk_size=8, expand=2, max_position_embeddings=1024,
        norm_eps=1e-5, layer_norm_epsilon=1e-5, n_routed_experts=8,
        num_experts_per_tok=5, n_shared_experts=1,
        moe_shared_expert_intermediate_size=40, moe_latent_size=32,
        norm_topk_prob=True, n_group=1, topk_group=1,
        routed_scaling_factor=5.0, mlp_hidden_act="relu2",
        mamba_hidden_act="silu", use_conv_bias=True, mamba_proj_bias=False,
        use_bias=False, mlp_bias=False, attention_bias=False,
        num_nextn_predict_layers=0, rope_theta=10000,
        partial_rotary_factor=1, time_step_min=0.001, time_step_max=0.1,
        tie_word_embeddings=False,
        localai_expert_share=dict(router_experts=16, first_expert=4)),
    "openpangu": dict(
        model_type="pangu_ultra_moe", vocab_size=96, hidden_size=48,
        intermediate_size=64, moe_intermediate_size=24, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
        q_lora_rank=40, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, max_position_embeddings=1024, rms_norm_eps=1e-5,
        rope_theta=25600000, first_k_dense_replace=2, n_routed_experts=8,
        num_experts_per_tok=2, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, sandwich_norm=True,
        num_nextn_predict_layers=1, attention_bias=False, hidden_act="silu",
        tie_word_embeddings=False,
        localai_expert_share=dict(router_experts=16, first_expert=4)),
}
MODELS.update(NEWER)
# a latent layer's cache is bfloat16 (an int8 one is refused by name)
CACHE_TYPE = {"openpangu": ""}
PROGRAMS = ("_admit_many_fn", "_extend_mid_fn", "_decode_nomask_fn",
            "_decode_loop_fn")
# model -> kernels -> program -> sha256 of its jaxpr, on the parent commit
PARENT = {
    "mixtral": {
        "xla": {
            "_admit_many_fn": "fbfb20269f039a48",
            "_extend_mid_fn": "187c8419e7c14645",
            "_decode_nomask_fn": "e3cec072d822b9bd",
            "_decode_loop_fn": "8a860900cd45289a",
        },
        "pallas": {
            "_admit_many_fn": "a0b28fe163d0393e",
            "_extend_mid_fn": "d034059b37c808e1",
            "_decode_nomask_fn": "2f0cbca457f87e88",
            "_decode_loop_fn": "638f5517a3e82122",
        },
    },
    "mellum2": {
        "xla": {
            "_admit_many_fn": "893396ee4f39a1a6",
            "_extend_mid_fn": "497555f18f24d9d7",
            "_decode_nomask_fn": "ecbe0c77ee57a8d4",
            "_decode_loop_fn": "ba6b6e51f0014472",
        },
        "pallas": {
            "_admit_many_fn": "ae1779dd90b9e0c1",
            "_extend_mid_fn": "73b5097510e740cb",
            "_decode_nomask_fn": "b65452d32874300b",
            "_decode_loop_fn": "20075d0627bfddc4",
        },
    },
    "solar-open2": {
        "xla": {
            "_admit_many_fn": "dfd70030e9cb5d93",
            "_extend_mid_fn": "9505c659ffe878f2",
            "_decode_nomask_fn": "ee68658b5c841909",
            "_decode_loop_fn": "8f3773a8464a0440",
        },
        # PR 51's own: a prompt's and a chunk's gated delta rule in the
        # kernel (PARENT_OF_51 has the parent's)
        "pallas": {
            "_admit_many_fn": "74861ad335c2533f",
            "_extend_mid_fn": "9c4f9f223a43790e",
            "_decode_nomask_fn": "a8466d8370543df4",
            "_decode_loop_fn": "538ce749c2b66c46",
        },
    },
}

# PR 46's parent (f4aa4f7) for the newer models; openPangu's `extend` with
# the kernels on is PR 46's own (PARENT_OF_46 has the parent's)
PARENT.update({
    "trinity": {
        "xla": {
            "_admit_many_fn": "2dc541b1bb0ea338",
            "_extend_mid_fn": "57453c63b3752287",
            "_decode_nomask_fn": "4f969bb1c858d8e7",
            "_decode_loop_fn": "27de698ee217f641",
        },
        "pallas": {
            "_admit_many_fn": "3fca136a096a7d8a",
            "_extend_mid_fn": "95c7f9a826a4d773",
            "_decode_nomask_fn": "08edf4c967fe5c5d",
            "_decode_loop_fn": "49342b1402988222",
        },
    },
    "nemotron3": {
        "xla": {
            "_admit_many_fn": "bdeed5dd4fd273f8",
            "_extend_mid_fn": "e86f0a3c3098b18e",
            "_decode_nomask_fn": "ee96a9ae161a34a4",
            "_decode_loop_fn": "cd45fa63b5916f0f",
        },
        "pallas": {
            "_admit_many_fn": "5a7db382ee38bae2",
            "_extend_mid_fn": "7b0fb8e0651ed4e2",
            "_decode_nomask_fn": "424a99fd375cf9b9",
            "_decode_loop_fn": "de456dc2d5ec5a8f",
        },
    },
    "openpangu": {
        "xla": {
            "_admit_many_fn": "c6c5b33050f08a8a",
            "_extend_mid_fn": "4a48dfa3eec8242f",
            "_decode_nomask_fn": "118d73f5d6e67418",
            "_decode_loop_fn": "25e35a53a3268d0a",
        },
        "pallas": {
            "_admit_many_fn": "8fcc076dac3d2017",
            "_extend_mid_fn": "824029f7259ed3d5",
            "_decode_nomask_fn": "b01e410c68908da1",
            "_decode_loop_fn": "918e069cb795e214",
        },
    },
})
PARENT_OF_46 = "115b3d97fce03bdc"
PARENT_OF_51 = {"_admit_many_fn": "7983ec38f42576db",
                "_extend_mid_fn": "f82f638d423e1b4a"}

# the parent's (d05db0e) hashes of the programs PR 53 moved, kernels on
PARENT_OF_53 = {
    "mixtral": {"_admit_many_fn": "8f4729f85dc0ea26",
                "_extend_mid_fn": "0d164d65a1d721f4"},
    "mellum2": {"_admit_many_fn": "f28fb2fdb76012f2",
                "_extend_mid_fn": "087969ebba11f2fc"},
    "solar-open2": {"_admit_many_fn": "8ca43fc684c3eea1",
                    "_extend_mid_fn": "a3ffa74a15683244",
                    "_decode_nomask_fn": "3e502a281b398fa4",
                    "_decode_loop_fn": "aff8bda03d3e95e8"},
    "trinity": {"_admit_many_fn": "442fd7f6812fdc9d",
                "_extend_mid_fn": "07ed3cd063528d9c",
                "_decode_nomask_fn": "401d33888884ce27",
                "_decode_loop_fn": "1cbbffa38315a98a"},
    "nemotron3": {"_admit_many_fn": "3a788d660edfbe8e",
                  "_extend_mid_fn": "0c8edc4ca79f22e0",
                  "_decode_nomask_fn": "833fa8079930f29d",
                  "_decode_loop_fn": "35a071f9118b2773"},
    "openpangu": {"_admit_many_fn": "839e475d81c54176",
                  "_extend_mid_fn": "ee18f355c3657b65",
                  "_decode_nomask_fn": "985e4b109b1cee2d",
                  "_decode_loop_fn": "2606d3e6878ce30c"},
}


def _text(jaxpr) -> str:
    """A jaxpr's text without what differs between two checkouts or two
    processes: addresses, and the path above the package."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return re.sub(r"[^\s'\"=(]*/(localai_tpu/)", r"\1", text)


def _drive(name: str, workdir: str) -> dict:
    """program -> hash, for one model: the engine is built as the backend
    builds it and driven once through each program."""
    from localai_tpu.engine import Engine, EngineConfig
    from localai_tpu.engine.loader import load_config, load_params
    from localai_tpu.ops.sampling import SamplingParams, sampler_row

    os.makedirs(workdir, exist_ok=True)
    with open(os.path.join(workdir, "config.json"), "w") as f:
        json.dump(dict(MODELS[name], localai_synthetic=True), f)
    cfg = load_config(workdir, dtype="int8")
    params = load_params(workdir, cfg, dtype="int8")
    eng = Engine(cfg, params, None, EngineConfig(
        max_slots=4, max_context=256, prefill_buckets=(64,),
        prefill_chunk=64, cache_type=CACHE_TYPE.get(name, "int8")))
    seen = {}

    def recorded(attr):
        fn = getattr(eng, attr)

        def call(*args, **kw):
            seen[attr] = hashlib.sha256(_text(
                fn.trace(*args, **kw).jaxpr).encode()).hexdigest()[:16]
            return fn(*args, **kw)

        setattr(eng, attr, call)

    for attr in PROGRAMS:
        recorded(attr)
    greedy = sampler_row(SamplingParams(temperature=0.0), cfg.vocab_size,
                         fallback_seed=1, include_bias=False)
    ids = np.ones((1, 64), np.int32)
    eng._dev_admit(ids, 40, 1, greedy, None)
    eng._dev_extend_mid(ids, 0, 2)
    active = np.array([False, True, False, False])
    eng._dev_decode(active).wait()
    eng._dev_decode_loop(active, np.array([0, 4, 0, 0], np.int32),
                         np.zeros((4,), bool)).wait()
    assert set(seen) == set(PROGRAMS), sorted(seen)
    return seen


def hashes(name: str, kernels: str, workdir: str) -> dict:
    """_drive as a served backend would trace it: synthetic weights
    allowed, the Pallas kernels forced or not, and the default precision of
    products (tests/conftest.py asks for float32 ones)."""
    import jax

    os.environ["LOCALAI_ALLOW_SYNTHETIC"] = "1"
    os.environ.pop("LOCALAI_FORCE_PALLAS", None)
    if kernels == "pallas":
        os.environ["LOCALAI_FORCE_PALLAS"] = "1"
    try:
        with jax.default_matmul_precision(None):
            return _drive(name, workdir)
    finally:
        os.environ.pop("LOCALAI_FORCE_PALLAS", None)


@pytest.mark.parametrize("kernels", ["xla", "pallas"])
@pytest.mark.parametrize("name", list(MODELS))
def test_the_older_models_programs_are_the_parents(name, kernels, tmp_path):
    got = hashes(name, kernels, str(tmp_path))
    assert got == PARENT[name][kernels]
    # the one program PR 46 moved holds the kernel, and no other does
    assert (got["_extend_mid_fn"] != PARENT_OF_46) or (
        name, kernels) != ("openpangu", "pallas")
    # and the two PR 51 moved are not the parent's
    if (name, kernels) == ("solar-open2", "pallas"):
        assert all(got[p] != h for p, h in PARENT_OF_51.items())
    # and the programs PR 53 moved (those that hold a grouped product) are
    # not the parent's
    if kernels == "pallas":
        assert all(got[p] != h for p, h in PARENT_OF_53[name].items())


if __name__ == "__main__":
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("LOCALAI_NO_PREWARM", "1")
    with tempfile.TemporaryDirectory() as tmp:
        print(json.dumps({name: {k: hashes(name, k, os.path.join(tmp, name, k))
                                 for k in ("xla", "pallas")}
                          for name in MODELS}, indent=1))
