"""Test harness: force a hermetic 8-device virtual CPU mesh.

The reference has no automated multi-node tests (SURVEY.md §4); we do better by
running every sharding-sensitive test on a virtual 8-device CPU mesh — the
TPU-idiomatic fake-cluster harness.

Both knobs are plain environment variables read when the CPU client is
created (lazily, at the first `jax.devices()`), and backend subprocesses the
tests spawn inherit them: `JAX_PLATFORMS=cpu` and
`XLA_FLAGS=--xla_force_host_platform_device_count=8`.
"""
import os
import re

# keep backend-spawning tests fast: skip the serving prewarm request the
# llm backend otherwise runs at LoadModel (backend/llm.py _prewarm)
os.environ.setdefault("LOCALAI_NO_PREWARM", "1")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# honor a pre-set count (the TP CI job runs `-m tp` on 4 devices — the
# exact mesh bench.py --mode tp uses); default stays 8
_m = re.search(r"xla_force_host_platform_device_count=(\d+)",
               os.environ["XLA_FLAGS"])
_FORCED_N = int(_m.group(1)) if _m else 8

# LOCALAI_TPU_TESTS=1 runs the suite on the real accelerator instead (the
# TPU-gated tests in test_tpu_real.py only execute in that mode — real-chip
# lowering is what interpret mode cannot check). Mesh-dependent tests need 8
# devices — on smaller TPU hosts only the real-TPU tests run.
_REAL = os.environ.get("LOCALAI_TPU_TESTS") == "1"
if not _REAL:
    os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402
import jax  # noqa: E402

# numerics tests compare against f64 numpy references; keep CPU matmuls exact
jax.config.update("jax_default_matmul_precision", "float32")

if not _REAL:
    assert jax.devices()[0].platform == "cpu", "tests must run on CPU"
    assert len(jax.devices()) == _FORCED_N >= 4, \
        f"virtual {_FORCED_N}-device mesh required (min 4)"


def pytest_collection_modifyitems(config, items):
    """Real-accelerator mode on a host with fewer than 8 devices: only the
    TPU-gated lowering tests are meaningful — the rest assume the virtual
    8-device mesh harness."""
    if not _REAL or len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(reason="LOCALAI_TPU_TESTS=1 with <8 devices: "
                                   "only real-TPU lowering tests run")
    for item in items:
        if "test_tpu_real" not in str(item.fspath):
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    """2x4 ('data','model') mesh over the virtual CPU devices."""
    if len(jax.devices()) < 8:
        pytest.skip("mesh8 needs the 8-device harness")
    from localai_tpu.parallel import MeshConfig, build_mesh

    return build_mesh(MeshConfig(data=2, model=4))
