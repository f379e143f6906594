"""Hardware capability keys (reference: /root/reference/pkg/system/
capabilities.go:28-99 — GPU vendor → capability string used to pick concrete
backends, force-file override :49-64; sysinfo pkg/xsysinfo).

TPU build: capability keys are `tpu-v4|tpu-v5e|tpu-v5p|tpu-v6e|cpu`. This
module never touches JAX: a chip belongs to one process at a time, and that
process is a backend. The control plane (HTTP server, CLI pre-flight, backend
gallery) learns the device from the force file/env or from what a loaded
backend reports (`Status.device_json`, system/device.py) — a control plane
that called `jax.devices()` would take the chip from every backend it spawns.
"""
from __future__ import annotations

import dataclasses
import os


CAPABILITY_FORCE_FILE = "/run/localai/capability"


@dataclasses.dataclass(frozen=True)
class Chip:
    capability: str
    bf16_flops: float       # peak dense bf16 FLOP/s per chip
    int8_ops: float         # peak int8 OP/s per chip
    hbm_bytes_per_s: float  # peak HBM bandwidth per chip


# The one table of accelerators this repo knows, keyed by JAX's
# `device.device_kind`. Peaks are the published per-chip figures (Google
# Cloud TPU documentation, system-architecture pages "TPU v4" / "TPU v5e" /
# "TPU v5p" / "TPU v6e"). There is no default and no CPU row: a device that
# is not here gets no MFU or roofline figure at all. The v5e key was read
# off the chip (chip_smoke.py, PR 21); the other three keys follow JAX's
# naming for those generations and have not been seen by this repo.
CHIPS: dict[str, Chip] = {
    "TPU v4": Chip("tpu-v4", 275e12, 275e12, 1228e9),
    "TPU v5 lite": Chip("tpu-v5e", 197e12, 393e12, 819e9),
    "TPU v5": Chip("tpu-v5p", 459e12, 918e12, 2765e9),
    "TPU v6 lite": Chip("tpu-v6e", 918e12, 1836e12, 1640e9),
}


def capability_of(platform: str, device_kind: str) -> str:
    """Capability key for a device as JAX names it (a backend's report)."""
    if platform == "cpu":
        return "cpu"
    chip = CHIPS.get(device_kind)
    return chip.capability if chip else platform


def detect_capability(device: dict | None = None) -> str:
    """Capability key: the operator's override, else the device a backend
    reported ({"platform", "device_kind"}), else "unknown" — the control
    plane does not probe for itself."""
    # force-file override wins, then the env (capabilities.go:49-64)
    if os.path.exists(CAPABILITY_FORCE_FILE):
        with open(CAPABILITY_FORCE_FILE) as f:
            forced = f.read().strip()
        if forced:
            return forced
    if os.environ.get("LOCALAI_FORCE_CAPABILITY"):
        return os.environ["LOCALAI_FORCE_CAPABILITY"]
    if device and device.get("platform"):
        return capability_of(device["platform"],
                             device.get("device_kind", ""))
    return "unknown"


def system_info(backends: dict[str, dict] | None = None) -> dict:
    """CPU/memory/accelerator summary (xsysinfo role). `backends` maps each
    loaded model to its backend's device report; the accelerator fields come
    from there (empty until a model is loaded)."""
    from localai_tpu.system.memory import hbm_table_bytes

    backends = backends or {}
    first = next(iter(backends.values()), None)
    info: dict = {"capability": detect_capability(first)}
    hbm = hbm_table_bytes(info["capability"])
    if hbm:
        info["hbm_bytes"] = hbm
    info["cpu_count"] = os.cpu_count()
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal"):
                    info["mem_total_kb"] = int(line.split()[1])
                    break
    except OSError:
        pass
    info["backends"] = backends
    return info
