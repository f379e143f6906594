"""Device-step profiler: per-stage time histograms, tokens/s, MFU estimate.

The engine's dispatches are asynchronous — a plain wall-clock around a jitted
call times only the Python enqueue. With `LOCALAI_PROFILE` set the engine
passes each dispatch's output through `record(..., fence=...)`, which calls
`jax.block_until_ready` before reading the clock: the measured interval is
the real host+device cost of that stage (and the pipeline is deliberately
serialized — profiling is a measurement mode, not a serving mode).

Stage samples accumulate into log-spaced histograms so one snapshot answers
"where do the milliseconds of a decode step go" (the Kernel Looping /
PRESERVE-style per-stage attribution the 33 ms step needs): count, total,
min/max, p50 (from the histogram), tokens/s, and one MFU number per stage:
`mfu`, backed by XLA's per-program cost analysis when the engine has fed
per-stage FLOP counts via set_costs() (ISSUE 13); None until then. The old
2·N·tokens analytic approximation (`mfu_analytic_legacy`) was kept one
release for scoreboard continuity and removed in ISSUE 16 — it overstated
stages that don't run the full forward and knew nothing about bandwidth.

Everything here is jax-free until a fence is actually requested, so the
module can load in processes that never touch the accelerator.
"""
from __future__ import annotations

import math
import os
import threading
import time

from localai_tpu.testing.lockdep import lockdep_lock

# histogram bucket upper bounds, in seconds (log-spaced 50 µs … 5 s + inf)
BUCKETS_S: tuple[float, ...] = (
    50e-6, 100e-6, 200e-6, 500e-6, 1e-3, 2e-3, 5e-3, 10e-3, 20e-3, 50e-3,
    100e-3, 200e-3, 500e-3, 1.0, 2.0, 5.0, math.inf,
)

_FORCED: bool | None = None


def profile_enabled() -> bool:
    if _FORCED is not None:
        return _FORCED
    return os.environ.get("LOCALAI_PROFILE", "") not in ("", "0")


def set_profile_enabled(value: bool | None) -> None:
    global _FORCED
    _FORCED = value


class _Stage:
    __slots__ = ("count", "total_s", "min_s", "max_s", "tokens", "hist")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.min_s = math.inf
        self.max_s = 0.0
        self.tokens = 0
        self.hist = [0] * len(BUCKETS_S)

    def add(self, dt: float, tokens: int):
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)
        self.tokens += tokens
        for i, ub in enumerate(BUCKETS_S):
            if dt <= ub:
                self.hist[i] += 1
                break

    def p50_s(self) -> float:
        """Median from the histogram (bucket upper bound — coarse but free)."""
        if not self.count:
            return 0.0
        half, acc = self.count / 2, 0
        for i, n in enumerate(self.hist):
            acc += n
            if acc >= half:
                return BUCKETS_S[i] if math.isfinite(BUCKETS_S[i]) \
                    else self.max_s
        return self.max_s


class StepProfiler:
    """Accumulates fenced stage timings; shared between the engine loop and
    concurrent GetTrace/GetMetrics readers (hence the lock — profiling mode
    already pays a fence per dispatch, a mutex is noise)."""

    def __init__(self, fence: bool = True, n_params: int = 0,
                 peak: float = 0.0, mesh: dict | None = None,
                 peak_bw: float = 0.0):
        """`mesh` is the serving mesh shape ({'data': d, 'model': m, ...},
        None for single chip). It is recorded in every report and scales the
        MFU denominator by the chip count, so a TP profile can never be
        scoreboard-read as a single-chip one."""
        self.fence = fence
        self.n_params = n_params
        self.peak = peak
        self.peak_bw = peak_bw
        self.mesh = dict(mesh) if mesh else None
        self.chips = 1
        for size in (mesh or {}).values():
            self.chips *= max(int(size), 1)
        self._stages: dict[str, _Stage] = {}
        self._gauges: dict[str, float] = {}
        self._costs: dict[str, dict] = {}
        self._lock = lockdep_lock("telemetry.profiler")
        self._first_t: float | None = None
        self._last_t: float = 0.0

    def set_costs(self, costs: dict[str, dict]) -> None:
        """Per-stage XLA cost analysis (ISSUE 13): stage name → {"flops":
        per-dispatch FLOPs, "bytes": per-dispatch bytes accessed}, from
        `jit(...).lower().compile().cost_analysis()` on the stage's compiled
        program. Once set, report()/flat() emit the cost-backed `mfu`
        (measured dispatch time against real FLOPs) beside the legacy
        2·N·tokens estimate."""
        with self._lock:
            for stage, c in costs.items():
                self._costs[stage] = {
                    "flops": float(c.get("flops", 0.0)),
                    "bytes": float(c.get("bytes", 0.0))}

    def set_gauges(self, **gauges: float) -> None:
        """Scalar engine-level gauges (dispatch-fusing telemetry: decode
        dispatch count, steps/dispatch, host-sync wait per token). Surfaced
        through report()["gauges"] and as bare prof_<name> GetMetrics keys
        so the bench scoreboard and Prometheus layer can gate on them."""
        with self._lock:
            for k, v in gauges.items():
                self._gauges[k] = float(v)

    def record(self, stage: str, t0: float, tokens: int = 0,
               fence=None) -> float:
        """Close a stage interval opened at perf_counter() `t0`; when `fence`
        is given (any pytree of device arrays) the device work is awaited
        first so the sample covers compute, not enqueue. Returns the
        duration in seconds."""
        if fence is not None and self.fence:
            import jax

            jax.block_until_ready(fence)
        now = time.perf_counter()
        dt = max(now - t0, 0.0)
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            st.add(dt, tokens)
            if self._first_t is None:
                self._first_t = t0
            self._last_t = now
        return dt

    # ------------------------------------------------------------- export

    def report(self) -> dict:
        """Full stage breakdown: per-stage stats + busy-window coverage
        (sum of stage time / first-to-last-sample wall time)."""
        with self._lock:
            wall = (self._last_t - self._first_t) if self._first_t else 0.0
            stages = {}
            total = 0.0
            for name, st in self._stages.items():
                total += st.total_s
                # cost-backed MFU (ISSUE 13): the stage's real compiled
                # FLOPs per dispatch, over measured dispatch time and the
                # mesh's peak — None until the engine feeds set_costs()
                mfu = None
                cost = self._costs.get(name)
                if cost and cost["flops"] and self.peak and st.total_s > 0:
                    mfu = (cost["flops"] * st.count
                           / (st.total_s * self.peak * self.chips))
                stages[name] = {
                    "count": st.count,
                    "total_ms": st.total_s * 1e3,
                    "mean_ms": st.total_s / st.count * 1e3,
                    "p50_ms": st.p50_s() * 1e3,
                    "min_ms": st.min_s * 1e3,
                    "max_ms": st.max_s * 1e3,
                    "tokens": st.tokens,
                    "tok_s": (st.tokens / st.total_s
                              if st.total_s > 0 else 0.0),
                    "mfu": mfu,
                    **({"cost_flops": cost["flops"],
                        "cost_bytes": cost["bytes"]} if cost else {}),
                    "hist_bucket_upper_ms": [
                        b * 1e3 if math.isfinite(b) else None
                        for b in BUCKETS_S],
                    "hist": list(st.hist),
                }
        for s in stages.values():
            s["share"] = s["total_ms"] / (total * 1e3) if total else 0.0
        return {
            "stages": stages,
            "gauges": dict(self._gauges),
            "wall_ms": wall * 1e3,
            "busy_ms": total * 1e3,
            "coverage": (total / wall) if wall > 0 else 0.0,
            "fenced": self.fence,
            "n_params": self.n_params,
            "peak_flops": self.peak,
            "mesh": self.mesh,
            "chips": self.chips,
        }

    def flat(self, prefix: str = "prof_") -> dict[str, float]:
        """Flattened floats for the GetMetrics map (the str→double proto
        surface every dashboard already scrapes)."""
        out: dict[str, float] = {}
        with self._lock:
            for name, st in self._stages.items():
                out[f"{prefix}{name}_count"] = float(st.count)
                out[f"{prefix}{name}_total_ms"] = st.total_s * 1e3
                out[f"{prefix}{name}_p50_ms"] = st.p50_s() * 1e3
                if st.tokens and st.total_s > 0:
                    out[f"{prefix}{name}_tok_s"] = st.tokens / st.total_s
                cost = self._costs.get(name)
                if cost and cost["flops"] and self.peak and st.total_s > 0:
                    out[f"{prefix}{name}_mfu"] = (
                        cost["flops"] * st.count
                        / (st.total_s * self.peak * self.chips))
            for name, v in self._gauges.items():
                out[f"{prefix}{name}"] = v
        return out


def engine_profiler(cfg=None, mesh=None) -> StepProfiler | None:
    """Build the engine's profiler when LOCALAI_PROFILE is set (else None —
    the engine's gate for keeping the hot path fence-free). `cfg` is a
    LlamaConfig used for the MFU param count; `mesh` is the engine's
    jax Mesh (or an axis-shape dict) — recorded in the artifacts."""
    if not profile_enabled():
        return None
    shape = mesh if isinstance(mesh, dict) or mesh is None else None
    if shape is None and mesh is not None:
        from localai_tpu.parallel.mesh import mesh_shape

        shape = mesh_shape(mesh)
    n_params = 0
    if cfg is not None:
        try:
            from localai_tpu.system.memory import param_count

            n_params = param_count(cfg)
        except Exception:
            n_params = 0
    import jax

    from localai_tpu.system.capabilities import CHIPS

    # no peak for a device the chip table does not know: the report then
    # carries timings and counts, and no MFU
    chip = CHIPS.get(jax.devices()[0].device_kind)
    return StepProfiler(fence=True, n_params=n_params,
                        peak=chip.bf16_flops if chip else 0.0, mesh=shape,
                        peak_bw=chip.hbm_bytes_per_s if chip else 0.0)
