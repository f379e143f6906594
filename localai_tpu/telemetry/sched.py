"""Scheduler X-ray (ISSUE 13): per-tick pack ledger, reason codes, rooflines.

Three pieces, all riding the LOCALAI_METRICS default-ON gate:

- `REASON_CODES`: the single registered taxonomy for every admission /
  fallback / demotion decision the engine makes. This is a STABLE CONTRACT
  (README "Scheduler X-ray"): codes are added, never renamed, and leave
  only with the path that emitted them (PR 47: the `pack` category); an
  unregistered code is a hard failure — a new fallback site
  that forgets to register its reason fails the tripwire test, not a
  dashboard query six weeks later. The "dispatch" category has an exactness
  invariant: every decode dispatch emits EXACTLY ONE dispatch-category
  code, so the per-code counters sum to `decode_dispatches`.

- `TickLedger`: per-engine ring of tick records. Each tick collects the
  pack composition of every dispatch (decode rows, prefill-chunk tokens,
  spec verify windows, mm inject rows, pad/dead rows)
  plus the tick's reason codes, and commits one record — the record also
  feeds the flight recorder's tick ring, so a post-mortem shows the last N
  *scheduling decisions*, not just dispatch counts. Disabled
  (LOCALAI_SCHED=0 or LOCALAI_METRICS=0) the engine keeps one attribute
  load + branch per tick.

- roofline helpers: fold XLA's `lower().compile().cost_analysis()` FLOPs +
  bytes into compute- vs bandwidth-bound attribution per compiled program
  variant. Peaks come from the one chip table (system/capabilities.CHIPS,
  keyed by `device_kind`); the ridge point (peak_flops / peak_bw) splits
  the two regimes, and the per-variant `mfu` is the roofline model's
  ceiling for that program — what the dispatch could reach if it ran
  exactly at the limiting resource's peak. A device the table does not
  know gets the cost counts only.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque

from localai_tpu.telemetry.metrics import metrics_enabled
from localai_tpu.testing.lockdep import lockdep_lock

# --------------------------------------------------------------- reason codes
# code -> (category, description). Categories:
#   dispatch  — why a decode dispatch ran instead of the fused while-loop
#               (exactly ONE per dispatch; sums to decode_dispatches)
#   demotion  — a fused block stepped DOWN the power-of-two ladder or was
#               forced to a single step (may co-occur with a dispatch code)
#   admission — a request was demoted/deferred/degraded at admission time
#   kv        — KV lifecycle tier actions (per block)
REASON_CODES: dict[str, tuple[str, str]] = {
    "loop_native": (
        "dispatch", "fused while-loop dispatch (the fast path, not a "
        "fallback — recorded so dispatch attribution is exhaustive)"),
    "loop_disabled": (
        "dispatch", "no while-loop program built (decode_loop=0 config)"),
    "draft_engine": (
        "dispatch", "speculative engine: the draft+verify program replaces "
        "the loop"),
    "grammar_hostonly": (
        "dispatch", "a live grammar overflowed the device tables and needs "
        "per-token host masks"),
    "pending_prefill": (
        "dispatch", "chunked prefill in flight: admission must not wait "
        "out a whole loop"),
    "pending_admission": (
        "dispatch", "queued request + free slot: per-token host decision "
        "pending"),
    "stop_string": (
        "dispatch", "an active slot has stop strings (per-token host scan)"),
    "spec_dense": (
        "dispatch", "speculative dispatch (draft engine: gamma draft "
        "steps and the verify window in one program)"),
    "context_margin": (
        "demotion", "a slot within 2*block of its context limit forced "
        "single-step dispatches"),
    "max_tokens_ladder": (
        "demotion", "a slot near max_tokens stepped the fused block down "
        "the power-of-two ladder"),
    "grammar_table_overflow": (
        "admission", "an automaton didn't fit the shared device grammar "
        "tables; the slot keeps per-token host masks"),
    "kv_policy_demotion": (
        "admission", "a full-attention request demoted to the windowed KV "
        "policy (compact table or low free pool)"),
    "kv_pool_exhausted": (
        "admission", "KV pool exhausted after reclaim: the request was "
        "deferred until blocks free"),
    "kv_eviction": (
        "kv", "a window-exited block was dropped (ring overwrite or full "
        "cold pool)"),
    "kv_cold_demotion": (
        "kv", "a window-exited block was quantized into the int8 cold "
        "pool"),
    "kv_host_spill": (
        "kv", "a dying device block (slot reclaim, prefix-cache rewrite, "
        "or kvtier eviction) was spilled to the host-RAM KV tier"),
    "kv_host_readmit": (
        "kv", "a host-tier block was re-admitted H2D during admission, "
        "extending the device prefix-cache hit"),
    "kv_host_miss_reprefill": (
        "kv", "device and host tiers both missed a full prefix block; the "
        "uncovered prefix falls back to re-prefill"),
    "kv_host_evict_budget": (
        "kv", "host-tier blocks were dropped (LRU over sessions) to "
        "respect the --kv-host-bytes budget"),
    "preempt_spill": (
        "kv", "a live slot's KV chain was force-spilled to the host tier "
        "by a preemption spill-drain (one count per frozen slot)"),
    "resume_readmit": (
        "admission", "a preempted request was re-admitted with its full-"
        "block KV prefix covered by the device/host caches (fast resume)"),
    "resume_reprefill": (
        "admission", "a preempted request resumed without KV coverage "
        "(host pool disabled, evicted, or budget too small) and fell back "
        "to re-prefilling prompt+emitted"),
}

DISPATCH_CODES: tuple[str, ...] = tuple(
    c for c, (cat, _) in REASON_CODES.items() if cat == "dispatch")


def reason_category(code: str) -> str:
    return REASON_CODES[code][0]


# ----------------------------------------------------------------- enablement
_FORCED: bool | None = None


def sched_enabled() -> bool:
    """Tick ledger gate: ON by default, off when LOCALAI_SCHED=0 or the
    whole metrics layer is disabled (LOCALAI_METRICS=0)."""
    if _FORCED is not None:
        return _FORCED
    if os.environ.get("LOCALAI_SCHED", "1") in ("", "0"):
        return False
    return metrics_enabled()


def set_sched_enabled(value: bool | None) -> None:
    """Test hook: force the gate on/off (None = back to the env)."""
    global _FORCED
    _FORCED = value


def maybe_ledger() -> "TickLedger | None":
    """Per-engine ledger (one fresh instance per call — bench runs several
    engines in one process and their streams must not mix), or None when
    disabled so the engine hot path stays one attribute load + branch."""
    return TickLedger() if sched_enabled() else None


# ----------------------------------------------------------------- tick ident
# the most recent engine tick id, process-wide: FlightRecorder.record_event
# stamps it into every event (breaker opens, reaps, tripwires) so post-
# mortems correlate with the scheduling stream. With several engines in one
# process the last to tick wins — events still land within one tick of the
# stream that produced them.
_CURRENT_TICK: int | None = None


def set_current_tick(n: int | None) -> None:
    global _CURRENT_TICK
    _CURRENT_TICK = n


def current_tick() -> int | None:
    return _CURRENT_TICK


# ------------------------------------------------------------------ rooflines
def roofline_entry(flops: float, bytes_: float,
                   peak_flops: float | None = None,
                   peak_bw: float | None = None) -> dict:
    """Fold one program's XLA cost analysis into roofline attribution.

    `mfu` here is the roofline-model CEILING for the program: the fraction
    of peak FLOP/s it could sustain if it ran exactly at the limiting
    resource's peak (1.0 when compute-bound, intensity/ridge when
    bandwidth-bound). Measured MFU can only be lower.

    Without peaks (a device not in system/capabilities.CHIPS — a CPU
    included) the entry carries the counts only: no time, bound or mfu is
    invented for a device nobody published peaks for."""
    entry = {
        "cost_flops": flops,
        "cost_bytes": bytes_,
        "intensity_flops_per_byte": (flops / bytes_) if bytes_ > 0 else 0.0,
    }
    if not peak_flops or not peak_bw:
        return entry
    t_c = flops / peak_flops
    t_m = bytes_ / peak_bw
    t = max(t_c, t_m)
    entry.update({
        "ridge_flops_per_byte": peak_flops / peak_bw,
        "bound": "compute" if t_c >= t_m else "bandwidth",
        "t_compute_us": t_c * 1e6,
        "t_memory_us": t_m * 1e6,
        "t_roofline_us": t * 1e6,
        "mfu": (t_c / t) if t > 0 else 0.0,
    })
    return entry


# ----------------------------------------------------------------- the ledger
_PACK_FIELDS = ("decode_rows", "prefill_tokens", "spec_windows", "mm_rows",
                "pad_rows", "rows_used", "packed")


class TickLedger:
    """Per-engine tick ledger. The engine drives it:

        ledger.begin(tick_n)
        ledger.reason("pending_admission")        # any decision site
        ledger.pack("loop", decode_rows=..., ...)  # each dispatch
        rec = ledger.commit(active_slots=..., queued=...)

    and hands the committed record to the flight recorder's tick ring.
    Counters/totals are cumulative since the last reset() (warmup resets so
    bench/production streams start clean); the ring keeps the last `ring`
    full tick records for /debug/sched. A lock guards only the snapshot
    path — begin/reason/pack/commit run on the single engine thread."""

    def __init__(self, ring: int = 256):
        self.ticks: deque = deque(maxlen=ring)
        self.counters: dict[str, int] = {}
        self.variants: dict[str, int] = {}
        self.totals: dict[str, int] = dict.fromkeys(_PACK_FIELDS, 0)
        self.n_ticks = 0
        self.n_dispatches = 0
        # per-variant roofline entries (engine.rooflines() fills this after
        # the AOT cost-analysis pass; flat()/snapshot() then export them)
        self.rooflines: dict[str, dict] = {}
        self._cur: dict | None = None
        self._rows: dict | None = None   # rows(): for the next pack's record
        self._lock = lockdep_lock("telemetry.sched")

    def reset(self) -> None:
        """Drop accumulated ticks/counters (NOT the cached rooflines) — the
        engine calls this after warmup so compile-burst dispatches don't
        pollute the serving stream."""
        with self._lock:
            self.ticks.clear()
            self.counters.clear()
            self.variants.clear()
            self.totals = dict.fromkeys(_PACK_FIELDS, 0)
            self.n_ticks = 0
            self.n_dispatches = 0
            self._cur = None
            self._rows = None

    # ------------------------------------------------------------ recording

    def begin(self, tick: int) -> None:
        self._cur = {"tick": tick, "reasons": [], "packs": []}

    def reason(self, code: str, **fields) -> None:
        """Record one scheduling decision. Unregistered codes raise — the
        taxonomy is the contract, and a site inventing a code must fail in
        tests, not ship an unqueryable string."""
        if code not in REASON_CODES:
            raise ValueError(
                f"unregistered scheduler reason code {code!r} — add it to "
                f"localai_tpu.telemetry.sched.REASON_CODES (stable "
                f"contract: codes are only ever added)")
        self.counters[code] = self.counters.get(code, 0) + 1
        cur = self._cur
        if cur is not None:
            cur["reasons"].append(
                dict(fields, code=code) if fields else code)

    def rows(self, active: int, prefill: int, free_queued: int,
             free_starved: int) -> None:
        """The engine's max_slots rows by state as the next decode dispatch
        is enqueued (engine._rows_at_dispatch): kept in that dispatch's pack
        record, so /debug/sched and the flight recorder show where each
        dispatch's rows were. Not summed here: rows weigh by the steps a
        dispatch runs, known at consume, and the engine's cumulative
        `decode_row_steps__*` counters carry that."""
        self._rows = {"rows_active": active, "rows_prefill": prefill,
                      "rows_free_queued": free_queued,
                      "rows_free_starved": free_starved}

    def pack(self, variant: str, *, decode_rows: int = 0,
             prefill_tokens: int = 0, spec_windows: int = 0,
             mm_rows: int = 0, pad_rows: int = 0, rows_used: int = 0,
             packed: int = 0) -> None:
        """Record one dispatch's pack composition under its compiled program
        variant name (the same name engine.rooflines() costs). Every field
        counts ONCE A DISPATCH, whatever its steps: `pad_rows` of a dense
        dispatch is max_slots less its decode rows, so a 64-step loop's
        empty rows weigh in `sched_pack__pad_rows` as one step's. Rows by
        step, and by why they were empty: the engine's
        `decode_row_steps__*`."""
        self.n_dispatches += 1
        self.variants[variant] = self.variants.get(variant, 0) + 1
        comp = {"decode_rows": decode_rows, "prefill_tokens": prefill_tokens,
                "spec_windows": spec_windows, "mm_rows": mm_rows,
                "pad_rows": pad_rows, "rows_used": rows_used,
                "packed": packed}
        t = self.totals
        for k, v in comp.items():
            t[k] += v
        rows, self._rows = self._rows, None
        cur = self._cur
        if cur is not None:
            cur["packs"].append(dict(comp, variant=variant, **(rows or {})))

    def commit(self, **meta) -> dict:
        """Seal the current tick record (begin() must have run) and append
        it to the ring. Returns the record — the engine forwards it to the
        flight recorder's tick ring verbatim."""
        rec = self._cur or {"tick": -1, "reasons": [], "packs": []}
        self._cur = None
        rec["t_wall"] = time.time()
        rec.update(meta)
        with self._lock:
            self.n_ticks += 1
            self.ticks.append(rec)
        return rec

    # -------------------------------------------------------------- export

    def pad_rows_frac(self) -> float:
        """Fraction of the rows dispatched that carried no live sequence.
        Counted once a dispatch (see pack): no measure of rows idle by
        step."""
        return self.totals["pad_rows"] / max(self.totals["rows_used"], 1)

    def flat(self, prefix: str = "sched_") -> dict[str, float]:
        """Flattened floats for the GetMetrics str→double surface. Only
        CACHED roofline entries are exported — this never compiles."""
        with self._lock:
            out: dict[str, float] = {
                f"{prefix}ticks_total": float(self.n_ticks),
                f"{prefix}dispatches_total": float(self.n_dispatches),
            }
            for code, n in self.counters.items():
                out[f"{prefix}reason__{code}"] = float(n)
            for name, n in self.variants.items():
                out[f"{prefix}variant__{name}"] = float(n)
            for k, v in self.totals.items():
                out[f"{prefix}pack__{k}"] = float(v)
            out[f"{prefix}pad_rows_frac"] = self.pad_rows_frac()
            for name, e in self.rooflines.items():
                out[f"{prefix}roofline__{name}__flops"] = e["cost_flops"]
                out[f"{prefix}roofline__{name}__bytes"] = e["cost_bytes"]
                if "mfu" in e:
                    out[f"{prefix}roofline__{name}__mfu"] = e["mfu"]
        return out

    def snapshot(self, last: int = 64) -> dict:
        """Structured export for /debug/sched and GetTrace."""
        with self._lock:
            return {
                "ticks_total": self.n_ticks,
                "dispatches_total": self.n_dispatches,
                "reason_counters": dict(self.counters),
                "variants": dict(self.variants),
                "pack_totals": dict(self.totals),
                "pad_rows_frac": self.pad_rows_frac(),
                "rooflines": {k: dict(v)
                              for k, v in self.rooflines.items()},
                "recent_ticks": list(self.ticks)[-last:],
            }
