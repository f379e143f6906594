"""Slot-based continuous-batching serving engine — the `update_slots` analog.

Reference: llama.cpp's server loop (task queue + slots, wired to gRPC at
/root/reference/backend/cpp/llama-cpp/grpc-server.cpp:69-97; stream path
:571-995) and the MLX backend's stream_generate
(/root/reference/backend/python/mlx/backend.py:193-231).

TPU-first design — everything the XLA compiler sees is fixed-shape:
- ONE decode computation over the full slot array [B] every step, compiled
  once; inactive slots compute masked garbage (cheaper than recompiling).
- prompt prefill is padded to a small set of length buckets (one compile per
  bucket, reused forever).
- per-slot sampler knobs are device arrays (ops/sampling.SamplerState), so any
  mix of temperatures/top-k/penalties shares the same compiled step.
- KV caches + sampler state are DONATED through the jitted step: no
  per-token reallocation, the cache lives in HBM across the whole session.
- host↔device traffic per step is [B] tokens + [B] logprobs out and [B]
  bools in — a few hundred bytes.

The host side owns: admission queue, stop sequences (with holdback so a
half-matched stop string is never emitted), EOS/max-token termination,
incremental UTF-8-safe detokenization, per-request output queues, and
tokens/sec + TTFT metrics (GetMetrics parity —
/root/reference/backend/backend.proto:40-46).
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import os
import threading
import time
from functools import partial
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models.kv import _pallas, _pallas_attention, chunk_rows
from localai_tpu.models.llama import (
    FULL,
    LATENT,
    LINEAR,
    ROUTED,
    SSM,
    WINDOW,
    LlamaConfig,
    cache_shift,
    decode_step,
    expert_form,
    extend,
    init_kv_cache,
    prefill,
    rope_tables,
)
from localai_tpu.ops.rope import rope_table
from localai_tpu.ops.sampling import (
    SamplerState,
    SamplingParams,
    sample,
    sampler_row,
    topk_by_blocks,
)
from localai_tpu.parallel.mesh import activate_mesh
from localai_tpu.testing import faults
from localai_tpu.testing.lockdep import lockdep_lock


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine shape knobs (reference: n_parallel / n_ctx in ModelOptions,
    /root/reference/backend/backend.proto:185-187,199)."""
    max_slots: int = 4            # n_parallel — concurrent sequences
    max_context: int = 1024       # n_ctx per slot
    prefill_buckets: tuple[int, ...] = (64, 256, 1024)
    prefill_chunk: int = 256      # chunked-prefill window (tokens/engine tick)
    pipeline: bool = True         # keep one decode step in flight
    decode_block: int = 16        # decode steps fused per device dispatch
                                  # (amortizes host↔device latency; falls back
                                  # to single steps around grammar masks,
                                  # pending admissions, and context limits)
    decode_loop: int = 64         # single-dispatch decode loop: up to this
                                  # many sample→decode steps fused into ONE
                                  # on-device lax.while_loop with per-slot
                                  # stop conditions (EOS set, max_tokens
                                  # budget, context margin) evaluated on
                                  # device and early exit when every live
                                  # slot finished. 0/1 disables — the engine
                                  # then serves on the decode_block scan
                                  # ladder. Grammar and stop-string slots
                                  # always keep the host-verified block path.
    dtype: str | None = None      # default: model dtype
    cache_type: str = ""          # ""|bf16 dense; int8|q8_0 quantized KV
                                  # (reference CacheTypeKey/Value,
                                  # backend.proto:257-258)
    mesh: Any | None = None       # jax.sharding.Mesh for TP/DP sharding
    shift_keep: int = 4           # context-shift: sink tokens always kept
    replicator: Any | None = None  # multi-host: rank-0 step broadcaster
                                   # (parallel/distributed.Replicator)
    gamma: int = 4                # speculative: draft tokens per step
                                  # (reference NDraft, backend.proto:150)
    prompt_cache: bool = True     # reuse a freed slot's KV prefix when a new
                                  # prompt shares it (llama.cpp prompt/slot
                                  # cache role, backend.proto:136-142)
    prompt_cache_min: int = 16    # minimum shared prefix worth reusing
    sampling_topk_width: int = 64  # sort-free decode sampling when every
                                   # active slot's top_k fits this width
                                   # (0 disables; see ops/sampling.sample)
    admit_per_tick: int = 4       # admission/prefill units per engine tick
                                  # while decodes are running (burst TTFT vs
                                  # decode-cadence trade; unbounded when the
                                  # engine is idle)
    kv_pages: int = 0             # paged KV: physical 128-token blocks in the
                                  # shared pool, incl. the reserved trash
                                  # block 0 (0 = dense per-slot cache). Slots
                                  # reserve ceil((prompt+max_tokens)/128)
                                  # blocks at admission, so the pool
                                  # oversubscribes max_context, not requests.
    grammar_table_states: int = 256  # device grammar tables: shared capacity
                                  # (automaton states across live grammars)
                                  # for the precompiled [S, ceil(V/32)] u32
                                  # mask rows + [S, V] transition table that
                                  # let constrained slots ride the fused
                                  # while-loop and the spec verify window
                                  # with the mask gathered ON DEVICE.
                                  # Grammars whose reachable state set
                                  # exceeds the cap (unbounded nesting) fall
                                  # back to per-token host masks. 0 disables
                                  # (every grammar slot is host-masked).
    kv_policy: str = "full"       # KV lifecycle tier (engine/kvtier.py):
                                  # "full" keeps every block hot (identical
                                  # to the untiered engine), "sink_window(
                                  # sinks=N, window=W[, quantize_cold=true])"
                                  # switches the paged table to COMPACT ring
                                  # geometry — O(sinks+window) resident
                                  # blocks per slot for ANY context length.
                                  # Requires kv_pages; per-request policies
                                  # (GenRequest.kv_policy) may only shrink
                                  # the engine geometry.
    kv_cold_pages: int = 0        # quantize_cold: physical 128-token blocks
                                  # in the int8 cold pool (incl. reserved
                                  # index 0 = "not demoted"). Blocks whose
                                  # tokens exit the window are copied here
                                  # with sub-channel per-token scales instead
                                  # of being dropped; a full cold pool falls
                                  # back to eviction (kv_evictions metric).
    kv_host_bytes: int = 0        # host-RAM KV spill tier (engine/kvhost.py):
                                  # byte budget for blocks the device pool
                                  # evicts (slot reclaim, prefix-cache
                                  # rewrite, kvtier eviction), held int8
                                  # sub-channel and keyed by the prefix
                                  # cache's chain hashes. Admission consults
                                  # the tier after _match_prefix_blocks and
                                  # re-admits hits H2D, overlapped with the
                                  # uncovered suffix's prefill. 0 disables.
    max_restarts: int = 2         # fatal step() errors survived per engine
                                  # lifetime: in-flight streams fail, device
                                  # state is rebuilt, new requests serve
                                  # (reference analog: the manager reaping +
                                  # respawning a dead backend — this recovers
                                  # WITHOUT losing the loaded weights)


@dataclasses.dataclass
class GenRequest:
    """One generation request (the PredictOptions surface that matters to the
    engine; prompt templating/grammar happen upstream)."""
    prompt_ids: list[int]
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    max_tokens: int = 128
    stop: tuple[str, ...] = ()
    ignore_eos: bool = False
    logprobs: bool = False
    grammar: str = ""             # GBNF; enforced via native matcher masks
    context_shift: bool = False   # evict-and-continue past max_context
                                  # (reference ctx_shift, backend.proto:22)
    prompt_cache_path: str = ""   # persist/reuse this prompt's KV on disk
                                  # (reference PromptCachePath,
                                  # backend.proto:136-142)
    prompt_cache_ro: bool = False  # reuse only; never rewrite the file
    trace_id: str = ""            # request id propagated from the HTTP layer
                                  # (telemetry span correlation; "" = untraced)
    trace_parent: int = 0         # parent span id (the gRPC handler's span)
    deadline: float = 0.0         # absolute time.monotonic() the request's
                                  # budget expires (PredictOptions.deadline_ms
                                  # via the HTTP middleware); the engine
                                  # evicts the slot with finish "timeout"
                                  # instead of decoding past it. 0 = none.
    kv_policy: str = ""           # per-request KV retention policy ("" =
                                  # inherit the engine's). "full" or
                                  # "sink_window(sinks=N, window=W)"; a
                                  # windowed request needs a windowed engine
                                  # and may only shrink its geometry
                                  # (engine/kvtier.resolve_policy)
    # multimodal (models/llava.py): projected image features [K, H] f32 and
    # the prompt positions they occupy (the expanded image-token slots) —
    # injected into prefill instead of token embeddings
    mm_embeds: Any = None          # np.ndarray [K, H] | None
    mm_positions: Any = None       # np.ndarray [K] i64 | None
    queued_t: float = 0.0          # time.monotonic() at submit() — the
                                   # arrival instant the SLO layer measures
                                   # queue wait and TTFT from (0 = direct
                                   # construction, falls back to admission)
    resume: dict | None = None     # preemption resume payload (ISSUE 19,
                                   # engine/resume.ResumeToken.payload()):
                                   # prompt_ids is prompt+emitted; "emitted"
                                   # counts the trailing checkpoint tokens,
                                   # "key" restores the slot's RNG chain,
                                   # "sent_chars" suppresses re-emission of
                                   # text the client already received


@dataclasses.dataclass
class StepOutput:
    """One streamed chunk."""
    request_id: int
    text: str                 # newly-stable text (may be "")
    token_id: int
    logprob: float
    finished: bool
    finish_reason: str | None = None   # stop | length | eos
    generated_tokens: int = 0
    prompt_tokens: int = 0
    timings: dict | None = None        # per-request phase timeline, attached
                                       # to the FINAL chunk only (ISSUE 11;
                                       # None mid-stream or with the SLO
                                       # layer disabled)
    resume: dict | None = None         # ResumeToken.to_dict() riding the
                                       # terminal "preempted" chunk — the
                                       # spill-drain's checkpoint of this
                                       # request (ISSUE 19); None otherwise
    finished_t: float | None = None    # time.monotonic() of the host's finish
                                       # decision in _emit (where hist_e2e
                                       # ends), on the FINAL chunk of a
                                       # request the engine finished; the
                                       # gRPC handler times its reply from it


@dataclasses.dataclass
class _Slot:
    request_id: int
    req: GenRequest
    out: queue.Queue
    detok: Any                       # _IncrementalDecoder | None
    pending_text: str = ""           # holdback buffer for stop-string scan
    sent_chars: int = 0              # detok chars released downstream since
                                     # the ORIGINAL prompt boundary (global
                                     # across resume segments — the preempt
                                     # checkpoint's dedup cursor; excludes
                                     # pending_text, which a resume replays)
    resume_base: int = 0             # emitted-chain tokens replayed into
                                     # this slot at resume admission; a
                                     # second preempt folds them back into
                                     # the checkpoint's emitted list so
                                     # resumes compose exactly
    matcher: Any = None              # grammar MatcherState | None
    generated: int = 0
    gen_ids: list[int] = dataclasses.field(default_factory=list)
    start_time: float = 0.0
    first_token_time: float | None = None
    prompt_len: int = 0
    prefilled: bool = True           # False while chunked prefill in progress
    prefill_pos: int = 0             # prompt tokens already written to KV
    row: Any = None                  # sampler row (installed at final chunk)
    counts_row: Any = None
    shifted: int = 0                 # tokens evicted by context shifts
    disk_prefix: int = 0             # prefix length loaded from the disk
                                     # prompt cache (skip the re-save)
    fast_w: int | None = None        # narrowest sort-free top-k width that
                                     # covers this slot's sampling (None =
                                     # needs the full-sort path)
    span: Any = None                 # open telemetry span for this request
                                     # (None when tracing is disabled)
    inflight: int = 0                # tokens reserved by in-flight (not yet
                                     # consumed) decode dispatches — the
                                     # pipelined loop path budgets the NEXT
                                     # dispatch's per-slot `remaining` net of
                                     # this, so a slot can never overshoot
                                     # max_tokens however dispatches overlap
    # SLO phase timeline (ISSUE 11) — maintained only when the registry is
    # enabled (engine._slo is not None); all zeros/None otherwise
    join_t: float | None = None      # enqueue of the first decode dispatch
                                     # that carried this slot (admit_to_join
                                     # ends and join_to_first starts here)
    last_token_t: float | None = None    # host arrival of the latest token
                                         # batch (TPOT reference point)
    obs_tokens: int = 0              # generated count at last_token_t — the
                                     # fused loop delivers token BURSTS, so
                                     # TPOT is the amortized gap over the
                                     # burst, weighted by its token count
    path: str = ""                   # decode path that served the latest
                                     # token (loop/dense/spec)
    dispatches: int = 0              # device dispatches this request rode
                                     # (Kernel Looping's per-request number)
    timeline: dict | None = None     # finished-request record handed to the
                                     # flight recorder at release
    gbase: int | None = None         # base row of this slot's grammar in the
                                     # shared device mask/transition tables;
                                     # None = host-masked (matcher walks the
                                     # mask) because the automaton overflowed
                                     # grammar_table_states or tables are off


# The engine thread's one tick may take this long (a cold compile of the
# largest program is a few minutes); past it the device is taken for hung
# (a kernel that never returns: PERF.md section 7.5, PR 32) and the engine
# fails what it holds, with a message, instead of waiting for ever
TICK_LIMIT_S = 900.0


class _AsyncFetch:
    """Async, double-buffered device→host result streaming (PRESERVE-style
    overlap): the D2H copy of a dispatch's small outputs (tokens, logprobs,
    per-slot counters) STARTS the moment the dispatch is enqueued —
    `copy_to_host_async` — so block N's tokens land in host memory while
    block N+1 computes. `wait()` then completes through `jax.device_get`
    (the sanctioned explicit transfer); on the pipelined hot path the data
    has already arrived and the call returns without a device stall."""

    __slots__ = ("_arrays",)

    def __init__(self, arrays):
        self._arrays = tuple(arrays)
        for a in self._arrays:
            try:
                a.copy_to_host_async()
            except Exception:
                # layouts without an async path (some sharded/committed
                # arrays): wait() still fetches correctly, just later
                pass

    def wait(self):
        """Finish the copies; returns host numpy arrays in input order."""
        return tuple(np.asarray(jax.device_get(a)) for a in self._arrays)


class Engine:
    """Continuous-batching engine over one loaded model."""

    def __init__(
        self,
        cfg: LlamaConfig,
        params,
        tokenizer=None,
        econfig: EngineConfig | None = None,
        draft: tuple | None = None,
        kvhost=None,
    ):
        """`draft=(draft_cfg, draft_params)` enables speculative decoding:
        the engine proposes ec.gamma tokens per step with the draft model and
        verifies them in one target forward (engine/spec.py).

        `kvhost`: an existing engine/kvhost.HostKVPool to adopt instead of
        building one from ec.kv_host_bytes — host RAM outlives device state,
        so a restarted/rerouted worker re-admits the previous process's
        spilled blocks (the bench --mode session restart leg)."""
        self.cfg = cfg
        self.params = params
        self.tok = tokenizer
        self.ec = econfig or EngineConfig()
        self._draft = draft
        if self.ec.max_context > cfg.max_position:
            raise ValueError("max_context exceeds model max_position")
        for b in self.ec.prefill_buckets:
            if b > self.ec.max_context:
                raise ValueError("prefill bucket larger than max_context")

        B, T, V = self.ec.max_slots, self.ec.max_context, cfg.vocab_size
        dtype = jnp.dtype(self.ec.dtype) if self.ec.dtype else cfg.jdtype
        self.mesh = self.ec.mesh
        # single-process meshes (one host driving all chips) keep every
        # shard addressable: the disk prompt cache can slice/inject KV
        # host-side. Multi-host meshes can't (rank 0 host code isn't
        # replayed on followers), so the cache stays off there.
        self._cache_addressable = (self.mesh is None
                                   or jax.process_count() == 1)

        # paged KV (ops/paged.py): block pool + per-slot tables instead of a
        # dense [B, T] product. Host owns allocation; the device sees a
        # [B, MAXB] table per dispatch. Under a mesh the pool rides the XLA
        # gather path — block axis replicated, KV heads sharded on 'model'.
        # Incompatible (v1) with the disk prompt cache; context-shift runs
        # block-granular (cache_shift_paged); speculative decoding pages
        # the TARGET cache (the small draft keeps a dense one).
        self._paged = self.ec.kv_pages > 0
        # window and full attention layers in one model: two kinds of cache
        # (models/llama.py PeriodKV). What knows one cache per layer stack
        # refuses such a model here, at load, by name.
        self._mixed = cfg.layer_types is not None
        # linear-attention layers: a recurrent state beside the KV cache
        # (models/kv.py StateKV). It is not kept per position, so nothing
        # that lends, saves, shifts or takes back a prefix can serve it.
        # (state-space layers likewise: kv.SsmKV; _state_kind names which)
        self._state_kind = next(
            (k for k in (LINEAR, SSM) if self._mixed
             and k in cfg.layer_types), None)
        self._linear = self._state_kind is not None
        # latent-attention layers: one buffer of latent rows a layer
        # (models/kv.py LatentKV), kept per position, bfloat16
        self._latent = self._mixed and LATENT in cfg.layer_types
        mixed_name = ("a model with state-space layers"
                      if self._state_kind == SSM
                      else "a model with linear-attention layers"
                      if self._linear
                      else "a model with latent-attention layers"
                      if self._latent
                      else "a model with window and full attention layers")
        if self._latent and self.mesh is not None:
            raise ValueError(
                f"{mixed_name} (kv_lora_rank) cannot be served under a "
                "mesh: its weights and its cache have no sharding rule "
                "(heads sharded against data-parallel attention)")
        if self._state_kind == SSM and self.mesh is not None:
            raise ValueError(
                f"{mixed_name} (hybrid_override_pattern) cannot be served "
                "under a mesh: its weights and its state have no sharding "
                "rule")
        if self._mixed and self._paged:
            raise ValueError(
                f"{mixed_name} "
                "(layer_types) cannot be served with paged KV (kv_pages > "
                "0), nor with what rests on it: kv_policy windows, the "
                "host KV tier (kv_host_bytes) and its resume: the block "
                "pool holds one kind of cache. Serve it with kv_pages: 0")
        if self._mixed and self._draft is not None:
            raise ValueError(
                f"{mixed_name} "
                "(layer_types) cannot be served with speculative decoding "
                "(a draft model): the verify window writes ahead into a "
                "ring, or a recurrent state, it may have to take back, and "
                "a latent layer's verify window has no kernel")
        if self._paged:
            if self.ec.kv_pages < 2:
                raise ValueError("kv_pages must be >= 2 (block 0 is trash)")
        # KV lifecycle tier (engine/kvtier.py): a windowed engine policy
        # switches the paged table to COMPACT geometry — the per-slot table
        # row holds only sink_blocks identity columns plus a reused ring, so
        # decode gathers O(sinks + window) rows however long the sequence
        # runs. kv_policy="full" (the default) keeps kvt=None on every
        # dispatch path — byte-identical programs to the untiered engine.
        from localai_tpu.engine import kvtier

        self._kv_policy = kvtier.parse_policy(self.ec.kv_policy)
        self._tiered = self._kv_policy.windowed
        self._cold = self._tiered and self._kv_policy.quantize_cold
        if self._tiered:
            if not self._paged:
                raise ValueError(
                    "kv_policy sink_window requires paged KV (set kv_pages)")
            if self._draft is not None:
                raise ValueError(
                    "kv_policy sink_window is incompatible with a draft "
                    "model (the dense draft cache has no ring geometry)")
            if self.ec.replicator is not None:
                raise ValueError(
                    "kv_policy sink_window does not support multi-host "
                    "replication (per-slot ring geometry is host state)")
            self._kv_margin = kvtier.engine_margin_tokens(self.ec)
            self._kv_ring = kvtier.ring_blocks(self._kv_policy.window,
                                               self._kv_margin)
            self._kv_resident = kvtier.resident_blocks(self._kv_policy,
                                                       self._kv_margin)
            if self._kv_resident > self.ec.kv_pages - 1:
                raise ValueError(
                    f"kv_policy {self._kv_policy.describe()} needs "
                    f"{self._kv_resident} resident blocks per slot but the "
                    f"pool has {self.ec.kv_pages - 1}; raise kv_pages or "
                    f"shrink sinks/window")
            if self._cold:
                if self.ec.kv_cold_pages < 2:
                    raise ValueError(
                        "quantize_cold needs kv_cold_pages >= 2 (cold "
                        "block 0 is the not-demoted sentinel)")
                from localai_tpu.ops.kvcache import is_quant_kind

                if is_quant_kind(self.ec.cache_type):
                    raise ValueError(
                        "quantize_cold requires a dense hot cache "
                        "(cache_type=''): the cold tier is already int8")
        elif self.ec.kv_cold_pages:
            raise ValueError(
                "kv_cold_pages needs kv_policy sink_window(..., "
                "quantize_cold=true)")
        # host-RAM KV spill tier (engine/kvhost.py, ISSUE 17): catches
        # blocks the device pool evicts, keyed by the prefix cache's chain
        # hashes. The pool may be injected (worker restart adopts the old
        # process's host RAM); ec.kv_host_bytes=0 with no injected pool
        # keeps self._kvhost None — every hook below is one branch.
        self._kvhost = None
        self._host_pending: list = []
        self._spill_group: bytes | None = None
        if kvhost is not None or self.ec.kv_host_bytes > 0:
            if not self._paged:
                raise ValueError(
                    "kv_host_bytes requires paged KV (set kv_pages)")
            if self._draft is not None:
                raise ValueError(
                    "kv_host_bytes is incompatible with a draft model "
                    "(draft engines never consult the prefix cache)")
            if self.ec.replicator is not None:
                raise ValueError(
                    "kv_host_bytes does not support multi-host replication "
                    "(the spill/readmit transfers are host-rank state)")
            from localai_tpu.engine.kvhost import HostKVPool

            self._kvhost = (kvhost if kvhost is not None
                            else HostKVPool(self.ec.kv_host_bytes))
        if self._draft is not None and self._draft[0].vocab_size != V:
            raise ValueError("draft vocab differs from target")
        self._kv_dtype = dtype
        self._init_device_state()
        # window the verify extend writes ahead of `lengths`; reserve it so
        # a spec step can never write past the cache end
        self._ctx_reserve = (self.ec.gamma + 1) if self._draft else 0
        # chunked prefill: chunk window + the buckets small enough to prefill
        # single-shot without stalling running decodes longer than one chunk
        if self.ec.prefill_chunk < 8:
            raise ValueError("prefill_chunk must be >= 8")
        self._chunk = min(self.ec.prefill_chunk, self.ec.max_context)
        if (self._mixed and WINDOW in cfg.layer_types
                and self._chunk < self.ec.decode_block):
            raise ValueError(
                "a model with window layers needs prefill_chunk >= "
                "decode_block: a grammar rollback (_repair) takes back up to "
                "a block of tokens, which their rings (window + "
                "prefill_chunk) must not have overwritten the window with")
        small = tuple(b for b in self.ec.prefill_buckets if b <= self._chunk)
        dropped = tuple(b for b in self.ec.prefill_buckets if b > self._chunk)
        if dropped:
            import warnings

            warnings.warn(
                f"prefill buckets {dropped} exceed prefill_chunk="
                f"{self._chunk}; prompts longer than "
                f"{max(small) if small else self._chunk} tokens will prefill "
                f"in {self._chunk}-token chunks instead of single-shot",
                stacklevel=3)
        self._small_buckets = small or (self._chunk,)
        self._small_max = max(self._small_buckets)
        self._prefillq: list[int] = []   # slot indices mid-prefill, FIFO
        self._pending = None             # in-flight decode (pipeline depth 1)
        self._inflight_steps = 0         # step count of the pending dispatch
        self._queue: "queue.Queue[tuple[int, GenRequest, queue.Queue]]" = queue.Queue()
        self._next_id = 0
        # request ids marked for eviction by cancel() (client disconnect /
        # gRPC termination). Written from handler threads under _lock; the
        # loop thread reads bare — set membership is atomic under the GIL,
        # and a one-tick-late observation only costs one extra token.
        self._cancelled: set[int] = set()
        self._live: set[int] = set()   # rids submitted but not yet terminal
        self._lock = lockdep_lock("engine.submit")
        self._grammar_lock = lockdep_lock("engine.grammar")
        self._wake = threading.Event()
        self._running = False
        self._dead = False
        self._tick_began = None
        # the last step failure the loop recovered from ("" = none): a
        # caller that only sees a request end "error" can name the cause
        self.last_error = ""
        self._thread: threading.Thread | None = None
        # preemption spill-drain handshake (ISSUE 19): preempt() arms the
        # request + grace deadline from any thread; the engine thread runs
        # _spill_drain at a tick boundary and signals done
        self._preempt_req = threading.Event()
        self._preempt_done = threading.Event()
        self._preempt_t = 0.0
        self._preempt_manifest: list[dict] = []

        # metrics (reference MetricsResponse: backend.proto:40-46)
        self.metrics = {
            "requests_completed": 0,
            "tokens_generated": 0,
            "prompt_tokens_processed": 0,
            "prompt_tokens_reused": 0,
            "prompt_cache_hits": 0,
            "ttft_ms_last": 0.0,
            "tokens_per_second_last": 0.0,
            # dispatch-fusing telemetry: every dispatch pays a fixed host
            # cost (launch, result fetch, one scheduler tick), so
            # decode_steps_dispatched / decode_dispatches says how far that
            # is amortized (its size on a local chip is not measured)
            "decode_dispatches": 0,
            "decode_steps_dispatched": 0,
            "admit_dispatches": 0,
            # per-dispatch counters, credited TOGETHER where a dispatch is
            # consumed (_credit_consumed): dispatches whose results reached
            # the host and the steps the device reports it ran in them.
            # requests_admitted is credited at slot assignment. Their
            # ratios say how many steps a dispatch ran and how many
            # requests it let in.
            "decode_dispatches_consumed": 0,
            "decode_steps_consumed": 0,
            # ... and those of them whose program took the sort-free
            # sampler's top-k in two stages (sampling.topk_by_blocks)
            "decode_steps__topk_blocks": 0,
            "requests_admitted": 0,
            # rows x steps, tiled (_credit_consumed): the max_slots rows of
            # every consumed decode dispatch, each put down to one state for
            # each step the device ran. live: a token _emit took. spent: an
            # active row's step that gave none (after its last token inside
            # a block or loop, a whole pipelined dispatch after the finish,
            # a cancelled or rolled-back row, a rejected draft). prefill:
            # the slot held a request that was not prefilled at dispatch.
            # free_queued / free_starved: an empty slot, by whether the
            # engine had a request to admit at dispatch (_queue, _deferred).
            # Their sum is max_slots x decode_steps_consumed, exactly
            "decode_row_steps__live": 0,
            "decode_row_steps__spent": 0,
            "decode_row_steps__prefill": 0,
            "decode_row_steps__free_queued": 0,
            "decode_row_steps__free_starved": 0,
            # tokens x MoE layers, by the form the expert layer takes for
            # the call's shape (models/llama.expert_form, what _mlp itself
            # evaluates): a prompt's tokens at dispatch, a decode
            # dispatch's at consume. 0 for a model without experts
            "expert_tokens__routed": 0,
            "expert_tokens__dense": 0,
            # the expert layers of every call that takes the routed form,
            # and of them those whose grouped products' grid ends at the
            # tiles in use (ops/pallas/grouped_matmul.py, by the rule
            # _grouped_experts goes by: the kernel, so a TPU and no mesh)
            # and not at the static worst case the XLA loop's buffer has
            "expert_tile_calls__seen": 0,
            "expert_tile_calls__bounded": 0,
            # once a prompt chunk (_extend_mid / _extend_final), for ONE
            # full-attention layer: the rows of the slot's cache row the
            # chunk's attention visits (a dense cache: the blocks up to the
            # context the chunk has, kv.chunk_rows; a pool, a tier or a
            # sequence axis: the whole row) and the row's capacity. Their
            # ratio says how far the work follows the context
            "chunk_ctx_tokens__attended": 0,
            "chunk_ctx_tokens__capacity": 0,
            # cumulative ms the engine thread spent BLOCKED waiting for a
            # dispatch's results to land on the host (the async-fetch wait,
            # not the detok/stream fan-out) — per token this is the number
            # the decode-loop + copy_to_host_async work is driving to zero
            "host_sync_wait_ms": 0.0,
            # per-path token attribution (ISSUE 13): always-on so live
            # servers can compute constrained_over_plain-style ratios
            # from GetMetrics
            "tokens_by_path__loop": 0,
            "tokens_by_path__spec": 0,
            "tokens_by_path__dense": 0,
            # preemption-safe serving (ISSUE 19): spill-drains run, blocks
            # force-spilled, and resume admissions by coverage outcome
            "preempts": 0,
            "preempt_spilled_blocks": 0,
            "resume_readmits": 0,
            "resume_reprefills": 0,
        }
        self._live_from = 0   # tokens_generated at the last _credit_consumed
        # odd while a consumed dispatch's tokens are being emitted (between
        # _credit_consumed and _credit_live): metrics_snapshot waits it out
        self._consume_seq = 0
        if self._draft is not None:
            self.metrics["draft_proposed"] = 0
            self.metrics["draft_accepted"] = 0
        # a full layer's cache row as a chunk's attention sees it
        # (_credit_chunk_ctx): capacity, the view's window (kv.view), and
        # whether it is read whole whatever the chunk's context (what
        # kernel_tiers reports of the view)
        if self._mixed:
            t, window = max((k.shape[-2] for k, kind in zip(
                self._kc.slots, cfg.cache_kinds) if kind in (FULL, LATENT)),
                default=0), None
        else:
            t = T if self._paged else self._kc.shape[-2]
            window = cfg.sliding_window
        tier = self.kernel_tiers()["chunk_attention"]
        self._full_row = (t, window, not t or tier == "xla")
        if self._mixed:
            # two kinds of cache: how many layers and bytes (K and V) each
            # kind holds (gauges), and the context tokens ONE layer of each
            # kind attended over in the decode dispatches consumed so far
            # (_credit_consumed): their ratio is what the window saves
            # (a leading dense layer counts with its kind, and once more
            # under layers__leading_dense)
            kinds = cfg.cache_kinds
            if cfg.leading_dense_layers:
                self.metrics["layers__leading_dense"] = (
                    cfg.leading_dense_layers)
            for kind in set(kinds):
                self.metrics[f"layers__{kind}"] = (
                    cfg.layer_types.count(kind))
                self.metrics[f"kv_bytes__{kind}"] = sum(
                    leaf.nbytes
                    for cache in (self._kc, self._vc)
                    for slot, k in zip(cache.slots, kinds) if k == kind
                    for leaf in jax.tree_util.tree_leaves(slot))
                if not self._linear:
                    self.metrics[f"decode_ctx_tokens__{kind}"] = 0
            if self._latent:
                # context rows a prompt chunk put through the layer's
                # up-projection (W_kvb), for ONE latent layer: the blocks
                # its attention visits, each expanded once a chunk
                # (_credit_chunk_ctx); 0 for a chunk attended absorbed
                self.metrics["chunk_latent_rows__expanded"] = 0
                # the same rows, where the chunk's program holds the kernel
                # (kv.LatentKV.attend_window, by the rule kernel_tiers
                # reports) and not its twin, the XLA block loop
                self.metrics["chunk_latent_rows__kernel"] = 0
                self._chunk_kernel = tier.startswith("pallas")
            if self._linear:
                # what the decode steps consumed so far moved of each kind
                # of cache (_credit_consumed): K and V bytes the softmax
                # layers attended over, state bytes the linear layers read
                # and wrote. Bytes a slot a layer, from the arrays held:
                self._cache_bytes = {
                    kind: self.metrics[f"kv_bytes__{kind}"]
                    / (cfg.layer_types.count(kind) * B)
                    / (self._kc.slots[kinds.index(kind)].shape[-2]
                       if kind == FULL else 1)
                    for kind in (FULL, self._state_kind)}
                self.metrics["decode_cache_bytes__full"] = 0
                self.metrics[f"decode_cache_bytes__{self._state_kind}"] = 0
            if self._state_kind == LINEAR:
                # the real tokens of a prefill or a chunk call x the linear
                # layers: what went through the chunkwise gated delta rule
                # (_credit_chunk_state), and of it what the kernel served
                # (ops/pallas/kda.py: kda_chunk, by the rule
                # kv.StateKV._mix goes by) and not its twin, ops/kda.py's
                self.metrics["chunk_state_tokens__seen"] = 0
                self.metrics["chunk_state_tokens__kernel"] = 0
                self._state_kernel = _pallas_attention(self.mesh)
        if self._tiered:
            # KV lifecycle telemetry: cold demotions, evictions (window-
            # exited blocks dropped — ring overwrite, or a full cold pool),
            # prefix-cache blocks re-prefilled because ring columns can't be
            # borrowed, admission-time full→window demotions, and pool
            # occupancy (peak proves the O(sinks+window) residency bound)
            self.metrics.update(
                kv_cold_blocks=0, kv_evictions=0, kv_recomputes=0,
                kv_policy_demotions=0, kv_blocks_in_use=0, kv_blocks_peak=0)
        if self._kvhost is not None:
            # host-tier telemetry (ISSUE 17): occupancy is refreshed from
            # the pool at each _host_drain; hits/spills/evictions are the
            # pool's cumulative counters (shared across engines adopting
            # the same pool — restart legs keep their history)
            self.metrics.update(
                kv_host_blocks=0, kv_host_bytes=0, kv_host_bytes_peak=0,
                kv_host_hits=0, kv_host_spills=0, kv_host_evictions=0)

        # telemetry (localai_tpu/telemetry): the ring tracer resolves to
        # None here when LOCALAI_TRACE is off; the phase clock is always on
        # (engine_host_ms__* / engine_wait_ms__* in self.metrics, a
        # TraceAnnotation per phase) and costs a clock read per phase switch,
        # a handful per tick — nothing per token or per step
        from localai_tpu import telemetry

        self._tracer = telemetry.maybe_tracer()
        self._phases = telemetry.PhaseClock(self.metrics, self._tracer)
        # serving SLO layer (ISSUE 11): streaming histograms + the flight
        # recorder, one attribute load and a branch when disabled
        # (LOCALAI_METRICS=0 → both None)
        self._slo = telemetry.maybe_slo()
        self._flightrec = (telemetry.flightrec()
                           if self._slo is not None else None)
        self._tick_n = 0
        # scheduler X-ray (ISSUE 13): the per-tick pack ledger — None when
        # disabled (LOCALAI_SCHED=0 / LOCALAI_METRICS=0), keeping step() on
        # the one-branch contract. Per-engine instance: bench runs several
        # engines in one process and their streams must not mix.
        self._sched = telemetry.maybe_ledger()
        self._set_tick = telemetry.set_current_tick
        # per-variant (jit fn, abstract arg shapes) captured at first
        # dispatch — rooflines() AOT-lowers the SAME traced programs later
        self._variant_avals: dict = {}
        self._rooflines: dict | None = None

        # runtime tripwire (localai_tpu/testing/tripwires): with
        # LOCALAI_TRANSFER_GUARD set, every decode dispatch runs under
        # jax.transfer_guard(level) — an implicit host transfer inside the
        # fused block raises instead of silently stalling the pipeline
        from localai_tpu.testing.tripwires import decode_guard_level

        self._xfer_guard = decode_guard_level()

        self._build_jit()

    def _init_device_state(self):
        """(Re)create all device-held serving state: KV caches, sampler,
        logits, lengths, paged tables, grammar masks, host slot table.
        Called at construction and again by the loop's self-restart path —
        params are never donated, so a fresh state block is all a recovery
        needs after a fatal device error."""
        cfg, B, T = self.cfg, self.ec.max_slots, self.ec.max_context
        V, dtype = cfg.vocab_size, self._kv_dtype
        if self._paged:
            from localai_tpu.ops.paged import BLOCK

            # tiered engines run the COMPACT table: resident columns per
            # slot (sinks + ring), not ceil(max_context/128) — the whole
            # point of the lifecycle tier (decode gathers O(resident) rows)
            self._maxb = (self._kv_resident if self._tiered
                          else -(-T // BLOCK))
            self._table = np.zeros((B, self._maxb), np.int32)
            self._kv_free: list[int] = list(range(1, self.ec.kv_pages))
            self._slot_blocks: list[list[int]] = [[] for _ in range(B)]
            self._released_lru: list[int] = []
            # block-level prefix cache: refcounted shared pages. A block's
            # refcount is the number of slot block-lists (live or released-
            # retained) holding it; the chain-hash index maps a full
            # 128-token content prefix to the physical block still storing
            # its K/V, letting a new admission map another tenant's pages
            # straight into its table (copy-on-write: borrowed pages are
            # never written — see _alloc_slot).
            self._block_ref = np.zeros(self.ec.kv_pages, np.int64)
            self._block_ref[0] = 1          # trash block: pinned forever
            self._hash_index: dict[bytes, int] = {}
            self._block_hash_of: dict[int, bytes] = {}
        if self._tiered:
            from localai_tpu.ops.paged import BLOCK

            # per-slot ring geometry, shipped with every dispatch (_kvt).
            # Full-policy sentinels: sb = table width makes the ring map the
            # identity and every column resident; window/sinks sentinels at
            # max_context keep the retention mask all-true for any length.
            self._kv_sb = np.full((B,), self._maxb, np.int32)
            self._kv_rw = np.ones((B,), np.int32)
            self._kv_sinks = np.full((B,), T, np.int32)
            self._kv_window = np.full((B,), T, np.int32)
            self._slot_policy: list = [None] * B
            # next raw (virtual) block index eligible for demotion/eviction
            # per slot — advanced by _kv_tick as tokens exit the window
            self._demote_next = np.zeros((B,), np.int64)
            if self._cold:
                self._cold_maxb = -(-T // BLOCK)
                self._cold_table = np.zeros((B, self._cold_maxb), np.int32)
                self._cold_free: list[int] = list(
                    range(1, self.ec.kv_cold_pages))
                self._slot_cold: list[list[int]] = [[] for _ in range(B)]
        self._deferred: tuple | None = None   # admission waiting on blocks
        self._admitting: tuple | None = None  # admission mid-device-call
        self._blocks_freed = False
        # in-flight D2H spills (hash, group, _AsyncFetch) — dropped on a
        # device-state rebuild: their source buffers died with the error
        # (the pool claims opened by begin_spill must be abandoned too, or
        # the chain pins they hold would leak forever)
        if getattr(self, "_host_pending", None) and self._kvhost is not None:
            for h, _group, _fetch in self._host_pending:
                self._kvhost.end_spill(h, None)
        self._host_pending = []

        with activate_mesh(self.mesh):
            self._cos, self._sin = rope_tables(cfg, T)
            if self._paged:
                from localai_tpu.ops.paged import init_paged

                self._kc, self._vc = init_paged(
                    cfg.num_layers, self.ec.kv_pages, cfg.num_kv_heads,
                    cfg.head_dim, dtype, cache_type=self.ec.cache_type)
                if self._cold:
                    # parallel int8 cold pool (sub-channel per-token scales,
                    # Transformer-Lite): window-exited blocks are copied
                    # here by _dev_demote and read back through cold_tab
                    self._ck, self._cv = init_paged(
                        cfg.num_layers, self.ec.kv_cold_pages,
                        cfg.num_kv_heads, cfg.head_dim, dtype,
                        cache_type="int8")
            else:
                self._kc, self._vc = init_kv_cache(
                    cfg, B, T, dtype, cache_type=self.ec.cache_type,
                    prefill_chunk=min(self.ec.prefill_chunk, T))
            if self.mesh is not None and jax.process_count() == 1:
                # pre-place the KV state under its serving sharding (slots
                # on 'data', KV heads on 'model'; paged pool: block axis
                # replicated) so the first donated dispatch doesn't pay a
                # layout move and GSPMD never defaults the pool to
                # replicated. safe_sharding degrades non-dividing axes to
                # replicated instead of refusing to serve.
                from localai_tpu.models.llama import (
                    kv_cache_spec, paged_pool_spec,
                )
                from localai_tpu.parallel.mesh import safe_sharding

                kv_spec = paged_pool_spec() if self._paged \
                    else kv_cache_spec()
                place = lambda t: jax.tree_util.tree_map(  # noqa: E731
                    lambda a: jax.device_put(
                        a, safe_sharding(self.mesh, kv_spec, a.shape)), t)
                self._kc, self._vc = place(self._kc), place(self._vc)
            self._sampler = SamplerState.init(B, V)
            self._last_logits = jnp.zeros((B, V), jnp.float32)
            self._lengths = jnp.zeros((B,), jnp.int32)
            # device-resident EOS id set for the fused decode loop's on-device
            # stop condition (padded with -1 when the model has no tokenizer —
            # no sampled token matches, the budget/margin conditions still
            # bound the loop). Uploaded once, never per dispatch.
            eos = sorted(self.tok.eos_ids) if (
                self.tok is not None and getattr(self.tok, "eos_ids", None)
            ) else []
            self._eos_dev = jnp.asarray(
                np.asarray(eos or [-1], np.int32))
            if self._draft is not None:
                dcfg = self._draft[0]
                self._cos_d, self._sin_d = rope_table(dcfg.rope, T)
                self._kcd, self._vcd = init_kv_cache(dcfg, B, T, dtype)
                self._next_tokens = jnp.zeros((B,), jnp.int32)

        # grammar masks: one bitmask row per slot, all-ones = unconstrained
        self._mask_nbytes = (V + 7) // 8
        self._mask_host = np.full((B, self._mask_nbytes), 0xFF, np.uint8)
        self._grammar_slots = 0
        self._grammar_hostonly = 0   # grammar slots WITHOUT device tables
                                     # (automaton overflowed the cap): these
                                     # keep the per-token host-mask paths and
                                     # bar the fused while-loop
        self._grammar_cache = None
        # device grammar tables (grammar_table_states > 0): ONE shared pair
        # of arrays for every live grammar — masks [cap, ceil(V/32)] u32
        # (LSB-first packed allowed-token rows) and trans [cap, V] i32
        # (absolute next-state per token). Row 0 is the IDENTITY state every
        # unconstrained slot sits in: all-ones mask (where(True, x, -inf) is
        # x exactly, so constrained and unconstrained slots share one
        # compiled program bit-identically) and a self-loop transition.
        # Grammars get base offsets in _grammar_table_entry; the np mirrors
        # are authoritative (host _emit advances _gstate through _gtrans_np)
        # and the device copies refresh lazily on new installs (_gtab —
        # same shapes, so no recompile).
        self._mask_nwords = (V + 31) // 32
        self._gtab_cap = max(int(self.ec.grammar_table_states), 0)
        self._gstate = np.zeros((B,), np.int32)
        if self._gtab_cap:
            self._gmasks_np = np.zeros((self._gtab_cap, self._mask_nwords),
                                       np.uint32)
            self._gmasks_np[0] = 0xFFFFFFFF
            self._gtrans_np = np.zeros((self._gtab_cap, V), np.int32)
            self._gtab_used = 1
            self._gtab_base: dict[str, int | None] = {}
            self._gtab_dirty = True
            self._gmasks_dev = None
            self._gtrans_dev = None

        # host-side slot table
        self._slots: list[_Slot | None] = [None] * B
        self._free: list[int] = list(range(B))
        # prompt cache: per slot, the token ids whose K/V rows are still
        # valid in that slot's cache region (recorded at release)
        self._slot_kv_tokens: list[list[int]] = [[] for _ in range(B)]

    # ------------------------------------------------------------ jit builds

    def _build_jit(self):
        cfg = self.cfg

        def _install_row(sampler, slot, row, counts_row):
            # single-row install == the K=1 batched case (one body to keep
            # in sync with SamplerState's fields)
            return _install_rows(
                sampler, slot[None], {k: v[None] for k, v in row.items()},
                None if counts_row is None else counts_row[None])

        def _install_rows(sampler, slots, rows, counts_rows):
            """Install K sampler rows at `slots` [K]; rows' fields are
            stacked [K, ...]. counts_rows is [K, V] or None. "Light" rows
            (no penalties, no bias — the common case) omit the [V]-sized
            logit_bias and counts so an admission ships a few scalars instead
            of ~1 MB of host→device copies; absent fields are
            zeroed on device. None/missing keys are static → each variant
            compiles once."""
            new_fields = {}
            for f in dataclasses.fields(SamplerState):
                cur = getattr(sampler, f.name)
                if f.name == "token_counts":
                    if counts_rows is None:
                        new_fields[f.name] = cur.at[slots].set(0)
                    else:
                        new_fields[f.name] = cur.at[slots].set(counts_rows)
                elif f.name == "logit_bias" and "logit_bias" not in rows:
                    new_fields[f.name] = cur.at[slots].set(0.0)
                else:
                    new_fields[f.name] = cur.at[slots].set(rows[f.name])
            return SamplerState(**new_fields)

        def _admit_many(params, cos, sin, kc, vc, sampler, last_logits,
                        lengths, tokens, lens, slots, rows, counts_rows,
                        table=None, inject=None, kvt=None):
            """Admission burst: prefill K same-bucket requests in ONE pass.

            The single-request _admit streams the full weight set per call —
            a 16-slot burst pays 16 weight streams and 16 dispatches.
            Batching the burst reads the weights once in one dispatch (the
            reference can't do this — llama.cpp prefills slots one ubatch at
            a time, grpc-server.cpp update_slots). The TTFT it buys on a
            local chip is not measured."""
            logits, kc, vc = prefill(
                params, cfg, tokens, lens, cos, sin, kc, vc, slots, table,
                inject, kvt
            )
            last_logits = last_logits.at[slots].set(logits)
            lengths = lengths.at[slots].set(lens)
            sampler = _install_rows(sampler, slots, rows, counts_rows)
            return kc, vc, sampler, last_logits, lengths

        def _extend_mid(params, cos, sin, kc, vc, tokens, start, slot,
                        table=None, inject=None, kvt=None):
            """One non-final prefill chunk: KV writes only. Mid chunks are
            always full (the final chunk takes _extend_final), so every
            position sits inside the slot's allocation → full_window keeps
            the paged scatter on the asserted-unique in-place path."""
            _, kc, vc = extend(params, cfg, tokens, start[None], cos, sin,
                               kc, vc, slot_map=slot[None], with_logits=False,
                               table=table, inject=inject, full_window=True,
                               kvt=kvt)
            return kc, vc

        def _extend_final(params, cos, sin, kc, vc, sampler, last_logits,
                          lengths, tokens, start, nvalid, slot, row,
                          counts_row, table=None, inject=None, kvt=None):
            """Final prefill chunk: KV writes + last-token logits + sampler
            row install (deferred to here so the request's RNG stream is
            independent of how many engine ticks the prefill spanned)."""
            logits, kc, vc = extend(
                params, cfg, tokens, start[None], cos, sin, kc, vc,
                slot_map=slot[None],
                last_pos=jnp.maximum(nvalid - 1, 0)[None], table=table,
                inject=inject, kvt=kvt)
            last_logits = last_logits.at[slot].set(logits[0])
            lengths = lengths.at[slot].set(start + nvalid)
            sampler = _install_row(sampler, slot, row, counts_row)
            return kc, vc, sampler, last_logits, lengths

        def _decode(params, cos, sin, kc, vc, sampler, last_logits, lengths,
                    active, mask_bits, fast_width=None, table=None, kvt=None):
            """sample(prev logits) → decode → next logits, for all slots."""
            tokens, keys, logprobs = sample(last_logits, sampler, mask_bits,
                                            topk_width=fast_width)
            logits, kc, vc = decode_step(
                params, cfg, tokens, lengths, cos, sin, kc, vc, active, table,
                kvt
            )
            act = active.astype(jnp.int32)
            counts = sampler.token_counts.at[
                jnp.arange(tokens.shape[0]), tokens
            ].add(act)
            sampler = dataclasses.replace(
                sampler, key=keys, token_counts=counts
            )
            lengths = lengths + act
            return tokens, logprobs, kc, vc, sampler, logits, lengths

        # multi-host: the engine's host decisions (tokens to write, slot
        # indices, masks) must be readable on rank 0 even when slots shard
        # over hosts — replicate the tiny per-step outputs
        from localai_tpu.parallel.mesh import constrain
        from jax.sharding import PartitionSpec as P

        _decode_raw = _decode

        def _decode(*a, **kw):
            tokens, logprobs, kc, vc, sampler, logits, lengths = _decode_raw(
                *a, **kw)
            return (constrain(tokens, P(None)), constrain(logprobs, P(None)),
                    kc, vc, sampler, logits, lengths)

        # donate the big carried buffers: cache stays in place in HBM.
        # mask_bits=None compiles a no-grammar variant with zero extra
        # host→device traffic on the common path.
        self._admit_many_fn = jax.jit(_admit_many,
                                      donate_argnums=(3, 4, 5, 6, 7))
        self._extend_mid_fn = jax.jit(_extend_mid, donate_argnums=(3, 4))
        self._extend_final_fn = jax.jit(_extend_final,
                                        donate_argnums=(3, 4, 5, 6, 7))
        # context shift: keep/discard are static → one compiled program
        if self._paged:
            # block-granular (models/llama.py cache_shift_paged): keep the
            # sink block(s), drop a half-context worth of whole blocks; the
            # slide itself is a host-side table permutation
            from localai_tpu.ops.paged import BLOCK

            from localai_tpu.models.llama import cache_shift_paged

            self._shift_keepb = max(1, -(-self.ec.shift_keep // BLOCK))
            self._shift_discb = max(1, (self._maxb - self._shift_keepb) // 2)
            self._shift_discard = self._shift_discb * BLOCK
            # a shift must leave at least one tail block to slide: tiny
            # contexts (maxb <= keepb+discb) cannot evict block-granularly —
            # submit() rejects context_shift there instead of driving
            # lengths negative
            self._shift_ok = self._maxb > (self._shift_keepb
                                           + self._shift_discb)

            def _shift_paged(kc, lengths, row_table, slot):
                kc = cache_shift_paged(
                    cfg, kc, row_table, keep_blocks=self._shift_keepb,
                    discard_blocks=self._shift_discb)
                return kc, lengths.at[slot].add(-self._shift_discard)

            self._shift_fn = jax.jit(_shift_paged, donate_argnums=(0, 1))
        else:
            self._shift_discard = max(
                1, (self.ec.max_context - self.ec.shift_keep) // 2)
            self._shift_fn = jax.jit(
                partial(cache_shift, cfg, keep=self.ec.shift_keep,
                        discard=self._shift_discard),
                donate_argnums=(0, 1, 2))

        if self._draft is not None:
            from localai_tpu.engine.spec import (
                build_draft_ingest, build_spec_admit_tail, build_spec_decode,
            )

            if self._paged:
                from localai_tpu.ops.paged import BLOCK

                if self.ec.max_slots * (self.ec.gamma + 1) > BLOCK:
                    import logging

                    logging.getLogger("localai_tpu").warning(
                        "paged spec verify: %d slots x (gamma+1)=%d trash "
                        "offsets exceed one %d-token block, so the verify "
                        "scatter cannot assert uniqueness — expect reduced "
                        "paged throughput; lower max_slots or gamma to "
                        "restore the in-place path",
                        self.ec.max_slots, self.ec.gamma + 1, BLOCK)

            dcfg = self._draft[0]
            _spec_raw = build_spec_decode(cfg, dcfg, self.ec.gamma)

            def _spec(*a):
                # host (rank 0) reads the small per-step outputs each spec
                # step — replicate them, as with _decode above
                (tokens_out, n_out, logprobs_out, next_tokens, kct, vct,
                 kcd, vcd, sampler, lengths, n_extra) = _spec_raw(*a)
                return (constrain(tokens_out, P(None)),
                        constrain(n_out, P(None)),
                        constrain(logprobs_out, P(None)),
                        constrain(next_tokens, P(None)),
                        kct, vct, kcd, vcd, sampler, lengths,
                        constrain(n_extra, P(None)))

            self._spec_fn = jax.jit(
                _spec, donate_argnums=(6, 7, 8, 9, 10, 11, 12))
            self._spec_admit_tail_fn = jax.jit(
                build_spec_admit_tail(cfg), donate_argnums=(0,))
            self._draft_ingest_fn = jax.jit(
                build_draft_ingest(dcfg), donate_argnums=(3, 4))

        def named(fn, name: str):
            # jax.jit names a functools.partial's program `jit__unknown`. A
            # model with window and full layers spends whole seconds in
            # single decode steps between prefill chunks, and a device trace
            # of those has to find them (benchmark/programs/decode.json
            # looks for jit__decode*): there the partial takes the name of
            # what it wraps. A model with one kind of layer keeps the
            # programs, names and compile-cache keys it had.
            if cfg.period:
                fn.__name__ = name
            return fn

        self._decode_fn = jax.jit(_decode, donate_argnums=(3, 4, 5, 6, 7),
                                  static_argnames=())
        self._decode_nomask_fn = jax.jit(
            named(partial(_decode, mask_bits=None), "_decode"),
            donate_argnums=(3, 4, 5, 6, 7))
        # fast_width static → one compiled variant per width (the base
        # width plus the 8x escalation tier: one wide-top_k tenant no
        # longer de-optimizes the whole batch to the full-sort path)
        self._decode_fast_fn = jax.jit(
            named(partial(_decode, mask_bits=None), "_decode"),
            donate_argnums=(3, 4, 5, 6, 7),
            static_argnames=("fast_width",))

        def _decode_block(params, cos, sin, kc, vc, sampler, last_logits,
                          lengths, active, mask_bits=None, table=None,
                          kvt=None, *, steps: int, fast_width=None):
            """`steps` fused sample→decode iterations in ONE device program.

            One dispatch + one result fetch per `steps` tokens amortizes
            the per-call host cost (launch, fetch, one scheduler tick) over
            the block; what that is worth on a local chip is not measured.
            Grammar slots ride the block with
            their block-START mask held fixed; the host verifies each sampled
            token against the PDA afterwards and rolls the slot back at the
            first stale-mask miss (engine._repair) — free slots keep full
            block speed either way."""
            def body(carry, _):
                kc, vc, sampler, last_logits, lengths = carry
                tokens, logprobs, kc, vc, sampler, last_logits, lengths = (
                    _decode(params, cos, sin, kc, vc, sampler, last_logits,
                            lengths, active, mask_bits, fast_width, table,
                            kvt))
                return (kc, vc, sampler, last_logits, lengths), (tokens,
                                                                 logprobs)
            carry = (kc, vc, sampler, last_logits, lengths)
            carry, (toks, lps) = jax.lax.scan(body, carry, None, length=steps)
            kc, vc, sampler, last_logits, lengths = carry
            return toks, lps, kc, vc, sampler, last_logits, lengths

        self._decode_block_fn = jax.jit(
            named(partial(_decode_block, mask_bits=None), "_decode_block"),
            donate_argnums=(3, 4, 5, 6, 7),
            static_argnames=("steps", "fast_width"))
        self._decode_block_mask_fn = jax.jit(
            _decode_block, donate_argnums=(3, 4, 5, 6, 7),
            static_argnames=("steps", "fast_width"))

        # single-dispatch decode loop (Kernel Looping): the while-loop
        # variant of the scan block, with stop conditions ON DEVICE and
        # early exit — one dispatch per decode_loop-token block instead of
        # the scan ladder's 4-8 (models/llama.build_decode_loop). The raw
        # (un-constrained) _decode is the body so the per-step RNG/count
        # semantics are bit-identical to the other paths; the tiny outputs
        # are replicated for the rank-0 host read like _decode's.
        self._decode_loop_fn = None
        if self.ec.decode_loop > 1:
            from localai_tpu.models.llama import build_decode_loop

            _loop_raw = build_decode_loop(
                _decode_raw,
                max_steps=self.ec.decode_loop,
                limit=self.ec.max_context - 2 - self._ctx_reserve)

            def _loop(*a, **kw):
                (toks, lps, n_out, steps, kc, vc, sampler, last_logits,
                 lengths) = _loop_raw(*a, **kw)
                return (constrain(toks, P(None, None)),
                        constrain(lps, P(None, None)),
                        constrain(n_out, P(None)), steps,
                        kc, vc, sampler, last_logits, lengths)

            self._decode_loop_fn = jax.jit(
                _loop, donate_argnums=(3, 4, 5, 6, 7),
                static_argnames=("fast_width",))

        # cold demotion: copy ONE hot physical block into a cold-pool index
        # with sub-channel (per-token over head_dim) int8 quantization.
        # pb/ci are traced scalars → one compiled program however many
        # blocks ever demote (the compile-count tripwire stays green).
        self._demote_fn = None
        if self._cold:
            from localai_tpu.ops.kvcache import QuantKV, quantize_tokens

            def _demote(kc, vc, ck, cv, pb, ci):
                def one(hot, cold):
                    blk = hot[:, pb]                      # [L, KVH, BS, D]
                    q, scale = quantize_tokens(blk)       # scale [L,KVH,BS]
                    return QuantKV(
                        cold.q.at[:, ci].set(q),
                        cold.s.at[:, ci].set(
                            scale[:, :, None, :].astype(cold.s.dtype)))
                return one(kc, ck), one(vc, cv)

            self._demote_fn = jax.jit(_demote, donate_argnums=(2, 3))

        # host-RAM spill tier (ISSUE 17): slice ONE physical block out of
        # the hot pool in int8 sub-channel form (spill), and write one host
        # block back into fresh physical pages (readmit). pb is a traced
        # scalar → one compiled program each however many blocks move (the
        # compile-count tripwire pins decode_step; these are admission-side
        # programs like _demote_fn). A quantized hot pool spills its q/s
        # bytes verbatim — the round trip is byte-exact, which is what the
        # --mode session greedy-parity gate measures; a dense pool pays the
        # same quantize_tokens error the kvtier cold read path accepts.
        self._spill_fn = None
        self._readmit_fn = None
        if self._kvhost is not None:
            from localai_tpu.ops.kvcache import (
                QuantKV, is_quant_kind, quantize_tokens,
            )

            if is_quant_kind(self.ec.cache_type):
                def _spill(kc, vc, pb):
                    return (kc.q[:, pb], kc.s[:, pb],
                            vc.q[:, pb], vc.s[:, pb])

                def _readmit(kc, vc, kq, ks, vq, vs, pb):
                    return (QuantKV(kc.q.at[:, pb].set(kq),
                                    kc.s.at[:, pb].set(ks)),
                            QuantKV(vc.q.at[:, pb].set(vq),
                                    vc.s.at[:, pb].set(vs)))
            else:
                def _spill(kc, vc, pb):
                    def one(hot):
                        q, scale = quantize_tokens(hot[:, pb])
                        # scale [L,KVH,BS] → the stored [L,KVH,1,BS] tile
                        return q, scale[:, :, None, :]
                    (kq, ks), (vq, vs) = one(kc), one(vc)
                    return kq, ks, vq, vs

                def _readmit(kc, vc, kq, ks, vq, vs, pb):
                    def one(hot, q, s):
                        blk = (q.astype(jnp.float32)
                               * s[:, :, 0, :, None]).astype(hot.dtype)
                        return hot.at[:, pb].set(blk)
                    return one(kc, kq, ks), one(vc, vq, vs)

            self._spill_fn = jax.jit(_spill)
            self._readmit_fn = jax.jit(_readmit, donate_argnums=(0, 1))

    # ------------------------------------------------------ device dispatch
    # Every device call goes through one of these. On a multi-host mesh the
    # rank-0 engine broadcasts (op, args) over the Replicator side channel
    # first; follower ranks replay the identical sequence via follow() so the
    # SPMD programs stay in lockstep (parallel/distributed.py).

    def _bcast(self, op: str, **kw):
        rep = self.ec.replicator
        if rep is not None:
            rep.broadcast(op, {
                k: (np.asarray(v) if hasattr(v, "shape") or isinstance(
                    v, (list, tuple)) else v)
                for k, v in kw.items()})

    def _tab(self):
        """Device copy of the block table for this dispatch (paged KV only).
        Tiny ([B, MAXB] i32) — shipping it per call keeps the host allocator
        the single source of truth with no donation bookkeeping."""
        return jnp.asarray(self._table) if self._paged else None

    def _kvt(self):
        """Per-slot KV-tier geometry for this dispatch (None on untiered
        engines — every jitted program then traces WITHOUT the tier branch,
        byte-identical to the pre-tier engine). Like _tab(), the tiny [B]
        arrays ship per call as runtime data: any mix of full and windowed
        slots (and any demotion state) reuses one compiled program."""
        if not self._tiered:
            return None
        d = {"sb": jnp.asarray(self._kv_sb), "rw": jnp.asarray(self._kv_rw),
             "sinks": jnp.asarray(self._kv_sinks),
             "window": jnp.asarray(self._kv_window)}
        if self._cold:
            d["cold_k"], d["cold_v"] = self._ck, self._cv
            d["cold_tab"] = jnp.asarray(self._cold_table)
        return d

    def _gtab(self):
        """Device copies of the shared grammar tables (masks u32, trans
        i32). Re-uploaded only after a new grammar install marked them
        dirty — same shapes every time, so every consumer program compiles
        exactly once and the upload is off the per-token hot path."""
        if self._gtab_dirty:
            with activate_mesh(self.mesh):
                self._gmasks_dev = jnp.asarray(self._gmasks_np)
                self._gtrans_dev = jnp.asarray(self._gtrans_np)
            self._gtab_dirty = False
        return self._gmasks_dev, self._gtrans_dev

    def _dev_gtable(self, base: int, masks, trans):
        """Install one grammar's precompiled rows at `base` in the shared
        table mirrors (device copies refresh lazily via _gtab). Broadcast so
        follower ranks hold identical tables for the loop/spec replays."""
        self._bcast("gtable", base=base, masks=masks, trans=trans)
        n = masks.shape[0]
        self._gmasks_np[base:base + n] = masks
        self._gtrans_np[base:base + n] = trans
        self._gtab_dirty = True

    def _grammar_table_entry(self, grammar: str) -> int | None:
        """Base offset of this grammar's rows in the shared device tables,
        building + installing them (off the hot path) on first use. None =
        the automaton doesn't fit (table overflow, or tables disabled) — the
        slot then keeps the per-token host-mask paths."""
        if not self._gtab_cap:
            return None
        if grammar in self._gtab_base:
            return self._gtab_base[grammar]
        cg = self._compile_grammar(grammar)
        tbl = cg.table(self._gtab_cap)
        base = None
        if tbl is not None and self._gtab_used + tbl.n_states <= self._gtab_cap:
            base = self._gtab_used
            masks = tbl.masks.copy()
            # local -1 (token masked off — never sampled) → absolute 0; the
            # identity row is harmless if ever gathered. Live states remap
            # to base-relative absolute indices.
            trans = np.where(tbl.trans < 0, 0,
                             tbl.trans + base).astype(np.int32)
            # EOS policy is per-tokenizer, injected here (the raw table has
            # no EOS bits — matcher.mask_bits parity): accepting states
            # allow EOS and self-loop on it, mirroring the host matcher
            # which never advances past EOS.
            V = self.cfg.vocab_size
            eos = [e for e in (self.tok.eos_ids if self.tok else ())
                   if 0 <= e < V]
            for s in range(tbl.n_states):
                if tbl.accepting[s]:
                    for e in eos:
                        masks[s, e >> 5] |= np.uint32(1) << np.uint32(e & 31)
                        trans[s, e] = base + s
            self._dev_gtable(base, masks, trans)
            self._gtab_used = base + tbl.n_states
            self.metrics["grammar_table_states"] = self._gtab_used
        else:
            self.metrics["grammar_table_overflows"] = (
                self.metrics.get("grammar_table_overflows", 0) + 1)
            if self._sched is not None:
                self._sched.reason("grammar_table_overflow",
                                   states=(0 if tbl is None
                                           else int(tbl.n_states)))
        self._gtab_base[grammar] = base
        return base

    def _note_pool(self):
        """Refresh the pool-occupancy gauges (tiered engines only — the
        peak is the bench's O(sinks+window) residency proof)."""
        if not self._tiered:
            return
        used = self.ec.kv_pages - 1 - len(self._kv_free)
        self.metrics["kv_blocks_in_use"] = used
        if used > self.metrics["kv_blocks_peak"]:
            self.metrics["kv_blocks_peak"] = used

    def _decode_guard(self):
        """Transfer-guard context for the decode dispatch (nullcontext unless
        LOCALAI_TRANSFER_GUARD is set — see testing/tripwires)."""
        if self._xfer_guard:
            return jax.transfer_guard(self._xfer_guard)
        return contextlib.nullcontext()

    def _sched_pack(self, variant: str, fn, fargs, fkw, **comp):
        """Tick-ledger dispatch record (ISSUE 13): the pack composition of
        one dispatch under its compiled-program variant name, plus a one-
        time capture of the program's abstract arg shapes
        (jax.ShapeDtypeStruct — no buffer refs, so donation can't dangle)
        for the lazy AOT cost-analysis pass in rooflines(). One None-check
        when the ledger is disabled."""
        sched = self._sched
        if sched is None:
            return
        if variant not in self._variant_avals:
            try:
                def _aval(x):
                    if hasattr(x, "shape") and hasattr(x, "dtype"):
                        return jax.ShapeDtypeStruct(x.shape, x.dtype)
                    return x
                self._variant_avals[variant] = (
                    fn, jax.tree_util.tree_map(_aval, fargs),
                    jax.tree_util.tree_map(_aval, fkw))
            except Exception:
                self._variant_avals[variant] = None
        sched.pack(variant, **comp)

    def _dev_admit(self, ids, n, slot, row, counts_row, inject=None):
        # single admission == the K=1 batched case (the delegate broadcasts
        # "admit_many"; the "admit" follower op is kept for replay compat)
        self._dev_admit_many(
            np.asarray(ids, np.int32), np.asarray([n], np.int32),
            np.asarray([slot], np.int32),
            {k: np.asarray(v)[None] for k, v in row.items()},
            None if counts_row is None else np.asarray(counts_row)[None],
            inject)

    def _dev_admit_many(self, ids, lens, slots, rows, counts_rows,
                        inject=None):
        self.metrics["admit_dispatches"] += 1
        self._credit_experts(np.size(ids), np.sum(lens))
        self._credit_chunk_state(np.sum(lens))
        self._bcast("admit_many", ids=ids, lens=lens, slots=slots,
                    rows={k: np.asarray(v) for k, v in rows.items()},
                    counts_rows=counts_rows, inject=self._inj_msg(inject))
        with activate_mesh(self.mesh):
            (self._kc, self._vc, self._sampler, self._last_logits,
             self._lengths) = self._admit_many_fn(
                self.params, self._cos, self._sin,
                self._kc, self._vc, self._sampler, self._last_logits,
                self._lengths,
                jnp.asarray(ids), jnp.asarray(lens), jnp.asarray(slots),
                {k: jnp.asarray(v) for k, v in rows.items()},
                None if counts_rows is None else jnp.asarray(counts_rows),
                self._tab(), self._inj(inject), self._kvt())

    @staticmethod
    def _inj(inject):
        """Host inject pair (extra [B,S,H] f32, is_embed [B,S] bool) → device
        arrays (None passes through; jit specializes the text-only variant)."""
        if inject is None:
            return None
        extra, is_embed = inject
        return (jnp.asarray(extra), jnp.asarray(is_embed))

    @staticmethod
    def _inj_msg(inject):
        """inject pair → broadcast-safe dict (the _bcast serializer would
        np.asarray a tuple, which fails on mismatched member shapes)."""
        if inject is None:
            return None
        return {"extra": np.asarray(inject[0]), "mask": np.asarray(inject[1])}

    @staticmethod
    def _inj_of(msg):
        """_inj_msg's inverse, for follower replay."""
        if msg is None:
            return None
        return (msg["extra"], msg["mask"])

    def _dev_extend_mid(self, buf, pos, idx, inject=None):
        self._credit_experts(np.size(buf), np.size(buf))
        self._credit_chunk_state(np.size(buf))
        self._credit_chunk_ctx(pos)
        self._bcast("extend_mid", buf=buf, pos=pos, idx=idx,
                    inject=self._inj_msg(inject))
        with activate_mesh(self.mesh):
            self._kc, self._vc = self._extend_mid_fn(
                self.params, self._cos, self._sin, self._kc, self._vc,
                jnp.asarray(buf), jnp.int32(pos), jnp.int32(idx), self._tab(),
                self._inj(inject), self._kvt())

    def _dev_extend_final(self, buf, pos, nvalid, idx, row, counts_row,
                          inject=None):
        self._credit_experts(np.size(buf), nvalid)
        self._credit_chunk_state(nvalid)
        self._credit_chunk_ctx(pos)
        self._bcast("extend_final", buf=buf, pos=pos, nvalid=nvalid, idx=idx,
                    row={k: np.asarray(v) for k, v in row.items()},
                    counts_row=counts_row, inject=self._inj_msg(inject))
        with activate_mesh(self.mesh):
            (self._kc, self._vc, self._sampler, self._last_logits,
             self._lengths) = self._extend_final_fn(
                self.params, self._cos, self._sin,
                self._kc, self._vc, self._sampler, self._last_logits,
                self._lengths, jnp.asarray(buf), jnp.int32(pos),
                jnp.int32(nvalid), jnp.int32(idx),
                {k: jnp.asarray(v) for k, v in row.items()},
                None if counts_row is None else jnp.asarray(counts_row),
                self._tab(), self._inj(inject), self._kvt())

    def _dev_decode(self, active, mask_host=None, fast_width=None):
        self.metrics["decode_dispatches"] += 1
        self.metrics["decode_steps_dispatched"] += 1
        self._bcast("decode", active=active,
                    mask=None if mask_host is None else mask_host,
                    fast_width=fast_width)
        with activate_mesh(self.mesh), self._decode_guard():
            args = (self.params, self._cos, self._sin,
                    self._kc, self._vc, self._sampler, self._last_logits,
                    self._lengths, jnp.asarray(active))
            if mask_host is not None:
                variant, fn = "decode_masked", self._decode_fn
                fargs = (*args, jnp.asarray(mask_host))
                fkw = dict(table=self._tab(), kvt=self._kvt())
            elif fast_width:
                variant, fn = f"decode_fast{fast_width}", self._decode_fast_fn
                fargs = args
                fkw = dict(table=self._tab(), kvt=self._kvt(),
                           fast_width=fast_width)
            else:
                variant, fn = "decode", self._decode_nomask_fn
                fargs = args
                fkw = dict(table=self._tab(), kvt=self._kvt())
            n_act = int(np.sum(active))
            B = self.ec.max_slots
            self._sched_pack(variant, fn, fargs, fkw, decode_rows=n_act,
                             rows_used=B, pad_rows=B - n_act, packed=n_act)
            (tokens, logprobs, self._kc, self._vc, self._sampler,
             self._last_logits, self._lengths) = fn(*fargs, **fkw)
        return _AsyncFetch((tokens, logprobs))

    def _dev_decode_block(self, active, steps: int, fast_width=None,
                          mask_host=None):
        self.metrics["decode_dispatches"] += 1
        self.metrics["decode_steps_dispatched"] += steps
        self._bcast("decode_block", active=active, steps=steps,
                    fast_width=fast_width,
                    mask=None if mask_host is None else mask_host)
        with activate_mesh(self.mesh), self._decode_guard():
            args = (self.params, self._cos, self._sin,
                    self._kc, self._vc, self._sampler, self._last_logits,
                    self._lengths, jnp.asarray(active))
            if mask_host is not None:
                variant = f"decode_block{steps}_masked"
                fn = self._decode_block_mask_fn
                fargs = (*args, jnp.asarray(mask_host))
                fkw = dict(table=self._tab(), kvt=self._kvt(), steps=steps,
                           fast_width=None)
            else:
                variant, fn = f"decode_block{steps}", self._decode_block_fn
                fargs = args
                fkw = dict(table=self._tab(), kvt=self._kvt(), steps=steps,
                           fast_width=fast_width)
            n_act = int(np.sum(active))
            B = self.ec.max_slots
            self._sched_pack(variant, fn, fargs, fkw, decode_rows=n_act,
                             rows_used=B, pad_rows=B - n_act,
                             packed=steps * n_act)
            (tokens, logprobs, self._kc, self._vc, self._sampler,
             self._last_logits, self._lengths) = fn(*fargs, **fkw)
        return _AsyncFetch((tokens, logprobs))

    def _dev_decode_loop(self, active, remaining, check_eos, fast_width=None,
                         gstate=None):
        """ONE while-loop dispatch covering up to ec.decode_loop decode steps
        with per-slot stop conditions on device (models/llama.py
        build_decode_loop). `remaining` [B] i32 is each slot's token budget
        for THIS dispatch (max_tokens net of in-flight reservations);
        `check_eos` [B] bool gates the EOS-set stop. `gstate` [B] i32 (or
        None) selects the grammar variant: each iteration gathers the
        per-slot mask row from the shared device tables and advances the
        automaton state on device, so table-backed grammar slots ride the
        full loop with NO per-token host round trip (unconstrained slots sit
        in identity row 0 — bit-identical sampling). Steps actually run come
        back with the async fetch — the dispatch-step metric is credited at
        consume time, when the early-exit count is known."""
        self.metrics["decode_dispatches"] += 1
        self._bcast("decode_loop", active=active, remaining=remaining,
                    check_eos=check_eos, fast_width=fast_width,
                    gstate=gstate)
        with activate_mesh(self.mesh), self._decode_guard():
            gkw = {}
            if gstate is not None:
                gmasks, gtrans = self._gtab()
                gkw = dict(gstate=jnp.asarray(np.asarray(gstate, np.int32)),
                           gmasks=gmasks, gtrans=gtrans)
            variant = ("loop" + (f"_fast{fast_width}" if fast_width else "")
                       + ("_grammar" if gstate is not None else ""))
            fargs = (self.params, self._cos, self._sin, self._kc, self._vc,
                     self._sampler, self._last_logits, self._lengths,
                     jnp.asarray(active), jnp.asarray(remaining),
                     jnp.asarray(check_eos), self._eos_dev, self._tab())
            fkw = dict(fast_width=fast_width, kvt=self._kvt(), **gkw)
            n_act = int(np.sum(active))
            B = self.ec.max_slots
            self._sched_pack(variant, self._decode_loop_fn, fargs, fkw,
                             decode_rows=n_act, rows_used=B,
                             pad_rows=B - n_act, packed=n_act)
            (toks, lps, n_out, steps, self._kc, self._vc, self._sampler,
             self._last_logits, self._lengths) = self._decode_loop_fn(
                *fargs, **fkw)
        return _AsyncFetch((toks, lps, n_out, steps))

    def _dev_demote(self, pb: int, ci: int):
        """Copy hot physical block `pb` into cold-pool index `ci` (int8,
        sub-channel scales). Enqueued AFTER any in-flight decode dispatch on
        the same stream, so the copy reads the block's final hot content."""
        self._bcast("demote", pb=pb, ci=ci)
        with activate_mesh(self.mesh):
            self._ck, self._cv = self._demote_fn(
                self._kc, self._vc, self._ck, self._cv,
                jnp.int32(pb), jnp.int32(ci))

    # ------------------------------------------------- host KV tier (ISSUE 17)

    def _spill_block(self, pb: int, h: bytes | None = None,
                     group: bytes | None = None):
        """Spill physical block `pb` to the host tier before its content
        dies (free, rewrite, or ring overwrite). The D2H copy starts NOW
        (copy_to_host_async) and is enqueued on the device stream before
        any later dispatch can rewrite the block, so finalizing it lazily
        in _host_drain is race-free — the same ordering argument as
        _dev_demote and the kvtier ring's slack blocks."""
        if self._kvhost is None:
            return
        if h is None:
            h = self._block_hash_of.get(pb)
        gkey = group if group is not None else self._spill_group
        # begin_spill claims the hash AND pins the group's resident chain
        # until _host_drain lands it — an LRU eviction racing the async
        # copy can no longer free the chain head under its in-flight tail
        if h is None or not self._kvhost.begin_spill(h, group=gkey):
            return
        with activate_mesh(self.mesh):
            arrs = self._spill_fn(self._kc, self._vc, jnp.int32(pb))
        self._host_pending.append((h, gkey, _AsyncFetch(arrs)))
        self.metrics["kv_host_spills"] += 1
        if self._sched is not None:
            self._sched.reason("kv_host_spill", block=int(pb))

    def _host_drain(self):
        """Land every in-flight spill in the HostKVPool. The copies were
        started at spill time, so wait() here is normally a no-op fetch of
        already-arrived host buffers — not a device stall."""
        if not self._host_pending:
            return
        from localai_tpu.engine.kvhost import HostKVBlock

        pending, self._host_pending = self._host_pending, []
        evicted = 0
        for h, group, fetch in pending:
            kq, ks, vq, vs = fetch.wait()
            evicted += self._kvhost.end_spill(
                h, HostKVBlock(kq=kq, ks=ks, vq=vq, vs=vs))
        if evicted:
            if self._sched is not None:
                self._sched.reason("kv_host_evict_budget", blocks=evicted)
            if self._flightrec is not None:
                self._flightrec.record_event("kv_host_evict_budget",
                                             blocks=evicted)
        self._host_note()

    def _host_note(self):
        """Refresh the kv_host_* GetMetrics keys from the pool (the pool
        may be shared across engines — restart legs keep its history)."""
        st = self._kvhost.stats()
        self.metrics["kv_host_blocks"] = st["blocks"]
        self.metrics["kv_host_bytes"] = st["bytes"]
        self.metrics["kv_host_bytes_peak"] = st["peak_bytes"]
        self.metrics["kv_host_spills"] = st["spills"]
        self.metrics["kv_host_hits"] = st["hits"]
        self.metrics["kv_host_evictions"] = st["evictions"]

    def _readmit_block(self, pb: int, blk):
        """Write one host-tier block into physical page `pb` (H2D). The
        jnp.asarray uploads are explicit sanctioned transfers on the
        admission path — the decode transfer guard wraps decode dispatches
        only, and the uploads overlap the uncovered suffix's prefill
        chunks (they are enqueued first on the same stream)."""
        with activate_mesh(self.mesh):
            self._kc, self._vc = self._readmit_fn(
                self._kc, self._vc,
                jnp.asarray(blk.kq), jnp.asarray(blk.ks),
                jnp.asarray(blk.vq), jnp.asarray(blk.vs), jnp.int32(pb))

    def _host_extend(self, slot: int, req: GenRequest, shared, shtok: int):
        """Extend a device prefix-cache match with host-tier blocks.

        Called from _admit_one right after _match_prefix_blocks: for each
        chain hash past the device hit, a host hit re-admits into a fresh
        physical page (registered in the hash index, so the NEXT tenant
        finds it on device); the first miss on both tiers ends the run —
        everything after it re-prefills. Returns the updated
        (shared, shtok); readmitted blocks are ref'd like matched ones."""
        if self._kvhost is None:
            return shared, shtok
        self._host_drain()   # a block spilled this tick is admissible now
        from localai_tpu.ops.paged import BLOCK

        limit = self.ec.max_context - 2 - self._ctx_reserve
        nfull = min(len(req.prompt_ids) - 1, limit - 1) // BLOCK
        base = len(shared) if shared is not None else 0
        if nfull <= base:
            return shared, shtok
        chain = self._chain_hashes(req.prompt_ids[:nfull * BLOCK])
        added: list[int] = []
        for vb in range(base, nfull):
            blk = self._kvhost.get(chain[vb])
            if blk is None:
                break
            got = self._take_blocks(1, keep_slot=slot)
            if got is None:
                break
            pb = got[0]
            self._readmit_block(pb, blk)
            # register: this page now holds the chain's content on device
            self._drop_hash(pb)
            self._hash_index[chain[vb]] = pb
            self._block_hash_of[pb] = chain[vb]
            added.append(pb)
            if self._sched is not None:
                self._sched.reason("kv_host_readmit", slot=int(slot),
                                   block=int(pb))
        if added:
            shared = (list(shared) if shared is not None else []) + added
            shtok = len(shared) * BLOCK
            if self._flightrec is not None:
                self._flightrec.record_event(
                    "kv_host_readmit", slot=int(slot),
                    blocks=len(added), covered_tokens=int(shtok))
        elif nfull > base and self._sched is not None:
            # both tiers missed at least one full prefix block: the
            # uncovered prefix pays full re-prefill
            self._sched.reason("kv_host_miss_reprefill",
                               blocks=int(nfull - base))
        self._host_note()
        return shared, shtok

    def kernel_tiers(self) -> dict[str, str]:
        """Which implementation (pallas / pallas-interpret / xla) serves each
        hot-path op of THIS engine — for the backend's device report."""
        from localai_tpu.models.llama import kernel_tiers

        return kernel_tiers(self.cfg, self.mesh, paged=self._paged,
                            tiered=self._tiered)

    def kvhost_snapshot(self) -> dict:
        """Host-tier stats for GetTrace/debug surfaces ({} when off)."""
        if self._kvhost is None:
            return {}
        st = self._kvhost.stats()
        st["pending"] = len(self._host_pending)
        return st

    def _dev_shift(self, idx):
        self._bcast("shift", idx=idx)
        with activate_mesh(self.mesh):
            if self._paged:
                # rotate K's tail blocks in place, then permute the table
                # row host-side: sink blocks stay, discarded blocks
                # re-append as fresh tail capacity (reservation unchanged)
                self._kc, self._lengths = self._shift_fn(
                    self._kc, self._lengths,
                    jnp.asarray(self._table[idx]), jnp.int32(idx))
                blocks = self._slot_blocks[idx]
                kb, db = self._shift_keepb, self._shift_discb
                if len(blocks) > kb + db:   # shift only fires at the cap,
                    # where the reservation spans the full context — the
                    # guard covers degenerate tiny-context configs
                    newb = (blocks[:kb] + blocks[kb + db:]
                            + blocks[kb:kb + db])
                    self._slot_blocks[idx] = newb
                    self._table[idx, :len(newb)] = newb
            else:
                self._kc, self._vc, self._lengths = self._shift_fn(
                    self._kc, self._vc, self._lengths, jnp.int32(idx))

    def _dev_draft_ingest(self, buf, pos, idx):
        self._bcast("draft_ingest", buf=buf, pos=pos, idx=idx)
        with activate_mesh(self.mesh):
            self._kcd, self._vcd = self._draft_ingest_fn(
                self._draft[1], self._cos_d, self._sin_d, self._kcd,
                self._vcd, jnp.asarray(buf), jnp.int32(pos), jnp.int32(idx))

    def _dev_spec_admit_tail(self, idx, mask=None):
        if mask is None:
            s = self._slots[idx]
            if s is not None and s.matcher is not None:
                # grammar slot: the admission token samples under the start
                # (or resumed) state's mask, same as every decode token
                mask = self._mask_host[idx:idx + 1].copy()
        self._bcast("spec_admit_tail", idx=idx, mask=mask)
        with activate_mesh(self.mesh):
            if mask is not None:
                tok, lp, self._sampler = self._spec_admit_tail_fn(
                    self._sampler, self._last_logits, jnp.int32(idx),
                    jnp.asarray(mask))
            else:
                tok, lp, self._sampler = self._spec_admit_tail_fn(
                    self._sampler, self._last_logits, jnp.int32(idx))
            self._next_tokens = self._next_tokens.at[idx].set(tok)
        # lint: allow(host-sync-cast) — spec invariant: the admission-sampled
        # first token must be emitted NOW (one sync per request, not per step)
        return int(tok), float(lp)

    def _dev_spec_decode(self, active):
        self.metrics["decode_dispatches"] += 1
        # one spec dispatch fuses gamma draft steps + the verify pass
        self.metrics["decode_steps_dispatched"] += self.ec.gamma + 1
        self._bcast("spec", active=active)
        with activate_mesh(self.mesh):
            fargs = (self.params, self._draft[1], self._cos, self._sin,
                     self._cos_d, self._sin_d, self._kc, self._vc,
                     self._kcd, self._vcd, self._sampler, self._lengths,
                     self._next_tokens, jnp.asarray(active), self._tab())
            n_act = int(np.sum(active))
            B = self.ec.max_slots
            if self._sched is not None:
                # a spec dispatch leaves the fused loop: it needs its
                # dispatch-category code for the fallback-sum invariant
                self._sched.reason("spec_dense")
            self._sched_pack("spec", self._spec_fn, fargs, {},
                             spec_windows=n_act, rows_used=B,
                             pad_rows=B - n_act,
                             packed=n_act * (self.ec.gamma + 1))
            (tokens_out, n_out, logprobs_out, self._next_tokens,
             self._kc, self._vc, self._kcd, self._vcd, self._sampler,
             self._lengths, n_extra) = self._spec_fn(*fargs)
        return _AsyncFetch((tokens_out, n_out, logprobs_out, n_extra))

    def follow(self, channel) -> None:
        """Follower-rank loop (multi-host, process_index > 0): replay the
        rank-0 engine's device dispatches against this process's shards of
        the same global arrays. Blocks until rank 0 sends `stop` or the
        channel drops."""
        while True:
            try:
                op, kw = channel.recv()
            except (ConnectionError, EOFError):
                return
            if op == "stop":
                return
            try:
                self._follow_op(op, kw)
            except Exception:
                # the same fatal device error rank 0 just hit: survive it so
                # the upcoming 'reset' replay can rebuild this rank's state —
                # dying here would leave rank 0's restart hanging on
                # collectives this rank never joins
                import traceback

                traceback.print_exc()

    def _follow_op(self, op: str, kw: dict) -> None:
        if op == "admit":
            self._dev_admit(kw["ids"], kw["n"], kw["slot"], kw["row"],
                            kw["counts_row"])
        elif op == "admit_many":
            self._dev_admit_many(kw["ids"], kw["lens"], kw["slots"],
                                 kw["rows"], kw["counts_rows"],
                                 self._inj_of(kw.get("inject")))
        elif op == "extend_mid":
            self._dev_extend_mid(kw["buf"], kw["pos"], kw["idx"],
                                 self._inj_of(kw.get("inject")))
        elif op == "extend_final":
            self._dev_extend_final(kw["buf"], kw["pos"], kw["nvalid"],
                                   kw["idx"], kw["row"], kw["counts_row"],
                                   self._inj_of(kw.get("inject")))
        elif op == "decode":
            self._dev_decode(kw["active"], kw["mask"],
                             kw.get("fast_width"))
        elif op == "decode_block":
            self._dev_decode_block(kw["active"], int(kw["steps"]),
                                   kw.get("fast_width"), kw.get("mask"))
        elif op == "decode_loop":
            self._dev_decode_loop(kw["active"], kw["remaining"],
                                  kw["check_eos"], kw.get("fast_width"),
                                  kw.get("gstate"))
        elif op == "gtable":
            self._dev_gtable(int(kw["base"]), kw["masks"], kw["trans"])
        elif op == "demote":
            self._dev_demote(kw["pb"], kw["ci"])
        elif op == "shift":
            self._dev_shift(kw["idx"])
        elif op == "draft_ingest":
            self._dev_draft_ingest(kw["buf"], kw["pos"], kw["idx"])
        elif op == "spec_admit_tail":
            self._dev_spec_admit_tail(kw["idx"], kw.get("mask"))
        elif op == "spec":
            self._dev_spec_decode(kw["active"])
        elif op == "reset":
            # rank 0 is self-restarting after a fatal step error
            self._init_device_state()

    # ------------------------------------------------------------ submission

    def submit(self, req: GenRequest) -> tuple[int, queue.Queue]:
        """Enqueue a request; returns (request_id, output queue of StepOutput)."""
        if self._dead:
            raise RuntimeError("engine loop has terminated; no new requests")
        if len(req.prompt_ids) == 0:
            raise ValueError("empty prompt")
        limit = self.ec.max_context - 2 - self._ctx_reserve
        if len(req.prompt_ids) > limit:
            raise ValueError(
                f"prompt length {len(req.prompt_ids)} exceeds {limit} "
                f"(max_context minus the decode margin); longer prompts "
                f"need a larger context window"
            )
        if req.grammar and self._draft is not None:
            raise ValueError(
                "grammar-constrained decoding is not served with a draft "
                "model: the speculative verify program has no grammar lane. "
                "Serve the model without a draft to constrain its output")
        if req.mm_embeds is not None:
            if self._draft is not None:
                raise ValueError(
                    "multimodal prompts are not served with a draft model: "
                    "the draft ingests token ids only, so it cannot follow "
                    "a prompt's feature rows")
            emb = np.asarray(req.mm_embeds, np.float32)
            pos = np.asarray(req.mm_positions, np.int64)
            if emb.ndim != 2 or emb.shape[1] != self.cfg.hidden_size:
                raise ValueError(
                    f"mm_embeds must be [K, {self.cfg.hidden_size}], got "
                    f"{emb.shape}")
            if pos.shape != (emb.shape[0],):
                raise ValueError("mm_positions must match mm_embeds rows")
            if len(pos) and (pos.min() < 0
                             or pos.max() >= len(req.prompt_ids)):
                raise ValueError("mm_positions outside the prompt")
            if len(pos) > 1 and (np.diff(pos) <= 0).any():
                raise ValueError("mm_positions must be strictly increasing")
            req.mm_embeds, req.mm_positions = emb, pos
        if req.context_shift and self._mixed:
            raise ValueError(
                "context_shift is not supported for a model with window and "
                "full attention layers, or linear-attention, state-space or "
                "latent-attention ones (cache_shift moves one full-length "
                "cache of keys and values, not a ring, a recurrent state or "
                "a buffer of latents)")
        if req.prompt_cache_path and self._linear:
            raise ValueError(
                "prompt_cache_path is not supported for a model with "
                "linear-attention or state-space layers: the disk prompt "
                "cache saves K and "
                "V per position, and a recurrent state at a prefix's end is "
                "not held")
        if req.context_shift and self._draft is not None:
            raise ValueError(
                "context_shift is not supported with a draft model "
                "(the draft cache would need shifting too)")
        if req.context_shift and self._paged and not self._shift_ok:
            raise ValueError(
                "context_shift with paged KV needs max_context spanning "
                "more than keep+discard blocks (128-token granularity); "
                "raise max_context or use a dense cache")
        if req.context_shift and self._tiered:
            raise ValueError(
                "context_shift is not supported under a sink_window "
                "kv_policy (the ring geometry already bounds residency; "
                "long sequences decode in place up to max_context)")
        if req.kv_policy:
            # reject malformed/oversized policies NOW (gRPC
            # INVALID_ARGUMENT) instead of failing in-band at admission
            from localai_tpu.engine import kvtier

            kvtier.resolve_policy(req.kv_policy, self._kv_policy)
        if self._paged and self._blocks_for(req) > self.ec.kv_pages - 1:
            raise ValueError(
                f"request needs {self._blocks_for(req)} KV blocks under "
                f"kv_policy {self._req_policy(req).describe()} "
                f"(prompt {len(req.prompt_ids)} + max_tokens "
                f"{req.max_tokens}) but the pool has {self.ec.kv_pages - 1}; "
                f"raise kv_pages or lower max_tokens")
        V = self.cfg.vocab_size
        if any(not (0 <= t < V) for t in req.prompt_ids):
            raise ValueError(f"prompt token id outside [0, {V})")
        if req.grammar:
            # compile now (cached) so a malformed GBNF rejects THIS call with
            # ValueError → gRPC INVALID_ARGUMENT, instead of surfacing later
            # as an in-band admission error
            self._compile_grammar(req.grammar)
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._live.add(rid)
        out: queue.Queue = queue.Queue()
        req.queued_t = time.monotonic()
        self._queue.put((rid, req, out))
        self._wake.set()
        return rid, out

    def cancel(self, rid: int):
        """Mark a submitted request for eviction: its slot finishes with
        reason "cancelled" at the next token (queued requests terminate at
        admission). Safe from any thread; unknown/finished rids are no-ops —
        gRPC termination callbacks fire on NORMAL completion too."""
        with self._lock:
            if rid in self._live:
                self._cancelled.add(rid)
        self._wake.set()

    def _finish_rid(self, rid: int):
        """A terminal StepOutput went out for `rid` — drop its bookkeeping."""
        with self._lock:
            self._live.discard(rid)
            self._cancelled.discard(rid)

    # ------------------------------------------------------------ the loop

    def _bucket(self, n: int) -> int:
        for b in self._small_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt too long for single-shot prefill: {n}")

    def _compile_grammar(self, grammar: str):
        """Compile (or fetch cached) GBNF → CompiledGrammar. Called from gRPC
        handler threads (submit-time validation) AND the engine loop thread.
        Only the lazy GrammarCache INIT is held under _grammar_lock (it walks
        the whole vocab once); the compile itself — which may include a slow
        device-table precompilation — runs outside any engine lock. The
        cache is internally thread-safe (functions/matcher.GrammarCache:
        double-checked insert), so a slow grammar compile never blocks other
        handler threads' cache hits or the engine loop."""
        cache = self._grammar_cache
        if cache is None:
            with self._grammar_lock:
                if self._grammar_cache is None:
                    if self.tok is None:
                        raise ValueError(
                            "grammar constraint requires a tokenizer")
                    from localai_tpu.functions.matcher import GrammarCache

                    self._grammar_cache = GrammarCache(self.tok)
                cache = self._grammar_cache
        return cache.get(grammar)

    def _matcher_for(self, grammar: str):
        return self._compile_grammar(grammar).state()

    def _admit_one(self, rid: int, req: GenRequest, out: queue.Queue,
                   batch: list | None = None) -> bool:
        # Host-side per-request failures (bad GBNF, missing tokenizer) must
        # reject THIS request only — never kill the loop, which would strand
        # every other in-flight stream (the reference rejects a bad grammar
        # per-request in the sampler). Device failures below are engine-fatal
        # on purpose: donation makes the state unrecoverable.
        try:
            matcher = self._matcher_for(req.grammar) if req.grammar else None
            # device grammar tables: installed once per grammar (BFS +
            # upload happen off the decode hot path); gbase None = overflow
            # → the slot keeps per-token host masks (and bars the loop)
            gbase = (self._grammar_table_entry(req.grammar)
                     if req.grammar else None)
            n = len(req.prompt_ids)
            chunked = n > self._small_max
            bucket = None if chunked else self._bucket(n)
            pol = self._req_policy(req) if self._tiered else None
        except Exception:
            self._finish_rid(rid)
            out.put(StepOutput(
                request_id=rid, text="", token_id=-1,
                logprob=0.0, finished=True, finish_reason="error",
                prompt_tokens=len(req.prompt_ids),
            ))
            return False
        mm = req.mm_embeds is not None
        if self._tiered and not pol.windowed:
            # admission-time policy demotion: a full-policy request that
            # cannot fit the compact table (its identity mapping would write
            # past the resident columns), or that lands while the free pool
            # runs low (windowed slots return ALL their blocks at release
            # instead of retaining a warm prefix), rides the engine's window
            # instead of being rejected
            from localai_tpu.ops.paged import blocks_needed

            margin = 2 * self.ec.decode_block + 1
            base = blocks_needed(min(n + max(req.max_tokens, 0) + margin,
                                     self.ec.max_context))
            if base > self._maxb or base > len(self._kv_free):
                pol = self._kv_policy
                self.metrics["kv_policy_demotions"] += 1
                if self._sched is not None:
                    self._sched.reason("kv_policy_demotion", rid=rid,
                                       blocks_needed=int(base))
        # multimodal: id-level prefix reuse would match the repeated image
        # token while the injected features differ — no slot or disk reuse
        slot, lcp = self._pick_slot([] if mm else req.prompt_ids)
        if self._paged:
            shared = None
            if req.context_shift:
                # a shift rotates this slot's pages IN PLACE — never run it
                # over pages other tenants read: no borrowed pages, and
                # lcp=0 makes _alloc_slot's copy-on-write pass swap every
                # externally-shared retained block before the cold prefill
                lcp = 0
            elif self.ec.prompt_cache and self._draft is None and not mm:
                # block-level prefix cache: another tenant's pages beat the
                # slot-retained token match when they cover more prefix
                shared, shtok = self._match_prefix_blocks(req.prompt_ids)
                if self._kvhost is not None:
                    # device miss → host tier: re-admit spilled blocks H2D
                    # before falling back to re-prefill (ISSUE 17). The
                    # uploads enqueue ahead of the suffix's prefill chunks,
                    # so the DMA hides under prefill compute
                    shared, shtok = self._host_extend(
                        slot, req, shared, shtok)
                if shtok > lcp:
                    lcp = shtok
                else:
                    self._unref_blocks(shared)
                    shared = None
            if pol is not None and pol.windowed and lcp:
                # a windowed slot may borrow/retain prefix pages ONLY for
                # whole sink blocks: everything past the sinks lives in
                # ring columns whose position mapping is per-tenant, so
                # those cached blocks are re-prefilled (block-granular
                # recompute — the prefix-cache-shared case)
                from localai_tpu.ops.paged import BLOCK

                keep = min(lcp // BLOCK, self._kv_policy.sink_blocks)
                self.metrics["kv_recomputes"] += max(
                    0, lcp // BLOCK - keep)
                if shared is not None:
                    if keep < len(shared):
                        self._unref_blocks(shared[keep:])
                        shared = shared[:keep]
                    if not shared:
                        shared = None
                lcp = keep * BLOCK
            eff = self._alloc_slot(slot, req, shared=shared, lcp=lcp)
            if eff is None:
                # pool exhausted even after reclaim: defer (FIFO) until
                # blocks free — the caller re-attempts on later ticks
                self._free.append(slot)
                self._deferred = (rid, req, out)
                if self._sched is not None:
                    self._sched.reason("kv_pool_exhausted", rid=rid)
                return None
            lcp = eff
            if self._tiered:
                # per-slot tier geometry: the RESIDENCY (sb/rw) always uses
                # the ENGINE window (the ring was sized for it); the request
                # policy narrows only the attention masks (sinks/window
                # token counts), so shrunken per-request windows share the
                # same table layout and compiled program
                if pol.windowed:
                    self._kv_sb[slot] = self._kv_policy.sink_blocks
                    self._kv_rw[slot] = self._kv_ring
                    self._kv_sinks[slot] = pol.sinks
                    self._kv_window[slot] = pol.window
                else:
                    self._kv_sb[slot] = self._maxb
                    self._kv_rw[slot] = 1
                    self._kv_sinks[slot] = self.ec.max_context
                    self._kv_window[slot] = self.ec.max_context
                self._slot_policy[slot] = pol
                self._demote_next[slot] = self._kv_policy.sink_blocks
                if self._cold:
                    for ci in self._slot_cold[slot]:
                        self._cold_free.append(ci)
                    self._slot_cold[slot] = []
                    self._cold_table[slot, :] = 0
                self._note_pool()
        self._slot_kv_tokens[slot] = []
        disk_prefix = 0
        if not lcp and req.prompt_cache_path and not mm:
            lcp = disk_prefix = self._load_prompt_cache(slot, req)
        if lcp:
            # shared prefix already in this slot's cache: prefill only the
            # suffix via the chunked-extend path (start offset = lcp)
            chunked = True
            self.metrics["prompt_cache_hits"] += 1
            self.metrics["prompt_tokens_reused"] += lcp
        if req.resume is not None:
            # resume outcome attribution (ISSUE 19): every full prefix
            # block covered by the device/host caches = fast resume; any
            # uncovered full block pays re-prefill of prompt+emitted
            if self._paged:
                from localai_tpu.ops.paged import BLOCK

                full = (min(n - 1, self.ec.max_context - 2
                            - self._ctx_reserve - 1) // BLOCK) * BLOCK
                fast = full > 0 and lcp >= full
            else:
                fast = lcp > 0
            self.metrics["resume_readmits" if fast
                         else "resume_reprefills"] += 1
            if self._sched is not None:
                self._sched.reason(
                    "resume_readmit" if fast else "resume_reprefill",
                    rid=rid, covered=int(lcp), prompt=int(n))
            if self._flightrec is not None:
                self._flightrec.record_event(
                    "resume", rid=int(rid), covered_tokens=int(lcp),
                    reprefill_tokens=int(n - lcp),
                    emitted=int(req.resume.get("emitted", 0)),
                    outcome="readmit" if fast else "reprefill")
        # token_counts/logit_bias only influence sampling when penalties or a
        # bias are actually set — the common case skips both [V]-sized
        # host→device transfers (~1 MB per admission)
        p = req.params.normalized()
        heavy = bool(p.logit_bias) or p.repeat_penalty != 1.0 \
            or p.presence_penalty != 0.0 or p.frequency_penalty != 0.0
        row = sampler_row(req.params, self.cfg.vocab_size,
                          fallback_seed=rid + 1, include_bias=heavy)
        if req.resume is not None and req.resume.get("key") is not None:
            # restore the preempted slot's RNG carry chain: the device key
            # read back at spill-drain continues the exact split sequence,
            # so sampled resumes are byte-identical (greedy ignores it)
            row = dict(row, key=np.asarray(req.resume["key"], np.uint32))
        if heavy:
            counts_row = np.zeros((self.cfg.vocab_size,), np.int32)
            pid, pcnt = np.unique(np.asarray(req.prompt_ids, np.int64),
                                  return_counts=True)
            counts_row[pid] = pcnt
        else:
            counts_row = None

        if not chunked:
            if batch is not None and self._draft is None and not mm:
                # defer the device call: _flush_admits batches same-bucket
                # admissions from this tick into one prefill pass
                batch.append(dict(slot=slot, n=n, bucket=bucket,
                                  prompt_ids=req.prompt_ids, row=row,
                                  counts_row=counts_row, heavy=heavy))
            else:
                ids = self._pad_ids([dict(n=n, prompt_ids=req.prompt_ids)],
                                    bucket)
                inject = self._mm_inject(req, 0, bucket) if mm else None
                self._dev_admit(ids, n, slot, row, counts_row, inject)
                if self._draft is not None:
                    self._dev_draft_ingest(ids, 0, slot)

        W = self.ec.sampling_topk_width
        # `p` is the normalized params from the heavy-row check above
        fast_w = None
        if W and not req.grammar and (p.typical_p is None
                                      or p.typical_p >= 1.0):
            V = self.cfg.vocab_size
            tk = min(p.top_k or 0, V)   # sampler_row clamps the row the same
            if p.greedy:
                # greedy is argmax — rank 0 of ANY top-k window is exact, so
                # a plain temperature=0 request (the most common of all)
                # always rides the sort-free path
                fast_w = min(W, V)
            elif 0 < tk <= min(W, V):
                fast_w = min(W, V)
            elif 0 < tk <= min(8 * W, V):
                # escalation tier: a wide-top_k request rides an 8x-wider
                # (vocab-capped) sort-free window instead of dragging the
                # whole batch onto the full [B, V] sort path
                fast_w = min(8 * W, V)
        slot_obj = _Slot(
            request_id=rid, req=req, out=out,
            detok=self.tok.stream_decoder() if self.tok else None,
            matcher=matcher,
            start_time=time.monotonic(), prompt_len=n,
            prefilled=not chunked, row=row, counts_row=counts_row,
            prefill_pos=lcp, disk_prefix=disk_prefix, fast_w=fast_w,
        )
        self.metrics["requests_admitted"] += 1
        if self._slo is not None and req.queued_t:
            self._slo.observe("queue_wait", "all",
                              slot_obj.start_time - req.queued_t)
        if self._tracer is not None:
            # one span per request, admission → release; request_id ties it
            # to the HTTP/gRPC spans of the same request, trace_parent nests
            # it under the gRPC handler's span in the merged trace
            slot_obj.span = self._tracer.begin(
                "engine.request", cat="engine",
                parent_id=req.trace_parent or None,
                args={"request_id": req.trace_id or f"rid-{rid}",
                      "slot": slot, "prompt_tokens": n})
        self._slots[slot] = slot_obj
        if chunked:
            self._prefillq.append(slot)
        if matcher is not None:
            eos = self.tok.eos_ids if self.tok else ()
            self._grammar_slots += 1
            slot_obj.gbase = gbase
            if gbase is not None:
                # table-backed slot: start in the grammar's initial state;
                # the host mask row materializes from the table mirror (u32
                # LSB-first words view as the same LSB-first u8 bytes), so
                # the per-token V-trial matcher mask walk is skipped for
                # the whole life of the request
                self._gstate[slot] = gbase
                self._mask_host[slot] = self._gmasks_np[gbase].view(
                    np.uint8)[:self._mask_nbytes]
            else:
                self._grammar_hostonly += 1
                self._mask_host[slot] = matcher.mask_bits(eos)
            if req.resume is not None:
                # replay the emitted tokens through the automaton so the
                # PDA (and the device table mirror) resumes mid-grammar
                # exactly where the preempted slot stopped
                for t in req.prompt_ids[n - int(req.resume.get(
                        "emitted", 0)):]:
                    if not matcher.accept(t):
                        break
                    if gbase is not None:
                        st = int(self._gtrans_np[self._gstate[slot], t])
                        self._gstate[slot] = st
                        self._mask_host[slot] = self._gmasks_np[st].view(
                            np.uint8)[:self._mask_nbytes]
                    else:
                        self._mask_host[slot] = matcher.mask_bits(eos)
        if req.resume is not None:
            # detokenizer replay: push the emitted chain through the fresh
            # incremental decoder (identical stream to the preempted run),
            # suppress the chars the client already received, and hand any
            # remainder — text the dead backend produced but never
            # released (stop-string holdback, or chars past the last
            # flushed chunk) — straight to the stream / holdback buffer
            cut = n - int(req.resume.get("emitted", 0))
            slot_obj.resume_base = n - cut
            replay = ""
            if slot_obj.detok is not None:
                for t in req.prompt_ids[cut:]:
                    replay += slot_obj.detok.push(t)
            sent = max(0, int(req.resume.get("sent_chars", 0)))
            leftover = replay[sent:]
            slot_obj.sent_chars = sent
            if req.stop:
                slot_obj.pending_text = leftover
            elif leftover:
                slot_obj.sent_chars += len(leftover)
                out.put(StepOutput(
                    request_id=rid, text=leftover, token_id=-1,
                    logprob=0.0, finished=False,
                    generated_tokens=0, prompt_tokens=n))
        self.metrics["prompt_tokens_processed"] += n - lcp
        if not chunked and self._draft is not None:
            # spec invariant: the first token is sampled (and emitted) at
            # admission; it becomes the carried next_token
            tok, lp = self._dev_spec_admit_tail(slot)
            self._emit(slot, slot_obj, tok, lp, time.monotonic(),
                       path="spec")
        return True

    def _prefill_tick(self):
        """Admission work for one engine tick: continue in-progress chunked
        prefills (oldest first) and admit queued requests, up to
        `admit_per_tick` units while decodes are running — bounding the work
        keeps running decodes at a steady cadence instead of stalling behind
        whole long prompts (the reference's update_slots interleaving,
        grpc-server.cpp:69-97). An idle engine (nothing decoding) has no
        cadence to protect, so it drains freely — burst TTFT at high slot
        counts is set by this path."""
        budget = max(1, self.ec.admit_per_tick)
        if not any(s is not None and s.prefilled for s in self._slots):
            budget = max(budget, self.ec.max_slots)
        pending: list = []
        try:
            self._prefill_drain(budget, pending)
        finally:
            self._flush_admits(pending)

    def _prefill_drain(self, budget: int, pending: list):
        for _ in range(budget):
            if self._prefillq:
                idx = self._prefillq[0]
                slot = self._slots[idx]
                ids = slot.req.prompt_ids
                pos = slot.prefill_pos
                nvalid = min(len(ids) - pos, self._chunk)
                buf = np.zeros((1, self._chunk), np.int32)
                buf[0, :nvalid] = ids[pos:pos + nvalid]
                final = pos + nvalid == len(ids)
                inject = (self._mm_inject(slot.req, pos, self._chunk)
                          if slot.req.mm_embeds is not None else None)
                if final:
                    self._dev_extend_final(buf, pos, nvalid, idx, slot.row,
                                           slot.counts_row, inject)
                else:
                    self._dev_extend_mid(buf, pos, idx, inject)
                if self._draft is not None:
                    self._dev_draft_ingest(buf, pos, idx)
                slot.prefill_pos = pos + nvalid
                if final:
                    slot.prefilled = True
                    self._prefillq.remove(idx)
                    if self._draft is not None:
                        tok, lp = self._dev_spec_admit_tail(idx)
                        self._emit(idx, slot, tok, lp, time.monotonic(),
                                   path="spec")
                continue
            if not self._free:
                return
            if self._deferred is not None:
                # a paged admission waiting on KV blocks retries only after
                # something released (head-of-line, preserving FIFO)
                if not self._blocks_freed:
                    return
                self._blocks_freed = False
                rid, req, out = self._deferred
                self._deferred = None
            else:
                try:
                    rid, req, out = self._queue.get_nowait()
                except queue.Empty:
                    return
            # dead-on-arrival requests (deadline spent waiting in the queue,
            # or cancelled before admission) terminate here — never paying
            # a prefill whose output nobody will read
            if (rid in self._cancelled
                    or (req.deadline and time.monotonic() > req.deadline)):
                reason = "cancelled" if rid in self._cancelled else "timeout"
                self._finish_rid(rid)
                out.put(StepOutput(
                    request_id=rid, text="", token_id=-1, logprob=0.0,
                    finished=True, finish_reason=reason,
                    prompt_tokens=len(req.prompt_ids)))
                continue
            # keep the popped triple reachable while the device call runs:
            # if admission dies mid-flight, _fail_active must still
            # terminate this stream (it is in neither _queue nor _slots)
            self._admitting = (rid, req, out)
            ok = self._admit_one(rid, req, out, batch=pending)
            self._admitting = None
            if ok is None:
                return

    _ADMIT_GROUP_SIZES = (2, 4, 8)

    @staticmethod
    def _mm_inject(req: GenRequest, start: int, width: int):
        """(extra [1, width, H] f32, mask [1, width] bool) for the prompt
        window [start, start+width): image-feature rows from req.mm_embeds
        land at their expanded positions, everything else stays a token."""
        pos, emb = req.mm_positions, req.mm_embeds
        lo = int(np.searchsorted(pos, start))
        hi = int(np.searchsorted(pos, start + width))
        extra = np.zeros((1, width, emb.shape[1]), np.float32)
        mask = np.zeros((1, width), bool)
        sel = (pos[lo:hi] - start).astype(np.int64)
        extra[0, sel] = emb[lo:hi]
        mask[0, sel] = True
        return (extra, mask)

    @staticmethod
    def _pad_ids(plans: list, bucket: int) -> np.ndarray:
        """[K, bucket] zero-padded prompt buffer from admission plans.
        (Chunked prefill pads its per-chunk window separately in
        _prefill_drain — different shape contract.)"""
        ids = np.zeros((len(plans), bucket), np.int32)
        for i, p in enumerate(plans):
            ids[i, :p["n"]] = p["prompt_ids"]
        return ids

    def _flush_admits(self, pending: list):
        """Execute this tick's deferred admissions: group by (bucket, heavy)
        and prefill each group in one batched device call. Group size is
        padded up to the next of _ADMIT_GROUP_SIZES by REPEATING the last
        plan — duplicate scatter rows write identical values, so the padding
        is a no-op on device state while keeping the set of compiled program
        shapes small. Singles take the existing single-request path."""
        groups: dict = {}
        for plan in pending:
            groups.setdefault((plan["bucket"], plan["heavy"]),
                              []).append(plan)
        for (bucket, heavy), g in groups.items():
            while g:
                if len(g) == 1:
                    p = g.pop()
                    self._dev_admit(self._pad_ids([p], bucket), p["n"],
                                    p["slot"], p["row"], p["counts_row"])
                    continue
                k = min(len(g), self._ADMIT_GROUP_SIZES[-1])
                size = next(s for s in self._ADMIT_GROUP_SIZES if s >= k)
                batch, g = g[:k], g[k:]
                batch = batch + [batch[-1]] * (size - k)
                ids = self._pad_ids(batch, bucket)
                lens = np.asarray([p["n"] for p in batch], np.int32)
                slots = np.asarray([p["slot"] for p in batch], np.int32)
                rows = {f: np.stack([np.asarray(p["row"][f]) for p in batch])
                        for f in batch[0]["row"]}
                counts = (np.stack([p["counts_row"] for p in batch])
                          if heavy else None)
                self._dev_admit_many(ids, lens, slots, rows, counts)

    def _active_mask(self) -> np.ndarray:
        return np.array([s is not None and s.prefilled for s in self._slots],
                        bool)

    def _row_states(self) -> tuple:
        """The max_slots rows as they stand now: (active, prefill,
        free_queued, free_starved). A slot that holds a prefilled request is
        active, one that holds a request mid-prefill (in _prefillq, or
        admitted and awaiting _flush_admits) is in prefill, and the empty
        ones are free: all queued if the engine has a request it could admit
        (_queue, _deferred), all starved if it has none."""
        active = prefill = 0
        for s in self._slots:
            if s is not None:
                if s.prefilled:
                    active += 1
                else:
                    prefill += 1
        free = len(self._slots) - active - prefill
        if self._deferred is not None or not self._queue.empty():
            return active, prefill, free, 0
        return active, prefill, 0, free

    def _rows_at_dispatch(self, fast_width=None) -> tuple:
        """_row_states as a decode dispatch is enqueued: carried in `pend`
        to _credit_consumed, which multiplies it by the steps the device
        ran, noted in the tick ledger's record of the dispatch and, while
        GET /debug/xprof traces, on the tick's next annotation. With them,
        last, whether the dispatch's program samples at `fast_width` and
        takes that top-k by blocks."""
        rows = self._row_states()
        if self._sched is not None:
            self._sched.rows(*rows)
        if self._phases.tracing():
            active, prefill, queued, starved = rows
            self._phases.note(rows_active=active, rows_prefill=prefill,
                              rows_free=queued + starved,
                              queued=self._queue.qsize())
        return (*rows, bool(fast_width) and topk_by_blocks(
            self.cfg.vocab_size, fast_width))

    def _block_steps(self) -> int:
        """How many decode steps the next dispatch may fuse. 1 whenever a
        per-token host decision is live: pending admissions or chunked
        prefills (so new requests don't wait a whole block) or a slot near
        its context limit / shift boundary. A slot approaching max_tokens
        steps the batch DOWN a power-of-two ladder (16→8→4→2→1) instead of
        collapsing it to single steps — each dispatch pays a fixed host
        cost, and the old cliff single-stepped the last 2*G tokens of
        EVERY request (a quarter of a 128-token stream).
        Grammar slots DO ride blocks — sampled under their block-start
        mask, host-verified against the PDA, rolled back at the first
        stale-mask miss — so one constrained request no longer serializes
        every other tenant."""
        G = self.ec.decode_block
        if (G <= 1 or not self.ec.pipeline or self._prefillq
                or (self._free and not self._queue.empty())):
            # a non-empty queue only matters if a slot is free to admit into —
            # a saturated engine keeps full block fusion
            return 1
        limit = self.ec.max_context - 2 - self._ctx_reserve
        steps = G
        for s in self._slots:
            if s is None or not s.prefilled:
                continue
            # 2G margin: with one block pipelined in flight, host-side
            # `generated` is stale by up to a full block when this guard runs
            if s.prompt_len + s.generated - s.shifted + 2 * G >= limit:
                if self._sched is not None:
                    self._sched.reason("context_margin")
                return 1
            # remaining tokens, discounted by the ACTUAL in-flight
            # dispatch's staleness (not the max block size — the tail then
            # rides 4/2-step dispatches to the end); overshooting a slot's
            # max_tokens only wastes its lanes (emission stops at the bound
            # and the slot is released), so the ladder trades a little tail
            # compute for fewer dispatches
            stale = self._inflight_steps if self._pending is not None else 0
            rem = s.req.max_tokens - s.generated - stale
            while steps > 1 and steps * 2 > max(rem, 1):
                steps //= 2
            if steps == 1:
                if self._sched is not None:
                    self._sched.reason("max_tokens_ladder")
                return 1
        if steps < G and self._sched is not None:
            self._sched.reason("max_tokens_ladder")
        return steps

    def _loop_block_reason(self, entries) -> str | None:
        """None when this dispatch can go loop-native (ONE while_loop
        dispatch, stop conditions on device); otherwise the registered
        reason code (telemetry.sched.REASON_CODES, "dispatch" category) for
        why the block/ladder path runs instead. Host-verified decisions
        keep the dense path: grammar masks and stop strings need per-token
        host checks, speculative decoding has its own fused program, and
        pending admissions/chunked prefills must not wait out a whole loop
        (the device cannot see the host queue mid-dispatch)."""
        if self._decode_loop_fn is None:
            return "loop_disabled"
        if self._draft is not None:
            return "draft_engine"
        # table-backed grammar slots ride the loop (the device gathers each
        # step's mask row and advances the automaton state); only automata
        # that OVERFLOWED the table still need per-token host masks
        if self._grammar_hostonly > 0:
            return "grammar_hostonly"
        if self._prefillq:
            return "pending_prefill"
        if self._free and not self._queue.empty():
            return "pending_admission"
        if any(self._slots[i].req.stop for i, _ in entries):
            return "stop_string"
        return None

    def _dispatch_loop(self, active, entries, fast):
        """Dispatch the fused while-loop block. Per-slot `remaining` budgets
        are max_tokens net of the PENDING dispatch's reservation, so two
        loop blocks can pipeline without ever overshooting a budget; a slot
        whose whole budget is already in flight sits this dispatch out (the
        device would run it zero steps anyway)."""
        G = self._loop_steps
        B = self.ec.max_slots
        remaining = np.zeros((B,), np.int32)
        check_eos = np.zeros((B,), bool)
        live = []
        for i, rid in entries:
            s = self._slots[i]
            rem = s.req.max_tokens - s.generated - s.inflight
            if rem <= 0:
                active[i] = False
                continue
            remaining[i] = min(rem, G) if self._mixed else rem
            check_eos[i] = self.tok is not None and not s.req.ignore_eos
            live.append((i, rid))
        if not live:
            return None
        res = {}
        for i, _ in live:
            res[i] = int(min(G, remaining[i]))
            self._slots[i].inflight += res[i]
        self._mark_join(live)
        self._inflight_steps = G
        if self._sched is not None:
            # the fast path is recorded too, so the dispatch-category codes
            # stay exhaustive over decode dispatches (they sum to
            # decode_dispatches)
            self._sched.reason("loop_native")
        gstate = self._gstate.copy() if self._grammar_slots > 0 else None
        rows = self._rows_at_dispatch(fast)
        fetch = self._dev_decode_loop(active, remaining, check_eos, fast,
                                      gstate=gstate)
        return ("loop", fetch, live, res, rows)

    def _dispatch(self):
        """Dispatch one decode step, a fused scan block, or a single-dispatch
        while loop for the currently-active slots; returns a tagged pend
        ("loop"|"block", async fetch, [(slot_idx, request_id)], ..., the
        rows' states at this moment) without waiting for the device — or
        None if nothing can run."""
        active = self._active_mask()
        if not active.any():
            return None
        entries = [(int(i), self._slots[i].request_id)
                   for i in np.where(active)[0]]
        # sort-free sampling only when EVERY active slot's knobs fit SOME
        # top-k window (and no grammar masks are live); the dispatch width
        # is the widest any active slot needs — one wide-top_k tenant costs
        # the batch a wider window, not the full-sort path
        fast = None
        if self._grammar_slots == 0:
            ws = [self._slots[i].fast_w if self._slots[i] is not None
                  else None for i, _ in entries]
            if all(w is not None for w in ws):
                fast = max(ws)
        loop_block = self._loop_block_reason(entries)
        if loop_block is None:
            return self._dispatch_loop(active, entries, fast)
        if self._sched is not None:
            # exactly ONE dispatch-category code per dispatch — this is
            # what lets a reader explain the dispatches that left the loop
            # as a sum of reason-code counts
            self._sched.reason(loop_block)
        steps = self._block_steps()
        if self._linear and self._grammar_slots > 0:
            # a block samples under its first step's masks and takes back
            # what the grammar then rejects (_repair): a recurrent state
            # cannot be taken back, so such a model steps once a dispatch
            steps = 1
        # snapshot the dispatch-time masks: _consume compares each slot's
        # refreshed mask against what the device sampled under, to catch the
        # allowed-set GROWING mid-block (see _consume)
        gmask = self._mask_host.copy() if self._grammar_slots > 0 else None
        self._inflight_steps = steps
        res = {}
        for i, _ in entries:
            res[i] = steps
            self._slots[i].inflight += steps
        self._mark_join(entries)
        rows = self._rows_at_dispatch(fast)
        if steps > 1:
            fetch = self._dev_decode_block(active, steps, fast, gmask)
        else:
            fetch = self._dev_decode(active, gmask, fast)
        return ("block", fetch, entries, gmask, res, rows)

    def _await(self, fetch):
        """Block for a dispatch's results: phase `device` for the wait (the
        cumulative host_sync_wait_ms is the same milliseconds), `emit` from
        there on — detok, stop scan and stream fan-out follow every fetch."""
        m, ph = self.metrics, self._phases
        waited = m["engine_wait_ms__device"]
        ph.switch("device")
        out = fetch.wait()
        ph.switch("emit")
        m["host_sync_wait_ms"] += m["engine_wait_ms__device"] - waited
        return out

    def _credit_experts(self, call_tokens: int, tokens: int):
        """`tokens` real tokens went through every MoE layer in a call of
        `call_tokens` tokens (batch x sequence, padding and all): credit
        them to the form that shape takes. Host arithmetic, once a call."""
        if self.cfg.num_experts:
            form = expert_form(self.cfg, call_tokens, self.mesh)
            self.metrics[f"expert_tokens__{form}"] += int(tokens) * (
                self.cfg.expert_layers)
            if form == ROUTED:
                self.metrics["expert_tile_calls__seen"] += (
                    self.cfg.expert_layers)
                self.metrics["expert_tile_calls__bounded"] += (
                    self.cfg.expert_layers * _pallas(self.mesh is None))

    def _credit_chunk_state(self, tokens: int):
        """`tokens` real tokens of a prefill or a chunk call went through
        every linear layer's chunkwise form. Host arithmetic, once a call."""
        if self._state_kind == LINEAR:
            seen = int(tokens) * self.cfg.layer_types.count(LINEAR)
            self.metrics["chunk_state_tokens__seen"] += seen
            self.metrics["chunk_state_tokens__kernel"] += (
                seen * self._state_kernel)

    def _credit_chunk_ctx(self, pos: int):
        """A chunk from position `pos` is dispatched: the rows of a full
        layer's cache row its attention visits, and the row's capacity.
        Host arithmetic, once a chunk."""
        t, window, whole = self._full_row
        rows = t if whole else chunk_rows(t, window, pos, self._chunk)
        self.metrics["chunk_ctx_tokens__attended"] += rows
        self.metrics["chunk_ctx_tokens__capacity"] += t
        if self._latent:
            self.metrics["chunk_latent_rows__expanded"] += rows
            self.metrics["chunk_latent_rows__kernel"] += (
                rows * self._chunk_kernel)

    def _credit_consumed(self, steps: int, entries=(), n_out=None,
                         rows=None):
        """One dispatch's results are on the host: credit it and the steps
        the device ran in it, together, and its max_slots rows x those steps
        by the state each row was in when the dispatch was enqueued (`rows`,
        _rows_at_dispatch; the active rows all as `spent` until _credit_live
        moves what _emit takes of them to `live`). For a model with window
        and full layers also the context its live rows (`entries`, each with
        `n_out[i]` steps, or all `steps`) attended over, in one layer of each
        kind: a row that stood at n tokens attends n + 1, n + 2, ... in a
        full layer and min(that, window) in a window layer. Call it BEFORE
        the dispatch's tokens are emitted (`generated` is then what it was
        at dispatch), and _credit_live after them."""
        m = self.metrics
        m["decode_dispatches_consumed"] += 1
        m["decode_steps_consumed"] += steps
        active, prefill, queued, starved, by_blocks = (
            rows or (*self._row_states(), False))
        m["decode_steps__topk_blocks"] += steps * by_blocks
        m["decode_row_steps__spent"] += active * steps
        m["decode_row_steps__prefill"] += prefill * steps
        m["decode_row_steps__free_queued"] += queued * steps
        m["decode_row_steps__free_starved"] += starved * steps
        self._live_from = m["tokens_generated"]
        self._consume_seq |= 1
        if self.cfg.num_experts:
            # a decode step is max_slots rows of one token
            self._credit_experts(self.ec.max_slots, sum(
                steps if n_out is None else int(n_out[i])
                for i, _ in entries))
        if not self._mixed:
            return
        if self._linear:
            return self._credit_cache_bytes(steps, entries, n_out)
        # (a model without window layers: every step is "not yet a window
        # long", and nothing is credited to a kind it does not have)
        window = self.cfg.sliding_window or self.ec.max_context
        full = win = 0
        for i, rid in entries:
            slot = self._slots[i]
            if slot is None or slot.request_id != rid:
                continue
            n = steps if n_out is None else int(n_out[i])
            lo = slot.prompt_len + slot.generated
            k = min(n, max(window - lo, 0))     # steps not yet a window long
            full += n * lo + n * (n + 1) // 2
            win += k * lo + k * (k + 1) // 2 + (n - k) * window
        for kind, tokens in ((FULL, full), (LATENT, full), (WINDOW, win)):
            if f"decode_ctx_tokens__{kind}" in m:
                m[f"decode_ctx_tokens__{kind}"] += tokens

    def _credit_live(self):
        """The consumed dispatch's tokens are emitted: what _emit took since
        _credit_consumed were steps of active rows that gave a token."""
        m = self.metrics
        took = m["tokens_generated"] - self._live_from
        self._live_from += took
        m["decode_row_steps__live"] += took
        m["decode_row_steps__spent"] -= took
        self._consume_seq += self._consume_seq & 1

    def metrics_snapshot(self, patience_s: float = 0.25) -> dict:
        """A copy of `metrics` for a scrape, from any thread. It is taken
        between two consumes where it can be: while a dispatch's tokens are
        emitted `tokens_generated` runs ahead of `decode_row_steps__live`,
        so a copy from inside that stretch (a few ms of each dispatch) is
        retaken, for up to `patience_s`; after that, or if a failed tick
        left the stretch open, the copy is served as it is."""
        deadline = time.monotonic() + patience_s
        while True:
            seq = self._consume_seq
            snap = dict(self.metrics)
            if (not seq & 1 and seq == self._consume_seq) \
                    or time.monotonic() >= deadline:
                return snap
            time.sleep(0.001)

    def _credit_cache_bytes(self, steps: int, entries, n_out):
        """_credit_consumed for a model with linear-attention layers, in
        bytes: a live row that stood at n tokens attends over n + 1, n + 2,
        ... tokens of K and V in each softmax layer, and reads and writes
        its whole state (and convolution tail) once a step in each linear
        layer, however long it is."""
        full = lin = 0
        for i, rid in entries:
            slot = self._slots[i]
            if slot is None or slot.request_id != rid:
                continue
            n = steps if n_out is None else int(n_out[i])
            lo = slot.prompt_len + slot.generated
            full += n * lo + n * (n + 1) // 2
            lin += n
        m, per, count = (self.metrics, self._cache_bytes,
                         self.cfg.layer_types.count)
        m["decode_cache_bytes__full"] += int(full * per[FULL] * count(FULL))
        state = self._state_kind
        m[f"decode_cache_bytes__{state}"] += int(
            2 * lin * per[state] * count(state))

    def _mark_join(self, entries):
        """Stamp the slots this decode dispatch is the first to carry, just
        before its enqueue: admit_to_join ends and join_to_first starts here.
        One clock read, and only in a dispatch that some slot joins."""
        now = None
        for i, _ in entries:
            s = self._slots[i]
            if s.join_t is None:
                if now is None:
                    now = time.monotonic()
                s.join_t = now

    def _admit_phase(self):
        with self._phases.within("admit"):
            self._prefill_tick()

    def _release_reservations(self, entries, res):
        """Return a consumed dispatch's per-slot token reservations (see
        _Slot.inflight) before emitting — emission moves the budget from
        `inflight` into `generated`."""
        for i, rid in entries:
            s = self._slots[i]
            if s is not None and s.request_id == rid:
                s.inflight = max(0, s.inflight - res.get(i, 0))

    def _consume_loop(self, pend):
        """Consume a fused while-loop dispatch: finish the async token fetch,
        credit the ACTUAL step count (early exit makes it <= decode_loop),
        and commit slot b's n_out[b] tokens in device order. The host still
        re-derives every finish decision in _emit — cancel/deadline can
        terminate a slot mid-buffer, and the rest of its tokens are dropped
        by the request-id check exactly as on the block path."""
        _, fetch, entries, res, rows = pend
        tokens, logprobs, n_out, steps = self._await(fetch)
        steps = int(steps)
        self.metrics["decode_steps_dispatched"] += steps
        self._credit_consumed(steps, entries, n_out, rows)
        self._release_reservations(entries, res)
        now = time.monotonic()
        if self._slo is not None:
            for i, rid in entries:
                s = self._slots[i]
                if s is not None and s.request_id == rid:
                    s.dispatches += 1
        for g in range(steps):
            for i, rid in entries:
                if g >= int(n_out[i]):
                    continue
                slot = self._slots[i]
                if slot is None or slot.request_id != rid:
                    continue  # finished earlier (cancel/deadline/shift race)
                self._emit(i, slot, int(tokens[g, i]),
                           float(logprobs[g, i]), now, path="loop")
        self._credit_live()

    def _consume(self, pend):
        """Block on a dispatched step's results and run the host-side token
        handling for every slot that was active at dispatch time and is still
        serving the same request. Grammar slots in a fused block sampled under
        their block-START mask: the first token a slot's (live) PDA rejects
        marks that slot for rollback — its accepted prefix stands, the rest of
        its block is discarded, and _repair restores the device state."""
        if pend[0] == "loop":
            self._consume_loop(pend)
            return
        _, fetch, entries, gmask, res, rows = pend
        tokens, logprobs = self._await(fetch)
        self._release_reservations(entries, res)
        now = time.monotonic()
        if tokens.ndim == 1:
            tokens, logprobs = tokens[None], logprobs[None]
        steps = tokens.shape[0]
        self._credit_consumed(steps, entries, rows=rows)
        if self._slo is not None:
            for i, rid in entries:
                s = self._slots[i]
                if s is not None and s.request_id == rid:
                    s.dispatches += 1
        rolled: list[int] = []
        for g in range(steps):
            for i, rid in entries:
                slot = self._slots[i]
                if slot is None or slot.request_id != rid or i in rolled:
                    continue  # finished earlier in this block (EOS/stop/len)
                if not self._emit(i, slot, int(tokens[g, i]),
                                  float(logprobs[g, i]), now,
                                  fresh_mask=(g == 0)):
                    rolled.append(i)
                    continue
                # mask-growth check: PDA-reject rollback makes in-block
                # grammar sampling exact REJECTION sampling while the
                # allowed set only shrinks — but if this token's acceptance
                # OPENED tokens the dispatch mask forbade, the rest of the
                # block was drawn from a wrongly-restricted distribution
                # and must be discarded even though the PDA might accept it.
                if (gmask is not None and g + 1 < steps
                        and self._slots[i] is slot
                        and slot.matcher is not None
                        and np.any(self._mask_host[i] & ~gmask[i])):
                    rolled.append(i)
        self._credit_live()
        for i in rolled:
            slot = self._slots[i]
            if slot is not None:
                self._repair(i, slot)

    def _repair(self, idx: int, slot: _Slot):
        """Roll a grammar slot back to its last PDA-accepted token after a
        fused block sampled past a stale mask (see _consume): re-run the model
        on that token through the extend path — rewriting the same KV row with
        identical values, restoring last_logits and lengths[slot] to the
        accepted position — and re-install the sampler row with a fresh
        deterministic RNG key (re-using the admission key would replay the
        block's draws). The rows the block wrote past the accepted position
        are garbage but unreadable: attention masks by lengths, and future
        decode steps overwrite them in order."""
        self.metrics["grammar_rollbacks"] = (
            self.metrics.get("grammar_rollbacks", 0) + 1)
        n = slot.prompt_len + slot.generated - slot.shifted  # valid rows
        seq = list(slot.req.prompt_ids) + slot.gen_ids
        buf = np.zeros((1, self._chunk), np.int32)
        buf[0, 0] = seq[-1]
        seed = (slot.request_id * 1000003 + slot.generated) & 0x7FFFFFFF
        key = jax.device_get(jax.random.key_data(
            jax.random.PRNGKey(seed))).astype(np.uint32)
        row = dict(slot.row, key=key)
        slot.row = row
        counts = slot.counts_row
        if counts is not None:
            counts = counts.copy()
            for t in slot.gen_ids:
                counts[t] += 1
        self._dev_extend_final(buf, n - 1, 1, idx, row, counts)

    def _step_spec(self) -> bool:
        """Spec-mode iteration: one batched draft+verify step for all active
        slots (engine/spec.py), emitting 1..gamma+1 tokens per slot."""
        active = self._active_mask()
        if active.any():
            entries = [(int(i), self._slots[i].request_id)
                       for i in np.where(active)[0]]
            self._mark_join(entries)
            rows = self._rows_at_dispatch()
            pend = self._dev_spec_decode(active)
            self._admit_phase()    # admission overlaps the device step
            tokens_out, n_out, logprobs_out, n_extra = self._await(pend)
            now = time.monotonic()
            G = self.ec.gamma
            self._credit_consumed(G + 1, rows=rows)
            for i, rid in entries:
                slot = self._slots[i]
                if slot is None or slot.request_id != rid:
                    continue
                self.metrics["draft_proposed"] += G
                self.metrics["draft_accepted"] += int(n_extra[i])
                if self._slo is not None:
                    slot.dispatches += 1
                for j in range(int(n_out[i])):
                    slot = self._slots[i]
                    if slot is None or slot.request_id != rid:
                        break  # finished mid-window (EOS/length/stop)
                    self._emit(i, slot, int(tokens_out[i, j]),
                               float(logprobs_out[i, j]), now, path="spec")
            self._credit_live()
        else:
            self._admit_phase()
        return (any(s is not None for s in self._slots)
                or not self._queue.empty() or self._deferred is not None)

    def _kv_tick(self):
        """Advance the hot→cold→evicted lifecycle for windowed slots.

        A raw block is eligible the moment its LAST token exits the window
        of the oldest position any in-flight or future query can hold (the
        host length only LAGS the device, so eligibility here is
        conservative). quantize_cold copies the block into the int8 cold
        pool — the dispatch is enqueued behind any in-flight decode on the
        same stream, and the ring's +2 slack blocks (kvtier.ring_blocks)
        guarantee the copy lands before the ring wraps over the block. A
        full cold pool, or a drop-policy slot, counts the block evicted
        (the ring overwrite IS the eviction — SnapStream semantics)."""
        if not self._tiered:
            return
        from localai_tpu.ops.paged import BLOCK

        for i, s in enumerate(self._slots):
            if s is None:
                continue
            pol = self._slot_policy[i]
            if pol is None or not pol.windowed:
                continue
            n = (s.prompt_len + s.generated - s.shifted if s.prefilled
                 else s.prefill_pos)
            sb = int(self._kv_sb[i])
            lim = n - int(self._kv_window[i])
            while True:
                raw = int(self._demote_next[i])
                if raw < sb or (raw + 1) * BLOCK > lim:
                    break
                self._demote_next[i] = raw + 1
                if not self._cold or not self._cold_free:
                    self.metrics["kv_evictions"] += 1
                    if self._sched is not None:
                        self._sched.reason("kv_eviction", slot=i, block=raw)
                    if (self._kvhost is not None and s.shifted == 0
                            and s.req.mm_embeds is None
                            and (raw + 1) * BLOCK
                            <= int(self._kv_window[i])):
                        # the ring will overwrite this block — spill a copy
                        # first. Ring content sits at TRUE positions (only
                        # the column mapping rotates), and every token in a
                        # block ending inside the first window span was
                        # computed with its FULL history still attendable —
                        # byte-equivalent to full-policy prefill, so it is
                        # valid prefix-cache content for any future tenant.
                        # Later blocks saw truncated attention and must not
                        # be served cross-tenant. The ring's +2 slack
                        # blocks order the async D2H before the wrap,
                        # exactly as for _dev_demote
                        ids = (list(s.req.prompt_ids) + s.gen_ids)
                        if len(ids) >= (raw + 1) * BLOCK:
                            chain = self._chain_hashes(
                                ids[:(raw + 1) * BLOCK])
                            col = sb + (raw - sb) % max(
                                int(self._kv_rw[i]), 1)
                            self._spill_block(
                                int(self._table[i, col]), h=chain[raw],
                                group=chain[0])
                    continue
                ci = self._cold_free.pop()
                col = sb + (raw - sb) % max(int(self._kv_rw[i]), 1)
                pb = int(self._table[i, col])
                self._cold_table[i, raw] = ci
                self._slot_cold[i].append(ci)
                self.metrics["kv_cold_blocks"] += 1
                if self._sched is not None:
                    self._sched.reason("kv_cold_demotion", slot=i, block=raw)
                self._dev_demote(pb, ci)

    def step(self) -> bool:
        """One engine iteration. In pipelined mode (the default, grammar-free)
        one decode step stays in flight: step N+1 is dispatched before step
        N's tokens are pulled to the host, hiding the device→host sync +
        Python bookkeeping behind the next step's compute. Grammar-constrained
        batches run synchronously (the sampled token must update the PDA mask
        before the next sample). Returns True while work remains.

        With the tick ledger live (ISSUE 13) each iteration runs bracketed
        by begin()/commit(): the committed record — pack composition +
        reason codes — feeds both /debug/sched's ring and the flight
        recorder's tick ring, so a post-mortem shows the last N scheduling
        DECISIONS, not just dispatch counts. Disabled, the overhead is the
        two attribute loads + branch below."""
        if faults.fire("engine_crash") is not None:
            # chaos hook (LOCALAI_FAULT=engine_crash): a deterministic fatal
            # step — drives the _loop restart + flight-recorder post-mortem
            # path in tests; one env dict miss when disarmed
            raise RuntimeError("injected engine_crash (LOCALAI_FAULT)")
        if self._preempt_req.is_set() and (
                time.monotonic() >= self._preempt_t
                or not any(s is not None for s in self._slots)):
            # grace expired (or nothing left decoding): freeze and spill
            # every live slot, manifest the queue, keep serving — the
            # caller owns what happens to the process next
            self._spill_drain()
        self._tick_n += 1
        # the engine thread's phases (telemetry.PhaseClock): a tick opens in
        # `dispatch`, _step_inner marks the rest, and everything between two
        # ticks — the idle wait of _loop, a caller driving step() — is `idle`
        self._phases.switch("dispatch", tick=self._tick_n)
        try:
            return self._step_tick()
        finally:
            self._phases.switch("idle")

    def _step_tick(self) -> bool:
        sched = self._sched
        if sched is None and self._flightrec is None:
            return self._step_inner()
        self._set_tick(self._tick_n)
        if sched is None:
            # flight recorder without the ledger: keep the coarse summary
            # every 64 ticks (the pre-ledger ring contents)
            if (self._tick_n & 63) == 0:
                self._flightrec.record_tick({
                    "tick": self._tick_n,
                    "t_wall": time.time(),
                    "active_slots": sum(s is not None for s in self._slots),
                    "queued": self._queue.qsize(),
                    "deferred": self._deferred is not None,
                    "tokens_generated": self.metrics["tokens_generated"],
                    "decode_dispatches": self.metrics["decode_dispatches"],
                })
            return self._step_inner()
        sched.begin(self._tick_n)
        busy = self._step_inner()
        rec = sched.commit(
            active_slots=sum(s is not None for s in self._slots),
            queued=self._queue.qsize(),
            deferred=self._deferred is not None,
            tokens_generated=self.metrics["tokens_generated"],
            decode_dispatches=self.metrics["decode_dispatches"])
        if self._flightrec is not None:
            self._flightrec.record_tick(rec)
        return busy

    def _step_inner(self) -> bool:
        if self._draft is not None:
            return self._step_spec()
        if self._tiered or self._host_pending:
            with self._phases.within("kv"):
                if self._tiered:
                    self._kv_tick()
                if self._host_pending:
                    # land last tick's spills (their D2H copies have arrived
                    # by now) so the pool's occupancy metrics stay current
                    # even on admission-free ticks
                    self._host_drain()
        sync = self._grammar_slots > 0 or not self.ec.pipeline
        if sync and self._pending is not None:
            self._consume(self._pending)
            self._pending = None
            self._phases.switch("dispatch")
        cur = self._dispatch()
        self._admit_phase()
        if cur is None:
            if self._pending is not None:
                self._consume(self._pending)
                self._pending = None
        elif sync:
            self._consume(cur)
        else:
            prev, self._pending = self._pending, cur
            if prev is not None:
                self._consume(prev)
        return (any(s is not None for s in self._slots)
                or not self._queue.empty() or self._pending is not None
                or self._deferred is not None)

    def _emit(self, idx: int, slot: _Slot, token_id: int, logprob: float,
              now: float, fresh_mask: bool = True,
              path: str = "dense") -> bool:
        """Commit one sampled token to `slot` (grammar advance, detok, stop
        scan, stream, maybe finish). Returns False — with NO state mutated —
        when the slot's grammar rejects a token sampled under a STALE fused-
        block mask (fresh_mask=False); the caller then rolls the device back
        (_repair). A rejection under a FRESH mask means mask and matcher
        disagree (should not happen): finish the request defensively instead
        of livelocking on an identical resample."""
        finish = None
        shift = False
        cache_len = slot.prompt_len + slot.generated + 1 - slot.shifted
        is_eos = self.tok is not None and token_id in self.tok.eos_ids
        if is_eos and not slot.req.ignore_eos:
            finish = "eos"
        elif slot.generated + 1 >= slot.req.max_tokens:
            finish = "length"
        elif cache_len >= self.ec.max_context - 2 - self._ctx_reserve:
            if slot.req.context_shift:
                # evict-and-continue (reference ctx_shift): slide the cache
                # left, re-rotating K; the in-flight pipelined step wrote at a
                # pre-shift position and is already part of the device state
                # (spec mode rejected context_shift at submit)
                shift = True
            else:
                finish = "length"
        # eviction (ISSUE 4): a cancelled request (client gone — gRPC
        # termination callback) or an expired deadline stops consuming decode
        # lanes at the next emitted token instead of running to max_tokens
        if finish is None and slot.request_id in self._cancelled:
            finish = "cancelled"
        elif finish is None and slot.req.deadline \
                and now > slot.req.deadline:
            finish = "timeout"

        # grammar: validate + advance the PDA BEFORE mutating anything, so a
        # stale-mask rejection leaves the slot exactly at its accepted prefix
        if slot.matcher is not None:
            eos = self.tok.eos_ids if self.tok else ()
            if is_eos:
                # EOS never advances the PDA; it is legal exactly when the
                # grammar is complete (mask_bits sets the EOS bits then). A
                # stale block mask can propose EOS mid-grammar — roll back.
                if not slot.matcher.done:
                    if not fresh_mask:
                        return False
                    if finish is None:
                        finish = "stop"  # mask/matcher disagreement
                elif finish is None:
                    # ignore_eos + completed grammar: the model stopped and
                    # rolling back would just re-sample the same EOS forever
                    finish = "stop"
            elif finish is None:
                if slot.matcher.accept(token_id):
                    if slot.gbase is not None:
                        # table-backed slot: advance the host mirror of the
                        # device automaton and take the mask row straight
                        # from the table (u32 LSB-first words view as the
                        # same LSB-first u8 bytes) — skips the V-trial
                        # matcher mask walk; matcher.accept above stays the
                        # arbiter for done/can_continue/rollback
                        st = int(self._gtrans_np[self._gstate[idx], token_id])
                        self._gstate[idx] = st
                        self._mask_host[idx] = self._gmasks_np[st].view(
                            np.uint8)[:self._mask_nbytes]
                    else:
                        self._mask_host[idx] = slot.matcher.mask_bits(eos)
                    if (slot.matcher.done and not slot.matcher.can_continue
                            and not eos):
                        finish = "stop"  # complete and nothing can follow
                elif not fresh_mask:
                    return False
                else:
                    finish = "stop"  # mask/matcher disagreement (defensive)

        if slot.first_token_time is None:
            slot.first_token_time = now
            # TTFT from ARRIVAL (queued_t) — the user-perceived number,
            # queue wait included; falls back to admission time for requests
            # submitted without a queue timestamp
            self.metrics["ttft_ms_last"] = \
                (now - (slot.req.queued_t or slot.start_time)) * 1e3
        slot.generated += 1
        slot.gen_ids.append(token_id)
        self.metrics["tokens_generated"] += 1
        self.metrics["tokens_by_path__" + path] += 1
        slo = self._slo
        if slo is not None:
            slot.path = path
            if slot.last_token_t is None:
                # TTFT from ARRIVAL (queued_t), matching ttft_ms_last above.
                # Its stages share their boundary timestamps, so queue_wait
                # + admit_to_join + join_to_first is this ttft exactly. A
                # slot no decode dispatch carried yet got its first token
                # at admission (spec mode): it joined when it was admitted.
                if slot.join_t is None:
                    slot.join_t = slot.start_time
                slo.observe("ttft", path,
                            now - (slot.req.queued_t or slot.start_time))
                slo.observe("admit_to_join", "all",
                            slot.join_t - slot.start_time)
                slo.observe("join_to_first", "all", now - slot.join_t)
                if self._tracer is not None:
                    self._stage_spans(slot, now)
                slot.last_token_t = now
                slot.obs_tokens = slot.generated
            elif now > slot.last_token_t:
                # amortized inter-token gap: a fused-loop dispatch delivers a
                # burst sharing one host arrival — weight the gap over the
                # burst instead of recording zeros inside it
                k = slot.generated - slot.obs_tokens
                if k > 0:
                    slo.observe("tpot", path,
                                (now - slot.last_token_t) / k, n=k)
                slot.last_token_t = now
                slot.obs_tokens = slot.generated
        if shift:
            self._dev_shift(idx)
            slot.shifted += self._shift_discard

        text = ""
        if slot.detok is not None:
            if finish != "eos":
                text = slot.detok.push(token_id)
            if finish is not None:
                text += slot.detok.flush()

        # stop-string scan with holdback
        emit_text = text
        if slot.req.stop:
            slot.pending_text += text
            hold = max(len(s) for s in slot.req.stop) - 1
            matched = None
            for s in slot.req.stop:
                j = slot.pending_text.find(s)
                if j != -1 and (matched is None or j < matched[0]):
                    matched = (j, s)
            if matched is not None:
                emit_text = slot.pending_text[: matched[0]]
                slot.pending_text = ""
                finish = "stop"
            elif finish is not None:
                emit_text = slot.pending_text
                slot.pending_text = ""
            else:
                stable = len(slot.pending_text) - hold
                emit_text = slot.pending_text[:stable] if stable > 0 else ""
                slot.pending_text = slot.pending_text[max(stable, 0):]

        timings = None
        if finish is not None and slo is not None:
            timings = self._timeline(slot, finish, now)
            slot.timeline = timings   # _release_slot → flight recorder
            slo.observe("e2e", slot.path or path,
                        now - (slot.req.queued_t or slot.start_time))
        slot.sent_chars += len(emit_text)
        slot.out.put(StepOutput(
            request_id=slot.request_id, text=emit_text, token_id=token_id,
            logprob=logprob, finished=finish is not None, finish_reason=finish,
            generated_tokens=slot.generated, prompt_tokens=slot.prompt_len,
            timings=timings, finished_t=None if finish is None else now,
        ))
        if finish is not None:
            dur = now - slot.start_time
            if dur > 0:
                self.metrics["tokens_per_second_last"] = slot.generated / dur
            self.metrics["requests_completed"] += 1
            self._release_slot(idx, slot)
        return True

    def _stage_spans(self, slot: _Slot, now: float):
        """Ring spans of a request's TTFT stages, under its request id
        (time.monotonic and perf_counter are one clock on Linux)."""
        args = {"request_id": slot.req.trace_id or f"rid-{slot.request_id}"}
        edges = (("queue_wait", slot.req.queued_t or slot.start_time),
                 ("admit_to_join", slot.start_time),
                 ("join_to_first", slot.join_t), ("", now))
        for (name, t0), (_, t1) in zip(edges, edges[1:]):
            self._tracer.add_complete("engine.stage." + name, t0, t1 - t0,
                                      cat="engine", args=args)

    def _timeline(self, slot: _Slot, reason: str, now: float) -> dict:
        """The request's phase timeline (ms, arrival-relative) — the final
        StepOutput's `timings` payload and the flight-recorder record."""
        qt = slot.req.queued_t or slot.start_time
        return {
            "request_id": slot.req.trace_id or f"rid-{slot.request_id}",
            "path": slot.path or "dense",
            "finish_reason": reason,
            "prompt_tokens": slot.prompt_len,
            "generated_tokens": slot.generated,
            "dispatches": slot.dispatches,
            "kv_policy": slot.req.kv_policy or self.ec.kv_policy or "full",
            "queue_wait_ms": (slot.start_time - qt) * 1e3,
            "admit_to_join_ms": ((slot.join_t - slot.start_time) * 1e3
                                 if slot.join_t is not None else None),
            "join_to_first_ms": (
                (slot.first_token_time - slot.join_t) * 1e3
                if slot.join_t is not None
                and slot.first_token_time is not None else None),
            "ttft_ms": ((slot.first_token_time - qt) * 1e3
                        if slot.first_token_time is not None else None),
            "e2e_ms": (now - qt) * 1e3,
            "t_wall_finished": time.time(),
        }

    # --------------------------------------------- paged-KV block allocator
    # Host-side, reservation-based: a request reserves every block it could
    # ever write (prompt + max_tokens + in-flight margin) at admission, so
    # generation can never exhaust the pool mid-flight — oversubscription
    # comes from max_tokens being much smaller than max_context. Released
    # slots RETAIN their blocks (the warm prefix cache) until the pool runs
    # short, then the least-recently-released slot is reclaimed. On top of
    # that, full 128-token blocks are content-hash-indexed at release, so a
    # NEW admission sharing the prompt prefix maps the same physical pages
    # into its own table (refcounted, copy-on-write: a borrower only ever
    # writes positions past the shared prefix, which live in fresh blocks).

    def _req_policy(self, req: GenRequest):
        """Effective retention policy for `req` (before pressure demotion).
        Falls back to the engine policy on a malformed request policy —
        submit() already rejected those; this keeps _blocks_for total."""
        from localai_tpu.engine import kvtier

        try:
            return kvtier.resolve_policy(req.kv_policy, self._kv_policy)
        except ValueError:
            return self._kv_policy

    def _blocks_for(self, req: GenRequest) -> int:
        from localai_tpu.ops.paged import blocks_needed

        margin = 2 * self.ec.decode_block + 1   # in-flight pipelined writes
        if self._draft is not None:
            # the spec-verify window writes up to gamma+1 positions past the
            # sampled length — the reservation must cover the overshoot or
            # the tail of the window silently lands in the trash block
            margin = max(margin, self.ec.gamma + 1)
        tokens = min(len(req.prompt_ids) + max(req.max_tokens, 0) + margin,
                     self.ec.max_context)
        need = blocks_needed(tokens)
        if self._tiered:
            # retention bounds residency: the compact table holds at most
            # sink+ring columns per slot however long the sequence runs
            # (the ring reuses its blocks in place), and a full-policy
            # request larger than the table demotes to the engine window at
            # admission — so a ctx-64k request under sink_window is NOT
            # rejected for blocks it will never hold resident
            need = min(need, self._maxb)
        return need

    def _ref_blocks(self, blocks):
        for pb in blocks:
            self._block_ref[pb] += 1

    def _unref_blocks(self, blocks):
        """Drop one reference from each block; blocks reaching zero return
        to the free pool (their content is dead — any hash entry with it)."""
        freed = False
        for pb in blocks:
            self._block_ref[pb] -= 1
            if self._block_ref[pb] <= 0:
                self._block_ref[pb] = 0
                if self._kvhost is not None:
                    # last reference on registered content: catch it in the
                    # host tier before the page returns to the free pool
                    self._spill_block(pb)
                self._drop_hash(pb)
                self._kv_free.append(pb)
                freed = True
        if freed:
            self._blocks_freed = True

    def _drop_hash(self, pb: int):
        """Forget a block's registered content (freed or about to be
        rewritten) so the prefix index can never serve stale pages."""
        h = self._block_hash_of.pop(pb, None)
        if h is not None and self._hash_index.get(h) == pb:
            del self._hash_index[h]

    @staticmethod
    def _chain_hashes(ids) -> list[bytes]:
        """Chain content hashes of consecutive full 128-token blocks: the
        hash of block v commits to every token before it, so equal hash ⇒
        equal whole prefix AND equal absolute positions (K rows are stored
        post-RoPE — position-dependent — which a flat per-block hash would
        get wrong)."""
        import hashlib

        from localai_tpu.ops.paged import BLOCK

        h = b""
        out = []
        for vb in range(len(ids) // BLOCK):
            blk = np.asarray(ids[vb * BLOCK:(vb + 1) * BLOCK], np.int64)
            h = hashlib.blake2b(h + blk.tobytes(), digest_size=16).digest()
            out.append(h)
        return out

    def _match_prefix_blocks(self, prompt_ids) -> tuple[list[int], int]:
        """Block-level prefix cache lookup: the longest run of leading full
        128-token blocks whose chain hash is registered. Matched blocks are
        ref'd for the caller — commit them via _alloc_slot(shared=...) or
        return them with _unref_blocks on any bail-out.
        Returns (physical blocks, tokens covered)."""
        from localai_tpu.ops.paged import BLOCK

        limit = self.ec.max_context - 2 - self._ctx_reserve
        nfull = min(len(prompt_ids) - 1, limit - 1) // BLOCK
        blocks: list[int] = []
        for h in self._chain_hashes(prompt_ids[:nfull * BLOCK]):
            pb = self._hash_index.get(h)
            if pb is None:
                break
            blocks.append(pb)
        self._ref_blocks(blocks)
        return blocks, len(blocks) * BLOCK

    def _take_blocks(self, k: int, keep_slot: int):
        """Pop k free blocks (ref'd for the caller), reclaiming released
        slots' retained blocks (oldest first, never `keep_slot` — its prefix
        is being reused). A victim's pages that other tenants still share
        stay alive (refcount) — only its last reference frees a block.
        Returns None when the pool genuinely cannot satisfy k."""
        while len(self._kv_free) < k:
            victim = next((s for s in self._released_lru if s != keep_slot),
                          None)
            if victim is None:
                return None
            self._released_lru.remove(victim)
            if self._kvhost is not None and self._slot_blocks[victim]:
                # the victim's retained chain dies as one session: group
                # its spills under the chain-head hash so host-tier LRU
                # evicts whole conversations, tail-first
                self._spill_group = self._block_hash_of.get(
                    self._slot_blocks[victim][0])
            self._unref_blocks(self._slot_blocks[victim])
            self._spill_group = None
            self._slot_blocks[victim] = []
            self._slot_kv_tokens[victim] = []
            self._table[victim, :] = 0
        out = self._kv_free[:k]
        del self._kv_free[:k]
        self._ref_blocks(out)
        return out

    def _alloc_slot(self, slot: int, req: GenRequest, shared=None,
                    lcp: int = 0):
        """Size `slot`'s block list for `req`; update the table row.

        `shared`: already-ref'd physical blocks from _match_prefix_blocks —
        they become the slot's head (the borrowed prefix pages). `lcp`: the
        token prefix the request will NOT rewrite (slot-retained or shared
        reuse). Returns the EFFECTIVE reusable prefix length (may shrink —
        see the copy-on-write pass), or None when the pool is exhausted
        (defer; `shared` refs are returned here on that path)."""
        from localai_tpu.ops.paged import BLOCK

        need = self._blocks_for(req)
        have = self._slot_blocks[slot]
        if shared is not None:
            fresh = self._take_blocks(need - len(shared), keep_slot=slot) \
                if need > len(shared) else []
            if fresh is None:
                self._unref_blocks(shared)
                return None
            self._unref_blocks(have)
            have = list(shared) + fresh
            self._slot_blocks[slot] = have
        else:
            old_len = len(have)
            if len(have) < need:
                got = self._take_blocks(need - len(have), keep_slot=slot)
                if got is None:
                    return None
                have.extend(got)
            elif len(have) > need:
                self._unref_blocks(have[need:])
                del have[need:]
            # copy-on-write: every block from the first written one onward
            # gets rewritten by this request. A page another tenant still
            # reads (ref > 1) must not be written in place — swap in a
            # fresh block. Context-shift requests rotate even their prefix
            # blocks, so for them EVERY shared page swaps (lcp arrives 0).
            j0 = lcp // BLOCK
            swap = [j for j in range(j0, len(have))
                    if self._block_ref[have[j]] > 1]
            if swap:
                got = self._take_blocks(len(swap), keep_slot=slot)
                if got is None:
                    # roll the extension back: a deferred slot must not sit
                    # on fresh blocks the retry (or another request) needs
                    if len(have) > old_len:
                        self._unref_blocks(have[old_len:])
                        del have[old_len:]
                    return None
                for j, nb in zip(swap, got):
                    self._unref_blocks([have[j]])
                    have[j] = nb
                if swap[0] == j0:
                    # the partially-reused block itself was swapped: the
                    # rows [j0*BLOCK, lcp) went with it
                    lcp = j0 * BLOCK
        # the to-be-written blocks' old content is dead the moment the
        # first new row lands — their hash entries must go now, or the
        # index would hand out pages mid-rewrite. The host tier catches
        # each registered block on the way out (the spill's async D2H is
        # enqueued before this request's first prefill dispatch can
        # rewrite the page — same-stream ordering)
        for j in range(lcp // BLOCK, len(have)):
            if self._kvhost is not None:
                self._spill_block(
                    have[j], group=self._block_hash_of.get(have[0]))
            self._drop_hash(have[j])
        self._table[slot, :] = 0
        self._table[slot, :len(have)] = have
        if slot in self._released_lru:
            self._released_lru.remove(slot)
        return lcp

    def _pick_slot(self, prompt_ids: list[int]) -> tuple[int, int]:
        """Choose a free slot, preferring one whose cached tokens share the
        longest prefix with the new prompt (llama.cpp's slot prompt cache).
        Returns (slot, reusable_prefix_len); 0 = cold prefill."""
        limit = self.ec.max_context - 2 - self._ctx_reserve

        def common(cached: list[int]) -> int:
            m = min(len(cached), len(prompt_ids) - 1, limit - 1)
            i = 0
            while i < m and cached[i] == prompt_ids[i]:
                i += 1
            return i

        best_slot, best_lcp = None, 0
        if self.ec.prompt_cache and self._draft is None:
            for s in self._free:
                lcp = common(self._slot_kv_tokens[s])
                if self._linear or (self._mixed and not self._ring_holds(
                        lcp, len(self._slot_kv_tokens[s]))):
                    # a linear layer's state at the prefix's end is not
                    # held (snapshots: PERF.md section 7.2): recomputed
                    lcp = 0
                if lcp > best_lcp:
                    best_slot, best_lcp = s, lcp
        if best_slot is not None and best_lcp >= self.ec.prompt_cache_min:
            self._free.remove(best_slot)
            return best_slot, best_lcp
        # cold admission: take the free slot with the LEAST useful cached
        # record, so other tenants' warm prefixes survive (llama.cpp picks
        # the slot without a usable cache the same way)
        cold = min(self._free,
                   key=lambda s: len(self._slot_kv_tokens[s]))
        self._free.remove(cold)
        return cold, 0

    def _ring_holds(self, lcp: int, cached: int) -> bool:
        """Whether a slot whose last tenant left `cached` tokens can lend a
        new one their first `lcp`: the window layers' rings (models/llama.py
        ring_len) must still hold the window the next query looks back over,
        positions lcp - window + 1 .. lcp - 1. Position p is gone once
        p + ring has been written, and the device may have written past what
        the host counts: the dispatches in flight when a request ends (up to
        two of decode_loop or decode_block steps). Else the prompt is
        prefilled from 0, never over a stale ring."""
        if WINDOW not in self.cfg.period:
            return True
        ring = self._kc.slots[self.cfg.period.index("window")].shape[3]
        written = cached + 2 * max(self.ec.decode_loop, self.ec.decode_block,
                                   1)
        return max(lcp - self.cfg.sliding_window + 1, 0) + ring >= written

    # --------------------------------------------- disk prompt cache
    # (reference PromptCachePath/PromptCacheAll/PromptCacheRO — llama.cpp
    # persists a prompt's KV to a file and restores it across restarts)

    def _load_prompt_cache(self, slot: int, req: GenRequest) -> int:
        """Restore a saved KV prefix into `slot` if the file's tokens prefix
        this prompt. Returns the reusable length (0 = cold)."""
        if (not self._cache_addressable or self._draft is not None
                or self._paged or self._mixed):
            # (mixed: the file holds one [L, ...] cache; the prompt is
            # prefilled instead)
            return 0
        try:
            with np.load(req.prompt_cache_path, allow_pickle=False) as z:
                tokens = z["tokens"].tolist()
                leaves = {k: z[k] for k in z.files if k != "tokens"}
        except Exception:
            # corrupt/truncated/foreign files raise a zoo (BadZipFile,
            # zlib.error, ValueError...) — all of them mean cold prefill,
            # never a dead engine
            return 0
        limit = self.ec.max_context - 2 - self._ctx_reserve
        m = min(len(tokens), len(req.prompt_ids) - 1, limit - 1)
        lcp = 0
        while lcp < m and tokens[lcp] == req.prompt_ids[lcp]:
            lcp += 1
        if lcp < self.ec.prompt_cache_min:
            return 0
        try:
            self._kc, self._vc = self._cache_inject(
                self._kc, self._vc, slot, leaves, lcp)
        except Exception:
            return 0
        return lcp

    def _cache_inject(self, kc, vc, slot: int, leaves: dict, n: int):
        """Write saved KV rows [L, KVH, n, D] into slot's cache region."""
        from localai_tpu.ops.kvcache import QuantKV

        if isinstance(kc, QuantKV):
            kc = QuantKV(kc.q.at[:, slot, :, :n].set(leaves["kq"][:, :, :n]),
                         kc.s.at[:, slot].set(leaves["ks"]))
            vc = QuantKV(vc.q.at[:, slot, :, :n].set(leaves["vq"][:, :, :n]),
                         vc.s.at[:, slot].set(leaves["vs"]))
            return kc, vc
        kc = kc.at[:, slot, :, :n].set(
            jnp.asarray(leaves["k"][:, :, :n], kc.dtype))
        vc = vc.at[:, slot, :, :n].set(
            jnp.asarray(leaves["v"][:, :, :n], vc.dtype))
        return kc, vc

    def _save_prompt_cache(self, idx: int, slot: _Slot):
        """Persist the slot's prompt-KV rows + token ids to the request's
        cache file (skipped for RO requests, meshes, shifted slots)."""
        if (not slot.req.prompt_cache_path or slot.req.prompt_cache_ro
                or not self._cache_addressable or self._draft is not None
                or self._paged or self._mixed or slot.shifted
                or not slot.prefilled
                or slot.req.mm_embeds is not None):
            # (mm: no reuse path can load it, and the repeated image-token
            # ids could positionally match a text prompt — see _release_slot)
            return
        n = min(slot.prompt_len, self.ec.max_context - 2)
        if slot.disk_prefix >= n - 1:
            return   # the file already covers this prompt — skip the
                     # device→host transfer + rewrite (hot shared prefix)
        try:
            from localai_tpu.ops.kvcache import QuantKV

            if isinstance(self._kc, QuantKV):
                leaves = {
                    "kq": np.asarray(self._kc.q[:, idx, :, :n]),
                    "ks": np.asarray(self._kc.s[:, idx]),
                    "vq": np.asarray(self._vc.q[:, idx, :, :n]),
                    "vs": np.asarray(self._vc.s[:, idx]),
                }
            else:
                # f32 on disk: npz round-trips bfloat16 as raw void bytes
                # that cannot cast back — upcast once here instead
                leaves = {
                    "k": np.asarray(self._kc[:, idx, :, :n]).astype(
                        np.float32),
                    "v": np.asarray(self._vc[:, idx, :, :n]).astype(
                        np.float32),
                }
            tmp = slot.req.prompt_cache_path + ".tmp"
            with open(tmp, "wb") as f:   # file handle: savez must not
                np.savez(f, tokens=np.asarray(   # append its own .npz
                    slot.req.prompt_ids[:n], np.int64), **leaves)
            os.replace(tmp, slot.req.prompt_cache_path)
        except Exception:   # best-effort: a faulted device or full disk
                            # must not break _fail_active's cleanup loop
            import logging

            logging.getLogger("localai_tpu").warning(
                "failed to write prompt cache %s",
                slot.req.prompt_cache_path, exc_info=True)

    def _release_slot(self, idx: int, slot: _Slot):
        self._finish_rid(slot.request_id)
        if self._flightrec is not None and slot.timeline is not None:
            self._flightrec.record_request(slot.timeline)
        if slot.span is not None and self._tracer is not None:
            ttft_ms = ((slot.first_token_time - slot.start_time) * 1e3
                       if slot.first_token_time is not None else None)
            self._tracer.finish(slot.span, generated=slot.generated,
                                ttft_ms=ttft_ms)
            slot.span = None
        self._save_prompt_cache(idx, slot)
        if slot.matcher is not None:
            self._mask_host[idx] = 0xFF
            self._grammar_slots -= 1
            self._gstate[idx] = 0  # row 0 = identity (all-ones, self-loop)
            if slot.gbase is None:
                self._grammar_hostonly -= 1
        windowed = False
        if self._tiered:
            pol = self._slot_policy[idx]
            windowed = pol is not None and pol.windowed
        if self._paged:
            if (self.ec.prompt_cache and slot.shifted == 0
                    and self._draft is None and not windowed):
                # retain ONLY the blocks holding cached rows as the warm
                # prefix cache (reclaimable oldest-first, _take_blocks); the
                # unused tail of the reservation returns to the pool now.
                # Safe against the in-flight pipelined step: it writes
                # through the table captured at ITS dispatch, and device
                # ordering runs it before any later admission's prefill.
                from localai_tpu.ops.paged import blocks_needed

                kept = min(slot.prompt_len + slot.generated,
                           self.ec.max_context - 2)
                keep = blocks_needed(kept)
                blocks = self._slot_blocks[idx]
                if len(blocks) > keep:
                    self._unref_blocks(blocks[keep:])
                    del blocks[keep:]
                    self._table[idx, keep:] = 0
                # register every FULL block in the content-hash index: a
                # future admission sharing the prefix maps these pages into
                # its own table (block-level prefix cache). Multimodal rows
                # are excluded for the same reason as the token record
                # below — identical image-token ids, different KV.
                if slot.req.mm_embeds is None:
                    ids = (list(slot.req.prompt_ids) + slot.gen_ids)[:kept]
                    for vb, h in enumerate(self._chain_hashes(ids)):
                        pb = blocks[vb]
                        if h not in self._hash_index:
                            self._drop_hash(pb)
                            self._hash_index[h] = pb
                            self._block_hash_of[pb] = h
                self._released_lru.append(idx)
            else:
                # windowed slots land here too: ring columns hold position-
                # rotated content no other tenant can address, so nothing is
                # retained or hash-registered — every block returns NOW
                self._unref_blocks(self._slot_blocks[idx])
                self._slot_blocks[idx] = []
                self._table[idx, :] = 0
            self._blocks_freed = True
        if self._tiered:
            # reset the slot's geometry to the full-policy sentinels (the
            # in-flight pipelined dispatch captured ITS OWN copy at
            # dispatch time — _kvt materializes per call)
            self._kv_sb[idx] = self._maxb
            self._kv_rw[idx] = 1
            self._kv_sinks[idx] = self.ec.max_context
            self._kv_window[idx] = self.ec.max_context
            self._slot_policy[idx] = None
            self._demote_next[idx] = 0
            if self._cold:
                for ci in self._slot_cold[idx]:
                    self._cold_free.append(ci)
                self._slot_cold[idx] = []
                self._cold_table[idx, :] = 0
            self._note_pool()
        # record what this slot's cache still holds (valid rows 0..len-1) so
        # a future prompt sharing the prefix skips that part of its prefill.
        # Shifted slots moved rows — their mapping is no longer positional.
        # (multimodal prompts excluded: their image-token ids all look alike
        # while the injected embeddings differ per image, so positional
        # prefix-matching on ids would reuse the WRONG image's KV)
        if (self.ec.prompt_cache and self._draft is None
                and slot.shifted == 0 and slot.req.mm_embeds is None
                and not windowed):
            kept = (list(slot.req.prompt_ids) + slot.gen_ids)[
                : self.ec.max_context - 2]
            self._slot_kv_tokens[idx] = kept
        else:
            self._slot_kv_tokens[idx] = []
        self._slots[idx] = None
        self._free.append(idx)

    # ------------------------------------------------------------ run modes

    def warmup(self):
        """Pre-compile the decode hot-path programs — the while-loop decode
        variants (every sort-free sampling tier) plus the remaining scan
        ladder widths the grammar/stop-string fallback still rides — so the
        first requests (and bench window 0) never pay an XLA compile
        mid-stream. Dispatches run with an all-inactive slot mask: every
        cache write redirects to the trash row/block and no slot state is
        consumed, but it MUST run before any request is admitted. Dispatch
        metrics are snapshotted so warmup doesn't pollute the fusing
        telemetry."""
        if any(s is not None for s in self._slots):
            raise RuntimeError("warmup() requires an idle engine")
        B, V = self.ec.max_slots, self.cfg.vocab_size
        snap = {k: self.metrics[k] for k in (
            "decode_dispatches", "decode_steps_dispatched",
            "host_sync_wait_ms")}
        idle = np.zeros((B,), bool)
        ones_mask = np.full((B, self._mask_nbytes), 0xFF, np.uint8)
        idle_gstate = (np.zeros((B,), np.int32)
                       if self._gtab_cap > 0 else None)
        try:
            if self._draft is not None:
                self._dev_spec_decode(idle).wait()
                return
            widths = [None]
            W = self.ec.sampling_topk_width
            if W:
                widths.append(min(W, V))
                if min(8 * W, V) != min(W, V):
                    widths.append(min(8 * W, V))   # the escalation tier
            for w in widths:
                if self._decode_loop_fn is not None:
                    self._dev_decode_loop(
                        idle, np.zeros((B,), np.int32),
                        np.zeros((B,), bool), w).wait()
                self._dev_decode(idle, None, w).wait()
            if self._decode_loop_fn is not None and idle_gstate is not None:
                # the grammar-table loop variant (full-sort sampling only —
                # masked slots never ride a fast_width tier)
                self._dev_decode_loop(idle, np.zeros((B,), np.int32),
                                      np.zeros((B,), bool), None,
                                      gstate=idle_gstate).wait()
            # the dense masked step: the path every grammar config can
            # still fall back to (host-only automata, decode_loop=0)
            self._dev_decode(idle, ones_mask, None).wait()
            steps = self.ec.decode_block
            while steps > 1:
                self._dev_decode_block(idle, steps, None, None).wait()
                steps //= 2
        finally:
            self.metrics.update(snap)
            if self._sched is not None:
                # keep the captured variant avals (rooflines needs them) but
                # drop the warmup dispatches from the ledger stream — the
                # serving/bench counters start clean, same as `snap` above
                self._sched.reset()

    def rooflines(self, force: bool = False) -> dict:
        """Per-variant XLA cost analysis → roofline attribution (ISSUE 13).

        AOT-lowers each captured decode/spec/loop variant with its
        abstract arg shapes (jax.ShapeDtypeStruct — see _sched_pack) and
        reads `compile().cost_analysis()` for FLOPs + bytes accessed. The
        AOT compile does NOT populate the jit call cache, so the
        compile-count tripwire (decode_compile_count) is unaffected — but
        it IS a real XLA compile per variant, visible to jax.log_compiles:
        call this off the measured path (bench: after the windows; server:
        first /debug/sched or GetTrace). Results are cached on the engine
        and mirrored into the tick ledger for GetMetrics `sched_roofline_*`
        keys."""
        if self._rooflines is not None and not force:
            return self._rooflines
        from localai_tpu import telemetry
        from localai_tpu.system.capabilities import CHIPS

        chip = CHIPS.get(jax.devices()[0].device_kind)
        peak = chip.bf16_flops if chip else None
        bw = chip.hbm_bytes_per_s if chip else None
        out: dict[str, dict] = {}
        for name, spec in list(self._variant_avals.items()):
            if spec is None:
                continue
            fn, fargs, fkw = spec
            try:
                with activate_mesh(self.mesh):
                    ca = fn.lower(*fargs, **fkw).compile().cost_analysis()
            except Exception:
                continue
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if not ca:
                continue
            flops = float(ca.get("flops", 0.0))
            bytes_ = float(ca.get("bytes accessed", 0.0))
            if flops <= 0 and bytes_ <= 0:
                continue
            out[name] = telemetry.roofline_entry(flops, bytes_, peak, bw)
        self._rooflines = out
        if self._sched is not None:
            self._sched.rooflines = out
        return out

    def sched_snapshot(self, ticks: int = 64,
                       with_rooflines: bool = True) -> dict:
        """Structured tick-ledger export for /debug/sched and GetTrace —
        {} when the ledger is disabled. Computes (and caches) the roofline
        pass on first call unless `with_rooflines` is False."""
        if self._sched is None:
            return {}
        if with_rooflines:
            try:
                self.rooflines()
            except Exception:
                pass
        snap = self._sched.snapshot(ticks)
        kh = self.kvhost_snapshot()
        if kh:
            snap["kv_host"] = kh
        return snap

    def start(self):
        """Run the engine loop in a background thread (serving mode)."""
        if self._running:
            return
        self._running = True
        self._dead = False
        self._tick_began = None
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        threading.Thread(target=self._watch, args=(self._thread,),
                         daemon=True).start()

    def _watch(self, thread):
        """Beside the engine thread: a tick that has not ended after
        TICK_LIMIT_S is a device that does not answer (the thread sits in a
        transfer or a dispatch and cannot be told). Say so, with every
        thread's stack, end the engine and fail what it holds: LoadModel's
        warm requests and the clients get a terminal output and a
        last_error, not silence. The engine thread is left where it is; if
        it ever returns it finds the engine ended."""
        while self._running and self._thread is thread:
            began = self._tick_began
            if began is not None and time.monotonic() - began > TICK_LIMIT_S:
                import faulthandler
                import sys

                self.last_error = (
                    f"engine tick has not ended after {TICK_LIMIT_S:.0f} s: "
                    "the device does not answer (a program that hangs); "
                    "the engine is ended")
                print(f"FATAL: {self.last_error}", file=sys.stderr,
                      flush=True)
                faulthandler.dump_traceback(file=sys.stderr)
                self._running = False
                self._dead = True
                self._fail_active("error")
                return
            time.sleep(min(5.0, TICK_LIMIT_S / 4))

    def stop(self):
        was_serving = self._thread is not None
        self._running = False
        self._dead = True
        self._wake.set()
        if self._thread:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                # thread stuck (e.g. mid-compile): do NOT reclaim slots it may
                # still touch — consumers see the engine as dead via submit()
                return
            self._thread = None
        if was_serving:
            self._fail_active("cancelled")

    def preempt(self, grace: float = 0.0) -> list[dict]:
        """Preemption notice (ISSUE 19): freeze every in-flight request,
        force-spill their KV chains to the host tier, and return a resume
        manifest (one ResumeToken dict per live/queued request).

        For up to ``grace`` seconds the engine keeps decoding — slots that
        finish naturally stream their normal terminal chunk — then the
        spill-drain runs at a tick boundary: each surviving slot gets a
        terminal StepOutput with finish_reason "preempted" carrying its
        checkpoint.  Unlike drain_model (wait for idle) or a kill (lose
        everything), nothing is waited to completion and nothing is lost.

        Safe from any thread; with no loop thread running (generate()/test
        mode) the drain runs inline.  The engine stays serviceable — a
        resume may be submitted right back into it."""
        if self._dead:
            return []
        self._preempt_manifest = []
        self._preempt_done.clear()
        self._preempt_t = time.monotonic() + max(float(grace), 0.0)
        if self._thread is not None and self._thread.is_alive():
            self._preempt_req.set()
            self._wake.set()
            self._preempt_done.wait(timeout=max(float(grace), 0.0) + 60.0)
        else:
            self._preempt_req.set()
            while (self._preempt_req.is_set()
                   and time.monotonic() < self._preempt_t
                   and any(s is not None for s in self._slots)):
                self.step()
            if self._preempt_req.is_set():
                self._spill_drain()
        return list(self._preempt_manifest)

    def _spill_drain(self):
        """Engine-thread half of preempt(): consume the in-flight pipelined
        dispatch, checkpoint + spill + release every live slot, manifest
        queued/deferred work, land the spills in the host pool."""
        from localai_tpu.engine.resume import ResumeToken

        self._preempt_req.clear()
        t0 = time.perf_counter()
        if self._pending is not None:
            self._consume(self._pending)
            self._pending = None
        self._prefillq.clear()
        manifest: list[dict] = []
        live = [i for i, s in enumerate(self._slots) if s is not None]
        keys = None
        if live:
            try:
                # explicit sanctioned D2H read (same class as _AsyncFetch
                # .wait): the per-slot RNG carry keys advance on device per
                # dispatch, so byte-exact sampled resume needs the real
                # device values, not a host-side replay from the seed
                keys = np.asarray(jax.device_get(self._sampler.key))
            except Exception:
                keys = None    # greedy-only resume still works
        now = time.monotonic()
        spilled_total = 0
        frozen_rids: set[int] = set()
        for idx in live:
            slot = self._slots[idx]
            if slot is None:
                continue
            frozen_rids.add(slot.request_id)
            tok, spilled = self._freeze_slot(idx, slot, keys, now)
            spilled_total += spilled
            manifest.append(tok.to_dict())
            timings = None
            if self._slo is not None:
                timings = self._timeline(slot, "preempted", now)
                slot.timeline = timings
            slot.out.put(StepOutput(
                request_id=slot.request_id, text="", token_id=-1,
                logprob=0.0, finished=True, finish_reason="preempted",
                generated_tokens=slot.generated,
                prompt_tokens=slot.prompt_len,
                timings=timings, resume=tok.to_dict(),
            ))
            if not slot.prefilled:
                # mid-prefill slot: its block list is only partially
                # written — take _release_slot's no-retention path (the
                # shifted branch) so garbage blocks are never registered
                # in the prefix-cache hash index
                slot.shifted = max(slot.shifted, 1)
            self._release_slot(idx, slot)
        # queued / deferred / mid-admission requests have no device state:
        # their manifest entries are plain resubmits (emitted=[])
        waiting = []
        if self._deferred is not None:
            waiting.append(self._deferred)
            self._deferred = None
        if self._admitting is not None:
            rid, req, out = self._admitting
            self._admitting = None
            if rid not in frozen_rids:   # died before reaching a slot
                waiting.append((rid, req, out))
        while True:
            try:
                waiting.append(self._queue.get_nowait())
            except queue.Empty:
                break
        for rid, req, out in waiting:
            tok = ResumeToken(
                prompt_ids=list(req.prompt_ids), emitted=[],
                deadline_left=(max(req.deadline - now, 0.0)
                               if req.deadline else 0.0),
                request_id=req.trace_id or f"rid-{rid}")
            manifest.append(tok.to_dict())
            self._finish_rid(rid)
            out.put(StepOutput(
                request_id=rid, text="", token_id=-1, logprob=0.0,
                finished=True, finish_reason="preempted",
                prompt_tokens=len(req.prompt_ids),
                resume=tok.to_dict(),
            ))
        self._host_drain()
        self.metrics["preempts"] += 1
        self.metrics["preempt_spilled_blocks"] += spilled_total
        if self._flightrec is not None:
            self._flightrec.record_event(
                "preempt", slots=len(live), queued=len(waiting),
                spilled_blocks=spilled_total,
                drain_ms=(time.perf_counter() - t0) * 1e3)
        self._preempt_manifest = manifest
        self._preempt_done.set()

    def _freeze_slot(self, idx: int, slot: _Slot, keys, now: float):
        """Checkpoint one live slot into a ResumeToken, force-spilling its
        full KV chain blocks to the host tier (same eligibility rules as
        _release_slot's retention: no mm, no shift, no draft, no window)."""
        from localai_tpu.engine.resume import ResumeToken

        req = slot.req
        spilled = 0
        chain_hex: list[str] = []
        windowed = False
        if self._tiered:
            pol = self._slot_policy[idx]
            windowed = pol is not None and pol.windowed
        if (self._paged and self.ec.prompt_cache and self._kvhost is not None
                and slot.prefilled and slot.shifted == 0
                and req.mm_embeds is None and self._draft is None
                and not windowed):
            from localai_tpu.ops.paged import BLOCK

            kept = min(slot.prompt_len + slot.generated,
                       self.ec.max_context - 2)
            ids = (list(req.prompt_ids) + slot.gen_ids)[:kept]
            chain = self._chain_hashes(ids)
            blocks = self._slot_blocks[idx]
            group = chain[0] if chain else None
            for vb, h in enumerate(chain):
                if vb >= len(blocks):
                    break
                self._spill_block(blocks[vb], h=h, group=group)
                spilled += 1
                chain_hex.append(h.hex())
            if spilled and self._sched is not None:
                self._sched.reason("preempt_spill", slot=int(idx),
                                   blocks=int(spilled))
        key = None
        if keys is not None and not req.params.normalized().greedy:
            key = [int(k) for k in np.asarray(keys[idx], np.uint32)]
        # a slot that is itself a resume carries replayed emitted-chain
        # tokens inside its prompt (resume_base); fold them back into the
        # checkpoint's emitted list so the ORIGINAL prompt boundary — and
        # with it detok replay and sent_chars dedup — stays fixed across
        # any number of preempt/resume rounds
        cut = slot.prompt_len - slot.resume_base
        return ResumeToken(
            prompt_ids=list(req.prompt_ids[:cut]),
            emitted=list(req.prompt_ids[cut:]) + list(slot.gen_ids),
            key=key,
            sent_chars=int(slot.sent_chars),
            chain=chain_hex,
            deadline_left=(max(req.deadline - now, 0.0)
                           if req.deadline else 0.0),
            request_id=req.trace_id or f"rid-{slot.request_id}",
        ), spilled

    def _fail_active(self, reason: str):
        """Send a terminal StepOutput to every in-flight slot + queued request
        so no consumer blocks forever on its output queue."""
        self._pending = None
        self._prefillq.clear()
        failed_rids = set()
        for slot in self._slots:
            if slot is not None:
                failed_rids.add(slot.request_id)
        if self._deferred is not None:
            rid, req, out = self._deferred
            self._deferred = None
            self._finish_rid(rid)
            out.put(StepOutput(request_id=rid, text="", token_id=-1,
                               logprob=0.0, finished=True,
                               finish_reason=reason))
        if self._admitting is not None:
            rid, req, out = self._admitting
            self._admitting = None
            if rid not in failed_rids:  # died before reaching a slot
                self._finish_rid(rid)
                out.put(StepOutput(request_id=rid, text="", token_id=-1,
                                   logprob=0.0, finished=True,
                                   finish_reason=reason))
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            timings = None
            if self._slo is not None:
                # the dying request's timeline reaches the flight recorder
                # (via _release_slot) and its terminal chunk — the black-box
                # record the post-mortem dump is for
                timings = self._timeline(slot, reason, time.monotonic())
                slot.timeline = timings
            slot.out.put(StepOutput(
                request_id=slot.request_id, text="", token_id=-1, logprob=0.0,
                finished=True, finish_reason=reason,
                generated_tokens=slot.generated, prompt_tokens=slot.prompt_len,
                timings=timings,
            ))
            self._release_slot(i, slot)
        while True:
            try:
                rid, req, out = self._queue.get_nowait()
            except queue.Empty:
                break
            self._finish_rid(rid)
            out.put(StepOutput(request_id=rid, text="", token_id=-1,
                               logprob=0.0, finished=True,
                               finish_reason=reason))

    def _loop(self):
        restarts = 0
        while self._running:
            try:
                self._tick_began = time.monotonic()
                busy = self.step()
                self._tick_began = None
            except Exception as e:  # device OOM, compile failure, ...
                import traceback

                traceback.print_exc()
                self.last_error = f"{type(e).__name__}: {e}"
                self._fail_active("error")
                # black box first (rare path — always recorded, dump capped):
                # the ring now holds every failed request's timeline
                from localai_tpu.telemetry import flightrec

                rec = flightrec()
                rec.record_event("engine_fatal",
                                 error=f"{type(e).__name__}: {e}",
                                 restarts=restarts)
                rec.auto_dump("engine_fatal")
                if restarts >= self.ec.max_restarts:
                    self._running = False
                    self._dead = True
                    return
                restarts += 1
                # donation may have invalidated the carried device buffers —
                # rebuild state from scratch (weights are never donated) and
                # keep serving new requests
                try:
                    self._bcast("reset")
                    self._init_device_state()
                except Exception:
                    traceback.print_exc()
                    self._running = False
                    self._dead = True
                    self._fail_active("error")
                    return
                continue
            if not busy:
                self._wake.clear()
                self._wake.wait(timeout=0.05)

    def generate(self, req: GenRequest) -> Iterator[StepOutput]:
        """Synchronous convenience: submit + drive the loop until finished.
        Only valid when the background thread is NOT running."""
        if self._running:
            raise RuntimeError("use submit() while the engine loop is running")
        rid, out = self.submit(req)
        done = False
        while not done:
            self.step()
            while True:
                try:
                    o = out.get_nowait()
                except queue.Empty:
                    break
                yield o
                if o.finished:
                    done = True

    def generate_text(self, req: GenRequest) -> str:
        return "".join(o.text for o in self.generate(req))

    @property
    def _loop_steps(self) -> int:
        """Steps one fused loop may run: the budget `_dispatch_loop` gives
        each row inside the decode_loop program, no other program. A loop's
        tokens reach their streams when it ends, and a row that finishes
        inside it keeps its slot until then. A model with window and full
        layers takes ~36 ms a step on a v5e (PERF.md section 5): 64 steps
        held every stream 2.3 s and handed the clients 64 x rows tokens at
        once, so its loops are a decode_block long. A one-kind model keeps
        decode_loop: shortening its loops is for a PR that re-measures the
        cells it is held to. (Down here so that no line above moves: a
        kernel's compile-cache key holds its callers' line numbers.)"""
        if self._mixed and 1 < self.ec.decode_block < self.ec.decode_loop:
            return self.ec.decode_block
        return self.ec.decode_loop
