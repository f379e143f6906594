"""The OpenAI-compatible HTTP server (L5) — aiohttp.

Surface mirrors the reference routes (/root/reference/core/http/routes/
openai.go:13-181 + localai.go): /v1/chat/completions (SSE streaming loop like
chat.go:334-449), /v1/completions, /v1/embeddings, /v1/models, rerank,
tokenize, Prometheus /metrics, health. The RequestExtractor middleware
semantics (request.go:118-211) live in `_merged_options`: per-request JSON
fields override the model YAML's `parameters:` defaults.

gRPC backends are synchronous; unary calls run in the default executor and
streams are bridged thread→asyncio.Queue so one slow model never blocks the
event loop.
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import contextvars
import json
import os
import threading
import time

import grpc
from aiohttp import web

from localai_tpu import telemetry
from localai_tpu.config import AppConfig, ModelConfig, ModelConfigLoader
from localai_tpu.core import resilience
from localai_tpu.core.manager import ModelManager
from localai_tpu.server import schema
from localai_tpu.testing.lockdep import lockdep_lock

try:
    from prometheus_client import (
        CONTENT_TYPE_LATEST, Counter, Gauge, Histogram, REGISTRY,
        generate_latest,
    )
    from prometheus_client.core import HistogramMetricFamily

    _API_CALLS = Counter("localai_api_calls_total", "API calls",
                         ["path", "status"])
    _API_LATENCY = Histogram("localai_api_latency_seconds", "API latency",
                             ["path"])
    # the engine thread's phases (telemetry.PhaseClock) and the backend's
    # XLA compiles, refreshed from GetMetrics engine_host_ms__* /
    # engine_wait_ms__* / xla_compiles_total at scrape time
    _ENGINE_PHASE = Counter(
        "localai_engine_phase_seconds_total",
        "Engine-thread time by phase (kind: host work or wait)",
        ["model", "kind", "phase"])
    _XLA_COMPILES = Counter(
        "localai_xla_compiles_total",
        "XLA backend compiles of the model's backend process", ["model"])
    # tokens x MoE layers by the form the expert layer took for the call
    # (engine expert_tokens__routed / __dense, models/llama.expert_form)
    _EXPERT_TOKENS = Counter(
        "localai_expert_tokens_total",
        "Tokens x MoE layers by the expert layer's form (routed or dense)",
        ["model", "form"])
    # rows of a full layer's cache row a prompt chunk's attention visited,
    # and the row's capacity (engine chunk_ctx_tokens__attended / __capacity)
    _CHUNK_CTX = Counter(
        "localai_chunk_ctx_tokens_total",
        "Cache rows a prompt chunk's attention visited (attended) and the "
        "row's capacity, a full-attention layer a chunk", ["model", "rows"])
    # context tokens ONE layer of each kind attended over in decode (engine
    # decode_ctx_tokens__full / __window / __latent), and the context rows a
    # latent layer's prompt chunks put through its up-projection
    # (chunk_latent_rows__expanded)
    _DECODE_CTX = Counter(
        "localai_decode_ctx_tokens_total",
        "Context tokens a decode step's live rows attended over, one layer "
        "of each kind", ["model", "kind"])
    _LATENT_ROWS = Counter(
        "localai_chunk_latent_rows_total",
        "Cached latent rows a prompt chunk expanded into keys and values, "
        "a latent layer a chunk", ["model"])
    # streams open against the model's backend now (the gate's count)
    _STREAMS_OPEN = Gauge("localai_streams_open",
                          "Streams open against the model's backend",
                          ["model"])
    # load shedding (ISSUE 4): every 429/503 the admission layer or the
    # drain path produces is counted here so shedding is observable
    _SHED = Counter("localai_shed_total",
                    "Requests shed by admission control or drain",
                    ["model", "reason"])
    # preemption-safe serving (ISSUE 19): mid-stream resumes by outcome —
    # "ok" (the resumed stream produced its next chunk), "error" (every
    # resume lane failed and the terminal SSE error surfaced), "replay"
    # (deterministic re-issue with prompt+emitted, resume lane disabled)
    _RESUME = Counter("localai_resume_total",
                      "Mid-stream preemption resumes", ["model", "outcome"])
    # backend supervision events (spawn retries, respawns, watchdog reaps,
    # breaker rejections) — refreshed from ModelManager.events at scrape;
    # cumulative event counts → Counter (was a mis-typed Gauge)
    _SUPERVISION = Counter("localai_backend_supervision_total",
                           "Backend supervision events", ["model", "event"])
    # scheduler X-ray (ISSUE 13): tick-ledger series refreshed from each
    # backend's GetMetrics sched_* keys at scrape time
    _SCHED_REASONS = Counter(
        "localai_sched_reason_total",
        "Scheduler decisions by registered reason code", ["model", "code"])
    _SCHED_DISPATCHES = Counter(
        "localai_sched_dispatches_total",
        "Engine dispatches by compiled program variant",
        ["model", "variant"])
    _SCHED_TICKS = Counter(
        "localai_sched_ticks_total", "Engine scheduler ticks", ["model"])
    _SCHED_PAD = Gauge(
        "localai_sched_pad_rows_frac",
        "Fraction of dispatched rows that carried no live sequence",
        ["model"])
    # host-RAM KV tier (ISSUE 17): pool occupancy is a level (Gauge);
    # spill/hit/eviction totals are cumulative (Counter via _counter_sync)
    _KV_HOST = Gauge(
        "localai_kv_host", "Host KV tier occupancy",
        ["model", "stat"])
    # NOTE: the counter family must not share the Gauge's base name —
    # prometheus_client strips the _total suffix at registration, so
    # "localai_kv_host_total" would collide with the Gauge above
    _KV_HOST_EVENTS = Counter(
        "localai_kv_host_events_total", "Host KV tier cumulative events",
        ["model", "event"])
    # last cumulative value each counter child was synced to, keyed by the
    # label tuple — a backend restart resets its counters, which _counter_sync
    # treats as a fresh start (standard Prometheus counter-reset semantics)
    _COUNTER_LAST: dict = {}

    def _counter_sync(counter, labels: tuple, value: float):
        """Bring a scrape-fed Counter child to an absolute cumulative value
        by inc-ing the delta (Counter has no .set, by design)."""
        key = (counter, labels)
        last = _COUNTER_LAST.get(key, 0.0)
        if value < last:     # source restarted: its series began again
            last = 0.0
        if value > last:
            counter.labels(*labels).inc(value - last)
            _COUNTER_LAST[key] = value
        elif key not in _COUNTER_LAST:
            counter.labels(*labels)   # materialize the child at 0
            _COUNTER_LAST[key] = value

    # latest per-model SLO histograms, refreshed at scrape from each
    # backend's GetMetrics hist_* keys (telemetry.metrics.parse_flat);
    # exposed as TRUE Prometheus histogram series by _SLOCollector
    _SLO_SCRAPE: dict = {}

    class _SLOCollector:
        """Custom collector rebuilding localai_request_<metric>_seconds
        histogram series (_bucket/_sum/_count, labels model+path) from the
        scraped engine histograms — prometheus_client's Histogram cannot be
        set to absolute bucket counts, a raw MetricFamily can."""

        def collect(self):
            fams = {}
            for model, hists in list(_SLO_SCRAPE.items()):
                for (metric, path), h in hists.items():
                    fam = fams.get(metric)
                    if fam is None:
                        fam = fams[metric] = HistogramMetricFamily(
                            f"localai_request_{metric}_seconds",
                            f"Per-request {metric} latency",
                            labels=["model", "path"])
                    acc, buckets = 0, []
                    for i, ub in enumerate(telemetry.BUCKETS_S):
                        acc += h.counts[i]
                        le = "+Inf" if ub == float("inf") else repr(ub)
                        buckets.append((le, acc))
                    fam.add_metric([model, path], buckets, h.sum)
            return list(fams.values())

    REGISTRY.register(_SLOCollector())
    _HAVE_PROM = True
except Exception:  # pragma: no cover - prometheus_client is in the image
    _HAVE_PROM = False

_OPEN_PATHS = {"/healthz", "/readyz", "/metrics"}

# sampling fields copied request-JSON → PredictOptions when present
_SAMPLING_FIELDS = (
    "temperature", "top_k", "top_p", "min_p", "typical_p", "repeat_penalty",
    "presence_penalty", "frequency_penalty", "seed", "ignore_eos",
)


_IMAGE_FETCH_LIMIT = 16 << 20   # 16 MiB of image bytes per URL


def _engine_timings(reply) -> dict:
    """The engine's per-request phase timeline (Reply.timings_json, set on
    the FINAL reply only) → the llama.cpp-style `timings` block: queued→
    admitted→first_token→finished ms, decode path, dispatch count."""
    raw = getattr(reply, "timings_json", "")
    if not raw:
        return {}
    try:
        t = json.loads(raw)
    except ValueError:
        return {}
    return t if isinstance(t, dict) else {}


def _fetch_image(url: str) -> str:
    """Fetch a remote image_url → base64, with the two server-side hazards
    closed: a size cap (the body is b64-expanded into the request pipeline)
    and an SSRF guard (no loopback/link-local/private targets — a chat
    request must not become a probe of the server's network)."""
    import base64
    import ipaddress
    import socket
    import urllib.parse
    import urllib.request

    host = urllib.parse.urlparse(url).hostname or ""
    try:
        infos = socket.getaddrinfo(host, None)
    except OSError as e:
        raise ValueError(f"cannot resolve image host {host!r}: {e}")
    for info in infos:
        ip = ipaddress.ip_address(info[4][0])
        if (ip.is_private or ip.is_loopback or ip.is_link_local
                or ip.is_reserved or ip.is_multicast):
            raise ValueError(f"image host {host!r} resolves to a "
                             f"non-public address")
    with urllib.request.urlopen(url, timeout=30) as r:
        data = r.read(_IMAGE_FETCH_LIMIT + 1)
    if len(data) > _IMAGE_FETCH_LIMIT:
        raise ValueError(f"image at {host!r} exceeds "
                         f"{_IMAGE_FETCH_LIMIT >> 20} MiB")
    return base64.b64encode(data).decode()


class _AdmissionGate:
    """Per-model admission state. `slots` is the model's `parallel`, the
    engine's decode rows; the gate grants `limit` = `slots` + `ahead`
    permits, `ahead` = max(2, slots // 4), so that when a row frees the next
    prompt already stands tokenised in the engine's queue and has not its
    whole way in (gRPC, the chat template, the tokeniser) still before it.
    A request ahead of the slots holds its prompt's ids in the backend and
    nothing else: no slot, no KV; the engine's queue is FIFO, as the gate
    is, and checks its deadline and its cancellation as before. The extra
    permits are only ever out when `slots` are (`gate_grants_ahead` counts
    them): under capacity this is a gate of `slots`. Past `limit` at most
    `depth` requests wait; the rest shed with 429. Nothing else bounds the
    streams open against the backend: every permit has a pump thread of its
    own (started on first use, asleep in a gRPC read while its stream is
    open), so a stream never waits for a thread that another stream holds."""

    def __init__(self, name: str, slots: int, depth: int):
        self.slots = max(1, int(slots))
        self.limit = self.slots + max(2, self.slots // 4)
        self.depth = max(0, int(depth))
        self.sem = asyncio.Semaphore(self.limit)
        self.waiting = 0
        self.pumps = concurrent.futures.ThreadPoolExecutor(
            max_workers=self.limit, thread_name_prefix=f"pump-{name}")
        # permits out now; grants ever made, and those of them made while
        # `slots` or more permits were out (a request let in ahead of a
        # slot). All written on the loop's thread, like the rest of the gate
        self.out = 0
        self.grants = 0
        self.grants_ahead = 0
        # the wait at this gate, one observation per request (0 included):
        # the first stage of a request's TTFT, merged into the model's
        # metrics as hist_gate_wait__all__* (/backend/monitor, /metrics)
        self.wait_hist = telemetry.Hist()
        # permit -> the stream's pump thread running, one observation per
        # stream (hist_stream_start__all__*), and the streams open now;
        # both written on the loop's thread only
        self.start_hist = telemetry.Hist()
        self.streams_open = 0
        # a permit's tail and its whole life, one observation each per
        # stream that ran to the backend's finished reply (release):
        # hist_reply_to_release__all__*, hist_permit_hold__all__*
        self.tail_hist = telemetry.Hist()
        self.hold_hist = telemetry.Hist()

    def granted(self) -> None:
        """Count the permit just taken from `sem`."""
        self.grants += 1
        if self.out >= self.slots:
            self.grants_ahead += 1
        self.out += 1

    def release(self, permit: "_Permit", model: str) -> None:
        """Hand the permit back. If its stream ran to the backend's finished
        reply, observe the tail (the pump thread read the stream's end ->
        now: the queue to the loop, SSE's last chunks and `[DONE]`) and the
        permit's whole life (granted -> now). A stream that was cancelled or
        cut, and a request that streamed nothing, observe neither: a permit
        held for a client that has gone is no measure of a request's life.
        On the loop's thread, like every write to the gate."""
        self.sem.release()
        self.out -= 1
        if permit.ended is None:
            return
        now = time.monotonic()
        self.tail_hist.observe(now - permit.ended)
        self.hold_hist.observe(now - permit.at)
        tr = telemetry.maybe_tracer()
        if tr is not None:
            args = {"model": model,
                    "request_id": telemetry.current_request_id()}
            tr.add_complete("http.stage.reply_to_release", permit.ended,
                            now - permit.ended, cat="http", args=args)
            tr.add_complete("http.stage.permit_hold", permit.at,
                            now - permit.at, cat="http", args=args)

    def metrics(self) -> dict:
        """This process's share of the model's metrics, under the flat keys
        the backend's GetMetrics uses."""
        return {**self.wait_hist.flat("gate_wait"),
                **self.start_hist.flat("stream_start"),
                **self.tail_hist.flat("reply_to_release"),
                **self.hold_hist.flat("permit_hold"),
                "streams_open": float(self.streams_open),
                "gate_grants": float(self.grants),
                "gate_grants_ahead": float(self.grants_ahead),
                "gate_slots": float(self.slots),
                "gate_limit": float(self.limit)}


class _Permit:
    """One request's hold of a gate permit: when it was granted (`at`), and
    when its stream's pump thread read the stream's end after the backend's
    finished reply (`ended`; None while no stream of it ran to that)."""

    __slots__ = ("at", "ended")

    def __init__(self, at: float):
        self.at, self.ended = at, None


# the gate permit of the request running in this context
_PERMIT: contextvars.ContextVar[_Permit] = contextvars.ContextVar(
    "localai_permit")


class API:
    def __init__(self, app_config: AppConfig, configs: ModelConfigLoader,
                 manager: ModelManager):
        self.cfg = app_config
        self.configs = configs
        self.manager = manager
        # KV-affinity gossip (ISSUE 17): text-chain ids of every chat/
        # completion conversation this worker served, reported via /healthz
        # so the federation picker routes follow-up turns here. Maintained
        # unconditionally — it is a bounded dict of hex strings; the
        # federation layer decides whether anyone listens.
        from localai_tpu.engine.kvhost import PrefixDigest

        self._kv_served = PrefixDigest(cap=2048)
        self.app = web.Application(middlewares=[self._middleware],
                                   client_max_size=app_config.max_request_bytes)
        r = self.app.router
        r.add_get("/healthz", self._health)
        r.add_get("/readyz", self._health)
        r.add_get("/metrics", self._metrics)
        r.add_get("/v1/models", self._models)
        r.add_get("/models", self._models)
        r.add_post("/v1/chat/completions", self._chat)
        r.add_post("/chat/completions", self._chat)
        r.add_post("/v1/completions", self._completions)
        r.add_post("/completions", self._completions)
        r.add_post("/v1/edits", self._edits)
        # MCP agentic chat (reference endpoints/openai/mcp.go:1-142)
        r.add_post("/mcp/v1/chat/completions", self._mcp_chat)
        r.add_post("/mcp/v1/completions", self._mcp_chat)
        r.add_post("/v1/embeddings", self._embeddings)
        r.add_post("/embeddings", self._embeddings)
        r.add_post("/v1/rerank", self._rerank)
        r.add_post("/rerank", self._rerank)
        r.add_post("/v1/detection", self._detection)
        r.add_post("/v1/tokenize", self._tokenize)
        r.add_post("/tokenize", self._tokenize)
        r.add_get("/v1/realtime", self._realtime)
        r.add_post("/v1/realtime/sessions", self._realtime_session)
        r.add_post("/v1/realtime/transcription_session",
                   self._realtime_transcription_session)
        r.add_post("/v1/images/generations", self._images)
        r.add_post("/v1/videos", self._videos)
        r.add_post("/video", self._videos)
        r.add_post("/v1/audio/transcriptions", self._transcriptions)
        r.add_post("/v1/audio/speech", self._speech)
        r.add_post("/tts", self._speech)
        r.add_post("/vad", self._vad)
        r.add_post("/sound-generation", self._sound_generation)
        # telemetry debug surface: the ring spans of this process and every
        # backend merged into one Chrome trace, and a device trace of the
        # backend that holds the chip, taken on request
        r.add_get("/debug/trace", self._debug_trace)
        r.add_get("/debug/xprof", self._debug_xprof)
        # SLO observability (ISSUE 11): percentile snapshot per model+path
        # and the crash flight recorder (recent request timelines, engine
        # ticks, tripwire/breaker/supervision events)
        r.add_get("/debug/slo", self._debug_slo)
        r.add_get("/debug/flightrec", self._debug_flightrec)
        # scheduler X-ray (ISSUE 13): per-tick pack ledger, reason-code
        # counters, and per-variant cost-analysis rooflines
        r.add_get("/debug/sched", self._debug_sched)
        r.add_get("/backend/monitor", self._backend_monitor)
        r.add_post("/backend/shutdown", self._backend_shutdown)
        # explicit preemption notice (ISSUE 19): spill-drain the model's
        # backend into resume checkpoints instead of draining to completion
        r.add_post("/backend/preempt", self._backend_preempt)
        r.add_get("/system", self._system)
        r.add_post("/stores/set", self._stores_set)
        r.add_post("/stores/get", self._stores_get)
        r.add_post("/stores/delete", self._stores_delete)
        r.add_post("/stores/find", self._stores_find)
        r.add_post("/models/apply", self._models_apply)
        r.add_get("/models/available", self._models_available)
        r.add_get("/models/jobs/{job_id}", self._models_job)
        # backend gallery (reference routes/localai.go:53-58)
        r.add_get("/backends", self._backends_list)
        r.add_get("/backends/available", self._backends_available)
        r.add_get("/backends/galleries", self._backends_galleries)
        r.add_post("/backends/apply", self._backends_apply)
        r.add_post("/backends/delete/{name}", self._backends_delete)
        r.add_get("/backends/jobs/{job_id}", self._backends_job)
        # WebUI (reference routes/ui.go role) + API-compat route families
        r.add_get("/", self._webui)
        r.add_get("/chat", self._webui)
        # elevenlabs compat (reference routes/elevenlabs.go)
        r.add_post("/v1/text-to-speech/{voice_id}", self._elevenlabs_tts)
        r.add_post("/v1/sound-generation", self._sound_generation)
        self.gallery_service = None  # wired by run_server when galleries set
        self.backend_gallery_service = None  # ditto (backend registry)
        self._mcp_sessions: dict[str, list] = {}   # model → MCP sessions
        self._mcp_lock = lockdep_lock("http.mcp")
        # resilience state (ISSUE 4): per-model admission gates, the drain
        # flag the middleware turns into 503s, and the live-request count
        # graceful shutdown waits on
        self._gates: dict[str, _AdmissionGate] = {}
        # /backend/monitor and the /metrics scrape get two threads of their
        # own: a unary request holds a thread of the loop's default executor
        # (cpu count + 4) until the backend answers, and a scrape must not
        # wait behind those
        self._scrape_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=2, thread_name_prefix="scrape")
        self._draining = False
        self._inflight = 0
        # SIGTERM → web.run_app GracefulExit → runner.cleanup → here:
        # drain in-flight work instead of reaping backends mid-generation
        self.app.on_shutdown.append(self._on_shutdown)

    # ------------------------------------------------------------ middleware

    async def _federation_ok(self, request: web.Request) -> bool:
        """A valid shared-token HMAC signature (federation/auth.py — the
        reference's p2p token role, p2p.go:31-66) authorizes a request like
        an API key: that's how a federation LB reaches api-key-protected
        workers without distributing the keys."""
        if not getattr(self.cfg, "federation_token", ""):
            return False
        from localai_tpu.federation.auth import HEADER, verify

        header = request.headers.get(HEADER)
        if not header:
            return False
        body = await request.read()   # aiohttp caches; handlers re-read
        return verify(self.cfg.federation_token, header, request.method,
                      request.path_qs, body)

    @web.middleware
    async def _middleware(self, request: web.Request, handler):
        t0 = time.perf_counter()
        status = 500
        # request-id propagation root: honor a caller-supplied X-Request-Id,
        # mint one otherwise; the contextvar follows this request through the
        # handler (and asyncio.to_thread copies the context) into the gRPC
        # client's x-localai-request-id metadata → backend → engine spans
        rid = request.headers.get("X-Request-Id") or telemetry.new_request_id()
        rid_token = telemetry.set_request_id(rid)
        # work requests are counted for graceful drain and carry a deadline
        # budget; /backend/shutdown and /backend/preempt stay admitted
        # (they DRIVE the drain / spill-drain)
        counted = (request.path not in _OPEN_PATHS
                   and request.path not in ("/backend/shutdown",
                                            "/backend/preempt"))
        dl_token = None
        try:
            if self.cfg.api_keys and request.path not in _OPEN_PATHS:
                auth = request.headers.get("Authorization", "")
                key = auth.removeprefix("Bearer ").strip()
                if key not in self.cfg.api_keys and not (
                        await self._federation_ok(request)):
                    status = 401
                    return web.json_response(
                        schema.error_body("invalid api key",
                                          "authentication_error", 401),
                        status=401)
            if self._draining and counted:
                # graceful shutdown in progress: shed new work loudly so the
                # LB moves on, while in-flight requests finish
                status = 503
                if _HAVE_PROM:
                    _SHED.labels("-", "draining").inc()
                return web.json_response(
                    schema.error_body("server is draining; retry elsewhere",
                                      "server_error", 503),
                    status=503, headers={"Retry-After": "1",
                                         "X-Request-Id": rid})
            # per-request deadline budget (ISSUE 4): middleware-minted,
            # contextvar-carried — the gRPC client shrinks its timeouts to
            # the remainder and ships it in-band so the engine can evict an
            # expired slot. X-Request-Timeout may only LOWER the app bound.
            budget = float(getattr(self.cfg, "request_timeout", 600.0) or 0)
            hdr = request.headers.get("X-Request-Timeout", "")
            if hdr:
                try:
                    v = float(hdr)
                    if v > 0:
                        budget = min(budget, v) if budget else v
                except ValueError:
                    pass
            if counted and budget > 0:
                dl_token = resilience.set_deadline(budget)
            if counted:
                self._inflight += 1
            try:
                resp = await handler(request)
            finally:
                if counted:
                    self._inflight -= 1
            status = resp.status
            if self.cfg.machine_tag:  # fleet tracking (app.go:93-100)
                resp.headers["Machine-Tag"] = self.cfg.machine_tag
            resp.headers["X-Request-Id"] = rid
            return resp
        except web.HTTPException as e:
            status = e.status
            e.headers["X-Request-Id"] = rid
            raise
        except resilience.ResilienceError as e:
            # typed serving failures (supervisor, breaker, admission,
            # deadline) carry their own HTTP translation + Retry-After
            status = e.status
            if _HAVE_PROM and isinstance(e, resilience.RequestShed):
                _SHED.labels(e.model or "-", e.reason or "overload").inc()
            headers = {"X-Request-Id": rid}
            if e.retry_after:
                headers["Retry-After"] = str(max(int(e.retry_after + 0.999),
                                                 1))
            kind = {429: "overloaded_error", 503: "server_error",
                    504: "timeout_error"}.get(status, "server_error")
            return web.json_response(
                schema.error_body(str(e), kind, status),
                status=status, headers=headers)
        except grpc.RpcError as e:
            # untranslated gRPC stragglers: deadline → 504, severed/refused
            # channel → 502 (the supervisor normally converts these first)
            code = e.code() if hasattr(e, "code") else None
            status = {grpc.StatusCode.DEADLINE_EXCEEDED: 504,
                      grpc.StatusCode.UNAVAILABLE: 502,
                      grpc.StatusCode.INVALID_ARGUMENT: 400,
                      grpc.StatusCode.CANCELLED: 499}.get(code, 500)
            return web.json_response(
                schema.error_body(f"backend rpc failed: {code}",
                                  "server_error", status),
                status=status, headers={"X-Request-Id": rid})
        except Exception as e:
            status = 500
            return web.json_response(
                schema.error_body(f"{type(e).__name__}: {e}", "server_error",
                                  500), status=500,
                headers={"X-Request-Id": rid})
        finally:
            if dl_token is not None:
                resilience.reset_deadline(dl_token)
            tr = telemetry.maybe_tracer()
            if tr is not None and request.path not in _OPEN_PATHS:
                tr.add_complete(f"http {request.path}", t0, cat="http",
                                args={"request_id": rid, "status": status,
                                      "method": request.method})
            telemetry.reset_request_id(rid_token)
            if _HAVE_PROM:
                _API_CALLS.labels(request.path, str(status)).inc()
                _API_LATENCY.labels(request.path).observe(
                    time.perf_counter() - t0)

    # ------------------------------------------------------------ helpers

    def _resolve(self, body: dict) -> ModelConfig:
        """Model-name defaulting + config resolve (request.go:87-117)."""
        name = body.get("model") or ""
        cfg = self.configs.get(name) if name else self.configs.first()
        if cfg is None:
            raise web.HTTPNotFound(
                text=json.dumps(schema.error_body(
                    f"model {name!r} not found", code=404)),
                content_type="application/json")
        return cfg

    async def _handle(self, cfg: ModelConfig):
        try:
            return await asyncio.to_thread(self.manager.load, cfg)
        except resilience.ResilienceError:
            raise   # middleware translates (503 + Retry-After etc.)
        except Exception as e:
            raise web.HTTPInternalServerError(
                text=json.dumps(schema.error_body(
                    f"backend load failed: {e}", "server_error", 500)),
                content_type="application/json")

    def _gate(self, cfg: ModelConfig) -> "_AdmissionGate":
        g = self._gates.get(cfg.name)
        if g is None:
            g = self._gates[cfg.name] = _AdmissionGate(
                cfg.name, cfg.parallel or self.cfg.parallel_requests,
                getattr(self.cfg, "queue_depth", 8))
        return g

    @contextlib.asynccontextmanager
    async def _admit(self, cfg: ModelConfig):
        """Admission control (ISSUE 4): bounded per-model in-flight plus a
        small bounded wait queue; past that, fail FAST with 429 +
        Retry-After (counted in localai_shed_total) instead of stacking
        unbounded work on an overloaded engine."""
        gate = self._gate(cfg)
        if gate.sem.locked() and gate.waiting >= gate.depth:
            raise resilience.RequestShed(
                f"model {cfg.name!r} is at capacity "
                f"({gate.limit} in flight for {gate.slots} slots, "
                f"{gate.waiting} queued)",
                model=cfg.name, reason="queue_full", retry_after=1.0)
        gate.waiting += 1
        t0 = time.monotonic()
        try:
            rem = resilience.deadline_remaining()
            try:
                await asyncio.wait_for(gate.sem.acquire(), timeout=rem)
            except (asyncio.TimeoutError, TimeoutError):
                raise resilience.RequestShed(
                    f"model {cfg.name!r}: request deadline expired while "
                    f"queued for a slot",
                    model=cfg.name, reason="queue_timeout", retry_after=1.0)
        finally:
            gate.waiting -= 1
        gate.granted()
        now = time.monotonic()
        waited = now - t0
        gate.wait_hist.observe(waited)
        tr = telemetry.maybe_tracer()
        if tr is not None:
            tr.add_complete("http.gate_wait", t0, waited, cat="http",
                            args={"model": cfg.name})
        permit = _Permit(now)
        token = _PERMIT.set(permit)
        try:
            yield
        finally:
            _PERMIT.reset(token)
            gate.release(permit, cfg.name)

    async def _unary(self, cfg: ModelConfig, method: str,
                     timeout: float = 600.0, **kw):
        """Supervised, cancellable unary RPC against `cfg`'s backend: the
        manager retries dead/UNAVAILABLE backends (respawning under the
        circuit breaker) since no bytes have reached the client yet, and a
        client disconnect cancels the in-flight RPC — the unary analog of
        the stream path's call.cancel()."""
        box: dict = {}

        def op(handle):
            fut = handle.client.start(method, timeout=timeout, **kw)
            box["fut"] = fut
            return fut.result()

        try:
            return await asyncio.to_thread(self.manager.supervised, cfg, op)
        except asyncio.CancelledError:
            fut = box.get("fut")
            if fut is not None:
                fut.cancel()
            raise

    def _merged_options(self, cfg: ModelConfig, body: dict) -> dict:
        """request JSON > model YAML defaults (request.go:118-211)."""
        p = cfg.parameters
        opts: dict = {}
        for f in _SAMPLING_FIELDS:
            v = body.get(f, getattr(p, f, None))
            if v is not None:
                opts[f] = v
        max_tokens = body.get("max_tokens", body.get("max_completion_tokens",
                                                     p.max_tokens))
        if max_tokens:
            opts["tokens"] = int(max_tokens)
        stop = body.get("stop", None)
        if stop is None:
            stop = list(cfg.stopwords)
        elif isinstance(stop, str):
            stop = [stop]
        if stop:
            opts["stop_prompts"] = stop
        bias = body.get("logit_bias", p.logit_bias)
        if bias:
            opts["logit_bias"] = {int(k): float(v) for k, v in bias.items()}
        if cfg.grammar:
            opts["grammar"] = cfg.grammar
        if body.get("response_format") or body.get("tools"):
            # grammar-constrained decoding wiring (functions/grammars)
            from localai_tpu.functions import grammar_for_request

            g = grammar_for_request(body)
            if g:
                opts["grammar"] = g
        if body.get("logprobs"):
            opts["logprobs"] = True
        return opts

    def _resume_enabled(self, cfg: ModelConfig) -> bool:
        """The ungraceful-death resume lane rides the host KV tier (ISSUE
        17): a model without a pool budget keeps the PR 4 contract (terminal
        SSE error once bytes have streamed), modulo the deterministic-replay
        fallback."""
        return bool(getattr(cfg, "kv_host_bytes", 0)
                    or getattr(self.cfg, "kv_host_bytes", 0))

    async def _stream_rpc(self, cfg: ModelConfig, opts: dict):
        """Supervised streaming call with mid-stream resume (ISSUE 19).

        Attempts that fail before ANY chunk reached the client retry
        transparently on a (re)spawned backend with capped backoff. Once
        bytes have streamed, three lanes run before the failure surfaces as
        the terminal SSE error event:

        - graceful preemption: a terminal ``finish_reason="preempted"``
          reply carries the engine's full spill-drain ResumeToken; the
          bridge swallows it, waits out the dying backend, and re-issues
          the RPC with the token — the respawned engine re-admits the
          checkpoint (host-pool hit or re-prefill) and the client sees one
          uninterrupted stream;
        - ungraceful death with the host KV tier enabled: the bridge
          synthesizes a token from its own accumulated state (prompt ids
          from the first chunk's minimal checkpoint, emitted ids, sent
          chars) and resumes the same way;
        - deterministic replay (resume lane disabled): temperature-0
          requests without tools/stop re-issue with ``prompt+emitted`` as
          the new prompt, holding back a short verification tail whose
          replayed tokens must match what the client already received —
          a divergent prefix falls back to the terminal error event.
        """
        retries = max(0, getattr(self.cfg, "retry_budget", 1))
        resume_budget = max(2, retries + 1)
        prompt_ids: list[int] = [int(t) for t in opts.get("prompt_ids") or []]
        emitted: list[int] = []      # every token id forwarded downstream
        sent_chars = 0               # every text char forwarded downstream
        orig_pt = 0                  # the ORIGINAL request's prompt_tokens
        base_tokens = 0              # generated count folded into resumes
        suppress: list[int] = []     # replay verification tail (determ. lane)
        ckpt: dict | None = None     # full spill-drain ResumeToken
        resumes = attempt = 0
        unconfirmed = ""             # resume mode awaiting its first chunk
        cur = opts
        gate = self._gate(cfg)
        # the stream's start is timed from its permit, once: a retry or a
        # resume opens another RPC of the same stream
        permit = _PERMIT.get(None) or _Permit(time.monotonic())
        since: float | None = permit.at
        while True:
            if attempt:
                await asyncio.sleep(resilience.backoff(attempt))
            handle = await self._handle(cfg)
            handle.mark_busy()
            streamed = bool(emitted or sent_chars)
            preempted = False
            err: Exception | None = None
            pump = self._pump_stream(gate, handle, cur, permit, since)
            since = None
            try:
                async for reply in pump:
                    if reply.resume_json:
                        try:
                            d = json.loads(reply.resume_json)
                        except ValueError:
                            d = {}
                        if reply.finish_reason == "preempted":
                            ckpt = d or None
                        elif d.get("prompt_ids") and not prompt_ids:
                            # minimal first-chunk checkpoint: the tokenized
                            # prompt the resume lanes rebuild prompts from
                            prompt_ids = [int(t) for t in d["prompt_ids"]]
                    if unconfirmed:
                        if _HAVE_PROM and unconfirmed == "resume":
                            _RESUME.labels(cfg.name, "ok").inc()
                        unconfirmed = ""
                    if reply.finish_reason == "preempted":
                        # swallowed, never forwarded: the resume lane
                        # continues the stream from the checkpoint
                        preempted = True
                        break
                    if suppress:
                        # deterministic replay: the verification tail streams
                        # again first; the client already has these tokens,
                        # so they are swallowed — and they must MATCH, or the
                        # replay diverged and the stream cannot be resumed
                        diverged = bool(reply.finish_reason)
                        for t in reply.token_ids:
                            if not suppress or suppress.pop(0) != int(t):
                                diverged = True
                                break
                        if diverged:
                            raise RuntimeError(
                                f"deterministic replay diverged for "
                                f"{cfg.name!r}; cannot resume the stream")
                        continue
                    streamed = True
                    for t in reply.token_ids:
                        emitted.append(int(t))
                    sent_chars += len(reply.message.decode("utf-8",
                                                           "replace"))
                    if reply.prompt_tokens:
                        if orig_pt:
                            reply.prompt_tokens = orig_pt
                        elif not resumes:
                            orig_pt = reply.prompt_tokens
                    if base_tokens and reply.tokens:
                        reply.tokens += base_tokens
                    yield reply
                if not preempted:
                    return
            except grpc.RpcError as e:
                retriable, terr = await asyncio.to_thread(
                    self.manager.classify_failure, handle, e)
                if not streamed:
                    if retriable and attempt < retries:
                        attempt += 1
                        self.manager.events[(cfg.name, "stream_retry")] += 1
                        continue
                    raise terr from e
                err = terr
            finally:
                await pump.aclose()
                handle.mark_idle()
            if preempted:
                # wait out the dying backend before respawning, so the
                # resume never lands on an engine that is mid-drain
                await asyncio.to_thread(self.manager.preempt_model, cfg.name)
            nxt = None
            if resumes < resume_budget:
                nxt = self._resume_opts(cfg, opts, prompt_ids, emitted,
                                        sent_chars, ckpt)
            if nxt is None:
                if _HAVE_PROM and (resumes or ckpt is not None):
                    _RESUME.labels(cfg.name, "error").inc()
                if err is None:
                    err = resilience.BackendUnavailable(
                        f"backend for {cfg.name!r} was preempted mid-stream "
                        f"and the request could not be resumed")
                raise err
            cur, mode, suppress, base_tokens = nxt
            ckpt = None
            resumes += 1
            unconfirmed = mode
            if _HAVE_PROM and mode == "replay":
                _RESUME.labels(cfg.name, "replay").inc()
            self.manager.events[(cfg.name, f"stream_{mode}")] += 1
            telemetry.flightrec().record_event(
                "resume", model=cfg.name, mode=mode, emitted=len(emitted),
                sent_chars=sent_chars, resumes=resumes)

    def _resume_opts(self, cfg: ModelConfig, opts: dict,
                     prompt_ids: list[int], emitted: list[int],
                     sent_chars: int, ckpt: dict | None):
        """Build the re-issued request for a mid-stream resume, or None when
        no lane applies. Returns (opts, mode, suppress_tail, base_tokens)."""
        if "images" in opts:
            # multimodal KV is never frozen (engine skips mm slots) and the
            # projector embeds can't be rebuilt from token ids alone
            return None
        ropts = {k: v for k, v in opts.items()
                 if k not in ("prompt", "messages_json",
                              "use_tokenizer_template", "tools_json")}
        orig_tokens = int(opts.get("tokens") or 128)
        if ckpt is not None:
            # graceful spill-drain checkpoint: engine-authoritative
            ropts["prompt_ids"] = ([int(t) for t in ckpt["prompt_ids"]]
                                   + [int(t) for t in ckpt["emitted"]])
            ropts["resume_json"] = json.dumps(ckpt)
            return ropts, "resume", [], len(ckpt["emitted"])
        if not prompt_ids or not emitted:
            return None
        if self._resume_enabled(cfg):
            # ungraceful death: synthesize the token from bridge state —
            # no RNG key (sampled requests resample from a fresh key) and
            # no chain hashes (the pool died with the process; re-admission
            # degrades to re-prefill)
            tok = {"v": 1, "prompt_ids": prompt_ids, "emitted": emitted,
                   "sent_chars": sent_chars, "generated": len(emitted),
                   "chain": [], "key": None}
            ropts["prompt_ids"] = prompt_ids + emitted
            ropts["resume_json"] = json.dumps(tok)
            return ropts, "resume", [], len(emitted)
        if (float(opts.get("temperature") or 0.0) == 0.0
                and not opts.get("tools_json")
                and not opts.get("stop_prompts")):
            # deterministic replay (resume disabled): fold all but a short
            # verification tail into the prompt; the tail re-generates and
            # must match what the client already received
            tail = min(len(emitted), 4)
            keep = len(emitted) - tail
            ropts["prompt_ids"] = prompt_ids + emitted[:keep]
            ropts["tokens"] = max(1, orig_tokens - keep)
            return ropts, "replay", list(emitted[keep:]), keep
        return None

    async def _pump_stream(self, gate: _AdmissionGate, handle, opts: dict,
                           permit: _Permit, since: float | None = None):
        """Bridge the blocking gRPC stream into an async queue, on one of
        the gate's pump threads. `since`: when the stream got its permit,
        for the first RPC of a stream; `permit` learns when the pump thread
        read the stream's end, if the backend's finished reply came first."""
        loop = asyncio.get_running_loop()
        # Bounded queue + BLOCKING put from the pump thread: backpressure
        # propagates to the gRPC stream instead of dropping chunks (or the
        # terminal sentinel) when the HTTP client reads slower than the
        # backend decodes. `stopped` ends the pump when the client goes away
        # so an abandoned stream doesn't buffer the rest of the generation.
        q: asyncio.Queue = asyncio.Queue(maxsize=256)
        stopped = threading.Event()
        call = handle.client.predict_stream(**opts)

        def _put(item) -> bool:
            """Blocking put with backpressure; bounded waits so a stopped
            consumer (or a dead event loop) can never wedge the pump thread."""
            while not stopped.is_set():
                fut = asyncio.run_coroutine_threadsafe(q.put(item), loop)
                try:
                    fut.result(timeout=1.0)
                    return True
                except TimeoutError:
                    if not fut.cancel():
                        try:
                            fut.result(timeout=0)
                            return True
                        except Exception:
                            return False
                except Exception:
                    return False
            return False

        def pump():
            try:
                if since is not None:
                    loop.call_soon_threadsafe(gate.start_hist.observe,
                                              time.monotonic() - since)
                reply = None
                for reply in call:
                    if not _put(("chunk", reply)):
                        return
                if reply is not None and reply.finish_reason not in (
                        "", "cancelled", "preempted"):
                    permit.ended = time.monotonic()
                _put(("done", None))
            except Exception as e:
                if not stopped.is_set():
                    _put(("error", e))

        gate.streams_open += 1
        try:
            loop.run_in_executor(gate.pumps, pump)
            while True:
                kind, item = await q.get()
                if kind == "chunk":
                    yield item
                elif kind == "done":
                    return
                else:
                    raise item
        finally:
            gate.streams_open -= 1
            stopped.set()
            # cancelling the RPC unblocks a pump waiting on the next reply
            # (client gone mid-generation) and tells the backend to stop
            call.cancel()
            while not q.empty():
                q.get_nowait()

    # ------------------------------------------------------------ endpoints

    async def _health(self, request):
        # kv_digest: served-prefix gossip for the federation picker's KV
        # affinity (ISSUE 17) — top-k most recent text-chain ids
        return web.json_response({
            "status": "ok",
            "kv_digest": self._kv_served.to_list(k=256),
        })

    def _note_served(self, body: dict):
        """Record a conversation's text-chain ids in the served-prefix
        digest (same helpers the federation proxy hashes the raw body
        with, so the ids agree by construction)."""
        from localai_tpu.engine.kvhost import (
            body_prompt_text, text_chain_ids,
        )

        try:
            self._kv_served.add(text_chain_ids(body_prompt_text(body)))
        except Exception:
            pass   # gossip is advisory — never fail the request for it

    async def _metrics(self, request):
        if not _HAVE_PROM:
            raise web.HTTPNotImplemented()
        await self._scrape(self._refresh_scraped_series)
        return web.Response(body=generate_latest(),
                            content_type=CONTENT_TYPE_LATEST.split(";")[0])

    def _scrape(self, fn):
        """Run a scrape's blocking half on the scrape pool (contextvars
        copied, as asyncio.to_thread does)."""
        return asyncio.get_running_loop().run_in_executor(
            self._scrape_pool, contextvars.copy_context().run, fn)

    def _gate_metrics(self, name: str) -> dict:
        """The HTTP process's own per-model metrics under the flat keys the
        backend's use ({} until the model has a gate)."""
        gate = self._gates.get(name)
        return gate.metrics() if gate is not None else {}

    def _refresh_scraped_series(self):
        """Pull each loaded backend's GetMetrics into the Prometheus series
        (best-effort — a wedged backend must not fail the scrape)."""
        for (model, event), n in list(self.manager.events.items()):
            _counter_sync(_SUPERVISION, (model, event), float(n))
        for name in self.manager.loaded():
            h = self.manager.get(name)
            if h is None:
                continue
            try:
                m = h.client.metrics(timeout=2.0)
            except Exception:
                continue
            m.update(self._gate_metrics(name))
            # SLO histograms: rebuilt whole from the flat keys; the custom
            # collector exposes them as true histogram series
            hists = telemetry.parse_flat(m)
            if hists:
                _SLO_SCRAPE[name] = hists
            for key, v in m.items():
                # scheduler X-ray series (ISSUE 13)
                if key.startswith("sched_reason__"):
                    _counter_sync(_SCHED_REASONS, (name, key[14:]), float(v))
                    continue
                if key.startswith("sched_variant__"):
                    _counter_sync(_SCHED_DISPATCHES, (name, key[15:]),
                                  float(v))
                    continue
                if key == "sched_ticks_total":
                    _counter_sync(_SCHED_TICKS, (name,), float(v))
                    continue
                if key == "sched_pad_rows_frac":
                    _SCHED_PAD.labels(name).set(v)
                    continue
                # host KV tier (ISSUE 17): occupancy levels vs cumulative
                # event counts out of the same kv_host_* key family
                if key in ("kv_host_blocks", "kv_host_bytes",
                           "kv_host_bytes_peak"):
                    _KV_HOST.labels(name, key[8:]).set(v)
                    continue
                if key in ("kv_host_hits", "kv_host_spills",
                           "kv_host_evictions"):
                    _counter_sync(_KV_HOST_EVENTS, (name, key[8:]),
                                  float(v))
                    continue
                if key == "xla_compiles_total":
                    _counter_sync(_XLA_COMPILES, (name,), float(v))
                    continue
                if key == "streams_open":
                    _STREAMS_OPEN.labels(name).set(v)
                    continue
                if key.startswith("expert_tokens__"):
                    _counter_sync(_EXPERT_TOKENS,
                                  (name, key.split("__", 1)[1]), float(v))
                    continue
                if key.startswith("chunk_ctx_tokens__"):
                    _counter_sync(_CHUNK_CTX,
                                  (name, key.split("__", 1)[1]), float(v))
                    continue
                if key.startswith("decode_ctx_tokens__"):
                    _counter_sync(_DECODE_CTX,
                                  (name, key.split("__", 1)[1]), float(v))
                    continue
                if key == "chunk_latent_rows__expanded":
                    _counter_sync(_LATENT_ROWS, (name,), float(v))
                    continue
                for kind in ("host", "wait"):
                    prefix = f"engine_{kind}_ms__"
                    if key.startswith(prefix):
                        _counter_sync(_ENGINE_PHASE,
                                      (name, kind, key[len(prefix):]),
                                      v / 1e3)

    async def _backend_traces(self, model: str = "") -> list[dict]:
        """GetTrace payloads from the loaded backends ({} on any failure)."""
        out = []
        for name in self.manager.loaded():
            if model and name != model:
                continue
            h = self.manager.get(name)
            if h is None:
                continue
            try:
                payload = await asyncio.to_thread(
                    lambda hh=h: hh.client.trace())
            except Exception:
                payload = {}
            # key by the config name — the backend reports its checkpoint
            # path as model_name, which is not what clients query by
            payload["model"] = name
            out.append(payload)
        return out

    async def _debug_trace(self, request):
        """GET /debug/trace[?model=x] → Chrome-trace JSON merging this
        process's spans with every backend subprocess's (load it at
        chrome://tracing or ui.perfetto.dev). Empty traceEvents unless the
        server runs with LOCALAI_TRACE=1."""
        events = list(telemetry.chrome_events())
        names = {os.getpid(): "localai-http"}
        for payload in await self._backend_traces(
                request.query.get("model", "")):
            events.extend(payload.get("spans") or [])
            if payload.get("pid"):
                names[payload["pid"]] = f"backend:{payload['model']}"
        events.sort(key=lambda e: e.get("ts", 0))
        return web.json_response(telemetry.chrome_trace(events, names))

    async def _debug_xprof(self, request):
        """GET /debug/xprof?model=<m>&seconds=<s> → the backend that holds
        the chip profiles itself (jax.profiler, Python tracer off, host
        tracer 1) for `s` <= 10 seconds and answers {"dir", "xplane", ...}:
        the device's ops and the engine's `engine.<phase>` annotations on
        one clock, for `python -m tools.trace_gaps <dir>`. One at a time; a
        refusal or a profiler failure is a 4xx/5xx JSON reply and nothing
        else — serving goes on."""
        name = request.query.get("model", "")
        try:
            seconds = float(request.query.get("seconds", "3"))
        except ValueError:
            seconds = -1.0
        if not 0 < seconds <= telemetry.XPROF_MAX_S:
            return web.json_response(
                {"error": f"seconds must be in (0, "
                          f"{telemetry.XPROF_MAX_S:g}]"}, status=400)
        loaded = self.manager.loaded()
        if not name and len(loaded) == 1:
            name = loaded[0]
        h = self.manager.get(name) if name in loaded else None
        if h is None:
            return web.json_response(
                {"error": f"model {name!r} is not loaded"}, status=404)
        try:
            # stopping the profiler takes several times the traced seconds
            payload = await asyncio.to_thread(
                lambda: h.client.trace(timeout=seconds * 10 + 120,
                                       xprof_seconds=seconds))
            out = payload.get("xprof") or {"error": "backend sent no trace"}
        except Exception as e:
            out = {"error": f"{type(e).__name__}: {e}"}
        out["model"] = name
        busy = "already running" in out.get("error", "")
        return web.json_response(
            out, status=409 if busy else 502 if "error" in out else 200)

    async def _debug_slo(self, request):
        """GET /debug/slo[?model=x] → per-model p50/p95/p99 snapshot of the
        serving SLO histograms (ttft and its stages, tpot, e2e,
        finish_to_reply, split by decode path), straight from each backend
        engine's registry, beside this process's own of the model's gate
        (gate_wait, stream_start, reply_to_release, permit_hold). Empty
        per-model blocks when LOCALAI_METRICS=0."""
        models = {}
        kv_host = {}
        for payload in await self._backend_traces(
                request.query.get("model", "")):
            models[payload["model"]] = {
                **(payload.get("slo") or {}),
                **(telemetry.snapshot_from_hists(telemetry.parse_flat(
                    self._gate_metrics(payload["model"])))
                   if telemetry.metrics_enabled() else {})}
            if payload.get("kvhost"):
                # host KV tier occupancy/hit stats (ISSUE 17) — present
                # only for backends running with kv_host_bytes > 0
                kv_host[payload["model"]] = payload["kvhost"]
        return web.json_response({
            "metrics_enabled": telemetry.metrics_enabled(),
            "bucket_edges_s": [b for b in telemetry.BUCKETS_S
                               if b != float("inf")],
            "models": models,
            "kv_host": kv_host,
        })

    async def _debug_sched(self, request):
        """GET /debug/sched[?model=x] → the scheduler X-ray (ISSUE 13): each
        backend engine's tick-ledger snapshot — pack-composition totals,
        admission/fallback/demotion reason-code counters, per-variant
        dispatch counts and cost-analysis rooflines, plus the recent tick
        ring. Empty per-model blocks unless the backend runs with
        LOCALAI_SCHED=1 (and metrics enabled)."""
        models = {}
        for payload in await self._backend_traces(
                request.query.get("model", "")):
            models[payload["model"]] = payload.get("sched") or {}
        return web.json_response({
            "sched_enabled": telemetry.sched_enabled(),
            "reason_codes": {code: {"category": cat, "description": desc}
                             for code, (cat, desc)
                             in telemetry.REASON_CODES.items()},
            "models": models,
        })

    async def _debug_flightrec(self, request):
        """GET /debug/flightrec[?model=x] → the flight recorder rings: this
        process's events plus each backend's recent request timelines,
        engine-tick summaries, and tripwire/breaker/supervision events."""
        models = {}
        for payload in await self._backend_traces(
                request.query.get("model", "")):
            models[payload["model"]] = payload.get("flightrec") or {}
        return web.json_response({
            "server": telemetry.flightrec().dump(),
            "models": models,
        })

    async def _models(self, request):
        return web.json_response(schema.models_list(self.configs.names()))

    @staticmethod
    def _extract_images(messages):
        """OpenAI vision content parts → (flattened messages, images list).

        image_url parts become an <image> marker in the text (the LLaVA
        placeholder the backend expands, models/llava.py) and their payload
        joins the proto `images` list (reference: base64 images through
        PredictOptions.images, backend.proto:131; content-part handling in
        core/http/endpoints/openai chat)."""
        images, out = [], []
        for m in messages:
            c = m.get("content")
            if not isinstance(c, list):
                out.append(m)
                continue
            parts = []
            for part in c:
                t = part.get("type")
                if t in ("image_url", "input_image"):
                    url = part.get("image_url")
                    if isinstance(url, dict):
                        url = url.get("url", "")
                    url = url or part.get("url", "")
                    if url.startswith("http://") or url.startswith("https://"):
                        url = _fetch_image(url)
                    images.append(url)
                    parts.append("<image>")
                elif t in ("text", "input_text"):
                    parts.append(part.get("text", ""))
            out.append(dict(m, content="\n".join(p for p in parts if p)))
        return out, images

    async def _chat(self, request):
        body = await request.json()
        cfg = self._resolve(body)
        self._note_served(body)
        messages = body.get("messages") or []
        if not messages:
            raise web.HTTPBadRequest(
                text=json.dumps(schema.error_body("messages required")),
                content_type="application/json")
        try:
            messages, images = await asyncio.to_thread(
                self._extract_images, messages)
        except Exception as e:
            raise web.HTTPBadRequest(
                text=json.dumps(schema.error_body(f"bad image: {e}")),
                content_type="application/json")
        opts = self._merged_options(cfg, body)
        if images:
            opts["images"] = images
        if cfg.template.use_tokenizer_template or not cfg.template.chat:
            opts["messages_json"] = json.dumps(messages)
            opts["use_tokenizer_template"] = True
            if body.get("tools"):
                # the backend renders these into the prompt through the
                # tokenizer chat template's `tools` variable
                opts["tools_json"] = json.dumps(body["tools"])
        else:
            from localai_tpu.templates import evaluate_chat

            opts["prompt"] = evaluate_chat(cfg, messages)

        # response_format wins over tools in grammar_for_request — the output
        # is then the USER's structured format, never a tool call
        tools_active = (bool(body.get("tools"))
                        and body.get("tool_choice") != "none"
                        and not body.get("response_format"))
        async with self._admit(cfg):
            if body.get("stream"):
                return await self._chat_stream(request, cfg, opts,
                                               tools_active=tools_active,
                                               body=body)
            reply = await self._unary(cfg, "Predict", **opts)
            text = reply.message.decode("utf-8", "replace")
            tool_calls = None
            if tools_active:
                # grammar-constrained output → OpenAI tool_calls; the
                # no-action "answer" alternative unwraps back into prose
                # (reference: pkg/functions/parse.go + functions.go no-action,
                # wired at chat.go:266-312)
                from localai_tpu.functions import parse_tool_response

                tool_calls, answer = parse_tool_response(text)
                if answer is not None:
                    text = answer
            timings = {
                "prompt_processing_s": reply.timing_prompt_processing,
                "token_generation_s": reply.timing_token_generation,
            }
            timings.update(_engine_timings(reply))
            resp = schema.chat_completion(
                cfg.name, text,
                reply.finish_reason, reply.prompt_tokens, reply.tokens,
                timings=timings,
                tool_calls=tool_calls)
            schema.merge_extra_usage(
                resp, bool(request.headers.get("Extra-Usage")),
                reply.timing_prompt_processing,
                reply.timing_token_generation)
            return web.json_response(resp)

    async def _sse_error(self, resp, send, e: Exception):
        """Mid-stream failure → a clean terminal SSE error event + [DONE]
        (never a silently hung or truncated connection — ISSUE 4). Best
        effort: the client itself may already be gone."""
        status = getattr(e, "status", 500)
        kind = {429: "overloaded_error", 503: "server_error",
                504: "timeout_error"}.get(status, "server_error")
        try:
            await send(schema.error_body(f"{e}", kind, status))
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except (ConnectionError, RuntimeError):
            pass
        return resp

    async def _chat_stream(self, request, cfg, opts,
                           tools_active: bool = False, body: dict | None = None):
        """SSE loop (reference chat.go:334-449): role chunk, deltas, usage
        chunk, data: [DONE]. With tools active the output is buffered (it is
        a grammar-constrained JSON object, meaningless as partial text) and
        emitted as one tool_calls delta, finish_reason "tool_calls"."""
        # load failures before any SSE bytes surface as plain HTTP errors
        await self._handle(cfg)
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
        })
        await resp.prepare(request)
        rid = schema._id("chatcmpl")

        async def send(obj):
            await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

        await send(schema.chat_chunk(rid, cfg.name, None, role=True))
        prompt_tokens = completion_tokens = 0
        t_prompt = t_gen = 0.0
        finish = "stop"
        buffered: list[str] = []
        timings: dict = {}
        try:
            async for reply in self._stream_rpc(cfg, opts):
                prompt_tokens = reply.prompt_tokens
                completion_tokens = reply.tokens
                t_prompt = reply.timing_prompt_processing or t_prompt
                t_gen = reply.timing_token_generation or t_gen
                timings = _engine_timings(reply) or timings
                text = reply.message.decode("utf-8", "replace")
                if text:
                    if tools_active:
                        buffered.append(text)
                    else:
                        await send(schema.chat_chunk(rid, cfg.name, text))
                if reply.finish_reason:
                    finish = reply.finish_reason
        except (asyncio.CancelledError, ConnectionError):
            raise          # client went away — nothing left to tell it
        except Exception as e:
            return await self._sse_error(resp, send, e)
        if tools_active:
            from localai_tpu.functions import parse_tool_response

            full = "".join(buffered)
            calls, answer = parse_tool_response(full)
            if calls:
                await send(schema.chat_chunk(rid, cfg.name, None,
                                             tool_calls=calls))
                finish = "tool_calls"
            elif answer is not None:
                # the no-action "answer" alternative: emit its message as a
                # plain content delta (prose, not a forced tool call)
                if answer:
                    await send(schema.chat_chunk(rid, cfg.name, answer))
            elif full:
                await send(schema.chat_chunk(rid, cfg.name, full))
        await send(schema.chat_chunk(rid, cfg.name, None, finish_reason=finish))
        stream_opts = (body or {}).get("stream_options") or {}
        if stream_opts.get("include_usage", True):
            # default-on: LocalAI clients expect the usage tail unless the
            # OpenAI stream_options flag explicitly disables it
            tail = schema.chat_usage_chunk(rid, cfg.name, prompt_tokens,
                                           completion_tokens)
            schema.merge_extra_usage(
                tail, bool(request.headers.get("Extra-Usage")),
                t_prompt, t_gen)
            if timings:
                # llama.cpp-style per-request timings in the final chunk
                tail["timings"] = timings
            await send(tail)
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    async def _completions(self, request):
        body = await request.json()
        cfg = self._resolve(body)
        self._note_served(body)
        prompt = body.get("prompt") or ""
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        opts = self._merged_options(cfg, body)
        if cfg.template.completion:
            from localai_tpu.templates import evaluate_completion

            prompt = evaluate_completion(cfg, prompt)
        opts["prompt"] = prompt

        async with self._admit(cfg):
            if body.get("stream"):
                return await self._completion_stream(request, cfg, opts)
            reply = await self._unary(cfg, "Predict", **opts)
            out = schema.text_completion(
                cfg.name, reply.message.decode("utf-8", "replace"),
                reply.finish_reason, reply.prompt_tokens, reply.tokens)
            schema.merge_extra_usage(
                out, bool(request.headers.get("Extra-Usage")),
                reply.timing_prompt_processing,
                reply.timing_token_generation)
            return web.json_response(out)

    async def _completion_stream(self, request, cfg, opts):
        await self._handle(cfg)   # load errors stay plain HTTP, not SSE
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await resp.prepare(request)
        rid = schema._id("cmpl")
        finish = "stop"
        prompt_tokens = completion_tokens = 0
        t_prompt = t_gen = 0.0
        timings: dict = {}

        async def send(obj):
            await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

        try:
            async for reply in self._stream_rpc(cfg, opts):
                text = reply.message.decode("utf-8", "replace")
                prompt_tokens = reply.prompt_tokens
                completion_tokens = reply.tokens
                t_prompt = reply.timing_prompt_processing or t_prompt
                t_gen = reply.timing_token_generation or t_gen
                timings = _engine_timings(reply) or timings
                if reply.finish_reason:
                    finish = reply.finish_reason
                if text:
                    await send(schema.text_completion_chunk(rid, cfg.name,
                                                            text))
        except (asyncio.CancelledError, ConnectionError):
            raise
        except Exception as e:
            return await self._sse_error(resp, send, e)
        final = schema.text_completion_chunk(rid, cfg.name, "", finish)
        if timings:
            final["timings"] = timings
        if request.headers.get("Extra-Usage"):
            # reference completion.go:74 parity on the stream too
            final["usage"] = schema.usage(prompt_tokens, completion_tokens)
            schema.merge_extra_usage(final, True, t_prompt, t_gen)
        await resp.write(
            f"data: {json.dumps(final)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        return resp

    async def _embeddings(self, request):
        body = await request.json()
        cfg = self._resolve(body)
        inputs = body.get("input") or ""
        if isinstance(inputs, str):
            inputs = [inputs]
        async with self._admit(cfg):
            # ONE RPC for the whole batch → one bucketed device call
            # (a batch-256 request used to make 512 round trips)
            r = await self._unary(cfg, "Embedding", prompts=inputs)
            vectors = [list(v.values) for v in r.vectors]
            return web.json_response(schema.embeddings_response(
                cfg.name, vectors, r.prompt_tokens))

    async def _rerank(self, request):
        body = await request.json()
        cfg = self._resolve(body)
        async with self._admit(cfg):
            r = await self._unary(cfg, "Rerank",
                                  query=body.get("query", ""),
                                  documents=body.get("documents", []),
                                  top_n=body.get("top_n", 0))
            return web.json_response({
                "model": cfg.name,
                "results": [{
                    "index": d.index,
                    "relevance_score": d.relevance_score,
                    "document": {"text": d.text},
                } for d in r.results],
            })

    async def _edits(self, request):
        """POST /v1/edits — legacy OpenAI edit API (reference
        endpoints/openai/edit.go, routed at routes/openai.go:56): apply
        `instruction` to `input` via the completion path."""
        body = await request.json()
        cfg = self._resolve(body)
        instruction = body.get("instruction", "")
        if not instruction:
            raise web.HTTPBadRequest(text="instruction required")
        inp = body.get("input", "")
        prompt = (f"Text: {inp}\nInstruction: {instruction}\n"
                  f"Edited text:")
        sub = {"model": cfg.name, "prompt": prompt}
        for f in _SAMPLING_FIELDS + ("max_tokens",):
            if f in body:
                sub[f] = body[f]
        # forward the Extra-Usage opt-in (reference edit.go:35) — the
        # completion leg then merges timings into the usage we relay
        eu = request.headers.get("Extra-Usage")
        resp = await self._loopback(
            "/v1/completions", sub,
            extra_headers={"Extra-Usage": eu} if eu else None)
        return web.json_response({
            "object": "edit",
            "created": int(time.time()),
            "choices": [{"index": i, "text": c.get("text", "")}
                        for i, c in enumerate(resp.get("choices", []))],
            "usage": resp.get("usage", {}),
        })

    async def _loopback(self, path: str, body: dict,
                        extra_headers: dict | None = None) -> dict:
        """POST to our own API (the reference's MCP agent does the same —
        mcp.go hands the local API address to the agent loop)."""
        import aiohttp

        headers = dict(extra_headers or {})
        if self.cfg.api_keys:
            headers["Authorization"] = f"Bearer {self.cfg.api_keys[0]}"
        url = f"http://{self.cfg.address}{path}"
        async with aiohttp.ClientSession() as s:
            async with s.post(url, json=body, headers=headers,
                              timeout=aiohttp.ClientTimeout(total=600)) as r:
                if r.status != 200:
                    raise web.HTTPInternalServerError(
                        text=f"loopback {path} failed: {await r.text()}")
                return await r.json()

    def _mcp_sessions_for(self, cfg):
        from localai_tpu.mcp import sessions_from_config

        with self._mcp_lock:
            cached = self._mcp_sessions.get(cfg.name)
        if cached is not None:
            return cached
        # session setup (process spawn + initialize handshake) happens
        # OUTSIDE the lock: a wedged server must not block other models
        sessions = sessions_from_config(cfg.mcp)
        with self._mcp_lock:
            existing = self._mcp_sessions.get(cfg.name)
            if existing is None:
                self._mcp_sessions[cfg.name] = sessions
                return sessions
        # lost the race: keep the first set, and close OUR spawned
        # sessions outside the lock — close() terminates the server
        # process and waits on it (lockdep flagged the old in-lock close:
        # a wedged MCP server would have blocked every model's MCP path)
        for s in sessions:
            try:
                s.close()
            except Exception:
                pass
        return existing

    def _mcp_evict(self, name: str):
        """Drop (and close) a model's cached MCP sessions — called when a
        transport dies so the next request reconnects instead of failing
        forever."""
        with self._mcp_lock:
            sessions = self._mcp_sessions.pop(name, None)
        for s in sessions or []:
            try:
                s.close()
            except Exception:
                pass

    async def _mcp_chat(self, request):
        """POST /mcp/v1/chat/completions — agentic chat with the model
        config's MCP servers' tools (reference mcp.go:1-142): the model's
        tool_calls are executed against the MCP sessions and fed back until
        it answers in prose (or the iteration budget runs out)."""
        body = await request.json()
        cfg = self._resolve(body)
        if not cfg.mcp:
            raise web.HTTPBadRequest(
                text=f"model {cfg.name!r} has no MCP servers configured")
        from localai_tpu.mcp import tools_as_openai

        try:
            sessions = await asyncio.to_thread(self._mcp_sessions_for, cfg)
        except Exception as e:
            raise web.HTTPInternalServerError(
                text=f"MCP session setup failed: {e}")
        tools, owner = tools_as_openai(sessions)
        if not tools:
            raise web.HTTPInternalServerError(
                text="no tools offered by the configured MCP servers")

        messages = list(body.get("messages") or [])
        if not messages and body.get("prompt"):
            messages = [{"role": "user", "content": body["prompt"]}]
        max_iter = int((cfg.agent or {}).get("max_iterations", 3))
        last = {}
        for it in range(max_iter):
            sub = {"model": cfg.name, "messages": messages}
            for f in _SAMPLING_FIELDS + ("max_tokens",):
                if f in body:
                    sub[f] = body[f]
            if it < max_iter - 1:
                sub["tools"] = tools   # final round: force a prose answer
                # the agent loop's contract is call-then-answer: non-final
                # rounds must produce a tool call (tool_choice "required"
                # keeps the no-action "answer" alternative out of the
                # grammar here — the final tool-less round is the answer)
                sub["tool_choice"] = "required"
                # a truncated tool-call JSON cannot parse — give the
                # grammar-constrained round enough budget to close the braces
                sub["max_tokens"] = max(int(sub.get("max_tokens") or 0), 128)
            last = await self._loopback("/v1/chat/completions", sub)
            choice = (last.get("choices") or [{}])[0]
            msg = choice.get("message", {})
            calls = msg.get("tool_calls")
            if not calls:
                break
            # the chat template renders only role+content, so serialize the
            # calls INTO the content — the next round's prompt must show
            # which tool was called with what and which result is whose
            call_desc = "; ".join(
                f"{c.get('function', {}).get('name', '?')}"
                f"({c.get('function', {}).get('arguments', '')})"
                for c in calls)
            messages.append({"role": "assistant", "tool_calls": calls,
                             "content": f"[tool calls] {call_desc}"})
            from localai_tpu.mcp import MCPError

            for call in calls:
                fn = call.get("function", {})
                name = fn.get("name", "")
                try:
                    args = json.loads(fn.get("arguments") or "{}")
                except ValueError:
                    args = {}
                sess = owner.get(name)
                if sess is None:
                    result = f"error: unknown tool {name!r}"
                else:
                    try:
                        result = await asyncio.to_thread(
                            sess.call_tool, name, args)
                    except MCPError as e:
                        # transport died: evict so the NEXT request
                        # reconnects instead of failing forever
                        self._mcp_evict(cfg.name)
                        result = f"error: {e}"
                    except Exception as e:
                        result = f"error: {e}"
                messages.append({"role": "tool",
                                 "tool_call_id": call.get("id", name),
                                 "name": name,
                                 "content": f"[{name}] {result}"})
        return web.json_response(last)

    async def _detection(self, request):
        """POST /v1/detection {model, image: base64|data-URI|file path} →
        {detections: [{x, y, width, height, confidence, class_name}]}
        (reference endpoints/localai/detection.go + schema.DetectionRequest)."""
        import base64
        import os
        import tempfile

        body = await request.json()
        cfg = self._resolve(body)
        image = body.get("image", "")
        if not image:
            raise web.HTTPBadRequest(text="image required")
        tmp = None
        if os.path.isfile(image):
            src = image
        else:
            if image.startswith("data:"):
                image = image.split(",", 1)[-1]
            try:
                blob = base64.b64decode(image, validate=True)
            except Exception:
                raise web.HTTPBadRequest(
                    text="image must be a file path, base64, or data URI")
            tmp = tempfile.NamedTemporaryFile(suffix=".img", delete=False)
            tmp.write(blob)
            tmp.close()
            src = tmp.name
        try:
            handle = await self._handle(cfg)
            handle.mark_busy()
            try:
                r = await asyncio.to_thread(
                    lambda: handle.client.detect(src=src))
                return web.json_response({"detections": [{
                    "x": d.x, "y": d.y, "width": d.width, "height": d.height,
                    "confidence": d.confidence, "class_name": d.class_name,
                } for d in r.detections]})
            finally:
                handle.mark_idle()
        finally:
            if tmp is not None:
                os.unlink(tmp.name)

    async def _tokenize(self, request):
        body = await request.json()
        cfg = self._resolve(body)
        handle = await self._handle(cfg)
        handle.mark_busy()
        try:
            t = await asyncio.to_thread(
                lambda: handle.client.tokenize(body.get("content", "")))
        finally:
            handle.mark_idle()
        return web.json_response({"tokens": list(t.tokens)})

    async def _backend_monitor(self, request):
        out = {}
        for name in self.manager.loaded():
            h = self.manager.get(name)
            if h is None:
                continue
            st = await self._scrape(h.client.status)
            try:
                metrics = await self._scrape(h.client.metrics)
            except Exception:
                metrics = {}
            # this process's share of the model's histograms (the wait at
            # the admission gate) under the same flat keys
            metrics.update(self._gate_metrics(name))
            out[name] = {
                "state": int(st.state),
                "memory_total": st.memory.total,
                "busy": h.busy,
                # the device as the backend sees it NOW (per-device bytes
                # in use included); /system keeps the load-time report
                "device": json.loads(st.device_json or "{}"),
                # per-backend engine metrics (reference GetMetrics +
                # get_token_metrics.go role): tok/s, ttft, cache hits...
                "metrics": metrics,
            }
        return web.json_response(out)

    async def _backend_shutdown(self, request):
        """POST /backend/shutdown — graceful (ISSUE 4). With {"model": x}:
        drain that backend's in-flight requests (up to drain_timeout) then
        reap it. Without a model: server-wide drain — new work 503s while
        in-flight requests finish under the hard deadline, then every
        backend stops."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        timeout = float(body.get("timeout",
                                 getattr(self.cfg, "drain_timeout", 30.0)))
        model = body.get("model", "")
        if model:
            ok = await asyncio.to_thread(
                self.manager.drain_model, model, timeout)
            return web.json_response({"success": ok})
        await self._drain(timeout)
        return web.json_response({"success": True, "draining": True})

    async def _backend_preempt(self, request):
        """POST /backend/preempt {"model": x, "grace": s} — preemption
        notice (ISSUE 19): SIGTERM the model's backend so live slots freeze
        into ResumeTokens (spill-drain) instead of finishing; their streams
        resume transparently on the respawned backend. Unlike
        /backend/shutdown this checkpoints requests mid-flight rather than
        waiting for them."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        model = body.get("model", "")
        if not model:
            return web.json_response(
                schema.error_body("model required", code=400), status=400)
        grace = body.get("grace")
        ok = await asyncio.to_thread(
            self.manager.preempt_model, model,
            float(grace) if grace is not None else None)
        return web.json_response({"success": ok})

    async def _drain(self, timeout: float):
        """Reject new work (middleware 503s while self._draining), wait for
        in-flight requests to finish — hard deadline — then stop backends."""
        self._draining = True
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(timeout, 0.0)
        while self._inflight > 0 and loop.time() < deadline:
            await asyncio.sleep(0.05)
        await asyncio.to_thread(self.manager.stop_all)

    async def _on_shutdown(self, app):
        # SIGTERM/cleanup path: drain unless an explicit /backend/shutdown
        # already did
        if not self._draining:
            await self._drain(getattr(self.cfg, "drain_timeout", 30.0))
        self._scrape_pool.shutdown(wait=False)
        for gate in self._gates.values():
            gate.pumps.shutdown(wait=False)

    async def _realtime(self, request):
        from localai_tpu.server.realtime import realtime_handler

        return await realtime_handler(self, request)

    async def _realtime_session(self, request):
        from localai_tpu.server.realtime import session_factory_handler

        return await session_factory_handler(self, request, "conversation")

    async def _realtime_transcription_session(self, request):
        from localai_tpu.server.realtime import session_factory_handler

        return await session_factory_handler(self, request, "transcription")

    # ------------------------------------------------------ image endpoints
    # (reference: endpoints/openai/image.go — b64_json/url response shapes)

    def _media_cfg(self, body: dict, backend: str) -> ModelConfig:
        name = body.get("model") or f"default-{backend}"
        cfg = self.configs.get(name)
        if cfg is None:
            cfg = ModelConfig(name=name, backend=backend)
        return cfg

    async def _images(self, request):
        import base64
        import tempfile

        body = await request.json()
        cfg = self._media_cfg(body, "image")
        handle = await self._handle(cfg)
        size = (body.get("size") or "256x256").lower().split("x")
        w, h = int(size[0]), int(size[1] if len(size) > 1 else size[0])
        with tempfile.NamedTemporaryFile(suffix=".png", delete=False) as t:
            path = t.name
        handle.mark_busy()
        try:
            await asyncio.to_thread(lambda: handle.client.generate_image(
                positive_prompt=body.get("prompt", ""),
                negative_prompt=body.get("negative_prompt", ""),
                width=w, height=h,
                step=int(body.get("step", 0)),
                seed=int(body.get("seed", 0)),
                dst=path))
            with open(path, "rb") as f:
                data = f.read()
            return web.json_response({"created": int(time.time()), "data": [
                {"b64_json": base64.b64encode(data).decode()}]})
        finally:
            handle.mark_idle()
            import os as _os

            _os.unlink(path)

    async def _videos(self, request):
        import base64
        import tempfile

        body = await request.json()
        cfg = self._media_cfg(body, "image")
        handle = await self._handle(cfg)
        with tempfile.NamedTemporaryFile(suffix=".gif", delete=False) as t:
            path = t.name
        handle.mark_busy()
        try:
            await asyncio.to_thread(
                lambda: handle.client.generate_video(
                    prompt=body.get("prompt", ""),
                    num_frames=int(body.get("num_frames", 8)),
                    fps=int(body.get("fps", 4)),
                    seed=int(body.get("seed", 0)),
                    dst=path))
            with open(path, "rb") as f:
                data = f.read()
            return web.json_response({"created": int(time.time()), "data": [
                {"b64_json": base64.b64encode(data).decode(),
                 "mime_type": "image/gif"}]})
        finally:
            handle.mark_idle()
            import os as _os

            _os.unlink(path)

    # ------------------------------------------------------ audio endpoints
    # (reference: endpoints/openai/transcription.go + localai tts/vad routes)

    async def _transcriptions(self, request):
        """OpenAI /v1/audio/transcriptions: multipart form (file, model)."""
        import tempfile

        form = await request.post()
        upload = form.get("file")
        if upload is None:
            raise web.HTTPBadRequest(
                text=json.dumps(schema.error_body("file field required")),
                content_type="application/json")
        cfg = self._resolve({"model": form.get("model", "")})
        handle = await self._handle(cfg)
        with tempfile.NamedTemporaryFile(suffix=".wav", delete=False) as t:
            t.write(upload.file.read())
            path = t.name
        handle.mark_busy()
        try:
            r = await asyncio.to_thread(
                lambda: handle.client.transcribe(
                    dst=path, language=form.get("language", "")))
            return web.json_response({
                "text": r.text,
                "segments": [{
                    "id": s.id, "start": s.start / 1e9, "end": s.end / 1e9,
                    "text": s.text,
                } for s in r.segments],
            })
        finally:
            handle.mark_idle()
            import os as _os

            _os.unlink(path)

    async def _tts_wav(self, name: str, text: str, voice: str,
                       language: str) -> web.Response:
        """Shared one-shot TTS → WAV response (speech/tts/elevenlabs routes)."""
        import os as _os
        import tempfile

        cfg = self.configs.get(name)
        if cfg is None:
            cfg = ModelConfig(name=name, backend="tts")
        handle = await self._handle(cfg)
        with tempfile.NamedTemporaryFile(suffix=".wav", delete=False) as t:
            path = t.name
        handle.mark_busy()
        try:
            r = await asyncio.to_thread(lambda: handle.client.tts(
                text=text, voice=voice, dst=path, language=language))
            if not r.success:
                raise web.HTTPInternalServerError(
                    text=json.dumps(schema.error_body(
                        f"tts failed: {r.message}", "server_error", 500)),
                    content_type="application/json")
            with open(path, "rb") as f:
                data = f.read()
            return web.Response(body=data, content_type="audio/wav")
        finally:
            handle.mark_idle()
            _os.unlink(path)

    async def _speech(self, request):
        """OpenAI /v1/audio/speech + localai /tts → WAV bytes."""
        body = await request.json()
        return await self._tts_wav(
            body.get("model") or "default-tts",
            body.get("input") or body.get("text") or "",
            body.get("voice", ""), body.get("language", ""))

    async def _webui(self, request):
        from localai_tpu.server.webui import INDEX_HTML

        return web.Response(text=INDEX_HTML, content_type="text/html")

    async def _elevenlabs_tts(self, request):
        """elevenlabs-shaped TTS: voice from the path, text in the body
        (reference core/http/endpoints/elevenlabs/tts.go)."""
        body = await request.json()
        return await self._tts_wav(
            body.get("model_id") or body.get("model") or "default-tts",
            body.get("text") or "",
            request.match_info.get("voice_id", ""),
            body.get("language_code", ""))

    async def _vad(self, request):
        body = await request.json()
        name = body.get("model") or "default-tts"
        cfg = self.configs.get(name)
        if cfg is None:
            cfg = ModelConfig(name=name, backend="tts")
        handle = await self._handle(cfg)
        handle.mark_busy()
        try:
            r = await asyncio.to_thread(
                lambda: handle.client.vad(body.get("audio", [])))
        finally:
            handle.mark_idle()
        return web.json_response({"segments": [
            {"start": s.start, "end": s.end} for s in r.segments]})

    async def _sound_generation(self, request):
        import tempfile

        body = await request.json()
        name = body.get("model") or "default-tts"
        cfg = self.configs.get(name)
        if cfg is None:
            cfg = ModelConfig(name=name, backend="tts")
        handle = await self._handle(cfg)
        with tempfile.NamedTemporaryFile(suffix=".wav", delete=False) as t:
            path = t.name
        handle.mark_busy()
        try:
            await asyncio.to_thread(
                lambda: handle.client.sound_generation(
                    text=body.get("text", body.get("input", "")),
                    duration=float(body.get("duration_seconds", 2.0)),
                    dst=path))
            with open(path, "rb") as f:
                data = f.read()
            return web.Response(body=data, content_type="audio/wav")
        finally:
            handle.mark_idle()
            import os as _os

            _os.unlink(path)

    # ------------------------------------------------------ stores endpoints
    # (reference: localai routes + backend/go/local-store; values are strings
    # on the wire, bytes at the backend)

    async def _store_handle(self, body: dict):
        name = body.get("store") or "default-store"
        cfg = self.configs.get(name)
        if cfg is None:
            cfg = ModelConfig(name=name, backend="store")
        return await self._handle(cfg)

    async def _stores_set(self, request):
        body = await request.json()
        h = await self._store_handle(body)
        h.mark_busy()
        try:
            await asyncio.to_thread(lambda: h.client.stores_set(
                body.get("keys", []),
                [v.encode() for v in body.get("values", [])]))
        finally:
            h.mark_idle()
        return web.json_response({})

    async def _stores_get(self, request):
        body = await request.json()
        h = await self._store_handle(body)
        h.mark_busy()
        try:
            r = await asyncio.to_thread(
                lambda: h.client.stores_get(body.get("keys", [])))
        finally:
            h.mark_idle()
        return web.json_response({
            "keys": [list(k.floats) for k in r.keys],
            "values": [v.bytes.decode("utf-8", "replace") for v in r.values],
        })

    async def _stores_delete(self, request):
        body = await request.json()
        h = await self._store_handle(body)
        h.mark_busy()
        try:
            await asyncio.to_thread(
                lambda: h.client.stores_delete(body.get("keys", [])))
        finally:
            h.mark_idle()
        return web.json_response({})

    async def _stores_find(self, request):
        body = await request.json()
        h = await self._store_handle(body)
        h.mark_busy()
        try:
            r = await asyncio.to_thread(lambda: h.client.stores_find(
                body.get("key", []), int(body.get("topk", 10))))
        finally:
            h.mark_idle()
        return web.json_response({
            "keys": [list(k.floats) for k in r.keys],
            "values": [v.bytes.decode("utf-8", "replace") for v in r.values],
            "similarities": list(r.similarities),
        })

    async def _system(self, request):
        from localai_tpu.system import system_info

        # device facts come from the loaded backends' own reports: this
        # process must never import JAX (it would take the chip from them)
        info = system_info(self.manager.devices())
        info["loaded_models"] = self.manager.loaded()
        return web.json_response(info)

    # ------------------------------------------------------ gallery endpoints
    # (reference routes: /models/apply + job status, localai.go)

    def _require_gallery(self):
        if self.gallery_service is None:
            raise web.HTTPNotImplemented(
                text=json.dumps(schema.error_body(
                    "no galleries configured", code=501)),
                content_type="application/json")
        return self.gallery_service

    async def _models_apply(self, request):
        svc = self._require_gallery()
        body = await request.json()
        name = body.get("id") or body.get("model") or ""
        job = svc.submit(name, overrides=body.get("config_overrides"))
        return web.json_response({"uuid": job,
                                  "status": f"/models/jobs/{job}"})

    async def _models_available(self, request):
        svc = self._require_gallery()
        models = await asyncio.to_thread(svc.gallery.models)
        return web.json_response([{
            "name": m.name, "description": m.description, "tags": m.tags,
            "installed": self.configs.get(m.name) is not None,
        } for m in models.values()])

    async def _models_job(self, request):
        svc = self._require_gallery()
        st = svc.status.get(request.match_info["job_id"])
        if st is None:
            raise web.HTTPNotFound()
        if st.get("state") == "done":
            self.configs.reload()  # new YAML becomes servable immediately
        return web.json_response(st)

    # ------------------------------------------------ backend gallery

    async def _backends_list(self, request):
        from localai_tpu.services.backend_gallery import list_system_backends

        return web.json_response(await asyncio.to_thread(
            list_system_backends, self.cfg.backends_path))

    def _require_backend_gallery(self):
        if self.backend_gallery_service is None:
            raise web.HTTPBadRequest(
                text="no backend galleries configured "
                     "(--backend-galleries / LOCALAI_BACKEND_GALLERIES)")
        return self.backend_gallery_service

    async def _backends_available(self, request):
        from localai_tpu.services.backend_gallery import list_system_backends

        svc = self._require_backend_gallery()
        backends = await asyncio.to_thread(svc.gallery.backends)
        installed = {b["name"] for b in await asyncio.to_thread(
            list_system_backends, self.cfg.backends_path)}
        return web.json_response([{
            "name": b.name, "description": b.description, "tags": b.tags,
            "meta": b.is_meta, "installed": b.name in installed,
        } for b in backends.values()])

    async def _backends_galleries(self, request):
        svc = self._require_backend_gallery()
        return web.json_response([{"url": s} for s in svc.gallery.sources])

    async def _backends_apply(self, request):
        svc = self._require_backend_gallery()
        body = await request.json()
        name = body.get("id") or body.get("name") or ""
        if not name:
            raise web.HTTPBadRequest(text="backend name required")
        job = svc.submit(name)
        return web.json_response({"uuid": job,
                                  "status": f"/backends/jobs/{job}"})

    async def _backends_delete(self, request):
        from localai_tpu.services.backend_gallery import delete_backend

        try:
            await asyncio.to_thread(delete_backend,
                                    self.cfg.backends_path,
                                    request.match_info["name"])
        except KeyError as e:
            raise web.HTTPNotFound(text=str(e))
        return web.json_response({"deleted": True})

    async def _backends_job(self, request):
        svc = self._require_backend_gallery()
        st = svc.status.get(request.match_info["job_id"])
        if st is None:
            raise web.HTTPNotFound()
        return web.json_response(st)


def run_server(args) -> int:
    """CLI `run` entrypoint: assemble config + manager + API and serve
    (reference: core/application/startup.go + cmd/local-ai/main.go)."""
    from localai_tpu.core.startup import (
        ConfigWatcher, load_env_files, preload_models,
    )

    env_file = getattr(args, "env_file", None)
    load_env_files([env_file] if env_file else None)
    # --trace goes through the environment so the ModelManager's backend
    # subprocesses (which inherit os.environ) pick it up too
    if getattr(args, "trace", False):
        os.environ["LOCALAI_TRACE"] = "1"
    app_cfg = AppConfig.from_env(
        address=getattr(args, "address", None),
        models_path=getattr(args, "models_path", None),
        context_size=getattr(args, "context_size", None),
        parallel_requests=getattr(args, "parallel_requests", None),
        tensor_parallel=getattr(args, "tensor_parallel", None),
        single_active_backend=getattr(args, "single_active_backend", None),
        api_keys=getattr(args, "api_keys", None),
        request_timeout=getattr(args, "request_timeout", None),
        retry_budget=getattr(args, "retry_budget", None),
        breaker_threshold=getattr(args, "breaker_threshold", None),
        breaker_cooldown=getattr(args, "breaker_cooldown", None),
        queue_depth=getattr(args, "queue_depth", None),
        drain_timeout=getattr(args, "drain_timeout", None),
        preempt_grace=getattr(args, "preempt_grace", None),
        kv_window=getattr(args, "kv_window", None),
        kv_sinks=getattr(args, "kv_sinks", None),
        kv_host_bytes=getattr(args, "kv_host_bytes", None),
    )
    for t in ("watchdog_idle_timeout", "watchdog_busy_timeout"):
        v = getattr(args, t, None)
        if v:
            setattr(app_cfg, t, float(v))
    configs = ModelConfigLoader(app_cfg.models_path)
    manager = ModelManager(app_cfg)
    manager.start_watchdog()
    api = API(app_cfg, configs, manager)
    galleries = getattr(args, "galleries", None)
    if galleries:
        from localai_tpu.services import Gallery, GalleryService

        svc = GalleryService(
            Gallery([s.strip() for s in galleries.split(",") if s.strip()]),
            app_cfg.models_path)
        svc.start()
        api.gallery_service = svc

    backends_path = getattr(args, "backends_path", None)
    if backends_path:
        app_cfg.backends_path = backends_path
    bgalleries = (getattr(args, "backend_galleries", None)
                  or os.environ.get("LOCALAI_BACKEND_GALLERIES", ""))
    if bgalleries:
        from localai_tpu.services.backend_gallery import (
            BackendGallery, BackendGalleryService,
        )

        app_cfg.backend_galleries = [
            s.strip() for s in bgalleries.split(",") if s.strip()]
        bsvc = BackendGalleryService(
            BackendGallery(app_cfg.backend_galleries),
            app_cfg.backends_path or os.path.join(
                app_cfg.models_path, "..", "backends"))
        if not app_cfg.backends_path:
            app_cfg.backends_path = bsvc.backends_path
        bsvc.start()
        api.backend_gallery_service = bsvc

    preload = getattr(args, "models", None) or []
    if preload:
        # warm the listed backends in the background so serving starts now
        # but first requests don't pay the model load (startup.go:65-105)
        threading.Thread(
            target=preload_models,
            args=(list(preload), configs, manager),
            kwargs={"gallery_service": getattr(api, "gallery_service", None)},
            daemon=True, name="preload").start()

    watcher = None
    if not getattr(args, "disable_config_watcher", False):
        watcher = ConfigWatcher(configs).start()

    host, _, port = app_cfg.address.rpartition(":")
    try:
        web.run_app(api.app, host=host or "127.0.0.1", port=int(port),
                    print=lambda *a: print(f"serving on {app_cfg.address}",
                                           flush=True))
    finally:
        if watcher:
            watcher.stop()
        manager.stop_all()
    return 0
