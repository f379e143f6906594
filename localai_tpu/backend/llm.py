"""The flagship LLM backend servicer — the llama.cpp-grpc-server role
(/root/reference/backend/cpp/llama-cpp/grpc-server.cpp:505,571,1003,1373,1552),
re-built over the TPU engine: LoadModel reads HF safetensors into (optionally
mesh-sharded) jax.Arrays, Predict/PredictStream drive the continuous-batching
Engine, Embedding runs the bucketed pooled encoder.
"""
from __future__ import annotations

import json
import os
import resource
import sys
import threading
import time

import grpc

from localai_tpu import telemetry
from localai_tpu.backend import pb
from localai_tpu.backend.base import BackendServicer
from localai_tpu.backend.client import REQUEST_ID_KEY, XPROF_SECONDS_KEY
from localai_tpu.ops.sampling import SamplingParams
from localai_tpu.testing import faults


def _inject_faults(context):
    """Chaos-harness hooks (LOCALAI_FAULT): deterministic gRPC-status faults
    on the generation path. No-ops (one env lookup) in normal serving."""
    if faults.fire("unavailable") is not None:
        context.abort(grpc.StatusCode.UNAVAILABLE,
                      "injected UNAVAILABLE (LOCALAI_FAULT)")
    if faults.fire("deadline") is not None:
        context.abort(grpc.StatusCode.DEADLINE_EXCEEDED,
                      "injected DEADLINE_EXCEEDED (LOCALAI_FAULT)")


def _metadata(context, key: str) -> str:
    """One value of the call's gRPC metadata ("" when it is not there)."""
    try:
        for k, v in context.invocation_metadata():
            if k == key:
                return v
    except Exception:
        pass
    return ""


def _request_id(context) -> str:
    """The HTTP layer's request id, if the client attached one (metadata
    propagation — backend/client.py _trace_md)."""
    return _metadata(context, REQUEST_ID_KEY)


def _finish_to_reply(o, trace_id: str) -> None:
    """gRPC has taken a stream's final reply: observe the time since the
    engine's finish decision (`StepOutput.finished_t`, where hist_e2e ends)
    as `hist_finish_to_reply__<decode path>`, once a request that ran to its
    end. A request the engine did not finish in _emit (refused, dead on
    arrival, failed) has no such time; one it ended for a client that had
    gone, or for a preemption, would time a reply to nobody."""
    if o.finished_t is None or o.finish_reason in ("cancelled", "preempted"):
        return
    slo = telemetry.maybe_slo()
    if slo is None:
        return
    took = time.monotonic() - o.finished_t
    slo.observe("finish_to_reply", (o.timings or {}).get("path", "all"), took)
    tr = telemetry.maybe_tracer()
    if tr is not None:
        tr.add_complete("engine.stage.finish_to_reply", o.finished_t, took,
                        cat="engine",
                        args={"request_id": trace_id
                              or f"rid-{o.request_id}"})


class LLMServicer(BackendServicer):
    def __init__(self, preloaded=None):
        """`preloaded=(engine, cfg, tok, name)` serves an engine built by the
        caller (the multi-host worker path, core/worker.py) — LoadModel then
        reports already-loaded instead of constructing a second engine."""
        self.engine = None
        self.embedder = None
        self.scorer = None
        self.tok = None
        self.cfg = None
        self.model_name = ""
        self._state = pb.StatusResponse.UNINITIALIZED
        self._load_seconds: dict = {}
        self._load_lock = threading.Lock()
        # counts this process's XLA compiles from before the first load
        telemetry.compile_counter()
        if preloaded is not None:
            self.engine, self.cfg, self.tok, self.model_name = preloaded
            self._state = pb.StatusResponse.READY

    # ------------------------------------------------------------ lifecycle

    def LoadModel(self, request, context):
        with self._load_lock:
            if self.engine is not None or self.embedder is not None:
                return pb.Result(success=True, message="already loaded")
            self._state = pb.StatusResponse.BUSY
            try:
                # lockdep: allow(lock-blocking) — the load lock serializes
                # the WHOLE load (weights + engine start + warmup compiles +
                # prewarm streams, minutes of blocking): that is its job.
                # It is the backend process's outermost lock (rank 0)
                self._load(request)
                self._state = pb.StatusResponse.READY
                return pb.Result(success=True, message="ok")
            except Exception as e:  # surface load errors to the control plane
                import traceback

                traceback.print_exc()
                self._state = pb.StatusResponse.ERROR
                # a half-loaded engine must not answer "already loaded" to
                # the next LoadModel, nor keep its loop thread on the chip.
                # lockdep: allow(lock-blocking) — stopping the engine this
                # load started is the tail of the same load; the load lock
                # is the outermost lock and covers it like the load itself
                self.shutdown()
                self.engine = self.embedder = self.scorer = None
                return pb.Result(success=False, message=f"{type(e).__name__}: {e}")

    def _load(self, request):
        import jax

        from localai_tpu.engine import Engine, EngineConfig
        from localai_tpu.engine.loader import (
            load_config, load_params, load_tokenizer,
        )
        from localai_tpu.engine.embedder import Embedder
        from localai_tpu.models.llama import max_model_axis
        from localai_tpu.parallel.mesh import MeshConfig, build_mesh

        model_dir = request.model
        if request.model_path and not os.path.exists(model_dir):
            model_dir = os.path.join(request.model_path, request.model)
        if os.path.isfile(model_dir) and model_dir.endswith(".gguf"):
            # GGUF ingestion (reference: llama.cpp serves GGUF natively;
            # here it converts once to the HF layout — services/gguf.py)
            from localai_tpu.services.gguf import resolve_gguf

            model_dir = resolve_gguf(model_dir)
        if not os.path.isdir(model_dir):
            raise FileNotFoundError(f"model directory not found: {model_dir}")

        from localai_tpu.models.bert import is_bert_dir

        if is_bert_dir(model_dir):
            # encoder checkpoint (BertModel/RobertaModel/...): the universal
            # embeddings role (reference transformers backend,
            # backend.py:37,323) — no generation engine, Embedding RPC only
            self._load_bert(request, model_dir)
            return

        cfg = load_config(model_dir, dtype=request.dtype or None)
        devices = jax.devices()
        mesh = None
        if request.mesh_data or request.mesh_model:
            # explicit mesh request: honor it (invalid shapes fail loudly)
            data = request.mesh_data or 1
            model = request.mesh_model or (len(devices) // data)
            mesh = build_mesh(MeshConfig(data=data, model=model),
                              devices[: data * model])
        elif len(devices) > 1:
            # auto-TP over as many devices as the model dims divide into —
            # quantized dtypes included: the loader quantizes per host-read
            # shard under param_specs(qbits=...), so the flagship int8
            # recipe boards the full mesh (a draft model rides the mesh
            # too — sharded when its dims divide the axis, replicated
            # otherwise)
            model = max_model_axis(cfg, len(devices))
            if model > 1:
                mesh = build_mesh(MeshConfig(data=1, model=model),
                                  devices[:model])

        from localai_tpu.ops.kvcache import is_quant_kind

        # normalize exactly like the engine does below: quant in EITHER
        # field means int8 KV
        kv_kind = "int8" if (is_quant_kind(request.cache_type_key)
                             or is_quant_kind(request.cache_type_value)) \
            else ""
        context_size = request.context_size or min(2048, cfg.max_position)

        draft_dir = dcfg = None
        if request.draft_model:
            draft_dir = request.draft_model
            if request.model_path and not os.path.isdir(draft_dir):
                draft_dir = os.path.join(request.model_path, draft_dir)
            dcfg = load_config(draft_dir, dtype=request.dtype or None)

        from localai_tpu.system.memory import estimate

        # per chip: weights shard over the TP ('model') axis only (data
        # replicas hold full copies); the KV cache shards over both axes
        # (kv_cache_spec: slots on 'data', kv heads on 'model')
        shards = 1 if mesh is None else int(
            dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1))
        kv_shards = 1 if mesh is None else int(mesh.devices.size)
        est = estimate(cfg, slots=request.parallel or 4,
                       context=context_size,
                       dtype=request.dtype or cfg.dtype,
                       cache_type=kv_kind, draft_cfg=dcfg, shards=shards,
                       kv_shards=kv_shards, kv_pages=request.kv_pages)
        if est.fits is False:
            import logging

            logging.getLogger("localai_tpu").warning(
                "model may not fit HBM: need ~%.1f GiB of %.1f GiB per chip "
                "(weights %.1f + kv %.1f + working %.1f, %d chip(s))",
                est.total_bytes / 2**30, (est.hbm_bytes or 0) / 2**30,
                est.weights_bytes / 2**30, est.kv_cache_bytes / 2**30,
                est.working_bytes / 2**30, shards)

        t0 = time.monotonic()
        params = load_params(model_dir, cfg, dtype=request.dtype or None,
                             mesh=mesh)
        jax.block_until_ready(params)   # so the phase below is not charged
        t_weights = time.monotonic() - t0
        tok = load_tokenizer(model_dir)
        # single-shot prefill up to the chunk size; longer prompts prefill in
        # chunk-sized pieces interleaved with running decodes
        chunk = min(512, context_size)
        buckets = tuple(request.prefill_buckets) or tuple(
            b for b in (64, 256, 512) if b <= chunk
        ) or (chunk,)
        draft = None
        if dcfg is not None:
            # speculative decoding (reference DraftModel, backend.proto:218)
            dspecs = None
            if mesh is not None:
                from localai_tpu.models.llama import replicated_specs

                model_ax = int(dict(zip(
                    mesh.axis_names, mesh.devices.shape)).get("model", 1))
                if max_model_axis(dcfg, model_ax) != model_ax:
                    dspecs = replicated_specs(
                        dcfg, qbits={"int8": 8, "q8": 8, "int4": 4,
                                     "q4": 4}.get(request.dtype))
            draft = (dcfg, load_params(draft_dir, dcfg,
                                       dtype=request.dtype or None,
                                       mesh=mesh, specs=dspecs))
        # one storage kind for both K and V (quantize when either side asks;
        # the reference allows split k/v types — grpc-server.cpp:236-251)
        cache_type = kv_kind
        # KV lifecycle tier rides the ModelOptions.options JSON blob (no
        # dedicated proto field — same lane as the hfapi endpoint override)
        kv_policy, kv_cold_pages, kv_host_bytes = "", 0, 0
        if request.options:
            import json

            opts = json.loads(request.options)  # typos fail the load loudly
            kv_policy = str(opts.get("kv_policy", ""))
            kv_cold_pages = int(opts.get("kv_cold_pages", 0))
            kv_host_bytes = int(opts.get("kv_host_bytes", 0))
        self.engine = Engine(cfg, params, tok, EngineConfig(
            max_slots=request.parallel or 4,
            max_context=context_size,
            prefill_buckets=buckets,
            prefill_chunk=chunk,
            mesh=mesh,
            gamma=request.n_draft or 4,
            cache_type=cache_type,
            kv_pages=request.kv_pages,
            kv_policy=kv_policy,
            kv_cold_pages=kv_cold_pages,
            kv_host_bytes=kv_host_bytes,
        ), draft=draft)
        if request.embeddings:
            from localai_tpu.engine.embedder import CrossScorer

            self.embedder = Embedder(cfg, params, buckets=buckets, mesh=mesh)
            self.scorer = CrossScorer(cfg, params, buckets=buckets, mesh=mesh)
        from localai_tpu.models.llava import is_llava, load_vision

        self.vision = None
        if is_llava(model_dir):
            # vision-language checkpoint: the CLIP tower + projector serve
            # request.images (the reference's mmproj / vLLM-multimodal role)
            self.vision = load_vision(model_dir)
        self.cfg, self.tok = cfg, tok
        self.model_name = request.model
        self.engine.start()
        t1 = time.monotonic()
        if os.environ.get("LOCALAI_NO_PREWARM") != "1":
            self._prewarm()
        # set-up time, for the device report and the log: where a cold start
        # goes (compiles land in prewarm; a warm compile cache shrinks it)
        self._load_seconds = {
            "weights": round(t_weights, 1),
            "engine": round(t1 - t0 - t_weights, 1),
            "prewarm": round(time.monotonic() - t1, 1),
        }
        print(f"[load] {request.model}: {self._load_seconds}",
              file=sys.stderr, flush=True)

    def _prewarm(self):
        """Compile the serving hot path before LoadModel returns READY (the
        llama.cpp server warms its graph the same way): K=1 admission, the
        fused decode block, and the fast-sampling tail. Without this the
        FIRST user request pays tens of seconds of XLA compiles on TPU.

        A failure here fails LoadModel: these are the programs every request
        runs, so a compile the device refuses (a Pallas kernel Mosaic will
        not lower, an OOM) is a model that cannot serve, not a warning."""
        from localai_tpu.engine import GenRequest
        from localai_tpu.ops.sampling import SamplingParams

        if faults.fire("prewarm_raise") is not None:
            raise RuntimeError(
                "injected prewarm failure (LOCALAI_FAULT=prewarm_raise)")
        try:
            # pre-compile every decode-loop variant, sort-free sampling
            # tier, and remaining scan-ladder width directly (all-inactive
            # dispatches) — the streamed requests below then only pay the
            # admission-bucket compiles, and the first USER request pays
            # nothing (the bench's window-0 204 tok/s vs 2760 steady-state
            # gap was exactly these mid-stream compiles)
            self.engine.warmup()
            n = 3 * self.engine.ec.decode_block + 2
            # three warm requests: the sort-free fast path (greedy/top_k),
            # its 8x escalation tier (wide top_k), and the full-sort path
            # (top_k=0 MUST be explicit — the dataclass default is 40,
            # which would silently warm the fast path twice)
            W = self.engine.ec.sampling_topk_width
            warm = [SamplingParams(temperature=0.0, top_k=40),
                    SamplingParams(temperature=0.8, top_p=0.9, top_k=0,
                                   seed=1)]
            if W and 2 * W <= self.cfg.vocab_size:
                warm.insert(1, SamplingParams(temperature=0.8, top_k=2 * W,
                                              seed=2))
            for sp in warm:
                _, q = self.engine.submit(GenRequest(
                    prompt_ids=[1], max_tokens=n, ignore_eos=True,
                    params=sp))
                while True:
                    o = q.get(timeout=600)
                    if o.finished:
                        break
                if o.finish_reason != "length":
                    # the engine loop caught a step failure, failed the
                    # request and restarted (engine._loop) — from here that
                    # looks like a short stream, so check what it ended on
                    raise RuntimeError(
                        f"prewarm request ended {o.finish_reason!r} after "
                        f"{o.generated_tokens}/{n} tokens: "
                        f"{self.engine.last_error or 'no engine error'}")
        finally:
            # the synthetic warm requests must not pollute the serving SLO
            # percentiles (warmup() snapshots the dispatch counters the same
            # way)
            slo = telemetry.maybe_slo()
            if slo is not None:
                slo.reset()

    def _load_bert(self, request, model_dir: str):
        """Embedding-only load path for BERT-family encoders: generation RPCs
        stay FAILED_PRECONDITION (engine is None), Embedding serves."""
        from localai_tpu.engine.loader import load_tokenizer
        from localai_tpu.models.bert import (
            BertEmbedder, load_bert_config, load_bert_params,
        )

        cfg = load_bert_config(model_dir, dtype=request.dtype or None)
        params = load_bert_params(model_dir, cfg)
        buckets = tuple(request.prefill_buckets) or (64, 256, 512)
        self.embedder = BertEmbedder(cfg, params, buckets=buckets)
        try:
            self.tok = load_tokenizer(model_dir)
        except FileNotFoundError:
            # tokenizer-less checkpoint still serves the prompt_ids path
            self.tok = None
        self.cfg = cfg
        self.model_name = request.model

    # ------------------------------------------------------------ helpers

    def _require_engine(self, context):
        if self.engine is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "no model loaded (call LoadModel first)")

    def _prompt_ids(self, request, context) -> list[int]:
        if request.prompt_ids:
            return list(request.prompt_ids)
        if self.tok is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "no tokenizer; pass prompt_ids")
        if request.use_tokenizer_template and request.messages_json:
            messages = json.loads(request.messages_json)
            # tool schemas render into the prompt via the chat template's
            # `tools` variable (engine/tokenizer.apply_chat_template) — the
            # grammar constrains the OUTPUT shape, but the model can only
            # pick sensible tools/arguments if it actually SEES them
            # (reference: chat.go:266-312 renders schemas before
            # constraining; VERDICT Missing #1)
            tools = None
            if request.tools_json:
                try:
                    tools = json.loads(request.tools_json) or None
                except json.JSONDecodeError:
                    context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                                  "tools_json is not valid JSON")
            return self.tok.encode_chat(messages, tools=tools)
        return self.tok.encode(request.prompt)

    @staticmethod
    def _sampling(request) -> SamplingParams:
        return SamplingParams(
            temperature=request.temperature,
            top_k=request.top_k or 0,
            top_p=request.top_p or 1.0,
            min_p=request.min_p,
            typical_p=request.typical_p or 1.0,
            repeat_penalty=request.repeat_penalty or 1.0,
            presence_penalty=request.presence_penalty,
            frequency_penalty=request.frequency_penalty,
            seed=request.seed if request.seed else -1,
            logit_bias=dict(request.logit_bias) or None,
        )

    def _submit(self, request, context, trace_id: str = "",
                trace_parent: int = 0):
        from localai_tpu.engine import GenRequest

        resume = None
        max_tokens = request.tokens or 128
        if request.resume_json:
            # preemption resume (ISSUE 19): the request carries a ResumeToken
            # — prompt becomes original+emitted, the payload drives the
            # engine's RNG/grammar/detok fixups, and the token budget shrinks
            # by what the preempted stream already produced
            from localai_tpu.engine.resume import ResumeToken

            try:
                tok = ResumeToken.from_json(request.resume_json)
            except (ValueError, KeyError, TypeError) as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                              f"bad resume_json: {e}")
            ids = tok.resume_prompt
            resume = tok.payload()
            max_tokens = max(1, max_tokens - tok.generated)
        else:
            ids = self._prompt_ids(request, context)
        mm_embeds = mm_positions = None
        if request.images:
            if self.vision is None:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                              "model has no vision tower; images unsupported")
            try:
                ids, mm_embeds, mm_positions = self._encode_images(
                    ids, list(request.images))
            except Exception as e:
                # bad base64 (binascii.Error), not-an-image payloads
                # (PIL.UnidentifiedImageError ⊂ OSError), placeholder-count
                # mismatches (ValueError) — all client errors, never fatal
                context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                              f"bad image: {e}")
        req = GenRequest(
            prompt_ids=ids,
            params=self._sampling(request),
            max_tokens=max_tokens,
            resume=resume,
            stop=tuple(request.stop_prompts),
            ignore_eos=request.ignore_eos,
            logprobs=request.logprobs,
            grammar=request.grammar,
            context_shift=request.context_shift,
            prompt_cache_path=request.prompt_cache_path,
            prompt_cache_ro=request.prompt_cache_ro,
            mm_embeds=mm_embeds,
            mm_positions=mm_positions,
            trace_id=trace_id,
            trace_parent=trace_parent,
            # remaining HTTP-request budget → absolute engine deadline: an
            # expired slot is evicted (finish "timeout") instead of decoding
            # tokens nobody will read
            deadline=(time.monotonic() + request.deadline_ms / 1e3
                      if request.deadline_ms else 0.0),
        )
        try:
            rid, out = self.engine.submit(req)
        except (ValueError, RuntimeError) as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        # RPC termination (client cancel/disconnect, deadline) evicts the
        # slot — the unary analog of the stream's call.cancel() path. Fires
        # on normal completion too, where cancel() is a no-op. (Direct
        # servicer tests pass context=None.)
        if context is not None:
            context.add_callback(lambda: self.engine.cancel(rid))
        return rid, out, ids

    def _encode_images(self, ids, images):
        """b64 images + prompt ids with <image> placeholders → (expanded ids,
        mm_embeds [K, H], mm_positions [K]). The CLIP tower + projector run
        as their own jit — per-request prefill-side work, off the decode
        loop (models/llava.py)."""
        import numpy as np

        from localai_tpu.models.llava import (
            decode_image_b64, encode_images, expand_image_tokens,
            preprocess_image,
        )

        vcfg, vparams, meta = self.vision
        px = np.concatenate(
            [preprocess_image(decode_image_b64(i), vcfg) for i in images])
        feats = np.asarray(encode_images(vparams, vcfg, meta, px),
                           np.float32)                  # [N, n_tok, H]
        n_tok = feats.shape[1]
        if meta.image_token_index not in ids and len(images) == 1:
            # prompt without a placeholder (plain chat with an attachment):
            # image goes first, like llava's "<image>\n<prompt>" convention
            ids = [meta.image_token_index] + list(ids)
        ids, positions = expand_image_tokens(
            ids, len(images), n_tok, meta.image_token_index)
        return ids, feats.reshape(-1, feats.shape[-1]), positions

    # ------------------------------------------------------------ inference

    def Predict(self, request, context):
        self._require_engine(context)
        _inject_faults(context)
        t0 = time.monotonic()
        trace_id = _request_id(context)
        tr = telemetry.maybe_tracer()
        gspan = tr.begin("grpc.Predict", cat="grpc",
                         args={"request_id": trace_id}) if tr else None
        text, ids, logprobs, ttft = [], [], [], 0.0
        o = None
        try:
            rid, out, _ = self._submit(request, context, trace_id=trace_id,
                                       trace_parent=gspan.sid if gspan else 0)
            while True:
                o = out.get()
                if o.token_id >= 0 and not ttft:
                    ttft = time.monotonic() - t0
                if o.text:
                    text.append(o.text)
                if o.token_id >= 0:
                    ids.append(o.token_id)
                    logprobs.append(o.logprob)
                if o.finished:
                    break
        finally:
            # a _submit abort / severed stream must still close the span, or
            # the request's trace never reaches the ring buffer
            if gspan is not None:
                tr.finish(gspan, tokens=o.generated_tokens if o else 0,
                          ttft_s=ttft)
        return pb.Reply(
            message="".join(text).encode(),
            tokens=o.generated_tokens,
            prompt_tokens=o.prompt_tokens,
            timing_prompt_processing=ttft,
            timing_token_generation=time.monotonic() - t0 - ttft,
            logprobs=logprobs if request.logprobs else [],
            token_ids=ids,
            finish_reason=o.finish_reason or "",
            timings_json=json.dumps(o.timings) if o.timings else "",
        )

    def PredictStream(self, request, context):
        self._require_engine(context)
        _inject_faults(context)
        stall = faults.fire("stall_stream")
        # preemption chaos kinds (ISSUE 19): `preempt:grace` raises SIGTERM
        # once the first token is out (the spill-drain path — server.py's
        # handler runs servicer.preempt and the terminal "preempted" reply
        # flushes through this still-open stream); `kill9_middecode:N` SIGKILLs
        # the process at the N-th emitted token — no drain, no checkpoint,
        # the HTTP bridge must resume from its own accumulated state
        pre_grace = faults.fire("preempt")
        kill_at = faults.fire("kill9_middecode")
        t0 = time.monotonic()
        trace_id = _request_id(context)
        tr = telemetry.maybe_tracer()
        gspan = tr.begin("grpc.PredictStream", cat="grpc",
                         args={"request_id": trace_id}) if tr else None
        ttft = 0.0
        sent_text = False
        emitted = 0
        first = True
        o = None
        try:
            rid, out, ids = self._submit(request, context, trace_id=trace_id,
                                         trace_parent=gspan.sid if gspan
                                         else 0)
            while True:
                o = out.get()
                if sent_text and stall:
                    # stall-mid-stream fault: the first TEXT chunk went out
                    # (so the client has provably received bytes), then the
                    # backend wedges for `stall` seconds (chaos harness)
                    time.sleep(stall)
                    stall = None
                if o.text:
                    sent_text = True
                if o.token_id >= 0:
                    emitted += 1
                    if not ttft:
                        ttft = time.monotonic() - t0
                resume_json = ""
                if first and not o.finished:
                    # minimal checkpoint on the FIRST chunk: the tokenized
                    # prompt, so the HTTP bridge can rebuild prompt+emitted
                    # for resume/deterministic-replay after an ungraceful
                    # death (no spill-drain ran, no full token exists)
                    resume_json = json.dumps({"v": 1, "prompt_ids": ids})
                elif o.finish_reason == "preempted" and o.resume is not None:
                    # spill-drain checkpoint: the full ResumeToken rides the
                    # terminal reply out before the process exits
                    resume_json = json.dumps(o.resume)
                first = False
                yield pb.Reply(
                    message=o.text.encode(),
                    tokens=o.generated_tokens,
                    prompt_tokens=o.prompt_tokens,
                    timing_prompt_processing=ttft if o.finished else 0.0,
                    timing_token_generation=(time.monotonic() - t0 - ttft)
                    if o.finished else 0.0,
                    logprobs=[o.logprob]
                    if request.logprobs and o.token_id >= 0 else [],
                    token_ids=[o.token_id] if o.token_id >= 0 else [],
                    finish_reason=o.finish_reason or "",
                    timings_json=(json.dumps(o.timings)
                                  if o.finished and o.timings else ""),
                    resume_json=resume_json,
                )
                if o.finished:
                    # control is back here once gRPC has taken the reply; a
                    # stream the client cut is closed at the yield instead
                    _finish_to_reply(o, trace_id)
                    return
                if emitted and pre_grace is not None:
                    import signal

                    os.environ["LOCALAI_PREEMPT_GRACE"] = str(pre_grace)
                    pre_grace = None
                    os.kill(os.getpid(), signal.SIGTERM)
                if (kill_at is not None
                        and emitted >= max(1, int(kill_at))):
                    import signal

                    os.kill(os.getpid(), signal.SIGKILL)
        finally:
            # client disconnects mid-stream (GeneratorExit) and _submit
            # aborts land here too — the span must always close
            if gspan is not None:
                tr.finish(gspan, tokens=o.generated_tokens if o else 0,
                          ttft_s=ttft)

    # ------------------------------------------------------------ aux RPCs

    def TokenizeString(self, request, context):
        if self.tok is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, "no tokenizer")
        ids = self.tok.encode(request.prompt)
        return pb.TokenizationResponse(length=len(ids), tokens=ids)

    def Embedding(self, request, context):
        if self.embedder is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "model loaded without embeddings=true")
        if request.prompts:
            # batched path: the whole input list in one RPC, one bucketed
            # device call (reference transformers/backend.py:323 batches too)
            if self.tok is None:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                              "no tokenizer; batched embeddings need one")
            ids_batch = [self.tok.encode(p) for p in request.prompts]
            try:
                vecs = self.embedder.embed(ids_batch)
            except ValueError as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
            return pb.EmbeddingResult(
                vectors=[pb.EmbeddingVector(values=v.tolist()) for v in vecs],
                prompt_tokens=sum(len(i) for i in ids_batch))
        ids = self._prompt_ids(request, context)
        try:
            vec = self.embedder.embed([ids])[0]
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.EmbeddingResult(embeddings=vec.tolist(),
                                  prompt_tokens=len(ids))

    def Rerank(self, request, context):
        """Cross-encoder rerank (reference Rerank RPC, grpc-server.cpp:1466 /
        rerankers backend): each document scored by the LM's conditional
        log-likelihood given the query — query+document attend jointly
        (engine/embedder.py CrossScorer), not bi-encoder cosine."""
        if self.scorer is None:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                          "model loaded without embeddings=true")
        if not request.query or not request.documents:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          "query and documents required")
        q_ids = self.tok.encode(request.query)
        d_ids = [self.tok.encode(d, add_bos=False)
                 for d in request.documents]
        try:
            sims = self.scorer.score(q_ids, d_ids)
        except ValueError as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        order = sims.argsort()[::-1]
        top_n = request.top_n or len(order)
        resp = pb.RerankResult()
        for i in order[:top_n]:
            resp.results.append(pb.RerankedDocument(
                index=int(i), text=request.documents[int(i)],
                relevance_score=float(sims[int(i)])))
        return resp

    def Status(self, request, context):
        with telemetry.span("grpc.Status", cat="grpc"):
            rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
            return pb.StatusResponse(
                state=self._state,
                memory=pb.MemoryUsageData(total=rss,
                                          breakdown={"rss_peak": rss}),
                device_json=json.dumps(self._device_report()),
            )

    def _device_report(self) -> dict:
        """The device this process holds and what runs on it — {} until a
        model is loaded (an idle backend has not touched the chip yet)."""
        if self.engine is None and self.embedder is None:
            return {}
        from localai_tpu.system.device import device_report

        rep = device_report()
        rep["model"] = self.model_name
        if self.engine is not None:
            from localai_tpu.parallel.mesh import mesh_shape

            rep["mesh"] = mesh_shape(self.engine.mesh)
            rep["tiers"] = self.engine.kernel_tiers()
            rep["load_seconds"] = self._load_seconds
        return rep

    def GetMetrics(self, request, context):
        with telemetry.span("grpc.GetMetrics", cat="grpc"):
            return self._get_metrics()

    def _get_metrics(self):
        """Host-side counters only: nothing here touches the device."""
        m = self.engine.metrics_snapshot() if self.engine else {}
        # XLA compiles of this process (xla_compiles_total,
        # xla_compile_ms_total, xla_compiles__<jit name>): there from the
        # first scrape, at whatever the load has compiled so far
        m.update(telemetry.compile_counter().flat())
        slo = telemetry.maybe_slo()
        if slo is not None:
            # SLO histograms (hist_<metric>__<path>__{bN,count,sum} +
            # ttft_ms_p50/p95) ride the same surface; the HTTP layer rebuilds
            # true Prometheus histogram series from these at scrape time
            m.update(slo.flat())
        sched = getattr(self.engine, "_sched", None) if self.engine else None
        if sched is not None:
            # tick-ledger counters + any CACHED rooflines (sched_* keys —
            # ISSUE 13); flat() never compiles, so scrapes stay cheap
            m.update(sched.flat())
        return pb.MetricsResponse(metrics={k: float(v) for k, v in m.items()})

    def GetTrace(self, request, context):
        seconds = _metadata(context, XPROF_SECONDS_KEY)
        if seconds:
            # GET /debug/xprof (backend/client.py trace(xprof_seconds=)): a
            # device trace of this process, taken on this handler thread;
            # the reply carries the directory or the error and nothing else
            return pb.Reply(message=json.dumps(
                {"xprof": telemetry.device_trace(float(seconds)),
                 "model": self.model_name}).encode())
        slo = telemetry.maybe_slo()
        payload = {
            "spans": telemetry.chrome_events(),
            # SLO percentile snapshot + flight-recorder dump (ISSUE 11):
            # the /debug/slo and /debug/flightrec lanes across the process
            # boundary, reusing the JSON-in-Reply transport
            "slo": slo.snapshot() if slo is not None else {},
            # scheduler X-ray (ISSUE 13): recent tick records + reason-code
            # counters + per-variant rooflines (the first call pays the
            # per-variant AOT cost-analysis compile, then it's cached)
            "sched": (self.engine.sched_snapshot()
                      if self.engine is not None else {}),
            # host KV tier occupancy (ISSUE 17): /debug/slo's kv_host
            # section; {} unless the engine runs with kv_host_bytes > 0
            "kvhost": (self.engine.kvhost_snapshot()
                       if self.engine is not None else {}),
            "flightrec": telemetry.flightrec().dump(),
            "pid": os.getpid(),
            "model": self.model_name,
        }
        return pb.Reply(message=json.dumps(payload).encode())

    def preempt(self, grace: float = 0.0) -> list[dict]:
        """Spill-drain the engine (ISSUE 19): freeze live slots, spill their
        KV into the host pool, and emit terminal "preempted" replies carrying
        ResumeTokens through the open streams. Returns the resume manifest
        (server.py's SIGTERM fast-path calls this before stopping)."""
        if self.engine is None:
            return []
        return self.engine.preempt(grace)

    def shutdown(self):
        if self.engine is not None:
            self.engine.stop()
