"""Typed gRPC client for the Backend contract — the control-plane side
(reference: /root/reference/pkg/grpc/client.go:53-519, one wrapper per RPC,
plus spawn-time health polling initializers.go:110-129).

No generated stubs (no grpc_tools in image): callables are derived from the
proto DESCRIPTOR, same wire format.
"""
from __future__ import annotations

import json
import time
from typing import Iterator

import grpc

from localai_tpu import telemetry
from localai_tpu.backend import pb
from localai_tpu.core import resilience

# gRPC metadata key carrying the HTTP request id into the backend process
# (server/http.py middleware → here → backend/llm.py → GenRequest.trace_id)
REQUEST_ID_KEY = "x-localai-request-id"
# gRPC metadata key that turns a GetTrace call into a device trace of the
# backend process for that many seconds (GET /debug/xprof)
XPROF_SECONDS_KEY = "x-localai-xprof-seconds"


def _trace_md():
    """Metadata tuple propagating the current context's request id (None
    when no request id is bound — the common non-traced path)."""
    rid = telemetry.current_request_id()
    return ((REQUEST_ID_KEY, rid),) if rid else None


class BackendClient:
    def __init__(self, addr: str):
        self.addr = addr
        # match the server's raised caps (server.py): a batched embedding
        # reply (256 × 4096 f32) exceeds gRPC's 4MB default
        self._channel = grpc.insecure_channel(addr, options=[
            ("grpc.max_receive_message_length", 128 * 1024 * 1024),
            ("grpc.max_send_message_length", 128 * 1024 * 1024),
            # spawn-time poll: the first connects race the child's bind, and
            # gRPC's default reconnect backoff then grows toward minutes —
            # longer than the whole health budget. Cap it; this channel only
            # ever talks to a subprocess on loopback.
            ("grpc.initial_reconnect_backoff_ms", 250),
            ("grpc.min_reconnect_backoff_ms", 250),
            ("grpc.max_reconnect_backoff_ms", 2000),
        ])
        self._calls = {}
        self._req_cls = {}
        sym = pb._pb2
        for m in pb.SERVICE.methods:
            req_cls = getattr(sym, m.input_type.name)
            resp_cls = getattr(sym, m.output_type.name)
            make = (self._channel.unary_stream if m.server_streaming
                    else self._channel.unary_unary)
            self._req_cls[m.name] = req_cls
            self._calls[m.name] = make(
                f"/{pb.SERVICE_NAME}/{m.name}",
                request_serializer=req_cls.SerializeToString,
                response_deserializer=resp_cls.FromString,
            )

    def close(self):
        self._channel.close()

    # ---------------------------------------------------- deadline plumbing

    @staticmethod
    def _timeout(default: float) -> float:
        """Shrink an RPC timeout to the current request's remaining deadline
        budget (core/resilience contextvar, minted by the HTTP middleware —
        asyncio.to_thread copies the context into worker threads)."""
        rem = resilience.deadline_remaining()
        if rem is None:
            return default
        return max(min(default, rem), 0.001)

    def _request(self, method: str, kw: dict):
        """Build the request message; PredictOptions additionally carries the
        remaining deadline in-band (deadline_ms) so the ENGINE can evict an
        expired slot instead of decoding tokens nobody will read."""
        cls = self._req_cls[method]
        if cls is pb.PredictOptions and "deadline_ms" not in kw:
            rem = resilience.deadline_remaining()
            if rem is not None:
                kw["deadline_ms"] = max(int(rem * 1000), 1)
        return cls(**kw)

    def start(self, method: str, timeout: float = 600.0, **kw):
        """Begin a unary RPC and return its grpc Future — the cancellable
        form the HTTP layer uses so a client disconnect can abort the call
        (`fut.cancel()`) the way `call.cancel()` already works for streams."""
        fut = self._calls[method].future(
            self._request(method, kw), timeout=self._timeout(timeout),
            metadata=_trace_md())
        tr = telemetry.maybe_tracer()
        if tr is not None:
            # same rpc.<Method> span the blocking wrappers record, closed
            # when the future settles (completion, error, or cancel). The
            # request id is captured HERE — the done callback runs on a gRPC
            # thread without this request's contextvars.
            args = {"addr": self.addr}
            rid = telemetry.current_request_id()
            if rid:
                args["request_id"] = rid
            s = tr.begin(f"rpc.{method}", cat="rpc", args=args)
            fut.add_done_callback(lambda _f: tr.finish(s))
        return fut

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------ health

    def health(self, timeout: float = 5.0, wait: bool = False) -> bool:
        try:
            r = self._calls["Health"](pb.HealthMessage(), timeout=timeout,
                                      wait_for_ready=wait)
            return r.message == b"OK"
        except grpc.RpcError:
            return False

    def wait_ready(self, attempts: int = 60, sleep: float = 0.5) -> bool:
        """Spawn-time health poll (reference initializers.go:110-129).
        wait_for_ready queues the RPC until the channel connects (instead of
        failing fast from backoff state), so a slow child startup costs one
        deadline, not the whole budget."""
        for _ in range(attempts):
            if self.health(timeout=2.0, wait=True):
                return True
            time.sleep(sleep)
        return False

    # ------------------------------------------------------------ RPCs

    def load_model(self, timeout: float = 600.0, **kw) -> "pb.Result":
        return self._calls["LoadModel"](pb.ModelOptions(**kw), timeout=timeout)

    def predict(self, timeout: float = 600.0, **kw) -> "pb.Reply":
        with telemetry.span("rpc.Predict", cat="rpc", addr=self.addr):
            return self._calls["Predict"](self._request("Predict", kw),
                                          timeout=self._timeout(timeout),
                                          metadata=_trace_md())

    def predict_stream(self, timeout: float = 600.0, **kw) -> Iterator["pb.Reply"]:
        # the span covers only the stream OPEN — iteration happens on the
        # caller's pump thread; the backend-side grpc.PredictStream span
        # carries the full generation interval
        with telemetry.span("rpc.PredictStream.open", cat="rpc",
                            addr=self.addr):
            return self._calls["PredictStream"](
                self._request("PredictStream", kw),
                timeout=self._timeout(timeout),
                metadata=_trace_md())

    def embedding(self, timeout: float = 600.0, **kw) -> "pb.EmbeddingResult":
        with telemetry.span("rpc.Embedding", cat="rpc", addr=self.addr):
            return self._calls["Embedding"](self._request("Embedding", kw),
                                            timeout=self._timeout(timeout),
                                            metadata=_trace_md())

    def tokenize(self, prompt: str, timeout: float = 60.0) -> "pb.TokenizationResponse":
        return self._calls["TokenizeString"](pb.PredictOptions(prompt=prompt),
                                             timeout=timeout)

    def rerank(self, timeout: float = 600.0, **kw) -> "pb.RerankResult":
        return self._calls["Rerank"](pb.RerankRequest(**kw), timeout=timeout)

    # the two halves of /backend/monitor: each has a span on this side
    # (rpc.*) and one in the backend's handler (grpc.*); what the first
    # holds beyond the second is the wait for a handler thread

    def status(self, timeout: float = 10.0) -> "pb.StatusResponse":
        with telemetry.span("rpc.Status", cat="rpc", addr=self.addr):
            return self._calls["Status"](pb.HealthMessage(), timeout=timeout)

    def metrics(self, timeout: float = 10.0) -> dict:
        with telemetry.span("rpc.GetMetrics", cat="rpc", addr=self.addr):
            r = self._calls["GetMetrics"](pb.MetricsRequest(),
                                          timeout=timeout)
            return dict(r.metrics)

    def trace(self, timeout: float = 30.0,
              xprof_seconds: float | None = None) -> dict:
        """Backend telemetry snapshot: {"spans": [chrome events], "slo",
        "sched", "flightrec", "pid": N} (GetTrace RPC). With
        `xprof_seconds` the backend instead profiles itself for that long
        and answers {"xprof": {"dir", "xplane", ...} or {"error"}}; give a
        `timeout` that covers the seconds and the profiler's stop."""
        md = (None if xprof_seconds is None
              else ((XPROF_SECONDS_KEY, repr(float(xprof_seconds))),))
        r = self._calls["GetTrace"](pb.MetricsRequest(), timeout=timeout,
                                    metadata=md)
        return json.loads(r.message.decode() or "{}")

    def tts(self, timeout: float = 600.0, **kw) -> "pb.Result":
        return self._calls["TTS"](pb.TTSRequest(**kw), timeout=timeout)

    def sound_generation(self, timeout: float = 600.0, **kw) -> "pb.Result":
        return self._calls["SoundGeneration"](
            pb.SoundGenerationRequest(**kw), timeout=timeout)

    def transcribe(self, timeout: float = 600.0, **kw) -> "pb.TranscriptResult":
        return self._calls["AudioTranscription"](pb.TranscriptRequest(**kw),
                                                 timeout=timeout)

    def vad(self, audio, timeout: float = 600.0) -> "pb.VADResponse":
        return self._calls["VAD"](pb.VADRequest(audio=audio), timeout=timeout)

    def generate_image(self, timeout: float = 600.0, **kw) -> "pb.Result":
        return self._calls["GenerateImage"](pb.GenerateImageRequest(**kw),
                                            timeout=timeout)

    def generate_video(self, timeout: float = 600.0, **kw) -> "pb.Result":
        return self._calls["GenerateVideo"](pb.GenerateVideoRequest(**kw),
                                            timeout=timeout)

    def detect(self, src: str, timeout: float = 600.0) -> "pb.DetectResponse":
        return self._calls["Detect"](pb.DetectOptions(src=src),
                                     timeout=timeout)

    def stores_set(self, keys, values, timeout: float = 60.0) -> "pb.Result":
        return self._calls["StoresSet"](pb.StoresSetOptions(
            keys=[pb.StoresKey(floats=k) for k in keys],
            values=[pb.StoresValue(bytes=v) for v in values]), timeout=timeout)

    def stores_get(self, keys, timeout: float = 60.0) -> "pb.StoresGetResult":
        return self._calls["StoresGet"](pb.StoresGetOptions(
            keys=[pb.StoresKey(floats=k) for k in keys]), timeout=timeout)

    def stores_delete(self, keys, timeout: float = 60.0) -> "pb.Result":
        return self._calls["StoresDelete"](pb.StoresDeleteOptions(
            keys=[pb.StoresKey(floats=k) for k in keys]), timeout=timeout)

    def stores_find(self, key, top_k: int, timeout: float = 60.0) -> "pb.StoresFindResult":
        return self._calls["StoresFind"](pb.StoresFindOptions(
            key=pb.StoresKey(floats=key), top_k=top_k), timeout=timeout)
