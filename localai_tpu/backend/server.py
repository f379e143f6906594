"""Backend process entrypoint: one gRPC server on a localhost port.

Spawn contract mirrors the reference's backend launch
(`--addr 127.0.0.1:<freeport>`, health-polled by the loader —
/root/reference/pkg/model/initializers.go:57-129): the control plane starts
`python -m localai_tpu.backend --addr ... --backend llm`, polls Health, then
issues LoadModel.
"""
from __future__ import annotations

import signal
import threading
from concurrent import futures

import grpc

from localai_tpu.backend.base import BackendServicer, add_backend_servicer

# role registry — the backend zoo (reference SURVEY §2.2/2.3 rows); roles are
# lazy imports so a store-only process never touches jax.
ROLES = {}


def _role(name):
    def reg(fn):
        ROLES[name] = fn
        return fn

    return reg


@_role("llm")
def _make_llm():
    from localai_tpu.backend.llm import LLMServicer

    return LLMServicer()


@_role("image")
def _make_image():
    from localai_tpu.backend.image import ImageServicer

    return ImageServicer()


@_role("whisper")
def _make_whisper():
    from localai_tpu.backend.whisper import WhisperServicer

    return WhisperServicer()


@_role("tts")
def _make_tts():
    from localai_tpu.backend.whisper import TTSServicer

    return TTSServicer()


@_role("huggingface")
def _make_hfapi():
    from localai_tpu.backend.hfapi import HFApiServicer

    return HFApiServicer()


@_role("detect")
def _make_detect():
    from localai_tpu.backend.detect import DetectServicer

    return DetectServicer()


@_role("store")
def _make_store():
    from localai_tpu.backend.store import StoreServicer

    return StoreServicer()


@_role("base")
def _make_base():
    return BackendServicer()


# A request holds a handler thread until it is answered, a stream for its
# whole life. The HTTP process sends a model's backend at most `limit`
# requests at once, its admission gate's permits: `parallel` (the engine's
# slots) + max(2, parallel // 4) ahead of them, one pump thread a stream
# (`server/http.py:_AdmissionGate`). A `parallel` of 32 opens 40 streams and
# one of 48 opens 60, so 64 handlers serve a `parallel` of up to 48 and leave
# Status, GetMetrics, GetTrace and Health a thread each: with 16,
# /backend/monitor waited seconds behind 16 open streams for a handler that
# then ran for 0.3 ms. A gate of more than 60 permits is capped here, not at
# the gate.
HANDLER_THREADS = 64


def serve(addr: str = "127.0.0.1:50051", backend: str = "llm",
          max_workers: int = HANDLER_THREADS, servicer=None):
    """Start a backend server; returns (grpc.Server, servicer, bound_port).
    `servicer` overrides role construction (multi-host worker preloads one)."""
    if servicer is None:
        if backend not in ROLES:
            raise ValueError(
                f"unknown backend role {backend!r}; have {sorted(ROLES)}")
        servicer = ROLES[backend]()
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=[("grpc.max_receive_message_length", 128 * 1024 * 1024),
                 ("grpc.max_send_message_length", 128 * 1024 * 1024)],
    )
    add_backend_servicer(server, servicer)
    port = server.add_insecure_port(addr)
    if port == 0:
        raise OSError(f"could not bind {addr}")
    server.start()
    return server, servicer, port


def serve_blocking(addr: str = "127.0.0.1:50051", backend: str = "llm",
                   servicer=None) -> int:
    server, servicer, port = serve(addr, backend, servicer=servicer)
    print(f"backend[{backend}] serving on port {port}", flush=True)
    stop = threading.Event()

    def _preempt_then_stop():
        # preemption fast-path (ISSUE 19): spill-drain live slots so their
        # terminal "preempted" replies (carrying ResumeTokens) flush through
        # the still-open streams, THEN stop. The drain runs off the signal
        # handler thread — engine.preempt blocks until the freeze completes.
        import os

        try:
            grace = float(os.environ.get("LOCALAI_PREEMPT_GRACE", "0") or 0)
            servicer.preempt(grace)
        except Exception:
            import traceback

            traceback.print_exc()
        finally:
            stop.set()

    def _sig(signum, frame):
        if signum == signal.SIGTERM and hasattr(servicer, "preempt"):
            threading.Thread(target=_preempt_then_stop, daemon=True).start()
        else:
            stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    stop.wait()
    if hasattr(servicer, "shutdown"):
        servicer.shutdown()
    server.stop(grace=5).wait(10)
    return 0


def serve_preloaded(addr: str, servicer) -> int:
    """Serve an already-constructed servicer (multi-host worker rank 0)."""
    return serve_blocking(addr, backend="worker", servicer=servicer)
