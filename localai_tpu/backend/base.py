"""Base servicer + descriptor-driven gRPC registration.

grpc_tools isn't in this image, so instead of generated service stubs the
handlers are derived from the proto DESCRIPTOR at runtime — same wire format,
no codegen. The Base class returns UNIMPLEMENTED for every RPC so each backend
role overrides only what it supports (the capability-negotiation idiom,
reference /root/reference/pkg/grpc/base/base.go:16-124).
"""
from __future__ import annotations

import grpc

from localai_tpu.backend import pb


def _unimplemented(name):
    def handler(self, request, context):
        context.abort(grpc.StatusCode.UNIMPLEMENTED,
                      f"{name} not implemented by this backend")

    handler.__name__ = name
    return handler


class BackendServicer:
    """Override the RPCs your backend supports; the rest stay UNIMPLEMENTED."""

    def Health(self, request, context):
        return pb.Reply(message=b"OK")

    def GetTrace(self, request, context):
        """Telemetry export (every role): this process's recorded spans as
        Chrome-trace events in Reply.message JSON. The llm role overrides it
        to add its SLO, scheduler and flight-recorder snapshots."""
        import json
        import os

        from localai_tpu import telemetry

        return pb.Reply(message=json.dumps({
            "spans": telemetry.chrome_events(),
            "profile": {},
            "pid": os.getpid(),
        }).encode())


for _m in pb.SERVICE.methods:
    if not hasattr(BackendServicer, _m.name):
        setattr(BackendServicer, _m.name, _unimplemented(_m.name))


def add_backend_servicer(server: grpc.Server, servicer: BackendServicer):
    """Register `servicer` under the Backend service using generic handlers."""
    sym = pb._pb2  # message classes by name

    handlers = {}
    for m in pb.SERVICE.methods:
        req_cls = getattr(sym, m.input_type.name)
        resp_cls = getattr(sym, m.output_type.name)
        fn = getattr(servicer, m.name)
        make = (grpc.unary_stream_rpc_method_handler if m.server_streaming
                else grpc.unary_unary_rpc_method_handler)
        handlers[m.name] = make(
            fn,
            request_deserializer=req_cls.FromString,
            response_serializer=resp_cls.SerializeToString,
        )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(pb.SERVICE_NAME, handlers),)
    )
