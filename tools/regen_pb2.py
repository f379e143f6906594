"""Regenerate localai_tpu/backend/backend_pb2.py WITHOUT protoc.

grpc_tools/protoc are not in this image (the pb2 module is a checked-in
artifact), so schema changes go through this script instead: it parses the
current module's serialized FileDescriptorProto, applies the edits declared
in EDITS below, and rewrites the generated file — same builder scaffolding,
offsets recomputed by locating each descriptor's bytes in the new blob.

Run from the repo root:  python tools/regen_pb2.py

`--check` (the proto-drift CI gate) rebuilds the CANONICAL file from the
parsed descriptor and compares byte-for-byte without writing: any hand edit
to the scaffolding, the offsets, or the descriptor blob (which desyncs the
recomputed _serialized_start/_end) exits 1.
"""
from __future__ import annotations

import os
import re
import sys

from google.protobuf import descriptor_pb2

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PB2 = os.path.join(ROOT, "localai_tpu", "backend", "backend_pb2.py")

# (message, field name, field number, type) — applied only when missing
EDITS = [
    ("PredictOptions", "tools_json", 26,
     descriptor_pb2.FieldDescriptorProto.TYPE_STRING),
    # remaining request-deadline budget in ms (ISSUE 4): HTTP middleware →
    # gRPC client → engine, so an expired slot is evicted instead of decoded
    ("PredictOptions", "deadline_ms", 27,
     descriptor_pb2.FieldDescriptorProto.TYPE_INT64),
    # per-request phase timeline (ISSUE 11): JSON blob on the FINAL
    # Predict/PredictStream reply only — engine StepOutput.timings → the
    # llama.cpp-style `timings` block in the last SSE chunk
    ("Reply", "timings_json", 9,
     descriptor_pb2.FieldDescriptorProto.TYPE_STRING),
    # preemption-safe serving (ISSUE 19): a resume request carries its
    # ResumeToken here (prompt+emitted resubmit with RNG/dedup fixups)...
    ("PredictOptions", "resume_json", 28,
     descriptor_pb2.FieldDescriptorProto.TYPE_STRING),
    # ...and streamed replies carry checkpoints back: the FIRST chunk a
    # minimal {"v","prompt_ids"} (deterministic-replay fallback), the
    # terminal "preempted" chunk the full spill-drain token
    ("Reply", "resume_json", 10,
     descriptor_pb2.FieldDescriptorProto.TYPE_STRING),
    # the device a backend holds, as JAX reports it to that process
    # (system/device.py) — the control plane never imports JAX (ISSUE 21)
    ("StatusResponse", "device_json", 3,
     descriptor_pb2.FieldDescriptorProto.TYPE_STRING),
]

# (method name, input message, output message, server_streaming) — added to
# the Backend service when missing (reuses existing messages: a new RPC needs
# no new types as long as its payload fits one — GetTrace ships spans JSON in
# Reply.message bytes)
SERVICE_EDITS = [
    ("GetTrace", "MetricsRequest", "Reply", False),
]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    check = "--check" in argv
    src = open(PB2).read()
    m = re.search(rb"AddSerializedFile\(b'(.*)'\)",
                  src.encode(), re.DOTALL)
    if not m:
        print("could not find serialized descriptor", file=sys.stderr)
        return 1
    blob = m.group(1).decode("unicode_escape").encode("latin-1")
    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.ParseFromString(blob)

    changed = False
    for msg_name, fname, fnum, ftype in EDITS:
        msg = next(t for t in fdp.message_type if t.name == msg_name)
        if any(f.name == fname for f in msg.field):
            continue
        f = msg.field.add()
        f.name = fname
        f.number = fnum
        f.label = descriptor_pb2.FieldDescriptorProto.LABEL_OPTIONAL
        f.type = ftype
        changed = True
    for svc in fdp.service:
        for mname, in_msg, out_msg, streaming in SERVICE_EDITS:
            if any(m.name == mname for m in svc.method):
                continue
            meth = svc.method.add()
            meth.name = mname
            meth.input_type = f".{fdp.package}.{in_msg}"
            meth.output_type = f".{fdp.package}.{out_msg}"
            if streaming:
                meth.server_streaming = True
            changed = True
    if not changed and not check:
        print("nothing to do")
        return 0

    blob = fdp.SerializeToString()

    def esc(b: bytes) -> str:
        out = []
        for ch in b:
            c = chr(ch)
            if c == "'":
                out.append("\\'")
            elif c == "\\":
                out.append("\\\\")
            elif 0x20 <= ch < 0x7F:
                out.append(c)
            elif c == "\n":
                out.append("\\n")
            elif c == "\t":
                out.append("\\t")
            elif c == "\r":
                out.append("\\r")
            else:
                out.append(f"\\x{ch:02x}")
        return "".join(out)

    # offsets: each descriptor's serialized bytes located in the file blob
    # (what protoc's _serialized_start/_end record)
    offsets = []

    def walk(prefix, messages):
        for t in messages:
            sub = t.SerializeToString()
            start = blob.find(sub)
            name = (prefix + "_" + t.name).upper()
            offsets.append((name, start, start + len(sub)))
            walk(prefix + "_" + t.name, t.nested_type)

    walk("", fdp.message_type)
    for s in fdp.service:
        sub = s.SerializeToString()
        start = blob.find(sub)
        offsets.append(("_" + s.name.upper(), start, start + len(sub)))

    lines = [
        "# -*- coding: utf-8 -*-",
        "# Generated by the protocol buffer compiler.  DO NOT EDIT!",
        "# source: backend.proto",
        "# (regenerated by tools/regen_pb2.py — no protoc in this image)",
        '"""Generated protocol buffer code."""',
        "from google.protobuf.internal import builder as _builder",
        "from google.protobuf import descriptor as _descriptor",
        "from google.protobuf import descriptor_pool as _descriptor_pool",
        "from google.protobuf import symbol_database as _symbol_database",
        "# @@protoc_insertion_point(imports)",
        "",
        "_sym_db = _symbol_database.Default()",
        "",
        "",
        "",
        "",
        "DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'"
        + esc(blob) + "')",
        "",
        "_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())",
        "_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'backend_pb2',"
        " globals())",
        "if _descriptor._USE_C_DESCRIPTORS == False:",
        "",
        "  DESCRIPTOR._options = None",
        "  _PREDICTOPTIONS_LOGITBIASENTRY._options = None",
        "  _PREDICTOPTIONS_LOGITBIASENTRY._serialized_options = b'8\\001'",
        "  _METRICSRESPONSE_METRICSENTRY._options = None",
        "  _METRICSRESPONSE_METRICSENTRY._serialized_options = b'8\\001'",
        "  _MEMORYUSAGEDATA_BREAKDOWNENTRY._options = None",
        "  _MEMORYUSAGEDATA_BREAKDOWNENTRY._serialized_options = b'8\\001'",
    ]
    for name, start, end in offsets:
        if start < 0:
            continue
        lines.append(f"  {name}._serialized_start={start}")
        lines.append(f"  {name}._serialized_end={end}")
    # enums nested in messages (StatusResponse.State)
    for t in fdp.message_type:
        for e in t.enum_type:
            sub = e.SerializeToString()
            start = blob.find(sub)
            if start >= 0:
                n = f"_{t.name}_{e.name}".upper()
                lines.append(f"  {n}._serialized_start={start}")
                lines.append(f"  {n}._serialized_end={start + len(sub)}")
    lines.append("# @@protoc_insertion_point(module_scope)")
    new_src = "\n".join(lines) + "\n"
    if check:
        if new_src != src or changed:
            print("backend_pb2.py drifts from the canonical generator "
                  "output — hand-edited, or a declared EDIT is missing. "
                  "Run `python tools/regen_pb2.py` and commit the result; "
                  "never edit the generated file.", file=sys.stderr)
            return 1
        print("backend_pb2.py is canonical")
        return 0
    with open(PB2, "w") as fh:
        fh.write(new_src)
    print(f"wrote {PB2} ({len(blob)} descriptor bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
