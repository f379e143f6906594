"""MCP client + /mcp/v1/chat/completions agent loop + /v1/edits."""
import json
import os
import sys
import threading

import pytest

SERVER = os.path.join(os.path.dirname(__file__), "mcp_test_server.py")


def test_stdio_session_tools_and_call(tmp_path):
    from localai_tpu.mcp import MCPSession, _StdioTransport

    log = str(tmp_path / "calls.jsonl")
    s = MCPSession("calc", _StdioTransport(f"{sys.executable} {SERVER} {log}"))
    try:
        assert [t["name"] for t in s.tools] == ["add"]
        out = s.call_tool("add", {"a": 2, "b": 40})
        assert out == "42"
        rec = json.loads(open(log).read().strip())
        assert rec["name"] == "add" and rec["arguments"] == {"a": 2, "b": 40}
    finally:
        s.close()


def test_http_session(tmp_path):
    """HTTP transport against an in-process JSON-RPC endpoint."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    from localai_tpu.mcp import MCPSession, _HttpTransport

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            body = json.loads(self.rfile.read(
                int(self.headers["Content-Length"])))
            if "id" not in body:
                self.send_response(202)
                self.end_headers()
                return
            method = body["method"]
            if method == "initialize":
                result = {"protocolVersion": "2024-11-05"}
            elif method == "tools/list":
                result = {"tools": [{"name": "echo",
                                     "inputSchema": {"type": "object"}}]}
            else:
                result = {"content": [{
                    "type": "text",
                    "text": body["params"]["arguments"].get("msg", "")}]}
            out = json.dumps({"jsonrpc": "2.0", "id": body["id"],
                              "result": result}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.end_headers()
            self.wfile.write(out)

    srv = HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        s = MCPSession("remote", _HttpTransport(
            f"http://127.0.0.1:{srv.server_address[1]}/mcp"))
        assert s.tools[0]["name"] == "echo"
        assert s.call_tool("echo", {"msg": "hi"}) == "hi"
    finally:
        srv.shutdown()


def test_tools_as_openai(tmp_path):
    from localai_tpu.mcp import (
        MCPSession, _StdioTransport, tools_as_openai,
    )

    s = MCPSession("calc", _StdioTransport(f"{sys.executable} {SERVER}"))
    try:
        tools, owner = tools_as_openai([s])
        assert tools[0]["function"]["name"] == "add"
        assert owner["add"] is s
    finally:
        s.close()


@pytest.fixture(scope="module")
def mcp_stack(tmp_path_factory):
    """Full API stack: tiny llm model configured with a stdio MCP server."""
    import asyncio
    import socket
    import time

    import requests
    import yaml
    from aiohttp import web

    sys.path.insert(0, os.path.dirname(__file__))
    from fixtures import tiny_checkpoint

    from localai_tpu.config import AppConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models")
    call_log = str(tmp_path_factory.mktemp("mcp") / "calls.jsonl")
    (models / "tiny.yaml").write_text(yaml.safe_dump({
        "name": "tiny", "backend": "llm", "context_size": 128,
        "parallel": 2, "dtype": "float32", "prefill_buckets": [32, 64],
        "parameters": {"model": ckpt, "temperature": 0.0, "max_tokens": 16},
        "mcp": {"stdio": [{
            "name": "calc",
            "command": f"{sys.executable} {SERVER} {call_log}"}]},
        "agent": {"max_iterations": 2},
    }))
    os.environ["JAX_PLATFORMS"] = "cpu"
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    app_cfg = AppConfig(address=f"127.0.0.1:{port}",
                        models_path=str(models), parallel_requests=2)
    manager = ModelManager(app_cfg)
    api = API(app_cfg, ModelConfigLoader(str(models)), manager)
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(api.app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    base = f"http://127.0.0.1:{port}"
    for _ in range(50):
        try:
            requests.get(base + "/healthz", timeout=1)
            break
        except requests.ConnectionError:
            time.sleep(0.1)
    yield base, call_log
    manager.stop_all()
    loop.call_soon_threadsafe(loop.stop)


def test_mcp_chat_executes_tools(mcp_stack):
    """The agent loop must produce at least one real MCP tools/call (the
    grammar forces the random model into a valid call on round 1) and return
    a normal chat completion."""
    import requests

    base, call_log = mcp_stack
    r = requests.post(base + "/mcp/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "add 2 and 3"}],
        "max_tokens": 24,
    }, timeout=600)
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["choices"][0]["message"]["role"] == "assistant"
    assert os.path.exists(call_log)
    calls = [json.loads(l) for l in open(call_log) if l.strip()]
    assert len(calls) >= 1
    assert calls[0]["name"] == "add"


def test_mcp_chat_requires_config(mcp_stack):
    import requests

    base, _ = mcp_stack
    r = requests.post(base + "/mcp/v1/chat/completions", json={
        "model": "definitely-not-there",
        "messages": [{"role": "user", "content": "x"}]}, timeout=30)
    assert r.status_code == 404


def test_edits_endpoint(mcp_stack):
    import requests

    base, _ = mcp_stack
    r = requests.post(base + "/v1/edits", json={
        "model": "tiny",
        "instruction": "capitalize everything",
        "input": "hello",
        "max_tokens": 8,
    }, timeout=600)
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["object"] == "edit"
    assert len(body["choices"]) == 1
    assert "text" in body["choices"][0]
