"""HTTP integration tests — the reference's app_test.go tier (SURVEY §4):
a REAL server (aiohttp in a thread), REAL backend subprocesses via the
ModelManager, driven over the wire with `requests`.
"""
import asyncio
import json
import os
import signal
import threading
import time

import numpy as np
import pytest
import requests
import yaml

from fixtures import tiny_checkpoint


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.fixture(scope="module")
def stack(tmp_path_factory):
    """models dir + config loader + manager + API server on a real port."""
    from aiohttp import web

    from localai_tpu.config import AppConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    ckpt = tiny_checkpoint(tmp_path_factory)
    models = tmp_path_factory.mktemp("models")

    # tiny whisper for the realtime transcription pipeline
    import torch
    from transformers import WhisperConfig, WhisperForConditionalGeneration

    wdir = str(tmp_path_factory.mktemp("whisper-ckpt"))
    torch.manual_seed(0)
    wcfg = WhisperConfig(
        vocab_size=51865, d_model=64, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=128, decoder_ffn_dim=128, num_mel_bins=80,
        max_source_positions=1500, max_target_positions=64)
    wm = WhisperForConditionalGeneration(wcfg)
    wm.generation_config.forced_decoder_ids = None
    wm.generation_config.suppress_tokens = None
    wm.generation_config.begin_suppress_tokens = None
    wm.save_pretrained(wdir, safe_serialization=True)
    (models / "whisper-tiny.yaml").write_text(yaml.safe_dump({
        "name": "whisper-tiny",
        "backend": "whisper",
        "parameters": {"model": wdir},
    }))

    (models / "tiny.yaml").write_text(yaml.safe_dump({
        "name": "tiny",
        "backend": "llm",
        "context_size": 128,
        "parallel": 2,
        "dtype": "float32",
        "embeddings": True,
        "prefill_buckets": [32, 64],
        "parameters": {
            "model": ckpt,
            "temperature": 0.0,
            "max_tokens": 8,
        },
        "pipeline": {
            "llm": "tiny",
            "tts": "default-tts",
            "transcription": "whisper-tiny",
        },
    }))

    os.environ["JAX_PLATFORMS"] = "cpu"
    port = _free_port()
    app_cfg = AppConfig(address=f"127.0.0.1:{port}",
                        models_path=str(models), parallel_requests=2)
    configs = ModelConfigLoader(str(models))
    manager = ModelManager(app_cfg)
    api = API(app_cfg, configs, manager)

    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(api.app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{port}"
    for _ in range(50):
        try:
            requests.get(base + "/healthz", timeout=1)
            break
        except requests.ConnectionError:
            time.sleep(0.1)
    yield base, manager
    manager.stop_all()
    loop.call_soon_threadsafe(loop.stop)


def test_models_list(stack):
    base, _ = stack
    r = requests.get(base + "/v1/models", timeout=10)
    assert r.status_code == 200
    assert sorted(m["id"] for m in r.json()["data"]) == ["tiny",
                                                         "whisper-tiny"]


def test_chat_nonstream(stack):
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 6,
    }, timeout=300)
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["object"] == "chat.completion"
    assert body["choices"][0]["message"]["role"] == "assistant"
    assert body["usage"]["completion_tokens"] == 6
    assert body["choices"][0]["finish_reason"] in ("length", "stop", "eos")


def test_chat_extra_usage_header(stack):
    """Extra-Usage request header (reference chat.go:47-50,191) merges the
    in-band timings into `usage`, llama.cpp field names in ms."""
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 4,
    }, headers={"Extra-Usage": "1"}, timeout=300)
    assert r.status_code == 200, r.text
    u = r.json()["usage"]
    assert u["timing_token_generation"] > 0
    assert "timing_prompt_processing" in u
    # completions endpoint honors it too (reference completion.go:74)
    rc = requests.post(base + "/v1/completions", json={
        "model": "tiny", "prompt": "hello", "max_tokens": 4,
    }, headers={"Extra-Usage": "1"}, timeout=300)
    assert "timing_token_generation" in rc.json()["usage"]
    # empty header value = disabled, matching the reference predicate
    r0 = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 4,
    }, headers={"Extra-Usage": ""}, timeout=300)
    assert "timing_token_generation" not in r0.json()["usage"]
    # absent header → plain OpenAI usage
    r2 = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 4,
    }, timeout=300)
    assert "timing_token_generation" not in r2.json()["usage"]


def test_chat_stream_sse(stack):
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "the quick"}],
        "max_tokens": 5,
        "stream": True,
    }, stream=True, timeout=300)
    assert r.status_code == 200
    assert r.headers["Content-Type"].startswith("text/event-stream")
    events = []
    for line in r.iter_lines():
        if line.startswith(b"data: "):
            payload = line[6:]
            if payload == b"[DONE]":
                events.append("DONE")
            else:
                events.append(json.loads(payload))
    assert events[-1] == "DONE"
    chunks = [e for e in events if e != "DONE"]
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    assert any(c["choices"] and c["choices"][0]["delta"].get("content")
               for c in chunks)
    finals = [c for c in chunks
              if c["choices"] and c["choices"][0]["finish_reason"]]
    assert finals, "missing finish_reason chunk"
    assert chunks[-1].get("usage", {}).get("completion_tokens") == 5


def test_completions(stack):
    base, _ = stack
    r = requests.post(base + "/v1/completions", json={
        "model": "tiny", "prompt": "pack my box", "max_tokens": 4,
    }, timeout=300)
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["object"] == "text_completion"
    assert body["usage"]["completion_tokens"] == 4


def test_embeddings_endpoint(stack):
    base, _ = stack
    r = requests.post(base + "/v1/embeddings", json={
        "model": "tiny",
        "input": ["the quick brown fox", "the quick brown foxes", "zzz 123"],
    }, timeout=300)
    assert r.status_code == 200, r.text
    data = r.json()["data"]
    v = [np.array(d["embedding"]) for d in data]
    assert all(abs(np.linalg.norm(x) - 1.0) < 1e-5 for x in v)
    assert float(v[0] @ v[1]) > float(v[0] @ v[2])


def test_tokenize_endpoint(stack):
    base, _ = stack
    r = requests.post(base + "/v1/tokenize", json={
        "model": "tiny", "content": "hello world"}, timeout=60)
    assert r.status_code == 200
    assert len(r.json()["tokens"]) > 0


def test_unknown_model_404(stack):
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "nope", "messages": [{"role": "user", "content": "x"}],
    }, timeout=30)
    assert r.status_code == 404


def test_backend_monitor(stack):
    base, _ = stack
    r = requests.get(base + "/backend/monitor", timeout=60)
    assert r.status_code == 200
    assert r.json()["tiny"]["state"] == 2  # READY


def test_metrics_endpoint(stack):
    base, _ = stack
    r = requests.get(base + "/metrics", timeout=10)
    assert r.status_code == 200
    assert b"localai_api_calls_total" in r.content


def test_tts_and_vad_http(stack):
    """/v1/audio/speech (implicit tts backend) returns WAV; /vad segments."""
    import io
    import wave

    base, _ = stack
    r = requests.post(base + "/v1/audio/speech", json={
        "input": "hello", "voice": "default"}, timeout=120)
    assert r.status_code == 200, r.text
    assert r.headers["Content-Type"].startswith("audio/wav")
    with wave.open(io.BytesIO(r.content)) as w:
        assert w.getframerate() == 16000
        assert w.getnframes() > 1000

    from localai_tpu.audio.tts import synthesize

    rate = 16000
    speech = synthesize("good morning everyone", voice="default",
                        language="en").astype(np.float32)[: rate]
    silence = 0.001 * np.random.default_rng(0).normal(size=rate)
    audio = np.concatenate([silence, speech, silence]).astype(np.float32)
    r = requests.post(base + "/vad", json={"audio": audio.tolist()},
                      timeout=120)
    assert r.status_code == 200
    segs = r.json()["segments"]
    assert len(segs) >= 1 and 0.6 < segs[0]["start"] < 1.4


def test_webui_served(stack):
    """GET / serves the built-in chat UI (reference routes/ui.go role)."""
    base, _ = stack
    r = requests.get(base + "/", timeout=30)
    assert r.status_code == 200
    assert r.headers["Content-Type"].startswith("text/html")
    assert "/v1/chat/completions" in r.text
    assert "/v1/models" in r.text


def test_elevenlabs_tts_route(stack):
    """elevenlabs-shaped /v1/text-to-speech/{voice_id} returns WAV
    (reference routes/elevenlabs.go)."""
    import io
    import wave

    base, _ = stack
    r = requests.post(base + "/v1/text-to-speech/premade-voice", json={
        "text": "hello there"}, timeout=120)
    assert r.status_code == 200, r.text
    assert r.headers["Content-Type"].startswith("audio/wav")
    with wave.open(io.BytesIO(r.content)) as w:
        assert w.getnframes() > 1000


def test_stores_http_roundtrip(stack):
    """/stores/* endpoints spawn an implicit store backend on demand."""
    base, _ = stack
    keys = [[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]]
    r = requests.post(base + "/stores/set", json={
        "keys": keys, "values": ["alpha", "beta"]}, timeout=120)
    assert r.status_code == 200, r.text
    r = requests.post(base + "/stores/find", json={
        "key": [0.9, 0.1, 0.0], "topk": 2}, timeout=60)
    body = r.json()
    assert body["values"][0] == "alpha"
    assert body["similarities"][0] > body["similarities"][1]
    r = requests.post(base + "/stores/get", json={"keys": keys[:1]},
                      timeout=60)
    assert r.json()["values"] == ["alpha"]
    requests.post(base + "/stores/delete", json={"keys": keys[:1]},
                  timeout=60)
    r = requests.post(base + "/stores/get", json={"keys": keys[:1]},
                      timeout=60)
    assert r.json()["values"] == []


def test_response_format_json_object(stack):
    """response_format=json_object → grammar-enforced valid JSON output even
    from random weights (chat.go:224-258 semantics, enforced on-device)."""
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "emit json"}],
        "max_tokens": 50,
        "temperature": 0.9,
        "seed": 11,
        "response_format": {"type": "json_object"},
    }, timeout=300)
    assert r.status_code == 200, r.text
    content = r.json()["choices"][0]["message"]["content"]
    assert content.startswith("{")
    if r.json()["choices"][0]["finish_reason"] in ("stop", "eos"):
        json.loads(content)


_WEATHER_TOOL = {
    "type": "function",
    "function": {
        "name": "get_weather",
        "description": "Get the weather for a city",
        "parameters": {
            "type": "object",
            "properties": {"city": {"type": "string"}},
            "required": ["city"],
        },
    },
}


def test_tools_returns_tool_calls(stack):
    """OpenAI tools request → grammar-constrained output parsed back into
    message.tool_calls with finish_reason "tool_calls"
    (reference: chat.go:266-312 + pkg/functions/parse.go)."""
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "weather in Paris?"}],
        "max_tokens": 60,
        "temperature": 0.0,
        "tools": [_WEATHER_TOOL],
    }, timeout=300)
    assert r.status_code == 200, r.text
    choice = r.json()["choices"][0]
    # grammar forces {"name": <tool|answer>, "arguments": {...}}; the
    # no-action "answer" alternative (tool_choice auto) unwraps to prose
    # content, and hitting max_tokens mid-object legitimately yields the
    # raw partial text
    if choice["finish_reason"] == "tool_calls":
        msg = choice["message"]
        assert msg["content"] is None
        calls = msg["tool_calls"]
        assert calls and calls[0]["type"] == "function"
        assert calls[0]["function"]["name"] == "get_weather"
        args = json.loads(calls[0]["function"]["arguments"])
        assert isinstance(args, dict)
        assert calls[0]["id"].startswith("call_")
    else:
        assert isinstance(choice["message"]["content"], str)


def test_tools_streaming_tool_call_delta(stack):
    """Streaming tools request buffers the grammar output and emits ONE
    tool_calls delta + finish_reason tool_calls (chat.go:334-449 role)."""
    base, _ = stack
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "weather in Oslo?"}],
        "max_tokens": 60,
        "temperature": 0.0,
        "stream": True,
        "tools": [_WEATHER_TOOL],
    }, stream=True, timeout=300)
    assert r.status_code == 200
    deltas, finishes = [], []
    for line in r.iter_lines():
        if not line or not line.startswith(b"data: "):
            continue
        payload = line[6:]
        if payload == b"[DONE]":
            break
        obj = json.loads(payload)
        for ch in obj.get("choices", []):
            deltas.append(ch.get("delta", {}))
            if ch.get("finish_reason"):
                finishes.append(ch["finish_reason"])
    tool_deltas = [d for d in deltas if d.get("tool_calls")]
    if "tool_calls" in finishes:
        assert len(tool_deltas) == 1
        tc = tool_deltas[0]["tool_calls"][0]
        assert tc["index"] == 0
        assert tc["function"]["name"] == "get_weather"
    else:
        # no-action "answer" (possibly with an empty message) or truncated
        # JSON — either way the stream must have terminated cleanly with a
        # finish chunk, and any buffered text arrives as content deltas
        assert finishes, "stream ended without a finish_reason chunk"


def test_realtime_websocket_text_session(stack):
    """WS session: item.create + response.create → text delta + TTS audio
    delta + done (the reference's realtime pipeline composition)."""
    import base64
    import io
    import wave

    pytest.importorskip("websockets")
    from websockets.sync.client import connect

    base, _ = stack
    url = base.replace("http://", "ws://") + "/v1/realtime?model=tiny"
    with connect(url, open_timeout=30) as ws:
        first = json.loads(ws.recv(timeout=30))
        assert first["type"] == "session.created"
        assert first["session"]["model"] == "tiny"

        ws.send(json.dumps({"type": "conversation.item.create",
                            "item": {"role": "user", "content": "hello"}}))
        assert json.loads(ws.recv(timeout=30))["type"] == \
            "conversation.item.created"

        ws.send(json.dumps({"type": "response.create"}))
        events = {}
        for _ in range(64):
            ev = json.loads(ws.recv(timeout=600))
            events[ev["type"]] = ev
            if ev["type"] == "response.done":
                break
        assert "response.created" in events
        assert "response.text.delta" in events
        assert "response.audio.delta" in events
        assert "response.done" in events
        assert events["response.done"]["status"] == "completed"
        wav_bytes = base64.b64decode(events["response.audio.delta"]["delta"])
        with wave.open(io.BytesIO(wav_bytes)) as w:
            assert w.getnframes() > 0

        # unknown event type surfaces an error event, session stays alive
        ws.send(json.dumps({"type": "bogus.event"}))
        assert json.loads(ws.recv(timeout=30))["type"] == "error"


def test_realtime_response_cancel(stack):
    """response.cancel interrupts an in-flight response: the terminal event
    is response.done with status cancelled (the reference stubs this,
    realtime.go:522 — we implement it)."""
    pytest.importorskip("websockets")
    from websockets.sync.client import connect

    base, _ = stack
    url = base.replace("http://", "ws://") + "/v1/realtime?model=tiny"
    with connect(url, open_timeout=30) as ws:
        assert json.loads(ws.recv(timeout=30))["type"] == "session.created"
        ws.send(json.dumps({"type": "conversation.item.create",
                            "item": {"role": "user", "content": "hi"}}))
        assert json.loads(ws.recv(timeout=30))["type"] == \
            "conversation.item.created"
        ws.send(json.dumps({"type": "response.create"}))
        ws.send(json.dumps({"type": "response.cancel"}))
        status = None
        for _ in range(64):
            ev = json.loads(ws.recv(timeout=600))
            if ev["type"] == "response.done":
                status = ev["status"]
                break
            assert ev["type"] in ("response.created", "response.text.delta",
                                  "response.audio.delta", "error")
        # cancelled when the cancel landed mid-flight; completed only if the
        # tiny model outran the cancel — either way done is terminal
        assert status in ("cancelled", "completed")

        # cancel with nothing active is an error event
        ws.send(json.dumps({"type": "response.cancel"}))
        assert json.loads(ws.recv(timeout=30))["type"] == "error"


def test_realtime_transcription_session(stack):
    """intent=transcription sessions (reference routes/openai.go:21-22,
    realtime.go:67): audio commit yields transcription delta + completed and
    NO response events; response.create is rejected; buffer.clear works."""
    import base64

    pytest.importorskip("websockets")
    from websockets.sync.client import connect

    from localai_tpu.audio.tts import synthesize

    base, _ = stack
    url = (base.replace("http://", "ws://")
           + "/v1/realtime?model=tiny&intent=transcription")
    with connect(url, open_timeout=120) as ws:
        first = json.loads(ws.recv(timeout=120))
        assert first["type"] == "transcription_session.created"
        assert first["session"]["object"] == "realtime.transcription_session"

        # clear path
        ws.send(json.dumps({"type": "input_audio_buffer.append",
                            "audio": base64.b64encode(b"\0\0" * 160).decode()}))
        ws.send(json.dumps({"type": "input_audio_buffer.clear"}))
        assert json.loads(ws.recv(timeout=120))["type"] == \
            "input_audio_buffer.cleared"

        # commit synthesized speech → transcription events only
        pcm = synthesize("hello there how are you", voice="default",
                         language="en")
        i16 = (np.clip(pcm, -1, 1) * 32767).astype(np.int16).tobytes()
        ws.send(json.dumps({"type": "input_audio_buffer.append",
                            "audio": base64.b64encode(i16).decode()}))
        ws.send(json.dumps({"type": "input_audio_buffer.commit"}))
        got = []
        for _ in range(64):
            ev = json.loads(ws.recv(timeout=600))
            got.append(ev["type"])
            if ev["type"] == \
                    "conversation.item.input_audio_transcription.completed":
                break
        assert "input_audio_buffer.committed" in got
        assert not any(t.startswith("response.") for t in got)

        # responses are a conversation-session concept
        ws.send(json.dumps({"type": "response.create"}))
        assert json.loads(ws.recv(timeout=120))["type"] == "error"


def test_realtime_session_factory_routes(stack):
    """POST /v1/realtime/sessions + /v1/realtime/transcription_session mint
    ephemeral session descriptors (reference routes/openai.go:21-22)."""
    base, _ = stack
    r = requests.post(base + "/v1/realtime/sessions",
                      json={"model": "tiny", "voice": "alto"}, timeout=30)
    assert r.status_code == 200
    s = r.json()
    assert s["object"] == "realtime.session"
    assert s["model"] == "tiny" and s["voice"] == "alto"
    assert s["client_secret"]["value"].startswith("ek_")

    r = requests.post(base + "/v1/realtime/transcription_session",
                      json={}, timeout=30)
    assert r.status_code == 200
    assert r.json()["object"] == "realtime.transcription_session"


def test_kill9_backend_recovers(stack):
    """Reference loader.go:191-225 semantics: dead backend is reaped on the
    next request and respawned transparently."""
    base, manager = stack
    h = manager.get("tiny")
    assert h is not None
    os.kill(h.proc.pid, signal.SIGKILL)
    h.proc.wait(timeout=10)
    r = requests.post(base + "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "alive again"}],
        "max_tokens": 3,
    }, timeout=600)
    assert r.status_code == 200, r.text
    assert r.json()["usage"]["completion_tokens"] == 3
    h2 = manager.get("tiny")
    assert h2 is not None and h2.proc.pid != h.proc.pid
