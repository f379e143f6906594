"""Unit tier: YAML config parsing/merging, template evaluation, grammar
generation (reference analogs: model_config_test.go, evaluator_test.go,
grammars/json_schema_test.go)."""
import json

import pytest
import yaml

from localai_tpu.config import ModelConfig, ModelConfigLoader
from localai_tpu.functions import (
    JSON_GRAMMAR, grammar_for_request, json_schema_grammar, parse_tool_calls,
    tools_schema,
)
from localai_tpu.templates import evaluate_chat, evaluate_completion


def test_model_config_yaml_roundtrip(tmp_path):
    (tmp_path / "m.yaml").write_text(yaml.safe_dump({
        "name": "llama3",
        "backend": "llm",
        "context_size": 4096,
        "stopwords": ["</s>"],
        "mesh": {"data": 1, "model": 4},
        "parameters": {"model": "ckpt-dir", "temperature": 0.6,
                       "top_p": 0.9, "max_tokens": 256},
        "template": {"use_tokenizer_template": True},
    }))
    loader = ModelConfigLoader(str(tmp_path))
    cfg = loader.get("llama3")
    assert cfg is not None
    assert cfg.parameters.temperature == 0.6
    assert cfg.mesh.model == 4
    assert cfg.stopwords == ["</s>"]
    assert cfg.model_dir("/models") == "/models/ckpt-dir"


def test_multi_model_single_file(tmp_path):
    (tmp_path / "all.yaml").write_text(yaml.safe_dump([
        {"name": "a", "parameters": {"model": "a-dir"}},
        {"name": "b", "parameters": {"model": "b-dir"}},
    ]))
    loader = ModelConfigLoader(str(tmp_path))
    assert loader.names() == ["a", "b"]


def test_bare_checkpoint_dir_autoregistered(tmp_path):
    d = tmp_path / "bare-model"
    d.mkdir()
    (d / "config.json").write_text("{}")
    loader = ModelConfigLoader(str(tmp_path))
    assert loader.get("bare-model") is not None


def test_hot_reload_picks_up_new_yaml(tmp_path):
    loader = ModelConfigLoader(str(tmp_path))
    assert loader.get("late") is None
    (tmp_path / "late.yaml").write_text(yaml.safe_dump(
        {"name": "late", "parameters": {"model": "x"}}))
    assert loader.get("late") is not None  # per-request rescan


def test_template_inline_chat():
    cfg = ModelConfig(name="t")
    cfg.template.chat_message = (
        "<|{{ role }}|>{{ content }}</|{{ role }}|>")
    cfg.template.chat = "{{ input }}\n<|assistant|>"
    out = evaluate_chat(cfg, [
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": "hi"},
    ])
    assert out == ("<|system|>be brief</|system|>\n<|user|>hi</|user|>\n"
                   "<|assistant|>")


def test_template_completion_and_file(tmp_path):
    (tmp_path / "comp.tmpl").write_text("Q: {{ input }}\nA:")
    cfg = ModelConfig(name="t")
    cfg.config_file = str(tmp_path / "m.yaml")
    cfg.template.completion = "comp"
    assert evaluate_completion(cfg, "why?") == "Q: why?\nA:"


def test_template_multimodal_content_parts():
    cfg = ModelConfig(name="t")
    out = evaluate_chat(cfg, [{"role": "user", "content": [
        {"type": "text", "text": "what is "},
        {"type": "image_url", "image_url": {"url": "x"}},
        {"type": "text", "text": "this?"},
    ]}])
    assert "what is this?" in out


# ------------------------------------------------------------------ grammars

def _terminals(grammar: str) -> str:
    return grammar


def test_json_object_grammar_has_core_rules():
    for rule in ("root ::=", "object ::=", "string ::=", "number ::="):
        assert rule in JSON_GRAMMAR


def test_schema_grammar_object():
    g = json_schema_grammar({
        "type": "object",
        "properties": {
            "name": {"type": "string"},
            "age": {"type": "integer"},
        },
        "required": ["name", "age"],
    })
    assert g.startswith("root ::=")
    assert '"\\"name\\""' in g and '"\\"age\\""' in g
    assert "integer ::=" in g


def test_schema_grammar_enum_and_oneof():
    g = json_schema_grammar({
        "oneOf": [
            {"type": "object", "properties": {"kind": {"const": "a"}},
             "required": ["kind"]},
            {"enum": ["x", "y"]},
        ],
    })
    assert '"\\"a\\""' in g
    assert '"\\"x\\""' in g and '"\\"y\\""' in g


def test_grammar_for_request_modes():
    assert grammar_for_request({"response_format": {"type": "json_object"}}) \
        == JSON_GRAMMAR
    g = grammar_for_request({"response_format": {
        "type": "json_schema",
        "json_schema": {"schema": {"type": "object", "properties": {
            "ok": {"type": "boolean"}}, "required": ["ok"]}},
    }})
    assert '"\\"ok\\""' in g
    tools = [{"type": "function", "function": {
        "name": "get_weather",
        "parameters": {"type": "object", "properties": {
            "city": {"type": "string"}}, "required": ["city"]},
    }}]
    g2 = grammar_for_request({"tools": tools})
    assert '"\\"get_weather\\""' in g2
    assert grammar_for_request({"tools": tools, "tool_choice": "none"}) == ""
    assert grammar_for_request({}) == ""


def test_parse_tool_calls():
    out = parse_tool_calls('{"name": "get_weather", "arguments": {"city": "Paris"}}')
    assert out is not None
    assert out[0]["function"]["name"] == "get_weather"
    assert json.loads(out[0]["function"]["arguments"]) == {"city": "Paris"}
    assert parse_tool_calls("just some text") is None
    assert parse_tool_calls('{"no_name": 1}') is None


def test_tools_schema_shape():
    s = tools_schema([{"function": {"name": "f",
                                    "parameters": {"type": "object"}}}])
    assert s["properties"]["name"]["const"] == "f"


def test_tools_answer_no_action_alternative():
    """tool_choice auto (or absent) includes the reference's no-action
    "answer" alternative so the grammar can produce prose; required /
    pinned choices stay tool-only (reference pkg/functions/functions.go)."""
    from localai_tpu.functions import parse_tool_response

    tools = [{"type": "function", "function": {
        "name": "get_weather", "parameters": {"type": "object"}}}]
    s = tools_schema(tools, allow_answer=True)
    names = [a["properties"]["name"]["const"] for a in s["oneOf"]]
    assert names == ["get_weather", "answer"]

    assert '"\\"answer\\""' in grammar_for_request({"tools": tools})
    assert '"\\"answer\\""' in grammar_for_request(
        {"tools": tools, "tool_choice": "auto"})
    assert '"\\"answer\\""' not in grammar_for_request(
        {"tools": tools, "tool_choice": "required"})
    assert '"\\"answer\\""' not in grammar_for_request(
        {"tools": tools,
         "tool_choice": {"type": "function",
                         "function": {"name": "get_weather"}}})

    # parse_tool_response unwraps the no-action object into prose content
    calls, answer = parse_tool_response(
        '{"name": "answer", "arguments": {"message": "it is sunny"}}')
    assert calls is None and answer == "it is sunny"
    calls, answer = parse_tool_response(
        '{"name": "get_weather", "arguments": {"city": "Oslo"}}')
    assert answer is None and calls[0]["function"]["name"] == "get_weather"
    assert parse_tool_response("plain prose") == (None, None)


def test_template_unsupported_fields_warn(caplog):
    """LocalAI YAMLs using the reference's functions/multimodal/reply-prefix
    template fields get a structured warning instead of silent dropping
    (VERDICT Weak #8)."""
    import logging

    from localai_tpu.config import ModelConfig

    with caplog.at_level(logging.WARNING, logger="localai_tpu"):
        cfg = ModelConfig.from_dict({"name": "ported", "template": {
            "chat": "tmpl", "function": "fn-tmpl", "multimodal": "mm",
            "reply_prefix": "> ",
        }})
    assert cfg.unsupported_template_fields == [
        "function", "multimodal", "reply_prefix"]
    warning = "\n".join(r.getMessage() for r in caplog.records)
    assert "ported" in warning and "reply_prefix" in warning
    assert "function" in warning and "multimodal" in warning
    # supported-only templates stay silent
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="localai_tpu"):
        clean = ModelConfig.from_dict(
            {"name": "ok", "template": {"chat": "tmpl"}})
    assert clean.unsupported_template_fields == []
    assert not caplog.records
    # empty values don't count as usage
    quiet = ModelConfig.from_dict(
        {"name": "q", "template": {"reply_prefix": ""}})
    assert quiet.unsupported_template_fields == []


# ------------------------------------------------------------------ watchdog

def test_watchdog_reaps_idle(tmp_path, tmp_path_factory):
    import time

    from fixtures import tiny_checkpoint
    from localai_tpu.config import AppConfig
    from localai_tpu.core.manager import ModelManager

    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    ckpt = tiny_checkpoint(tmp_path_factory)
    cfg = ModelConfig(name="tiny", context_size=64, parallel=1, dtype="float32")
    cfg.parameters.model = ckpt
    cfg.prefill_buckets = [32]
    app = AppConfig(models_path="", watchdog_idle_timeout=1.0)
    mgr = ModelManager(app)
    try:
        h = mgr.load(cfg)
        assert h.alive()
        mgr.start_watchdog(interval=0.3)
        deadline = time.monotonic() + 20
        while mgr.get("tiny") is not None and time.monotonic() < deadline:
            time.sleep(0.3)
        assert mgr.get("tiny") is None, "watchdog never reaped idle backend"
        # the reaper drops the handle from the map BEFORE terminating the
        # child (and waits up to 10s for it to die) — poll, don't race it
        while h.alive() and time.monotonic() < deadline:
            time.sleep(0.1)
        assert not h.alive()
    finally:
        mgr.stop_all()
