"""Gallery / downloader / importer tests — all offline via file:// URIs
(reference tiers: core/gallery tests + pkg/downloader/uri_test.go)."""
import hashlib
import json
import os

import pytest
import yaml

from localai_tpu.downloader import download_file, resolve_uri
from localai_tpu.services import Gallery, GalleryService, install_model
from localai_tpu.services.importers import guess_model_config


def _sha(path):
    return hashlib.sha256(open(path, "rb").read()).hexdigest()


def test_resolve_uri_schemes():
    assert resolve_uri("huggingface://org/repo/model.safetensors") == \
        "https://huggingface.co/org/repo/resolve/main/model.safetensors"
    assert resolve_uri("github:owner/repo/path/file.yaml@dev") == \
        "https://raw.githubusercontent.com/owner/repo/dev/path/file.yaml"
    assert resolve_uri("https://x/y") == "https://x/y"


def test_download_file_sha256(tmp_path):
    src = tmp_path / "src.bin"
    src.write_bytes(b"hello artifact")
    dest = tmp_path / "out" / "dst.bin"
    download_file(f"file://{src}", str(dest), sha256=_sha(str(src)))
    assert dest.read_bytes() == b"hello artifact"
    with pytest.raises(ValueError, match="sha256 mismatch"):
        download_file(f"file://{src}", str(tmp_path / "bad.bin"),
                      sha256="0" * 64)


@pytest.fixture()
def gallery_fixture(tmp_path):
    """A gallery index + artifacts laid out on disk."""
    art = tmp_path / "artifacts"
    art.mkdir()
    (art / "config.json").write_text(json.dumps(
        {"architectures": ["LlamaForCausalLM"], "hidden_size": 64}))
    (art / "weights.safetensors").write_bytes(b"\x00" * 16)
    index = tmp_path / "index.yaml"
    index.write_text(yaml.safe_dump([{
        "name": "demo-model",
        "description": "test entry",
        "tags": ["llm"],
        "files": [
            {"filename": "demo-model/config.json",
             "uri": f"file://{art}/config.json",
             "sha256": _sha(str(art / "config.json"))},
            {"filename": "demo-model/weights.safetensors",
             "uri": f"file://{art}/weights.safetensors"},
        ],
        "config": {
            "backend": "llm",
            "context_size": 512,
            "parameters": {"model": "demo-model"},
        },
    }]))
    return index


def test_gallery_install(gallery_fixture, tmp_path):
    models = tmp_path / "models"
    g = Gallery([str(gallery_fixture)])
    assert "demo-model" in g.models()
    ypath = install_model(g, "demo-model", str(models))
    cfg = yaml.safe_load(open(ypath))
    assert cfg["name"] == "demo-model"
    assert cfg["context_size"] == 512
    assert (models / "demo-model" / "config.json").exists()
    # installed model is visible to the config loader
    from localai_tpu.config import ModelConfigLoader

    loader = ModelConfigLoader(str(models))
    assert loader.get("demo-model").context_size == 512


def test_gallery_service_job_queue(gallery_fixture, tmp_path):
    import time

    svc = GalleryService(Gallery([str(gallery_fixture)]),
                         str(tmp_path / "models"))
    svc.start()
    try:
        job = svc.submit("demo-model")
        deadline = time.monotonic() + 10
        while (svc.status[job]["state"] in ("queued", "processing")
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert svc.status[job]["state"] == "done", svc.status[job]
        bad = svc.submit("nonexistent")
        deadline = time.monotonic() + 10
        while (svc.status[bad]["state"] in ("queued", "processing")
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert svc.status[bad]["state"] == "error"
    finally:
        svc.stop()


def test_importer_guesses_llm(tmp_path):
    d = tmp_path / "ck"
    d.mkdir()
    (d / "config.json").write_text(json.dumps({
        "architectures": ["MistralForCausalLM"],
        "hidden_size": 4096, "max_position_embeddings": 32768,
    }))
    cfg = guess_model_config(str(d))
    assert cfg["backend"] == "llm"
    assert cfg["context_size"] == 8192  # capped
    assert cfg["template"]["use_tokenizer_template"] is True


def test_importer_small_model_embeddings(tmp_path):
    d = tmp_path / "ck"
    d.mkdir()
    (d / "config.json").write_text(json.dumps({
        "architectures": ["LlamaForCausalLM"], "hidden_size": 512,
    }))
    assert guess_model_config(str(d))["embeddings"] is True


def test_capability_detection_forced(monkeypatch):
    from localai_tpu.system import capabilities

    monkeypatch.setenv("LOCALAI_FORCE_CAPABILITY", "tpu-v5e")
    assert capabilities.detect_capability() == "tpu-v5e"
    monkeypatch.delenv("LOCALAI_FORCE_CAPABILITY")
    # the control plane does not probe: without an override the capability
    # is whatever a backend reported, else unknown
    assert capabilities.detect_capability() == "unknown"
    assert capabilities.detect_capability(
        {"platform": "cpu", "device_kind": "cpu"}) == "cpu"
    assert capabilities.detect_capability(
        {"platform": "tpu", "device_kind": "TPU v5 lite"}) == "tpu-v5e"
    # a TPU the chip table does not know keeps its platform, gets no peaks
    assert capabilities.detect_capability(
        {"platform": "tpu", "device_kind": "TPU v9"}) == "tpu"


def test_gallery_path_traversal_rejected(gallery_fixture, tmp_path):
    """Untrusted index filenames must stay confined to the models dir
    (reference verifyPath; an upstream CVE class)."""
    models = tmp_path / "models"
    g = Gallery([str(gallery_fixture)])
    gm = g.get("demo-model")
    for evil in ("../escape.yaml", "/etc/cron.d/x", "a/../../b"):
        gm.files = [{"filename": evil, "uri": "file:///dev/null"}]
        with pytest.raises(ValueError, match="path traversal"):
            install_model(g, "demo-model", str(models))
    # a malicious model NAME must not escape either (YAML path)
    gm.files = []
    gm.name = "../../evil"
    g._models["../../evil"] = gm
    with pytest.raises(ValueError, match="path traversal"):
        install_model(g, "../../evil", str(models))
