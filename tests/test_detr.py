"""DETR detection (Detect RPC model family) vs HF torch parity on a
locally-built tiny random checkpoint."""
import json
import os

import numpy as np
import pytest


def _make_ckpt(tmpdir, layer_type="basic"):
    import torch
    from transformers import DetrConfig, DetrForObjectDetection, ResNetConfig

    torch.manual_seed(0)
    cfg = DetrConfig(
        use_timm_backbone=False, use_pretrained_backbone=False,
        backbone_config=ResNetConfig(
            embedding_size=8, hidden_sizes=[8, 16], depths=[1, 2],
            layer_type=layer_type, num_channels=3),
        d_model=32, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=64, decoder_ffn_dim=64, num_queries=6, num_labels=4,
        id2label={0: "cat", 1: "dog", 2: "bird", 3: "fish"},
        label2id={"cat": 0, "dog": 1, "bird": 2, "fish": 3},
    )
    m = DetrForObjectDetection(cfg)
    m.eval()
    m.save_pretrained(tmpdir, safe_serialization=True)
    return m


@pytest.fixture(scope="module", params=["basic", "bottleneck"])
def detr_pair(request, tmp_path_factory):
    d = str(tmp_path_factory.mktemp(f"detr-{request.param}"))
    m = _make_ckpt(d, request.param)
    return d, m


def test_forward_matches_hf(detr_pair):
    import torch

    import jax.numpy as jnp
    from localai_tpu.models.detr import (
        detr_forward, load_detr_config, load_detr_params,
    )

    d, m = detr_pair
    cfg = load_detr_config(d)
    params = load_detr_params(d, cfg)
    rng = np.random.default_rng(0)
    pix = rng.normal(size=(1, 64, 64, 3)).astype(np.float32)

    logits, boxes = detr_forward(params, cfg, jnp.asarray(pix))
    with torch.no_grad():
        ref = m(pixel_values=torch.tensor(pix.transpose(0, 3, 1, 2)))
    np.testing.assert_allclose(np.asarray(logits), ref.logits.numpy(),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(boxes), ref.pred_boxes.numpy(),
                               rtol=1e-3, atol=1e-3)


def test_detector_end_to_end(detr_pair, tmp_path):
    from PIL import Image

    from localai_tpu.models.detr import (
        Detector, load_detr_config, load_detr_params,
    )

    d, _ = detr_pair
    cfg = load_detr_config(d)
    params = load_detr_params(d, cfg)
    det = Detector(cfg, params, sizes=(64,), threshold=0.0)
    img = Image.fromarray(
        (np.random.default_rng(1).uniform(0, 255, (48, 80, 3))).astype(
            np.uint8))
    path = str(tmp_path / "img.png")
    img.save(path)
    dets = det.detect(path)
    assert len(dets) > 0
    for dd in dets:
        assert dd.class_name in ("cat", "dog", "bird", "fish")
        assert 0.0 <= dd.confidence <= 1.0


def test_detect_servicer(detr_pair, tmp_path):
    from PIL import Image

    from localai_tpu.backend import pb
    from localai_tpu.backend.detect import DetectServicer

    d, _ = detr_pair
    s = DetectServicer()
    r = s.LoadModel(pb.ModelOptions(model=d), None)
    assert r.success, r.message
    img = Image.fromarray(np.zeros((32, 32, 3), np.uint8))
    path = str(tmp_path / "z.png")
    img.save(path)
    resp = s.Detect(pb.DetectOptions(src=path), _Ctx())
    assert isinstance(resp.detections, object)


class _Ctx:
    def abort(self, code, details):
        raise AssertionError(f"{code}: {details}")


@pytest.fixture(scope="module")
def detect_stack(tmp_path_factory):
    """API server + real spawned detect backend subprocess."""
    import asyncio
    import socket
    import threading
    import time

    import requests
    import yaml
    from aiohttp import web

    from localai_tpu.config import AppConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API

    ckpt = str(tmp_path_factory.mktemp("detr-http"))
    _make_ckpt(ckpt, "basic")
    models = tmp_path_factory.mktemp("models")
    (models / "det.yaml").write_text(yaml.safe_dump({
        "name": "det", "backend": "detect",
        "parameters": {"model": ckpt},
    }))
    os.environ["JAX_PLATFORMS"] = "cpu"
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    app_cfg = AppConfig(address=f"127.0.0.1:{port}",
                        models_path=str(models))
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
    yield base
    manager.stop_all()
    loop.call_soon_threadsafe(loop.stop)


def test_http_detection_endpoint(detect_stack, tmp_path):
    import base64

    import requests
    from PIL import Image

    img = Image.fromarray(
        np.random.default_rng(7).integers(0, 255, (40, 60, 3), np.uint8,
                                          endpoint=False))
    path = tmp_path / "det.png"
    img.save(str(path))
    b64 = base64.b64encode(path.read_bytes()).decode()
    r = requests.post(detect_stack + "/v1/detection", json={
        "model": "det", "image": b64}, timeout=600)
    assert r.status_code == 200, r.text
    dets = r.json()["detections"]
    for d in dets:
        assert set(d) == {"x", "y", "width", "height", "confidence",
                          "class_name"}
