"""Backend gallery: registry index, meta-backend capability resolution,
install payload kinds, external run.sh spawn, and the /backends HTTP family."""
import json
import os
import tarfile

import pytest
import yaml


@pytest.fixture()
def index(tmp_path):
    """Local registry index: a meta backend + two concrete candidates
    (dir payload + tarball payload)."""
    cpu_payload = tmp_path / "payload-cpu"
    cpu_payload.mkdir()
    (cpu_payload / "run.sh").write_text("#!/bin/sh\necho cpu backend\n")
    tpu_payload = tmp_path / "payload-tpu"
    tpu_payload.mkdir()
    (tpu_payload / "run.sh").write_text("#!/bin/sh\necho tpu backend\n")
    tarball = tmp_path / "tool.tar.gz"
    with tarfile.open(tarball, "w:gz") as tf:
        tf.add(str(cpu_payload / "run.sh"), arcname="run.sh")
    idx = tmp_path / "index.yaml"
    idx.write_text(yaml.safe_dump([
        {"name": "fastllm", "alias": "fast",
         "description": "meta backend",
         "capabilities": {"default": "cpu-fastllm",
                          "tpu-v5e": "tpu-fastllm"}},
        {"name": "cpu-fastllm", "uri": f"file://{cpu_payload}"},
        {"name": "tpu-fastllm", "uri": f"file://{tpu_payload}"},
        {"name": "tool", "uri": str(tarball)},
    ]))
    return str(idx)


def test_index_parse_and_meta(index):
    from localai_tpu.services.backend_gallery import BackendGallery

    g = BackendGallery([index])
    assert set(g.backends()) == {"fastllm", "cpu-fastllm", "tpu-fastllm",
                                 "tool"}
    assert g.get("fastllm").is_meta
    assert not g.get("tool").is_meta


def test_meta_resolution_by_capability(index):
    from localai_tpu.services.backend_gallery import (
        BackendGallery, resolve_meta,
    )

    g = BackendGallery([index])
    meta = g.get("fastllm")
    assert resolve_meta(g, meta, "tpu-v5e").name == "tpu-fastllm"
    assert resolve_meta(g, meta, "weird-hw").name == "cpu-fastllm"


def test_install_meta_creates_alias_dir(index, tmp_path):
    from localai_tpu.services.backend_gallery import (
        BackendGallery, install_backend, list_system_backends,
    )

    bp = str(tmp_path / "backends")
    g = BackendGallery([index])
    dest = install_backend(g, "fastllm", bp, capability="tpu-v5e")
    assert dest.endswith("tpu-fastllm")
    assert os.path.isfile(os.path.join(dest, "run.sh"))
    meta = json.load(open(os.path.join(bp, "fastllm", "metadata.json")))
    assert meta["meta_backend_for"] == "tpu-fastllm"
    names = {b["name"]: b for b in list_system_backends(bp)}
    assert "tpu-fastllm" in names and "fastllm" in names
    assert names["llm"]["system"] is True   # in-tree roles listed too


def test_install_tarball_and_idempotence(index, tmp_path):
    from localai_tpu.services.backend_gallery import (
        BackendGallery, install_backend,
    )

    bp = str(tmp_path / "backends")
    g = BackendGallery([index])
    dest = install_backend(g, "tool", bp)
    assert os.path.isfile(os.path.join(dest, "run.sh"))
    marker = os.path.join(dest, "marker")
    open(marker, "w").write("1")
    install_backend(g, "tool", bp)          # idempotent: no reinstall
    assert os.path.exists(marker)


def test_install_oci_payload(tmp_path):
    """Backend shipped as an OCI image (the reference's actual distribution
    channel, backends.go + index.yaml uri: oci://...)."""
    from test_oci import _FakeRegistry, _tar_layer

    from localai_tpu.services.backend_gallery import (
        BackendGallery, install_backend,
    )

    reg = _FakeRegistry()
    srv = reg.serve()
    host = f"127.0.0.1:{srv.server_address[1]}"
    try:
        layer = _tar_layer({"run.sh": b"#!/bin/sh\necho oci\n"})
        reg.add_image("org/b", "v1", [
            (layer, "application/vnd.oci.image.layer.v1.tar+gzip")])
        idx = tmp_path / "idx.yaml"
        idx.write_text(yaml.safe_dump([
            {"name": "ocib", "uri": f"oci://{host}/org/b:v1"}]))
        bp = str(tmp_path / "backends")
        dest = install_backend(BackendGallery([str(idx)]), "ocib", bp)
        assert open(os.path.join(dest, "run.sh")).read().startswith("#!/bin")
    finally:
        srv.shutdown()


def test_delete_backend(index, tmp_path):
    from localai_tpu.services.backend_gallery import (
        BackendGallery, delete_backend, install_backend,
        list_system_backends,
    )

    bp = str(tmp_path / "backends")
    g = BackendGallery([index])
    install_backend(g, "fastllm", bp, capability="tpu-v5e")
    delete_backend(bp, "fastllm")
    names = {b["name"] for b in list_system_backends(bp)
             if not b.get("system")}
    assert names == set()


def test_resolve_backend_dir_alias_and_meta(index, tmp_path):
    from localai_tpu.services.backend_gallery import (
        BackendGallery, install_backend, resolve_backend_dir,
    )

    bp = str(tmp_path / "backends")
    g = BackendGallery([index])
    install_backend(g, "cpu-fastllm", bp)
    # alias defined on the concrete entry's metadata
    meta_path = os.path.join(bp, "cpu-fastllm", "metadata.json")
    meta = json.load(open(meta_path))
    meta["alias"] = "fast"
    json.dump(meta, open(meta_path, "w"))
    assert resolve_backend_dir(bp, "cpu-fastllm").endswith("cpu-fastllm")
    assert resolve_backend_dir(bp, "fast").endswith("cpu-fastllm")
    assert resolve_backend_dir(bp, "llm") is None  # in-tree role


def test_manager_spawns_external_backend(tmp_path):
    """A gallery-installed backend whose run.sh execs a real gRPC server must
    pass the manager's health/load cycle (initializers.go:50-99 contract)."""
    import sys

    from localai_tpu.config import AppConfig, ModelConfig
    from localai_tpu.core.manager import ModelManager

    bp = tmp_path / "backends"
    bdir = bp / "echo-store"
    bdir.mkdir(parents=True)
    (bdir / "metadata.json").write_text(json.dumps({"name": "echo-store"}))
    (bdir / "run.sh").write_text(
        f"#!/bin/sh\nexec {sys.executable} -m localai_tpu.backend "
        "--backend store \"$@\"\n")
    store_dir = tmp_path / "store-data"
    store_dir.mkdir()
    os.environ["JAX_PLATFORMS"] = "cpu"
    app = AppConfig(models_path=str(tmp_path), backends_path=str(bp))
    mgr = ModelManager(app)
    cfg = ModelConfig.from_dict({
        "name": "ext", "backend": "echo-store",
        "parameters": {"model": str(store_dir)}})
    try:
        h = mgr.load(cfg)
        assert h.client.health()
    finally:
        mgr.stop_all()


def test_backends_http_family(index, tmp_path):
    """GET /backends, /backends/available, POST /backends/apply + job poll,
    POST /backends/delete through the real aiohttp app."""
    import asyncio
    import socket
    import threading
    import time

    import requests
    from aiohttp import web

    from localai_tpu.config import AppConfig, ModelConfigLoader
    from localai_tpu.core.manager import ModelManager
    from localai_tpu.server.http import API
    from localai_tpu.services.backend_gallery import (
        BackendGallery, BackendGalleryService,
    )

    bp = str(tmp_path / "backends")
    models = tmp_path / "models"
    models.mkdir()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    app_cfg = AppConfig(address=f"127.0.0.1:{port}",
                        models_path=str(models), backends_path=bp)
    api = API(app_cfg, ModelConfigLoader(str(models)), ModelManager(app_cfg))
    svc = BackendGalleryService(BackendGallery([index]), bp)
    svc.start()
    api.backend_gallery_service = svc
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
    try:
        avail = requests.get(base + "/backends/available", timeout=10).json()
        assert {b["name"] for b in avail} >= {"fastllm", "tool"}
        sysb = requests.get(base + "/backends", timeout=10).json()
        assert any(b["name"] == "llm" and b["system"] for b in sysb)
        gals = requests.get(base + "/backends/galleries", timeout=10).json()
        assert gals == [{"url": index}]

        os.environ["LOCALAI_FORCE_CAPABILITY"] = "tpu-v5e"
        try:
            job = requests.post(base + "/backends/apply",
                                json={"name": "fastllm"}, timeout=10).json()
            for _ in range(100):
                st = requests.get(base + f"/backends/jobs/{job['uuid']}",
                                  timeout=10).json()
                if st["state"] in ("done", "error"):
                    break
                time.sleep(0.1)
            assert st["state"] == "done", st
        finally:
            os.environ.pop("LOCALAI_FORCE_CAPABILITY", None)
        installed = requests.get(base + "/backends", timeout=10).json()
        assert any(b["name"] == "tpu-fastllm" for b in installed)

        r = requests.post(base + "/backends/delete/fastllm", timeout=10)
        assert r.status_code == 200
        installed = requests.get(base + "/backends", timeout=10).json()
        assert not any(b["name"] == "tpu-fastllm" for b in installed)
    finally:
        svc.stop()
        loop.call_soon_threadsafe(loop.stop)
