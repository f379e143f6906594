"""Launcher (GUI-launcher role): start/stop/health/log-tail around a real
server process."""
import os
import subprocess
import sys


def test_launcher_lifecycle(tmp_path, monkeypatch):
    from localai_tpu.launcher import Launcher

    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    models = tmp_path / "models"
    models.mkdir()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    l = Launcher(address=f"127.0.0.1:{port}", models_path=str(models))
    assert not l.running
    assert l.start()
    try:
        assert l.wait_healthy(attempts=100)
        assert l.running and l.healthy()
        assert l.webui_url.endswith(f":{port}/")
        assert any("serving" in line for line in l.tail(50))
    finally:
        l.stop()
    assert not l.running
    assert not l.healthy()


def test_launcher_repl_commands(tmp_path):
    """Drive the interactive REPL over stdin (health + webui + quit without
    starting a server)."""
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "localai_tpu.cli", "launcher",
         "--address", "127.0.0.1:1", "--models-path", str(tmp_path)],
        input="h\nw\nbogus\nq\n", capture_output=True, text=True,
        timeout=60, env=env)
    assert out.returncode == 0
    assert "not running" in out.stdout
    assert "http://127.0.0.1:1/" in out.stdout
    assert "unknown command" in out.stdout
