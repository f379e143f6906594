"""CLI one-shot inference subcommands (reference core/cli/tts.go +
transcript.go): real backend subprocesses, real files."""
import json
import os
import wave

import pytest

from localai_tpu.cli import main


@pytest.fixture(scope="module")
def whisper_models_dir(tmp_path_factory):
    """models dir with a tiny whisper checkpoint named default-whisper."""
    import torch
    from transformers import WhisperConfig, WhisperForConditionalGeneration

    root = tmp_path_factory.mktemp("cli-models")
    d = root / "default-whisper"
    torch.manual_seed(0)
    cfg = WhisperConfig(
        vocab_size=51865, d_model=64, encoder_layers=2, decoder_layers=2,
        encoder_attention_heads=4, decoder_attention_heads=4,
        encoder_ffn_dim=128, decoder_ffn_dim=128, num_mel_bins=80,
        max_source_positions=1500, max_target_positions=64)
    m = WhisperForConditionalGeneration(cfg)
    m.generation_config.forced_decoder_ids = None
    m.generation_config.suppress_tokens = None
    m.generation_config.begin_suppress_tokens = None
    m.save_pretrained(str(d), safe_serialization=True)
    return str(root)


def test_cli_version(capsys):
    assert main(["version"]) == 0
    assert capsys.readouterr().out.strip()


def test_cli_tts_writes_wav(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = tmp_path / "speech.wav"
    rc = main(["tts", "hello from the cli", "--output-file", str(out),
               "--models-path", str(tmp_path)])
    assert rc == 0
    with wave.open(str(out)) as w:
        assert w.getframerate() == 16000
        assert w.getnframes() > 1000


def test_cli_soundgeneration_writes_wav(tmp_path, monkeypatch):
    """`soundgeneration` wraps the existing SoundGeneration RPC (reference
    core/cli/soundgeneration.go; VERDICT Missing #7)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    out = tmp_path / "rain.wav"
    rc = main(["soundgeneration", "rain on a tin roof", "--duration", "1.0",
               "--output-file", str(out), "--models-path", str(tmp_path)])
    assert rc == 0
    with wave.open(str(out)) as w:
        assert w.getframerate() == 16000
        assert w.getnframes() >= 16000  # >= the requested 1 s


def test_cli_transcript_formats(tmp_path, monkeypatch, whisper_models_dir,
                                capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    wav = tmp_path / "in.wav"
    rc = main(["tts", "testing one two three", "--output-file", str(wav),
               "--models-path", str(tmp_path)])
    assert rc == 0
    capsys.readouterr()
    rc = main(["transcript", str(wav), "--model", "default-whisper",
               "--models-path", whisper_models_dir,
               "--output-format", "json"])
    assert rc == 0
    out = capsys.readouterr().out
    payload = json.loads(out[out.index("{"):])
    assert "text" in payload and "segments" in payload


def test_util_hf_info_and_fits(tmp_path):
    import json
    import subprocess
    import sys

    cfg = {"architectures": ["LlamaForCausalLM"], "vocab_size": 1000,
           "hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "max_position_embeddings": 256, "rms_norm_eps": 1e-5}
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    env = dict(__import__("os").environ)
    repo = __import__("os").path.dirname(
        __import__("os").path.dirname(__import__("os").path.abspath(__file__)))
    env["PYTHONPATH"] = repo

    out = subprocess.run(
        [sys.executable, "-m", "localai_tpu.cli", "util", "hf-info",
         str(tmp_path)], capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    info = json.loads(out.stdout)
    assert info["layers"] == 2 and info["parameters"] > 0

    out = subprocess.run(
        [sys.executable, "-m", "localai_tpu.cli", "util", "fits",
         str(tmp_path), "--hbm-gb", "16"],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    fit = json.loads(out.stdout)
    assert fit["fits"] is True
