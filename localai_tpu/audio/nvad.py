"""Neural voice-activity detection — the silero-vad role, as a JAX model.

Reference: /root/reference/backend/go/silero-vad/vad.go:1-58 serves the VAD
RPC with silero's learned model (ONNX runtime). That runtime isn't in this
image, so the learned detector here is a compact spectral conv net *trained
in-repo* (train.py in this module): log-mel frames → 3 dilated conv layers
(receptive field ~11 frames) → per-frame speech probability. Training data
is generated on the fly — positives from the formant speech synthesizer
(audio/tts.py), negatives from silence / white & pink noise / pure tones /
clicks — so, unlike the adaptive-energy fallback (audio/vad.py), the model
rejects stationary tones and hum that carry plenty of energy but no speech
structure.

The shipped weights (vad_model.npz, a few KB) are committed; retrain with
`python -m localai_tpu.audio.nvad` (~1 min on CPU).
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

_WEIGHTS = os.path.join(os.path.dirname(__file__), "vad_model.npz")
RATE = 16000
N_MELS = 40
HOP = 160                       # 10 ms frames


@dataclasses.dataclass
class NVADConfig:
    threshold: float = 0.5
    hangover_ms: float = 240.0
    min_speech_ms: float = 90.0
    frame_ms: float = 10.0      # = HOP / RATE


def _features(audio: np.ndarray) -> np.ndarray:
    """mono f32 → [T, N_MELS] log-mel frames (10 ms hop)."""
    from localai_tpu.audio.mel import log_mel_spectrogram

    mel = log_mel_spectrogram(audio, n_mels=N_MELS, pad_to_chunk=False)
    return np.asarray(mel, np.float32).T


# ---------------------------------------------------------------- model

def init_params(key=0):
    rng = np.random.default_rng(key)

    def w(shape, fan_in):
        return (rng.standard_normal(shape) * fan_in ** -0.5).astype(
            np.float32)

    # conv kernels [k, in, out]; dilations 1,2,4 → receptive field 11 frames
    return {
        "c1": w((3, N_MELS, 32), 3 * N_MELS), "b1": np.zeros(32, np.float32),
        "c2": w((3, 32, 32), 96), "b2": np.zeros(32, np.float32),
        "c3": w((3, 32, 32), 96), "b3": np.zeros(32, np.float32),
        "out": w((32, 1), 32), "bout": np.zeros(1, np.float32),
    }


def apply(params, feats):
    """[T, N_MELS] → per-frame speech logits [T] (pure JAX)."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(feats)[None]                    # [1, T, F]
    # per-utterance mean/var norm: robust to recording gain
    x = (x - x.mean(axis=(1, 2), keepdims=True)) / (
        x.std(axis=(1, 2), keepdims=True) + 1e-5)

    def conv(x, w, b, dilation):
        out = jax.lax.conv_general_dilated(
            x, jnp.asarray(w), (1,), [(dilation, dilation)],
            rhs_dilation=(dilation,),
            dimension_numbers=("NHC", "HIO", "NHC"))
        return jax.nn.relu(out + jnp.asarray(b))

    x = conv(x, params["c1"], params["b1"], 1)
    x = conv(x, params["c2"], params["b2"], 2)
    x = conv(x, params["c3"], params["b3"], 4)
    logits = x @ jnp.asarray(params["out"]) + jnp.asarray(params["bout"])
    return logits[0, :, 0]


def load_params(path: str | None = None):
    path = path or _WEIGHTS
    if not os.path.exists(path):
        return None
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


def speech_probs(audio: np.ndarray, params=None) -> np.ndarray:
    """mono f32 @16k → per-10ms-frame speech probability."""
    import jax.nn

    params = params if params is not None else load_params()
    if params is None:
        raise FileNotFoundError("no VAD weights (run python -m "
                                "localai_tpu.audio.nvad to train)")
    feats = _features(audio)
    if feats.shape[0] == 0:
        return np.zeros((0,), np.float32)
    return np.asarray(jax.nn.sigmoid(apply(params, feats)))


def detect_segments_model(audio: np.ndarray, cfg: NVADConfig | None = None,
                          params=None) -> list[tuple[float, float]]:
    """Segment extraction with hangover merging (same output contract as the
    energy fallback, audio/vad.py)."""
    from localai_tpu.audio.vad import frames_to_segments

    cfg = cfg or NVADConfig()
    probs = speech_probs(audio, params)
    active = probs > cfg.threshold
    hang = max(1, int(cfg.hangover_ms / cfg.frame_ms))
    min_frames = max(1, int(cfg.min_speech_ms / cfg.frame_ms))
    segments = frames_to_segments(active, hang, min_frames)
    sec = cfg.frame_ms / 1000.0
    return [(s * sec, e * sec) for s, e in segments]


# ---------------------------------------------------------------- training

def _rand_text(rng, n=24):
    chars = "aeiouy bcdfgklmnprst "
    return "".join(chars[rng.integers(0, len(chars))] for _ in range(n))


def _frame_labels_from_energy(clean: np.ndarray, frames: int) -> np.ndarray:
    """Per-frame speech labels from the CLEAN speech signal's energy: padded
    or inter-word silence inside a speech clip trains as 0, not 1 (labeling
    whole clips would teach the model to hold 'speech' through silence)."""
    n_frames = min(frames, len(clean) // HOP)
    lab = np.zeros(frames, np.float32)
    if n_frames <= 0:
        return lab
    x = clean[: n_frames * HOP].reshape(n_frames, HOP)
    rms = np.sqrt((x ** 2).mean(axis=1))
    lab[:n_frames] = (rms > 0.01).astype(np.float32)
    return lab


def _make_clip(rng) -> tuple[np.ndarray, np.ndarray]:
    """(audio ~1.5s, per-frame labels) — positives: synthesized speech
    (optionally in noise); negatives: non-speech that fools energy VADs
    (tones, hum, clicks)."""
    from localai_tpu.audio.tts import synthesize

    kind = rng.integers(0, 6)
    n = int(1.5 * RATE)
    frames = n // HOP
    t = np.arange(n) / RATE
    if kind in (0, 1):                              # speech (+ noise)
        a = synthesize(_rand_text(rng), voice="default", language="en")
        a = a[:n] if len(a) >= n else np.pad(a, (0, n - len(a)))
        labels = _frame_labels_from_energy(a, frames)
        if kind == 1:
            a = a + 0.02 * rng.standard_normal(n)
        return a.astype(np.float32), labels
    zeros = np.zeros(frames, np.float32)
    if kind == 2:                                   # silence / hiss
        return (0.01 * rng.standard_normal(n)).astype(np.float32), zeros
    if kind == 3:                                   # pure tone(s) — loud!
        f = rng.uniform(80, 3000)
        a = 0.4 * np.sin(2 * np.pi * f * t)
        if rng.random() < 0.5:
            a += 0.2 * np.sin(2 * np.pi * rng.uniform(80, 3000) * t)
        return a.astype(np.float32), zeros
    if kind == 4:                                   # mains hum + noise
        a = 0.3 * np.sin(2 * np.pi * 50 * t) + 0.05 * rng.standard_normal(n)
        return a.astype(np.float32), zeros
    # clicks / impulses
    a = np.zeros(n, np.float32)
    for _ in range(rng.integers(2, 8)):
        i = rng.integers(0, n - 100)
        a[i:i + 100] = rng.uniform(-0.8, 0.8)
    return a, zeros


def train(steps: int = 250, seed: int = 0, save: str | None = _WEIGHTS,
          frames: int = 151):
    """Train the detector on generated clips; returns params. Clips are
    padded/cropped to a fixed frame count so the jitted update compiles
    once."""
    import jax
    import jax.numpy as jnp
    import optax

    rng = np.random.default_rng(seed)
    params = jax.tree_util.tree_map(jnp.asarray, init_params(seed))
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)

    def loss_fn(params, feats, labels):
        logits = apply(params, feats)
        return optax.sigmoid_binary_cross_entropy(logits, labels).mean()

    @jax.jit
    def step_fn(params, opt_state, feats, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, feats, labels)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    for step in range(steps):
        audio, labels = _make_clip(rng)
        feats = _features(audio)[:frames]
        if feats.shape[0] < frames:
            feats = np.pad(feats, ((0, frames - feats.shape[0]), (0, 0)))
        labels = labels[:feats.shape[0]]
        if labels.shape[0] < frames:
            labels = np.pad(labels, (0, frames - labels.shape[0]))
        params, opt_state, loss = step_fn(params, opt_state,
                                          jnp.asarray(feats),
                                          jnp.asarray(labels))
        if step % 50 == 0:
            print(f"step {step}: loss {float(loss):.4f}", flush=True)
    out = {k: np.asarray(v) for k, v in params.items()}
    if save:
        np.savez(save, **out)
        print(f"saved {save}", flush=True)
    return out


if __name__ == "__main__":
    import jax

    # the model is tiny — train on host CPU even when an accelerator is
    # attached (a chip belongs to one process; do not take it for this)
    jax.config.update("jax_platforms", "cpu")
    train()
