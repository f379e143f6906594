"""The serving engine. Names resolve on first use: `kvhost`'s text-chain
helpers are JAX-free and the HTTP control plane imports them, so importing
this package must not import jax (a control plane stays off the device
runtime — system/capabilities.py)."""
import importlib

_EXPORTS = {
    "load_config": "loader", "load_params": "loader", "load_model": "loader",
    "Tokenizer": "tokenizer",
    "Engine": "engine", "EngineConfig": "engine", "GenRequest": "engine",
    "StepOutput": "engine",
}
__all__ = sorted(_EXPORTS)


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{mod}"), name)
