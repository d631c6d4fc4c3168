"""paddle.incubate.autotune — tuning-config facade.

Parity: `python/paddle/incubate/autotune.py:24` set_config (kernel /
layout / dataloader tuning).  TPU seat: XLA owns kernel autotuning; the
knobs with real effect here are the persistent compilation cache
(kernel.enable — saved autotune results ride the cached executables) and
dataloader tuning (accepted and recorded — the io.DataLoader picks
worker counts itself on this host).

kernel.enable routes through :mod:`paddle_tpu.core.compile_cache` — the
ONE cache-dir source of truth (``FLAGS_compilation_cache_dir``; this
module's legacy ``~/.paddle_tpu_cache`` survives only as the fallback
when the flag is unset).  ``get_config()`` reports the directory
actually applied.
"""

from __future__ import annotations

import json
import warnings

__all__ = ["set_config"]

_config = {"kernel": {"enable": False},
           "layout": {"enable": False},
           "dataloader": {"enable": False}}


def set_config(config=None):
    """Accepts a dict or a JSON file path (the reference's contract)."""
    if config is None:
        _config["kernel"]["enable"] = True
        _config["layout"]["enable"] = True
        _config["dataloader"]["enable"] = True
    elif isinstance(config, str):
        with open(config) as f:
            set_config(json.load(f))
        return
    elif isinstance(config, dict):
        for k, v in config.items():
            if k not in _config:
                warnings.warn(f"autotune.set_config: unknown field {k!r}")
                continue
            _config[k].update(v)
    if _config["kernel"]["enable"]:
        # XLA's kernel autotune runs unconditionally; the persistent
        # compile cache is the knob that saves its results across runs
        # (core/compile_cache.py decides where it lives).
        from ..core import compile_cache as _cc
        _config["kernel"]["cache_dir"] = _cc.configure()


def get_config():
    return {k: dict(v) for k, v in _config.items()}
