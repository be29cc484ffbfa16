"""Process set-up shared by the entry points: compile cache, served dtype.

Each ``main()`` (``repro-serve``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``, ``chip_smoke.py``) calls
:func:`use_compile_cache` first; nothing here runs at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

from repro.configs.base import ModelConfig

#: the checkout's fixed cache directory: the path is part of what JAX keys a
#: cache entry on, so a directory that moves between runs never hits
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as it is (JAX reads
    the variable itself); otherwise the cache lives at
    :data:`CHECKOUT_CACHE_DIR`.  Every program is cached, however quickly
    it compiled: a serving process compiles many small ones."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def served_config(cfg: ModelConfig) -> ModelConfig:
    """``cfg`` as this process runs it: its own dtype on an accelerator,
    float32 on the CPU (where the tests run)."""
    if jax.default_backend() == "cpu":
        return cfg.replace(dtype="float32")
    return cfg
