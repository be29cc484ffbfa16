"""The benchmark's parts found by name: ``perfbench/<kind>/<name>.py``.

A cell names its configuration and traffic mix; a mix names its prompt
``source``, its ``arrivals`` and its ``driver``; ``BENCHMARK.json`` names
each per-layer metric.  Each is a file of its own, loaded here once, so a
new one is a new file and no existing file changes.
"""
from __future__ import annotations

import importlib.util
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LOADED: dict = {}


def load(kind: str, name: str):
    """The module ``perfbench/<kind>/<name>.py``."""
    path = os.path.join(ROOT, kind, name + ".py")
    if path not in _LOADED:
        if not os.path.isfile(path):
            raise ValueError(f"no {kind} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            re.sub(r"\W", "_", f"perfbench_{kind}_{name}"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]
