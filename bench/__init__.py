"""The chip benchmark: cells of ``BENCHMARK.json``, each a configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<name>.json``),
driven through a system adapter (``systems/<name>.py``) and checked
against a plain reference (``reference/<name>.py``); each metric is read by
``metrics/<name>.py``. Everything is found by name, so a cell, a mix, a
configuration or a metric is added by adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = ["BENCH", "ROOT", "load_json", "load_module"]


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    key = f"bench.{kind}.{name.replace('.', '_').replace('-', '_')}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod
