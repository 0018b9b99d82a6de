"""The benchmark's files, found by name: ``bench/<kind>/<name>.py`` for code
(a stencil, a restriction, a product, a metric's reader) and
``bench/<kind>/<name>.json`` for data (a configuration's traffic mix, a
cell's limits). A new one is a new file; nothing that exists is edited."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def data(kind: str, name: str, root: Path = ROOT):
    """The JSON file ``bench/<kind>/<name>.json``."""
    return read_json(root / "bench" / kind / f"{name}.json")


def load(kind: str, name: str, root: Path = ROOT):
    """The module ``bench/<kind>/<name>.py``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", f"{kind}_{name}"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
