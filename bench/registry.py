"""Find a cell's pieces by name: spec, configuration, traffic, app, readers.

Every piece lives in a file of its own, named after the entry of
``BENCHMARK.json`` that uses it, so a later change adds a cell, a mix, a
metric or a kernel count by adding files and entries, never by editing one.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec(root: Path = ROOT) -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return load_json(root / "BENCHMARK.json")


def cell(spec_: dict, workload: str) -> dict:
    for w in spec_["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload named {workload!r} in BENCHMARK.json")


def config(spec_: dict, name: str, root: Path = ROOT) -> dict:
    for c in spec_["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no config named {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{_checked(name)}.json")


def peaks() -> dict:
    return load_json(BENCH_DIR / "peaks.json")


def _module(kind: str, name: str) -> ModuleType:
    path = BENCH_DIR / kind / f"{_checked(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    mod_name = f"bench_{kind}_" + re.sub(r"\W", "_", name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec_ = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec_)
    sys.modules[mod_name] = mod
    spec_.loader.exec_module(mod)
    return mod


def app(name: str) -> ModuleType:
    """How one application's data and servable are made (imports repro)."""
    return _module("apps", name)


def reference(name: str) -> ModuleType:
    """The plain reference of one application (imports nothing of repro)."""
    return _module("reference", name)


def metric(name: str) -> ModuleType:
    """The reader of one per-layer metric: ``read(ctx) -> float | None``."""
    return _module("metrics", name)


def kernel(name: str) -> ModuleType:
    """One kernel's algorithmic count: ``work(**shape) -> (flops, bytes)``
    and ``MATCH``, the names its device operations carry in a trace."""
    return _module("kernels", name)
