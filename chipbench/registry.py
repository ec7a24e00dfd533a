"""Find a cell's configuration, traffic, loop and metric readers by name.

A later cell or metric is added by adding files: ``configs/<name>.json``,
``traffic/<name>.json``, ``metrics/<name>.py`` (and, for a new kind of
traffic loop, ``kinds/<kind>.py``), plus entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def benchmark(path: Path | None = None) -> dict:
    with open(path or CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def _json(kind: str, name: str, root: Path) -> dict:
    path = root / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def config(name: str, root: Path = HERE) -> dict:
    return _json("configs", name, root)


def traffic(name: str, root: Path = HERE) -> dict:
    return _json("traffic", name, root)


def loop(kind: str):
    """The serving loop of one kind of traffic (``kinds/<kind>.py``)."""
    return importlib.import_module(f"chipbench.kinds.{kind}")


def reader(name: str, root: Path = HERE) -> Callable[[dict], object]:
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = root / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(bench: dict, cell_name: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: dict, cell_name: str) -> List[dict]:
    """Per-layer metrics the cell reports: those that list it, and those
    without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in reported)]


def readers(bench: dict, cell_name: str,
            root: Path = HERE) -> Dict[str, Callable[[dict], object]]:
    return {m["name"]: reader(m["name"], root)
            for m in per_layer(bench, cell_name)}
