"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own, found by name:

    configs/<config>.json        sizes as run, source, reduced, assumed
    reference/<reference>.py     the configuration's plain reference
    traffic/<traffic>.json       kind, mesh, batch, lengths
    kinds/<kind>.py              the runner for that kind of traffic
    limits/<cell>.json           the limits that decide ``correct``
    metrics/<metric>.py          one reader per per-layer metric

so a later change adds a cell, a configuration or a metric by adding
files and entries, and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
SPEC_FILE = ROOT / "BENCHMARK.json"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(path: Path = SPEC_FILE) -> dict:
    return _json(path)


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(CHIP / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(CHIP / "traffic" / f"{name}.json")


def limits(cell: str) -> dict:
    return _json(CHIP / "limits" / f"{cell}.json")


def load_module(path: Path):
    """Import a file by its path (names may hold dots)."""
    try:
        parts = path.relative_to(CHIP).with_suffix("").parts
    except ValueError:
        parts = path.with_suffix("").parts[-2:]
    name = "chipbench_" + "_".join(parts).replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def kind(name: str):
    return load_module(CHIP / "kinds" / f"{name}.py")


def reference(name: str):
    return load_module(CHIP / "reference" / f"{name}.py")


def metric_reader(name: str):
    return load_module(CHIP / "metrics" / f"{name}.py")


def _applies(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_of_cell


def end_to_end(spec: dict, cell: str) -> list:
    """The end-to-end metrics the cell reports (``setup_s`` among them)."""
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer(spec: dict, cell: str) -> list:
    """The per-layer metrics a ``--trace 1`` run of the cell reports."""
    e2e = {m["name"] for m in end_to_end(spec, cell)}
    return [m for m in spec["per_layer"] if _applies(m, cell, e2e)]
