"""What ``BENCHMARK.json`` names, found by name: a cell's configuration
file, its traffic mix, its limits, and the reader of each metric it
reports.  Adding a cell, a mix or a metric adds files and entries; no
file here changes.

    configs/<config>.json     sizes and deployment of a configuration
    traffic/<traffic>.json    parameters of a mix for traffic.py
    kinds/<kind>.py           the generator of a mix's ``kind``
    limits/<workload>.json    the limits that decide ``correct``
    metrics/<metric>.py       ``read(run) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            spec = json.loads((ROOT / c["file"]).read_text())
            if spec["name"] != name:
                raise ValueError(f"{c['file']} names {spec['name']!r}")
            return spec
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def reported(bench: dict, cell: str, section: str) -> list:
    """Metric entries of ``section`` that cell ``cell`` reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
