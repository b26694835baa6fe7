"""What ``BENCHMARK.json`` names, found by name: a cell's configuration
file, its block family, its traffic mix, its limits, and the reader of
each metric it reports.  Adding a cell, a mix, a metric or a model adds
files and entries; no file here changes.

    configs/<config>.json     sizes and deployment of a configuration,
                              with the ``family`` of its blocks
    families/<family>.py      what one family of blocks is (below)
    traffic/<traffic>.json    parameters of a mix for traffic.py
    kinds/<kind>.py           the generator of a mix's ``kind``
    limits/<workload>.json    the limits that decide ``correct``
    metrics/<metric>.py       ``read(run) -> float | None``

A family module gives, for a configuration ``spec``:

* ``groups(spec)``: the ordered layer groups, ``[(kind, count), ...]``;
  the layers are numbered globally in that order.
* ``LEAVES``: every layer leaf name of every kind, once, in the order
  that fixes each leaf's key (``weights.py`` puts the top leaves after
  them), and ``leaves(spec, kind)``: ``{name: (shape, std, is_norm)}``
  of one layer of ``kind``.
* ``WIDTHS`` (configuration key -> ``ArchConfig`` field),
  ``arch_config(spec, check)`` and ``program_params(canon, model)``: the
  program's configuration and parameter tree for the benchmark's
  weights.
* ``layer(kind, w, x, spec, quant)``: the float32 forward of one layer,
  jitted, on the reference's helpers (``_mm``, ``_rms``,
  ``QUERY_BLOCK``; ``quant`` is the float8 control).
* the counts the metrics read through ``arith.py``: ``token_flops``,
  ``prefill_flops``, ``decode_flops``, ``decode_least_bytes``,
  ``weight_bytes``, ``kv_bytes_per_token``, and ``matmul_params``,
  ``layer_matmul_params``, ``attn_context`` where they exist.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FAMILIES = HERE / "families"


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


def _module(path: Path, prefix: str):
    name = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"{prefix}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _module(HERE / "metrics" / f"{metric}.py", "bench_metric").read


def family(name: str):
    """The module ``families/<name>.py``, loaded once per path."""
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in FAMILIES.glob("*.py"))
        raise ValueError(f"unknown block family {name!r}: no {path.name} "
                         f"in {FAMILIES.name}/; have {have}")
    return _family(path)


@functools.cache
def _family(path: Path):
    return _module(path, "bench_family")
