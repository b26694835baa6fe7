"""Every cell of BENCHMARK.json resolves to a configuration, a traffic
mix, its limits and a reader for each metric it reports."""
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import cells  # noqa: E402
import traffic  # noqa: E402

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = cells.workload(BENCH, cell)
    spec = cells.config(BENCH, w["config"])
    mix = cells.traffic(w["traffic"])
    limits = json.loads((HERE / "limits" / f"{cell}.json").read_text())
    assert limits["logit_gap_max"]["limit"] > 0
    assert callable(traffic.kind(mix["kind"]))
    assert w["chips"] == spec["deployment"]["chips"] == 1
    e2e = cells.reported(BENCH, cell, "end_to_end")
    layer = cells.reported(BENCH, cell, "per_layer")
    e2e_names = {m["name"] for m in e2e}
    assert "setup_s" in e2e_names and len(e2e_names) >= 2
    assert layer, "a cell reports at least one per-layer metric"
    for m in layer:
        assert m["moves"] in e2e_names, (m["name"], m["moves"])
    for m in e2e + layer:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("path", sorted((HERE / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_matches_program(path):
    """Published widths equal the program's registered configuration,
    apart from what the file lists as reduced; a configuration in
    BENCHMARK.json lists the same reduced keys there."""
    spec = json.loads(path.read_text())
    assert spec["name"] == path.stem
    for entry in BENCH["configs"]:
        if entry["name"] == spec["name"]:
            assert entry["reduced"] == spec["reduced"]
            assert (ROOT / entry["file"]) == path
    fam = cells.family(spec["family"])
    arch = fam.arch_config(spec, check=True)
    assert arch.n_layers == spec["num_hidden_layers"]
    for key, field in fam.WIDTHS.items():
        assert getattr(arch, field) == spec[key], key
    assert arch.dtype == spec["torch_dtype"] == arch.param_dtype


@pytest.mark.parametrize("path", sorted((HERE / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_names_a_family(path):
    """Every configuration file names the family of its blocks, and the
    family gives what the harness reads (``cells.py``)."""
    fam = cells.family(json.loads(path.read_text())["family"])
    for name in ("groups", "LEAVES", "leaves", "WIDTHS", "arch_config",
                 "program_params", "layer", "token_flops", "prefill_flops",
                 "decode_flops", "decode_least_bytes", "weight_bytes",
                 "kv_bytes_per_token"):
        assert hasattr(fam, name), name


def test_every_metric_names_cells_that_exist():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]


def test_traffic_generator_reads_every_mix():
    for path in sorted((HERE / "traffic").glob("*.json")):
        mix = json.loads(path.read_text())
        if mix["kind"] == "backlog":
            mix = dict(mix, requests=4)
        t = traffic.generate(mix, 32000, 16, 1)
        assert t.timed or t.backlog
