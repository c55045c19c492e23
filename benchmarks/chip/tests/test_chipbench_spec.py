"""BENCHMARK.json against the files the harness finds by its names."""
import json
import math
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_and_entry_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_and_files():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    chip = ROOT / BENCH["paths"][0]
    assert (ROOT / BENCH["command"][1]).parent == chip
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert set(c["reduced"]) <= set(conf) and c["reduced"] == \
            conf["reduced"]
        assert math.isfinite(conf["limits"]["mismatch_share"])
    for w in BENCH["workloads"]:
        assert (chip / "traffic" / f"{w['traffic']}.json").exists()
    for m in BENCH["per_layer"]:
        assert (chip / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    def of(metrics):
        return [m for m in metrics if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in of(BENCH["end_to_end"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = of(BENCH["per_layer"])
    assert layers and all(m["moves"] in e2e for m in layers)
