"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration's file
is the one its ``configs`` entry gives; the mix is
``benchmarks/chip/traffic/<traffic>.json``; each per-layer metric is read
by ``benchmarks/chip/metrics/<name>.py``. A configuration's published
keys (every key but the harness's own, ``HARNESS_KEYS``) become the
program's ModelConfig through ``benchmarks/chip/hf/<model_type>.py``,
and are run through ``benchmarks/chip/references/<model_type>.py``, the
plain reference. Nothing here knows a cell, model type or metric by
name, so a later cell is added with files and entries alone. A key the
harness itself adds to configuration files goes into ``HARNESS_KEYS``,
or it is refused as a published key that no mapping reads.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from .traffic import longest

CHIP_DIR = "benchmarks/chip"
# a configuration file's own keys; every other key is a published one
HARNESS_KEYS = frozenset((
    "name", "source", "paper", "reduced", "published", "deployment",
    "assumed", "departures", "engine", "limits"))


@dataclasses.dataclass(frozen=True)
class Cell:
    root: Path
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic file
    model: Any                      # the program's ModelConfig of it
    reference: Any                  # references/<model_type>.py
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def capacity(self) -> int:
        """KV capacity: the longest prompt plus the longest output plus
        one free slot, rounded up to a whole page."""
        page = self.config["engine"]["page_size"]
        need = (longest(self.traffic["prompt"])
                + longest(self.traffic["output"]) + 1)
        return -(-need // page) * page


def _for_cell(metrics, cell: str) -> List[Dict[str, Any]]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell's files. Raises where its configuration has a published
    key that ``hf/<model_type>.py`` neither reads nor skips, or where its
    ``model_type`` has no mapping or no reference file: before any weights
    are made."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / CHIP_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=config, traffic=traffic,
                model=model_config(root, config),
                reference=reference(root, config),
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


def published(conf: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's published keys: Hugging Face ``config.json``
    names and values, as run."""
    return {k: v for k, v in conf.items() if k not in HARNESS_KEYS}


def _module(root: Path, kind: str, name: str, what: str):
    """``benchmarks/chip/<kind>/<name>.py`` of the checkout at ``root``,
    loaded by path."""
    path = Path(root) / CHIP_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {what} {name!r}: "
                         f"{CHIP_DIR}/{kind}/{name}.py is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(root: Path, conf: Dict[str, Any]):
    """The program's ModelConfig for a configuration file, through
    ``hf/<model_type>.py``. Raises, naming the key and the type, where a
    published key is neither read (mapped or checked) nor skipped there,
    or where the program does not do what a read key says."""
    keys = published(conf)
    kind = keys.get("model_type")
    hf = _module(root, "hf", str(kind), "program mapping of model_type")
    unknown = sorted(set(keys) - hf.READ - set(hf.SKIPPED))
    if unknown:
        raise ValueError(f"model_type {kind!r}: published key(s) "
                         f"{', '.join(unknown)} neither read nor skipped "
                         f"by {CHIP_DIR}/hf/{kind}.py")
    return hf.model_config(conf["name"], keys)


def reference(root: Path, conf: Dict[str, Any]):
    """The plain reference of the configuration's ``model_type``:
    ``references/<model_type>.py``, whose ``logits(params, keys, tokens,
    length, fp8)`` takes the published keys."""
    return _module(root, "references", str(conf.get("model_type")),
                   "reference of model_type")


def engine_settings(cell: Cell) -> Dict[str, Any]:
    """``build()``'s cache and serving arguments for the cell."""
    eng = dict(cell.config["engine"])
    cache = eng.pop("cache")
    eng["capacity"] = cell.capacity
    return {"cache": cache, "serving": eng}


def load_reader(root: Path, metric: str) -> Callable[[Any], Optional[float]]:
    """The per-layer metric's reader: ``metrics/<metric>.py``'s
    ``read(ctx)``."""
    return _module(root, "metrics", metric, "reader of metric").read


def peaks(root: Path, device_kind: str) -> Dict[str, Any]:
    table = json.loads((Path(root) / CHIP_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; add its row with a source")
    return table["devices"][device_kind]
