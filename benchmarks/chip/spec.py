"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration's file
is the one its ``configs`` entry gives; the mix is
``benchmarks/chip/traffic/<traffic>.json``; each per-layer metric is read
by ``benchmarks/chip/metrics/<name>.py``. Nothing here knows a cell,
model or metric by name, so a later cell is added with files and entries
alone.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from .traffic import longest

CHIP_DIR = "benchmarks/chip"


@dataclasses.dataclass(frozen=True)
class Cell:
    root: Path
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic file
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
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


def model_config(conf: Dict[str, Any]):
    """The program's ModelConfig for a configuration file's published
    keys (Hugging Face ``config.json`` names)."""
    from repro.config import ModelConfig, MoEConfig
    heads = conf["num_attention_heads"]
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=heads,
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf.get("head_dim") or conf["hidden_size"] // heads,
        d_ff=0, vocab_size=conf["vocab_size"],
        moe=MoEConfig(num_experts=conf["num_local_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_ff=conf["intermediate_size"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        max_seq_len=conf["max_position_embeddings"],
        dtype=conf["torch_dtype"])


def engine_settings(cell: Cell) -> Dict[str, Any]:
    """``build()``'s cache and serving arguments for the cell."""
    eng = dict(cell.config["engine"])
    cache = eng.pop("cache")
    eng["capacity"] = cell.capacity
    return {"cache": cache, "serving": eng}


def load_reader(root: Path, metric: str) -> Callable[[Any], Optional[float]]:
    """The per-layer metric's reader: ``metrics/<metric>.py``'s
    ``read(ctx)``."""
    path = Path(root) / CHIP_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(root: Path, device_kind: str) -> Dict[str, Any]:
    table = json.loads((Path(root) / CHIP_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json; add its row with a source")
    return table["devices"][device_kind]
