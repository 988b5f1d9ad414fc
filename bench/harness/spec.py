"""What a run is: the cell, its configuration and traffic files, and the
metrics, all found by name from ``BENCHMARK.json``.

A configuration file (``bench/configs/<name>.json``) holds the model's sizes
under ``model`` (ModelConfig fields),
the mux under ``mux``, the serving layout under ``serving``, the serving
shape (``batch`` slots, ``max_len`` positions), the plain reference under
``reference`` and the correctness limit under ``check``.  Whatever it leaves
out takes the program's default: implementation knobs are measured as users
get them.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list       # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _reports(m, name) and m["moves"] in moved]
    return Cell(name=name, config=config, traffic=traffic, chips=w["chips"],
                end_to_end=e2e, per_layer=per_layer)


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig, MuxConfig, ServingConfig
    return ModelConfig(name=config["name"], **config["model"],
                       mux=MuxConfig(**config.get("mux", {})),
                       serving=ServingConfig(**config.get("serving", {})))
