"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

- a configuration: ``bench/configs/<config>.json`` (the ``file`` of its entry);
- a traffic mix: ``bench/traffic/<traffic>.json``;
- a cell's offered load, drain and warmed lanes: ``bench/cells/<workload>.json``;
- a per-layer metric: ``bench/metrics/<metric>.py``, with a ``read(ctx)``.

A later change adds a cell, a configuration or a metric by adding files
and entries; nothing here needs an edit for it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from bench.traffic import Mix

__all__ = ["ROOT", "BENCH", "Cell", "load_cell", "load_metric"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: Mix
    load: dict  # {"rate_per_s", "drain_s", optional "warm_lanes", ...}
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = Mix.from_dict(w["traffic"], json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()))
    load = json.loads((BENCH / "cells" / f"{name}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        load=load,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def load_metric(name: str):
    """The reader module of a per-layer metric."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
