"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

- a configuration: ``bench/configs/<config>.json`` (the ``file`` of its entry);
- a traffic mix: ``bench/traffic/<traffic>.json``;
- a cell's offered load, drain and warmed lanes: ``bench/cells/<workload>.json``;
- a per-layer metric: ``bench/metrics/<metric>.py``, with a ``read(ctx)``;
- a configuration's architecture: ``bench/architectures/<architecture>.py``,
  named by the file's ``"architecture"`` key, with ``widths``,
  ``program_model``, ``check_program``, ``served_params``,
  ``reference_logits`` and ``token_work`` (``bench/architectures/dense.py``
  says what each is).

A later change adds a cell, a configuration, an architecture or a metric
by adding files and entries; nothing here needs an edit for it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
import types
from pathlib import Path

from bench.traffic import Mix

__all__ = ["ROOT", "BENCH", "Cell", "load_cell", "load_metric", "load_architecture"]

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
    arch: types.ModuleType  # the configuration's architecture module


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root / "BENCHMARK.json"``, every piece read
    from under ``root``."""
    bench = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = Mix.from_dict(w["traffic"], json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()))
    load = json.loads((bench / "cells" / f"{name}.json").read_text())
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        mix=mix,
        load=load,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        arch=load_architecture(config, root),
    )


def load_metric(name: str):
    """The reader module of a per-layer metric."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_architecture(config: dict, root: Path = ROOT) -> types.ModuleType:
    """The module ``root / "bench/architectures/<architecture>.py"`` that the
    configuration names. Each file is run once a process: its jitted
    functions and their compiled programs are shared by every caller."""
    arch_dir = root / "bench" / "architectures"
    if "architecture" not in config:
        raise KeyError(f"configuration {config.get('name')!r} names no \"architecture\"; "
                       f"it would be a module under {arch_dir}")
    path = (arch_dir / f"{config['architecture']}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config.get('name')!r} names architecture "
                                f"{config['architecture']!r}: no module {path}")
    # Keyed by the path: two checkouts' modules of one name stay apart.
    key = f"bench_architecture:{path}"
    if key not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[key] = mod  # a dataclass looks its module up while it is made
        try:
            mod_spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]
