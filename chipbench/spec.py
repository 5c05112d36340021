"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix, per-layer
metric, reference family or cell lives in a file of its own, found by
name:

* configuration ``<c>``:     ``chipbench/configs/<c>.json`` (the file that
  ``BENCHMARK.json`` names)
* traffic mix ``<t>``:       ``chipbench/traffic/<t>.json``
* per-layer metric ``<m>``:  ``chipbench/metrics/<m before the first dot>.py``
* correctness limit:         ``chipbench/limits/<workload>.json``
* the configuration's family, by its ``model_type``:
  ``chipbench/families/<model_type>.py`` (``model_config`` and
  ``reference_weights``: the configuration's keys and the program's
  weights mapped onto the program and the reference) and
  ``chipbench/reference/<model_type>.py`` (``reference``: the plain
  float32 model; ``work``: what one served call needs, in the terms of
  ``chipbench/counts.py``)

So a family adds three files: its configuration, its program mapping,
and its reference with its counts. A new cell, mix, configuration,
family or metric is new files and new entries in ``BENCHMARK.json``;
nothing here changes, and no shared file names a family's keys.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

__all__ = ["BENCH_DIR", "ROOT", "FAMILY_KINDS", "Cell", "load_benchmark",
           "load_cell", "family_path", "load_family", "metric_reader_path",
           "load_module"]

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: the directories that hold a file of each family, by ``model_type``
FAMILY_KINDS = ("families", "reference")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file, as run
    traffic: dict           # the traffic mix's parameters
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list
    limits: dict            # the correctness limits of this cell
    bench_dir: Path = BENCH_DIR   # where its references and readers are


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT, bench: dict | None = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name``; ``root`` holds ``BENCHMARK.json`` and the
    files it names, ``bench_dir`` the traffic mixes and limits."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    with open(root / centry["file"]) as f:
        config = json.load(f)
    for kind in FAMILY_KINDS:
        family_path(kind, config, bench_dir)
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(bench_dir / "limits" / f"{name}.json") as f:
        limits = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name),
                limits=limits, bench_dir=bench_dir)


def family_path(kind: str, config: dict, bench_dir: Path = BENCH_DIR
                ) -> Path:
    """``<bench_dir>/<kind>/<model_type>.py``; a family with no such
    file is an error that names the path."""
    path = bench_dir / kind / f"{config['model_type']}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"model_type {config['model_type']!r} has no {kind} file: "
            f"{path} is missing")
    return path


def load_family(kind: str, config: dict, bench_dir: Path = BENCH_DIR):
    """The family module of ``kind`` for the configuration."""
    return load_module(family_path(kind, config, bench_dir))


def metric_reader_path(metric: str, bench_dir: Path = BENCH_DIR) -> Path:
    """``compose_ms.itl`` and ``compose_ms.tps`` share
    ``metrics/compose_ms.py``: one quantity, read alike in every cell."""
    return bench_dir / "metrics" / f"{metric.split('.')[0]}.py"


def load_module(path: Path):
    """Import a file of this directory by its path (its name may hold
    dots, which ``import`` cannot take)."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_dyn_{path.parent.name}_{path.stem.replace('.', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
