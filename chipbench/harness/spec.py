"""Look up a cell, its configuration, its traffic and its metrics by name.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* configurations: ``chipbench/configs/<config>.json``, named by the
  ``file`` key of the configuration's entry;
* cells: ``chipbench/cells/<cell>.json`` (the traffic mix and the
  serving geometry of one workload);
* metrics: ``chipbench/metrics/<metric>.py``, each with a
  ``read(run) -> float | None``.

Adding a cell, a configuration of an existing kind or a metric is
adding files; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: str | Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, cell_name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """The workload entry, the configuration file and the cell file."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; have {sorted(cells)}")
    work = cells[cell_name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / confs[work["config"]]["file"])
    cell = load_json(BENCH_DIR / "cells" / f"{cell_name}.json")
    if cell.get("traffic") != work["traffic"]:
        raise ValueError(
            f"cell file {cell_name}.json holds traffic {cell.get('traffic')!r}, "
            f"BENCHMARK.json names {work['traffic']!r}"
        )
    return work, config, cell


def end_to_end_metrics(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics a cell reports: those that list it, and
    those that list no cells."""
    return [m for m in bench["end_to_end"] if cell_name in m.get("workloads", [cell_name])]


def per_layer_metrics(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics a cell reports: those that list it, and
    those without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_metrics(bench, cell_name)}
    out = []
    for m in bench["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def load_reader(metric_name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The metric's own reader module, ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{metric_name}.py"
    mod_name = "chipbench_metric_" + metric_name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {metric_name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
