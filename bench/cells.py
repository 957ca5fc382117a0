"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration, its traffic mix and its per-layer metric readers.

Every piece lives in a file of its own, so a new cell or metric is added by
adding files and entries, never by editing one:

* ``bench/configs/<config>.json``   the deployment (operator, solver, target)
* ``bench/traffic/<traffic>.json``  the right-hand-side stream and basis format
* ``bench/metrics/<metric>.py``     a reader ``read(ctx) -> float | None``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple    # BENCHMARK.json metric entries this cell reports
    per_layer: tuple


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    w = entries[name]
    bench = root / "bench"
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_load_json(bench / "configs" / f"{w['config']}.json"),
        traffic=_load_json(bench / "traffic" / f"{w['traffic']}.json"),
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, name)),
    )


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``<root>/bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
