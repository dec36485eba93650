"""BENCHMARK.json and the files it names.  A cell is found by its name:
its configuration's file, its traffic mix's file (which names the driver),
the driver's module, and for each per-layer metric of the cell the
metric's file (which names its reader).  Nothing here touches JAX.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object  # module benchmarks.drivers.<traffic["driver"]>
    end_to_end: list[dict]  # BENCHMARK.json entries that apply here
    per_layer: list[dict]  # the same, each with "reader" and "args" added


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py, found by name."""
    if not os.path.isfile(os.path.join(HERE, kind, name + ".py")):
        raise SpecError(f"no benchmarks/{kind}/{name}.py")
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def resolve(cell_name: str, bench: dict | None = None, root: str = ROOT) -> Cell:
    bench = bench or load_benchmark(root)
    here = os.path.join(root, "benchmarks")
    try:
        workload = next(w for w in bench["workloads"] if w["name"] == cell_name)
    except StopIteration:
        raise SpecError(f"BENCHMARK.json has no workload {cell_name!r}") from None
    try:
        entry = next(c for c in bench["configs"] if c["name"] == workload["config"])
    except StopIteration:
        raise SpecError(f"no configuration {workload['config']!r}") from None
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(here, "traffic", workload["traffic"] + ".json"))
    per_layer = []
    for m in bench["per_layer"]:
        if _applies(m, cell_name):
            how = load_json(os.path.join(here, "layer_metrics", m["name"] + ".json"))
            module("readers", how["reader"])
            per_layer.append({**m, "reader": how["reader"], "args": how.get("args", {})})
    return Cell(
        name=cell_name,
        chips=workload["chips"],
        config=config,
        traffic=traffic,
        driver=module("drivers", traffic["driver"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, cell_name)],
        per_layer=per_layer,
    )
