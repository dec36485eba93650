"""What the tests of a cell's shape read as ``bench``: BENCHMARK.json as
committed, and a copy with what a later PR appends (a configuration, a
cell listed under both verdict metrics and one shared per-layer metric,
and a per-layer metric of its own).  A test that passes on both finds
its entries by name and holds no list's tail and no count of entries,
so a cell is taken by appended entries alone.  Each test file makes its
own fixture of ``bench_for`` over ``BENCHES``; a conftest.py here would
take the module name ``conftest`` from tests/conftest.py."""

import copy
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import spec  # noqa: E402

STAND_IN = "stand-in-cell"


def appended(bench: dict) -> dict:
    """A copy of ``bench`` with a stand-in cell's entries appended."""
    bench = copy.deepcopy(bench)
    bench["configs"].append({
        "name": "stand-in-config", "source": "a later deployment's source",
        "file": "benchmarks/configs/stand-in-config.json", "reduced": [],
        "why": "a later PR's configuration"})
    bench["workloads"].append({
        "name": STAND_IN, "config": "stand-in-config", "traffic": "serial-pool8",
        "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"]:
        if m["name"] in ("verdict_p50_ms", "verdict_p90_ms"):
            m["workloads"].append(STAND_IN)
    shared = next(m for m in bench["per_layer"] if m["name"] == "verify_device_ms")
    shared["workloads"].append(STAND_IN)
    bench["per_layer"].append({
        "name": "stand_in_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "verifier", "moves": "verdict_p50_ms",
        "workloads": [STAND_IN]})
    return bench


BENCHES = ["as_committed", "a_cell_appended"]


def bench_for(which: str) -> dict:
    committed = spec.load_benchmark()
    return committed if which == "as_committed" else appended(committed)
