"""BENCHMARK.json against the contract's limits, and the rule that a
cell, a traffic mix and a per-layer metric are files found by name: a
later PR adds files and entries, and edits none."""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import spec  # noqa: E402

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    # the command names no file outside paths
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert word.startswith(tuple(p + "/" for p in BENCH["paths"]))


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
            # one line of 1 to 200 characters: a why, a layer, and the
            # source of a configuration (a metric's source is an enum)
            lines = ("why", "source") if group == "configs" else ("why", "layer")
            for key in lines:
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    assert {w["config"] for w in BENCH["workloads"]} == {
        c["name"] for c in BENCH["configs"]
    }


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    for cell in CELLS:
        here = [n for n, m in end.items() if cell in m.get("workloads", CELLS)]
        assert len(here) >= 2, cell
        assert any(cell in m.get("workloads", CELLS) for m in BENCH["per_layer"])
    # what a per-layer metric moves is reported wherever the metric is
    for m in BENCH["per_layer"]:
        moved = end[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS)), m


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_list_stays_inside_the_list_of_what_it_moves(metric):
    """A traced run of a cell that does not report the moved end-to-end
    metric could not show the layer metric moving it."""
    m = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
    assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS))
    assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_its_files_by_name(cell_name):
    cell = spec.resolve(cell_name)
    workload = next(w for w in BENCH["workloads"] if w["name"] == cell_name)
    assert cell.config["name"] == workload["config"]
    assert cell.config["reduced"] == next(
        c["reduced"] for c in BENCH["configs"] if c["name"] == workload["config"]
    )
    assert cell.driver.__name__ == "benchmarks.drivers." + cell.traffic["driver"]
    for hook in ("setup", "warm", "run", "finish", "end_to_end"):
        assert callable(getattr(cell.driver, hook))
    assert cell.per_layer and all(callable(
        spec.module("readers", m["reader"]).read) for m in cell.per_layer)
    assert {"setup_s"} < {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("name", [
    c["name"] for c in BENCH["configs"]
])
def test_config_file_states_source_shapes_and_guarantees(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    config = spec.load_json(os.path.join(REPO, entry["file"]))
    for key in ("source", "validators", "key_type", "reduced", "assumed",
                "guarantees", "commit_shape"):
        assert key in config, key
    assert config["name"] == name and config["reduced"] == entry["reduced"]


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [x for x in dirs if x not in ("__pycache__", ".trace")]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert ok.match(rel), rel


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.resolve("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.module("drivers", "no_such_driver")


def test_a_new_cell_and_a_new_span_metric_are_new_files_only(tmp_path):
    """What a later PR does: one more traffic file, one more metric
    file, two more entries; no file that was there is edited, and the
    harness takes them."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for d in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", d),
                        tmp_path / "benchmarks" / d)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    (tmp_path / "benchmarks/traffic/serial-pool2.json").write_text(json.dumps(
        {"driver": "commit_serial", "pool": 2, "warm_s": 3}))
    (tmp_path / "benchmarks/layer_metrics/sched_dispatch_ms.json").write_text(
        json.dumps({"reader": "span_median",
                    "args": {"per": "span", "spans": ["verify.sched.dispatch"]}}))
    bench["workloads"].append({
        "name": "commit-175-pool2", "config": "valset-175-ed25519",
        "traffic": "serial-pool2", "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("commit-175-pool2")
    bench["per_layer"].append({
        "name": "sched_dispatch_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "verdict_p50_ms", "workloads": ["commit-175-pool2"]})
    cell = spec.resolve("commit-175-pool2", bench, root=str(tmp_path))
    assert cell.traffic["pool"] == 2
    assert cell.driver.__name__ == "benchmarks.drivers.commit_serial"
    assert [m["name"] for m in cell.per_layer] == ["sched_dispatch_ms"]
    assert {m["name"] for m in cell.end_to_end} == {
        "verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    # and the reader it names reads a ring as it stands
    from benchmarks.readers import span_median

    ring = [{"name": "verify.sched.dispatch", "ph": "X", "ts": 0.0, "dur": d}
            for d in (100.0, 300.0, 200.0)]
    assert span_median.read(cell.per_layer[0]["args"], {"spans": ring}) == 0.2
