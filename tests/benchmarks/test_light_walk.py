"""The cell ``light-175-skip`` on the CPU: it resolves through
BENCHMARK.json, its driver runs at a small size through
``harness.run_cell``, and each control (a guarantee broken) comes out
not correct: a pass answered from the host, a client that accepts every
hop, a tampered header left in the chain.  At 40 validators with the
device floor lowered (here, never in the benchmark) the walk runs the
comb program at 128 lanes and is correct.
"""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, light_chain, spec  # noqa: E402
from benchmarks import reference_light as ref  # noqa: E402
from benchmarks.drivers import light_walk  # noqa: E402
from benchmarks.readers import span_median_within  # noqa: E402
from tests.benchmarks.cell_appended import BENCHES, bench_for  # noqa: E402

CELL = "light-175-skip"
LIGHT_METRICS = {
    "light_header_check_ms", "light_trusting_check_ms", "light_commit_check_ms",
    "light_refused_hop_ms", "light_hops_per_walk",
}


@pytest.fixture
def fresh(monkeypatch):
    """A fresh metrics hub, a fresh global verify service and an empty
    span ring around a run_cell."""
    from cometbft_tpu.utils import metrics, tracing
    from cometbft_tpu.verifysvc import service as svc_mod

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    svc_mod.reset_global_service()
    was_on = tracing.enabled()
    yield
    svc_mod.reset_global_service()
    tracing.set_enabled(was_on)
    tracing.reset()


def small_cell(width: int, heights: int = 64, warm_s: float = 0.05):
    cell = spec.resolve(CELL)
    cell.config = dict(cell.config, validators=width, heights=heights,
                       trusted_height=1)
    cell.traffic = dict(cell.traffic, warm_s=warm_s)
    return cell


def run_small(cell, seconds=0.5, seed=(1 << 31) + 29):
    import jax

    return harness.run_cell(
        cell, seed, seconds, False, time.monotonic(), jax.devices())


@pytest.fixture(params=BENCHES)
def bench(request):
    return bench_for(request.param)


def test_the_cell_resolves_to_its_own_files(bench):
    cell = spec.resolve(CELL, bench)
    assert cell.driver is light_walk and cell.chips == 1
    assert cell.config["name"] == "light-175-skipping"
    assert (cell.config["validators"], cell.config["heights"]) == (175, 1000)
    assert cell.config["reduced"] == [] == next(
        c["reduced"] for c in bench["configs"]
        if c["name"] == cell.config["name"])
    assert cell.config["trusted_height"] == 1
    assert len(cell.config["guarantees"]) >= 5
    assert cell.config["client"]["trust_level"] == [1, 3]
    assert cell.traffic["driver"] == "light_walk" and cell.traffic["warm_s"] == 5
    assert {m["name"] for m in cell.end_to_end} == {
        "verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert LIGHT_METRICS <= names and "verify_device_ms" in names
    assert "validation_host_ms" not in names  # several verifies a request
    for m in cell.per_layer:
        if m["name"] in LIGHT_METRICS:
            assert m["layer"] == "light client"
            assert m["reader"] == "span_median_within"
    # no cell that was there reads the new metrics
    assert not LIGHT_METRICS & {
        m["name"] for m in spec.resolve("commit-175-serial", bench).per_layer}
    config = next(c for c in bench["configs"] if c["name"] == cell.config["name"])
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for entry in (config, workload):
        assert len(entry["why"]) <= 200 and len(entry.get("source", "")) <= 200


def test_a_host_routed_walk_is_right_and_not_correct(fresh):
    """Twelve validators take the program's host route: every walk goes
    as the reference's, and the run still says not correct."""
    result, facts = run_small(small_cell(12))
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["correct"] is False
    assert result["compared"]["batches_off_the_device"]["value"] > 0
    assert result["compared"]["requests_failed"]["value"] == 0
    assert set(result["metrics"]) == {"verdict_p50_ms", "verdict_p90_ms", "setup_s"}
    walk = facts["end_to_end"]["light_walk"]
    assert walk["hops"]["accepted"] + walk["hops"]["refused"] >= 10
    assert walk["first_walk_s"] > 0 and walk["window_cache"] == {}
    assert list(result)[-1] == "compared"


def test_a_client_that_accepts_every_hop_is_not_correct(fresh, monkeypatch):
    """After set-up the verifier accepts whatever it is given: the
    client jumps to the target at once, which is not the reference's
    walk, so every request fails; and the tampered chains are accepted
    after the window."""
    from cometbft_tpu.light import client as light_client

    setup = light_walk.setup

    def setup_then_break(cell, seed, log):
        state = setup(cell, seed, log)
        monkeypatch.setattr(light_client, "verify", lambda *a, **kw: None)
        return state

    monkeypatch.setattr(light_walk, "setup", setup_then_break)
    result, facts = run_small(small_cell(12, warm_s=0))
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert any(p.startswith("after the window:") and "accepted" in p
               for p in facts["problems"])


def test_a_tampered_header_left_in_the_chain_is_not_correct(fresh, monkeypatch):
    setup = light_walk.setup

    def setup_then_tamper(cell, seed, log):
        state = setup(cell, seed, log)
        b = next(b for a, b, r in state.want.hops if r.kind == ref.OK)
        rows, _ = ref.commit_rows(state.chain.block(b))
        state.chain._light[b] = light_chain.light_block(
            state.chain.flipped(b, [rows[0][0]]))
        return state

    monkeypatch.setattr(light_walk, "setup", setup_then_tamper)
    result, _ = run_small(small_cell(12, warm_s=0))
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert result["compared"]["requests_failed"]["value"] == result["failed"]


def test_a_walk_through_the_comb_program_is_correct(fresh, monkeypatch):
    """Forty validators bind (the default floor is 32); with the device
    floor lowered their 14 to 27 live rows a pass run the comb program
    at 128 lanes: five sets bound by the first walk, none inside the
    window, no host route."""
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    result, facts = run_small(small_cell(40), seconds=1.0)
    assert facts["problems"] == []
    assert result["correct"] is True and result["failed"] == 0
    walk = facts["end_to_end"]["light_walk"]
    assert walk["first_walk_cache"]["comb_table_cache.miss"] == walk["blocks_fetched"]
    assert "comb_table_cache.miss" not in walk["window_cache"]
    assert "comb_program_cache.compile" not in walk["window_cache"]
    assert walk["window_cache"]["comb_table_cache.hit"] > 0
    assert facts["route"]["verify_host_route"] == 0


def _span(name, ts, dur, tid=1, **labels):
    e = {"ph": "X", "name": name, "ts": ts, "dur": dur, "tid": tid}
    if labels:
        e["args"] = labels
    return e


def test_span_median_within_reads_what_its_arguments_say():
    spans = [
        _span("light.walk", 0, 1000),
        _span("light.hop", 10, 100, result="cant_be_trusted"),
        _span("light.header_check", 12, 30),
        _span("light.hop", 200, 300, result="ok"),
        _span("light.header_check", 205, 10),
        _span("light.trusting_check", 220, 120),
        _span("light.hop", 600, 200, result="ok"),
        _span("light.header_check", 601, 20),
        _span("light.walk", 2000, 500),
        _span("light.hop", 2010, 50, result="cant_be_trusted"),
        _span("light.header_check", 205, 999, tid=2),  # another thread
    ]
    read = lambda **args: span_median_within.read(args, {"spans": spans})
    ok = {"within": "light.hop", "where": {"result": "ok"}}
    assert read(spans=["light.header_check"], **ok) == pytest.approx(0.015)
    assert read(spans=["light.trusting_check"], **ok) == pytest.approx(0.120)
    assert read(spans=["light.hop"],
                where={"result": "cant_be_trusted"}) == pytest.approx(0.075)
    assert read(spans=["light.hop"], within="light.walk", count=True) == 2.0
    assert read(spans=["light.commit_check"], **ok) is None
    assert span_median_within.read(
        {"spans": ["light.hop"], "within": "light.walk", "count": True},
        {"spans": []}) is None  # a program without the spans: nothing


def test_same_seed_same_chain_and_no_length_depends_on_it():
    cfg = dict(spec.resolve(CELL).config, validators=6,
               heights=8,
               trusted_height=1)
    a, b = light_chain.Chain(cfg, 11), light_chain.Chain(cfg, 11)
    c = light_chain.Chain(cfg, (1 << 31) + 5)
    wire = [light_chain.encode(x.block(3)) for x in (a, b, c)]
    assert wire[0] == wire[1] != wire[2] and len(wire[0]) == len(wire[2])
    assert len(a.block(3).sign_bytes(0)) == len(c.block(8).sign_bytes(5))
