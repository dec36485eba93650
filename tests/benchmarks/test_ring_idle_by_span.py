"""The reader of the chip's idle time by the span of the request's path
that covers it (benchmarks/readers/ring_idle_by_span.py), on hand-written
rows: a profile of two requests and the span ring of the same run, the
ring's clock 1 ms ahead of the profile's."""

import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import profile_rows, spec, xplane  # noqa: E402
from benchmarks.readers import ring_idle_by_span as reader  # noqa: E402

OFFSET = 1_000_000.0  # ring ns - profile ns
A, B = "a" * 32, "b" * 32  # this request's trace, another request's

# two requests; the chip runs 1500..2500 and 7000..8000
REQUESTS = [(1000.0, 5000.0, xplane.REQUEST), (6000.0, 10000.0, xplane.REQUEST)]
CHIP = [(1500.0, 2500.0, "%fusion.1 = fusion()"),
        (7000.0, 8000.0, "%fusion.1 = fusion()")]
CALLER, SCHED, COLLECT, BUILD = 1, 2, 3, 4


def span(name, s, t, tid, trace=None, parent=None, wait=False):
    args = {"sid": int(s * 10 + tid)}
    if trace:
        args["trace_id"] = trace
    if parent is not None:
        args["parent"] = parent
    if wait:
        args["wait"] = True
    return {"name": name, "ph": "X", "ts": (s + OFFSET) / 1e3,
            "dur": (t - s) / 1e3, "tid": tid, "args": args}


def anchor(at):
    return span(reader.ANCHOR, at, at, CALLER)


# request one: every stretch named; request two: only its preparation
RING = [
    anchor(500.0),
    span("commit.prepare", 1000.0, 1200.0, CALLER),
    span("commit.assemble", 1200.0, 1400.0, CALLER),
    span("commit.verify", 1400.0, 4800.0, CALLER, wait=True),
    span("verify.svc.submit", 1400.0, 1450.0, CALLER, A, parent=1),
    span("verify.svc.queued", 1420.0, 1600.0, SCHED, A, parent=2),
    span("verify.sched.dispatch", 1600.0, 1700.0, SCHED, A, parent=2),
    span("verify.sched.collect", 1700.0, 4600.0, COLLECT, A, 2, wait=True),
    span("verify.device_wait", 1710.0, 4000.0, COLLECT, A, 3, wait=True),
    span("verify.sched.split", 4600.0, 4650.0, COLLECT, A, parent=2),
    span("verify.svc.wake", 4620.0, 4700.0, CALLER, A, parent=1),
    span("commit.judge", 4800.0, 4950.0, CALLER),
    # off the path: a background bind, another request's collect
    span("verify.table_build", 2600.0, 3000.0, BUILD),
    span("verify.sched.collect", 3000.0, 3500.0, COLLECT, B, parent=9),
    span("commit.prepare", 6000.0, 6500.0, CALLER),
]
# what the profile mirrored: the anchor and the program's spans
ANNOTATIONS = sorted(
    [(500.0, 500.0, reader.ANCHOR)]
    + [(e["ts"] * 1e3 - OFFSET, (e["ts"] + e["dur"]) * 1e3 - OFFSET, e["name"])
       for e in RING if e["name"] not in (reader.ANCHOR, "verify.svc.queued",
                                          "verify.svc.wake")]
)
WANT = {  # ns of idle time inside the requests, by owner
    "commit.prepare": 200.0 + 500.0, "commit.assemble": 200.0,
    "verify.svc.submit": 20.0, "verify.svc.queued": 80.0,
    "verify.device_wait": 1500.0, "verify.sched.collect": 600.0,
    "verify.sched.split": 20.0, "verify.svc.wake": 80.0,
    "commit.verify": 100.0, "commit.judge": 150.0,
    None: 50.0 + 500.0 + 2000.0,
}


def sources(ring=RING, annotations=ANNOTATIONS):
    trace = xplane.Trace(ops={"/device:TPU:0": list(CHIP)},
                         requests=list(REQUESTS))
    rows = profile_rows.Rows(annotations=list(annotations))
    return {"trace": trace, "profile_rows": rows, "spans": list(ring)}


def idle(ring=RING, annotations=ANNOTATIONS):
    src = sources(ring, annotations)
    return reader.reduce(src["trace"], annotations, src["spans"])


def test_the_latest_started_work_span_wins():
    got = idle()["idle"]
    # 1400..1420 the submit; from 1420 the queue interval, started later
    assert got["verify.svc.submit"] == 20.0
    assert got["verify.svc.queued"] == 30.0 + 50.0
    # 4600..4620 the split; from 4620 the caller's wake-up
    assert got["verify.sched.split"] == 20.0
    assert got["verify.svc.wake"] == 30.0 + 50.0


def test_a_wait_gets_only_what_no_work_span_covers():
    got = idle()["idle"]
    assert got["verify.device_wait"] == 1500.0  # 2500..4000
    assert got["verify.sched.collect"] == 600.0  # 4000..4600
    assert got["commit.verify"] == 100.0  # 4700..4800: only the caller waits


def test_a_span_whose_child_waits_is_not_at_work_meanwhile():
    """On one thread the innermost span says what the thread does: a
    span around the caller's wait (a light check around commit.verify)
    owns only its own stretches, and the collector's waits keep theirs."""
    ring = RING + [span("light.commit_check", 1390.0, 4960.0, CALLER)]
    got = idle(ring)["idle"]
    assert got["light.commit_check"] == 10.0 + 10.0  # 1390..1400, 4950..4960
    assert got["commit.assemble"] == WANT["commit.assemble"] - 10.0
    assert got[None] == WANT[None] - 10.0
    for name in ("verify.device_wait", "verify.sched.collect", "commit.verify",
                 "verify.svc.submit", "verify.svc.wake", "verify.sched.split",
                 "commit.judge"):
        assert got[name] == WANT[name], name
    assert sum(got.values()) == 6000.0


def test_other_traces_and_background_threads_are_off_the_path():
    got = idle()["idle"]
    assert "verify.table_build" not in got
    # the other request's collect started later and would have taken
    # 3000..3500 of the wait
    assert got == WANT
    # with a trace id of this request, the same span is on the path
    ring = RING + [span("verify.sched.collect", 3000.0, 3500.0, COLLECT, A,
                        parent=2)]
    assert idle(ring)["idle"]["verify.sched.collect"] == 600.0 + 500.0


def test_the_pieces_add_up_to_the_idle_time_inside_requests():
    got = idle()
    classes = profile_rows.idle_by_class(
        CHIP, REQUESTS, ANNOTATIONS, [["commit.assemble"]], 1000.0, 10000.0)
    assert sum(got["idle"].values()) == got["total"] == sum(classes) == 6000.0


def test_metrics_read_ms_per_request():
    src = sources()
    queued = {"spans": ["verify.svc.queued", "verify.staging.queued",
                        "verify.host.queued"]}
    assert reader.read(queued, src) == 80.0 / 1e6 / 2
    assert reader.read({"unnamed": True}, src) == 2550.0 / 1e6 / 2
    assert reader.read({"spans": ["light.fetch"]}, src) == 0.0
    assert "ring_idle_by_span" in src["profile_rows"].memo  # reduced once


def test_the_ring_lands_on_the_profiles_clock():
    got = idle()
    assert got["anchors"] == 1
    assert got["residual_ns"] and max(got["residual_ns"]) == 0.0
    pairs = reader.anchor_pairs(RING, ANNOTATIONS)
    assert reader.to_profile(pairs, 1234.0 + OFFSET) == 1234.0


def test_an_anchor_the_profile_lacks_does_not_move_the_clock():
    """The ring holds an anchor the profile lacks: one offset per ring
    anchor, each agreed on by one pair; the mirrored spans decide."""
    ring = [anchor(-2_000_000_000.0)] + RING
    pairs = reader.anchor_pairs(ring, ANNOTATIONS)
    assert pairs == [(500.0 + OFFSET, 500.0)]
    assert idle(ring)["idle"] == WANT
    # two anchors on each side: the offset both agree on
    ring = RING + [anchor(2_000_000_000.0)]
    annotations = ANNOTATIONS + [(2_000_000_000.0, 2_000_000_000.0,
                                  reader.ANCHOR)]
    assert len(reader.anchor_pairs(ring, annotations)) == 2


def test_a_program_without_anchors_gives_nothing():
    ring = [e for e in RING if e["name"] != reader.ANCHOR]
    assert reader.read({"unnamed": True}, sources(ring)) is None
    src = sources()
    src["trace"].requests = []
    assert reader.read({"unnamed": True}, src) is None


NEW = {
    "commit_prepare_ms": ("span_median", ["commit.prepare"]),
    "idle_queued_ms": ("ring_idle_by_span", [
        "verify.svc.queued", "verify.staging.queued", "verify.host.queued"]),
    "idle_handover_ms": ("ring_idle_by_span", [
        "verify.svc.submit", "verify.sched.add"]),
    "idle_result_ms": ("ring_idle_by_span", [
        "verify.sched.split", "verify.svc.wake"]),
    "idle_unnamed_ms": ("ring_idle_by_span", None),
    "idle_light_io_ms": ("ring_idle_by_span", ["light.fetch", "light.store"]),
}


def _program_names() -> set[str]:
    names = set()
    for root, _, files in os.walk(os.path.join(REPO, "cometbft_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    names |= set(re.findall(r'"((?:verify|commit|light)\.[a-z_.]+)"',
                                            fh.read()))
    return names


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_names_its_reader_and_spans_the_program_records(name):
    how = spec.load_json(os.path.join(
        REPO, "benchmarks", "layer_metrics", name + ".json"))
    reader_name, spans = NEW[name]
    assert how["reader"] == reader_name and how["what"]
    assert callable(spec.module("readers", how["reader"]).read)
    if spans is None:
        assert how["args"] == {"unnamed": True}
        return
    assert how["args"]["spans"] == spans
    assert set(spans) <= _program_names()


SERIAL_CELLS = ["commit-175-serial", "commit-10k-serial", "commit-175-moving-set",
                "commit-10k-churn"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_metric_is_listed_for_the_cells_whose_requests_it_splits(name):
    """Found by name in BENCHMARK.json, and on the list of every cell it
    reads: the light client's fetches and store in the walk alone, the
    rest in every cell."""
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    # the span's own length, or the chip's idle gaps from the profile that
    # the ring's spans split
    source = "program_span" if NEW[name][0] == "span_median" else "device_trace"
    assert (entry["unit"], entry["better"], entry["moves"], entry["source"]) == (
        "ms", "lower", "verdict_p50_ms", source)
    layer = "commit validation" if name == "commit_prepare_ms" else "device"
    assert entry["layer"] == layer
    cells = ["light-175-skip"] + ([] if name == "idle_light_io_ms" else SERIAL_CELLS)
    assert set(cells) <= set(entry["workloads"])
    for cell_name in cells:
        m = next(m for m in spec.resolve(cell_name, bench).per_layer
                 if m["name"] == name)
        assert m["reader"] == NEW[name][0]
