"""Span tracer (utils/tracing), consensus flight recorder
(utils/flightrec), the crash-report bundle (utils/debugdump), the
/dump_consensus_trace RPC route, and the trace_verify_pipeline script
smoke — the observability plane of PR 2.

The tracer is process-global (like the metrics hub), so every test
restores the disabled default and clears the ring on exit.
"""

import json
import os
import threading

import pytest

from cometbft_tpu.utils import tracing
from cometbft_tpu.utils.flightrec import FlightRecorder, recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    tracing.set_enabled(False, ring_capacity=65536)
    tracing.reset()


# ------------------------------------------------------------------ tracer


def test_export_carries_wall_clock_anchor(tmp_path):
    """Every export carries one wall_clock_anchor metadata record — a
    (wall_ns, perf_ns) pair sampled at one instant — so the pure
    perf_counter trace timeline can be correlated with flight-recorder
    wall_ns entries and log timestamps."""
    import time as _time

    tracing.set_enabled(True)
    tracing.reset()
    with tracing.span("anchored"):
        pass
    events = tracing.chrome_trace_events()
    anchors = [e for e in events if e["name"] == "wall_clock_anchor"]
    assert len(anchors) == 1
    a = anchors[0]
    assert a["ph"] == "M"  # metadata: no timeline footprint of its own
    args = a["args"]
    # both clocks sampled "now": each within a generous bound of a fresh
    # reading, and the pair coherent enough to reconstruct wall time of
    # the span to sub-second accuracy
    assert abs(args["wall_time_ns"] - _time.time_ns()) < 5e9
    assert abs(args["perf_counter_ns"] - _time.perf_counter_ns()) < 5e9
    span_ev = next(e for e in events if e["name"] == "anchored")
    wall_of_span = args["wall_time_ns"] + (
        span_ev["ts"] * 1e3 - args["perf_counter_ns"]
    )
    assert abs(wall_of_span - _time.time_ns()) < 5e9
    # metadata records stay excluded from the exported span count
    path = str(tmp_path / "anchored.trace.json")
    n = tracing.export_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)
    assert n == sum(1 for e in doc["traceEvents"] if e["ph"] != "M")
    assert any(
        e["name"] == "wall_clock_anchor" for e in doc["traceEvents"]
    )


def test_disabled_path_is_shared_noop():
    """Trace off (the default): span() must return one shared no-op
    object — no allocation, no clock read — and record nothing."""
    tracing.set_enabled(False)
    tracing.reset()
    s1 = tracing.span("hot.path")
    s2 = tracing.span("other")
    assert s1 is s2, "disabled span must be a shared singleton"
    with s1:
        pass
    tracing.instant("marker")
    evs = [e for e in tracing.chrome_trace_events() if e["ph"] != "M"]
    assert evs == []


def test_span_nesting_and_chrome_schema(tmp_path):
    tracing.set_enabled(True)
    tracing.reset()
    with tracing.span("outer", {"height": 5}):
        with tracing.span("inner"):
            pass
        tracing.instant("mark", {"kind": "x"})
    path = str(tmp_path / "t.trace.json")
    n = tracing.export_chrome_trace(path)
    assert n == 3
    with open(path) as f:
        doc = json.load(f)
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "mark"}
    for e in evs:  # the Chrome trace-event required fields
        assert {"ph", "name", "cat", "pid", "tid", "ts"} <= set(e)
    outer, inner, mark = by_name["outer"], by_name["inner"], by_name["mark"]
    assert outer["ph"] == "X" and "dur" in outer
    assert mark["ph"] == "i" and mark["s"] == "t" and "dur" not in mark
    assert outer["args"] == {"height": 5, "sid": outer["args"]["sid"]}
    # nesting: inner lies within outer on the same thread track
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    # thread-name metadata present for the recording thread
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(
        m["tid"] == outer["tid"] and m["args"]["name"]
        for m in metas
        if m["name"] == "thread_name"
    )


def test_ring_bounds_memory_and_keeps_newest():
    tracing.set_enabled(True, ring_capacity=100)
    tracing.reset()
    for i in range(500):
        tracing.instant(f"e{i}")
    evs = [e for e in tracing.chrome_trace_events() if e["ph"] != "M"]
    assert len(evs) <= 100
    assert tracing.dropped_count() >= 400
    names = {e["name"] for e in evs}
    assert "e499" in names and "e0" not in names  # FIFO eviction


def test_cross_thread_spans_drain_on_export():
    """Events buffered thread-locally must all appear in one export,
    tagged with their own tid."""
    tracing.set_enabled(True)
    tracing.reset()

    def work():
        with tracing.span("worker.span"):
            pass

    t = threading.Thread(target=work, name="trace-worker")
    t.start()
    t.join()
    with tracing.span("main.span"):
        pass
    evs = [e for e in tracing.chrome_trace_events() if e["ph"] != "M"]
    by_name = {e["name"]: e for e in evs}
    assert {"worker.span", "main.span"} <= set(by_name)
    assert by_name["worker.span"]["tid"] != by_name["main.span"]["tid"]


# ------------------------------------------ what caused what, and waits


def _x(name):
    return next(e for e in tracing.chrome_trace_events()
                if e["ph"] == "X" and e["name"] == name)


def test_nested_spans_name_their_parent():
    tracing.set_enabled(True)
    tracing.reset()
    with tracing.span("outer"):
        with tracing.span("inner"):
            tracing.instant("mark")
        with tracing.span("second"):
            pass
    evs = {e["name"]: e for e in tracing.chrome_trace_events() if e["ph"] != "M"}
    outer, inner, second = evs["outer"]["args"], evs["inner"]["args"], evs["second"]["args"]
    assert "parent" not in outer
    assert inner["parent"] == second["parent"] == outer["sid"]
    assert len({outer["sid"], inner["sid"], second["sid"]}) == 3
    assert evs["mark"]["args"] == {"parent": inner["sid"]}  # no id of its own
    with tracing.span("after"):  # the thread's current span was restored
        pass
    assert "parent" not in _x("after").get("args", {})


def test_a_span_that_raises_restores_its_parent():
    tracing.set_enabled(True)
    tracing.reset()
    with tracing.span("outer"):
        with pytest.raises(ValueError):
            with tracing.span("failing"):
                raise ValueError("x")
        with tracing.span("next"):
            pass
    assert _x("next")["args"]["parent"] == _x("outer")["args"]["sid"]


def test_carry_context_hands_the_current_span_to_another_thread():
    """The work a span hands to another thread names it as its parent,
    and carries the trace id where a context is installed."""
    from concurrent.futures import ThreadPoolExecutor

    tracing.set_enabled(True)
    tracing.reset()

    def work():
        with tracing.span("worker.span"):
            pass

    root = tracing.new_context()
    with ThreadPoolExecutor(1) as pool:
        with tracing.span("plain.hand"):
            pool.submit(tracing.carry_context(work)).result()
        with tracing.context_scope(root), tracing.span("traced.hand"):
            pool.submit(tracing.carry_context(work)).result()
        pool.submit(work).result()  # nothing carried: no parent
    workers = [e for e in tracing.chrome_trace_events()
               if e["name"] == "worker.span"]
    assert [w["args"].get("parent") for w in workers] == [
        _x("plain.hand")["args"]["sid"], _x("traced.hand")["args"]["sid"], None]
    assert "trace_id" not in workers[0]["args"]
    assert workers[1]["args"]["trace_id"] == root.trace_id
    assert "trace_id" not in workers[2]["args"]
    assert tracing.current_context() is None


def test_carry_context_with_tracing_off_is_the_function_itself():
    tracing.set_enabled(False)

    def work():
        pass

    assert tracing.carry_context(work) is work


def test_a_handoff_context_parents_the_receivers_spans():
    """What a queued request carries: the receiver's spans under it
    name the submitter's span; a context not made by handoff() (one off
    the wire) names no parent."""
    tracing.set_enabled(True)
    tracing.reset()
    root = tracing.new_context()
    with tracing.context_scope(root), tracing.span("submit"):
        ctx = tracing.handoff()
    assert ctx.trace_id == root.trace_id and ctx == root  # same identity

    def receive(c, name):
        with tracing.context_scope(c), tracing.span(name):
            pass

    for c, name in ((ctx, "received"), (root, "from.wire")):
        t = threading.Thread(target=receive, args=(c, name))
        t.start()
        t.join()
    got = _x("received")["args"]
    assert got["parent"] == _x("submit")["args"]["sid"]
    assert got["trace_id"] == root.trace_id
    assert "parent" not in _x("from.wire")["args"]
    assert tracing.handoff() is None  # no context installed here


def test_a_wait_is_an_arg_and_not_a_name():
    tracing.set_enabled(True)
    tracing.reset()
    with tracing.span("device.wait", wait=True):
        pass
    with tracing.phase("host.work", "assembly"):
        pass
    assert _x("device.wait")["args"]["wait"] is True
    assert "wait" not in _x("host.work")["args"]


@pytest.mark.parametrize("on", [True, False])
def test_interval_records_a_ring_span_from_an_earlier_stamp(
    monkeypatch, annotations, on
):
    """Tracing off, interval() reads no clock and records nothing (and
    a call site that stamped nothing passes 0); on, the span runs from
    the stamp to now, in the ring only, under the thread's span."""
    tracing.set_enabled(on)
    tracing.reset()
    clock = _CountingClock()
    with tracing.span("holder"):
        with monkeypatch.context() as m:
            m.setattr(tracing, "time", clock)
            assert (tracing.stamp() != 0) is on
            tracing.interval("queue.wait", 250_000)
            tracing.interval("not.stamped", 0)
            tracing.interval("given.end", 250_000, 750_000, {"k": 1})
    got = [e for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    if not on:
        assert clock.reads == 0 and got == []
        return
    assert clock.reads == 2  # the stamp, and "now" for the first interval
    by = {e["name"]: e for e in got}
    assert set(by) == {"holder", "queue.wait", "given.end"}
    # the stamp read 1 ms, "now" 4 ms
    assert (by["queue.wait"]["ts"], by["queue.wait"]["dur"]) == (250.0, 3750.0)
    assert (by["given.end"]["ts"], by["given.end"]["dur"]) == (250.0, 500.0)
    assert by["given.end"]["args"]["k"] == 1
    for name in ("queue.wait", "given.end"):
        assert by[name]["args"]["parent"] == by["holder"]["args"]["sid"]
    assert [a[1] for a in annotations] == ["holder", "holder"]  # not mirrored


# ------------------------------------ the profiler's clock, one convention


class _FakeAnnotation:
    """Stands where jax.profiler.TraceAnnotation stands: records who
    entered and left what, on which thread."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name, threading.get_ident()))
        return False


@pytest.fixture
def annotations(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(tracing, "_ANNOTATION", _FakeAnnotation)
    return _FakeAnnotation.log


def test_recording_span_mirrors_one_annotation_on_its_own_thread(annotations):
    tracing.set_enabled(True)
    tracing.reset()

    def work():
        with tracing.span("worker.phase", {"k": 1}):
            pass

    t = threading.Thread(target=work)
    t.start()
    t.join()
    with tracing.span("caller.phase"):
        tracing.instant("not.mirrored")
    me = threading.get_ident()
    assert annotations == [
        ("enter", "worker.phase", t.ident), ("exit", "worker.phase", t.ident),
        ("enter", "caller.phase", me), ("exit", "caller.phase", me),
    ]
    names = [e["name"] for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    assert sorted(names) == ["caller.phase", "worker.phase"]


def test_disabled_span_touches_no_annotation(annotations):
    tracing.set_enabled(False)
    s = tracing.span("hot.path")
    assert s is tracing.span("other")  # the shared no-op, as before
    with s:
        pass
    assert annotations == []


def test_real_annotation_is_resolved_when_tracing_is_turned_on(monkeypatch):
    """JAX is looked up by set_enabled(True), not at import and not in a
    span; with JAX here that is jax.profiler.TraceAnnotation, and a
    span under it records as before."""
    monkeypatch.setattr(tracing, "_ANNOTATION", tracing._UNRESOLVED)
    tracing.set_enabled(True)
    from jax.profiler import TraceAnnotation

    assert tracing._ANNOTATION is TraceAnnotation
    tracing.reset()
    with tracing.span("real.annotation"):
        pass
    assert [e["name"] for e in tracing.chrome_trace_events()
            if e["ph"] == "X"] == ["real.annotation"]


def test_jax_is_imported_when_tracing_is_on_and_never_at_import():
    """A process that never traces never pays for JAX here; under
    COMETBFT_TPU_TRACE the first recording span looks it up."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import cometbft_tpu.utils.tracing as t\n"
        "assert 'jax' not in sys.modules\n"
        "with t.span('x'): pass\n"
        "print(t.enabled(), 'jax' in sys.modules)\n"
    )
    for trace, want in (("", "False False"), ("1", "True True")):
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=REPO, timeout=120,
            env=dict(os.environ, COMETBFT_TPU_TRACE=trace, JAX_PLATFORMS="cpu"),
        )
        assert r.returncode == 0, r.stderr
        assert r.stdout.strip() == want


class _CountingClock:
    """perf_counter_ns that counts its reads: 1 ms, then 4 ms, ..."""

    def __init__(self):
        self.reads = 0

    def perf_counter_ns(self):
        self.reads += 1
        return 1_000_000 + 3_000_000 * (self.reads - 1)

    def time_ns(self):
        return 0


@pytest.mark.parametrize("on", [True, False])
def test_phase_reads_the_clock_twice_and_feeds_everyone_the_same(
    monkeypatch, annotations, on
):
    from cometbft_tpu.utils import metrics

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    tracing.set_enabled(on)
    tracing.reset()
    clock = _CountingClock()
    with monkeypatch.context() as m:  # the export below reads the real clock
        m.setattr(tracing, "time", clock)
        with tracing.phase("verify.slab_fill", "assembly") as fill:
            pass
    assert clock.reads == 2
    assert fill.ns == 3_000_000  # the duration stays on the phase
    hist = metrics.hub().verify_phase_seconds
    k = hist._label_key({"phase": "assembly"})
    assert hist._totals[k] == 1 and hist._sums[k] == pytest.approx(0.003)
    spans = [e for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    if on:
        assert [(e["name"], e["dur"]) for e in spans] == [
            ("verify.slab_fill", 3000.0)]
        assert [a[:2] for a in annotations] == [
            ("enter", "verify.slab_fill"), ("exit", "verify.slab_fill")]
    else:
        assert spans == [] and annotations == []


def test_phase_that_raises_closes_its_span_and_feeds_no_duration(monkeypatch):
    from cometbft_tpu.utils import metrics

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    tracing.set_enabled(True)
    tracing.reset()
    with pytest.raises(RuntimeError):
        with tracing.phase("verify.device_wait", "device_wait", wait=True):
            raise RuntimeError("lost device")
    assert metrics.hub().verify_phase_seconds._totals == {}
    spans = [e for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["verify.device_wait"]
    assert spans[0]["args"]["wait"] is True


@pytest.mark.parametrize("on", [True, False])
def test_verify_commit_leaves_the_commit_spans_nested_as_written(on):
    """commit.prepare, commit.assemble, commit.verify, commit.judge: in
    that order, one after the other on the caller's thread, and the
    verifier's spans of that thread inside commit.verify; none with
    tracing off."""
    from test_types import _keys, _signed_commit, _valset

    import cometbft_tpu.types as T

    keys = _keys(4)
    vals = _valset(keys)
    bid, commit = _signed_commit(keys, vals)
    tracing.set_enabled(on)
    tracing.reset()
    T.verify_commit("test-chain", vals, bid, 5, commit)
    spans = [e for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    mine = [e for e in spans if e["name"].startswith("commit.")]
    if not on:
        assert spans == []
        return
    assert [e["name"] for e in mine] == [
        "commit.prepare", "commit.assemble", "commit.verify", "commit.judge"]
    assert len({e["tid"] for e in mine}) == 1
    for a, b in zip(mine, mine[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
    verify = mine[2]
    assert verify["args"]["wait"] is True
    assert not any("wait" in e.get("args", {}) for e in mine if e is not verify)
    for e in spans:
        if e["tid"] == verify["tid"] and e["name"].startswith("verify."):
            assert verify["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= verify["ts"] + verify["dur"] + 1e-6


def test_pending_light_verification_leaves_the_commit_spans(monkeypatch):
    """The pipelined path: commit.prepare, commit.assemble and
    commit.verify (the submit) at submit time, commit.verify (the
    collect) and commit.judge at collect time."""
    from test_types import _keys, _signed_commit, _valset

    from cometbft_tpu.types import validation

    class Seam:
        def __init__(self):
            self.items = []

        def add(self, pk, msg, sig):
            self.items.append((pk, msg, sig))

        def submit(self):
            return "ticket"

        def collect(self, ticket):
            return True, [True] * len(self.items)

    monkeypatch.setattr(
        validation.crypto_batch, "create_batch_verifier",
        lambda *a, **kw: Seam(),
    )
    keys = _keys(4)
    vals = _valset(keys)
    bid, commit = _signed_commit(keys, vals)
    tracing.set_enabled(True)
    tracing.reset()
    pending = validation.submit_verify_commit_light(
        "test-chain", vals, bid, 5, commit)
    pending.collect()
    assert [e["name"] for e in tracing.chrome_trace_events()
            if e["ph"] == "X"] == [
        "commit.prepare", "commit.assemble", "commit.verify", "commit.verify",
        "commit.judge"]


@pytest.mark.parametrize("lane", ["uncached", "comb"])
def test_host_route_is_counted_by_lane(monkeypatch, lane):
    """A batch under DEVICE_BATCH_MIN leaves the span it always left and
    now a counter, so /metrics shows the route without the ring."""
    from cometbft_tpu.crypto import ed25519 as host
    from cometbft_tpu.models import verifier
    from cometbft_tpu.utils import metrics

    monkeypatch.setattr(metrics, "_HUB", metrics.Hub())
    tracing.set_enabled(True)
    tracing.reset()
    priv = host.PrivKey.from_seed(b"\x07" * 32)
    msg = b"m"
    items = [(priv.pub_key().bytes(), msg, priv.sign(msg))]
    assert verifier.host_route(items, lane) == (True, [True])
    c = metrics.hub().verify_host_route
    assert c.value(lane=lane, reason="below_batch_min") == 1.0
    assert "cometbft_verify_host_route_total" in (
        metrics.hub().registry.expose_text())
    assert [e["name"] for e in tracing.chrome_trace_events()
            if e["ph"] == "X"] == ["verify.host_route"]


class _ProfilerAnnotation(_FakeAnnotation):
    """A profiler that records: each annotation's start on a clock of
    its own, offset from perf_counter_ns by a constant."""

    OFFSET = 123_456_789_000
    starts: list = []

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        import time as _time

        _ProfilerAnnotation.starts.append(
            (_time.perf_counter_ns() - self.OFFSET, self.name))
        return self


def test_the_anchor_maps_a_ring_event_onto_the_profilers_clock(monkeypatch):
    """While the profiler records, the first span to end writes one
    clock anchor (a ring event and an annotation) and no other within a
    second; its pair places each ring span within microseconds of the
    start the profiler gave that span's annotation."""
    import sys

    sys.path.insert(0, REPO)
    from benchmarks.readers import ring_idle_by_span as reader

    _ProfilerAnnotation.starts = []
    monkeypatch.setattr(tracing, "_ANNOTATION", _ProfilerAnnotation)
    monkeypatch.setattr(tracing, "_PROFILING", _ProfilerAnnotation.is_enabled)
    tracing.set_enabled(True)
    tracing.reset()
    for i in range(5):
        with tracing.span(f"step.{i}"):
            pass
    ring = [e for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    anchors = [e for e in ring if e["name"] == tracing.ANCHOR]
    assert len(anchors) == 1 and anchors[0]["dur"] == 0
    args = anchors[0]["args"]
    # the export writes ts as perf_ns / 1e3 (microseconds); the product
    # back need not round-trip to the integer, so the check divides
    assert anchors[0]["ts"] == args["perf_ns"] / 1e3 and args["wall_ns"] > 0
    profile = [(s, s, name) for s, name in _ProfilerAnnotation.starts]
    pairs = reader.anchor_pairs(ring, profile)
    assert len(pairs) == 1
    got = {name: s for s, _, name in profile}
    for e in ring:
        if e["name"].startswith("step."):
            mapped = reader.to_profile(pairs, e["ts"] * 1e3)
            # what is left is how long entering an annotation took
            assert abs(mapped - got[e["name"]]) < 50_000


def test_no_anchor_while_the_profiler_is_off(monkeypatch, annotations):
    monkeypatch.setattr(tracing, "_PROFILING", lambda: False)
    tracing.set_enabled(True)
    tracing.reset()
    with tracing.span("x"):
        pass
    assert [e["name"] for e in tracing.chrome_trace_events()
            if e["ph"] == "X"] == ["x"]


# --------------------------------------------------------- flight recorder


def test_flight_recorder_bounded_dump_is_json():
    fr = FlightRecorder(capacity=4)
    for h in range(10):
        fr.record("step", height=h, round=0, step=1, note=f"n{h}")
    d = fr.dump()
    assert d["count"] == 4 and d["capacity"] == 4 and d["evicted"] == 6
    assert [e["height"] for e in d["entries"]] == [6, 7, 8, 9]
    assert d["entries"][0]["seq"] == 7  # seq keeps counting across eviction
    e = d["entries"][-1]
    assert e["kind"] == "step" and e["wall_ns"] > 0
    assert e["detail"] == {"note": "n9"}
    json.dumps(d)  # the RPC returns this verbatim: must serialize as-is


def test_flight_recorder_votes_do_not_evict_control_events():
    """A flood of per-signature vote arrivals (the 10k-validator case)
    must never push step/timeout history out of the recorder."""
    fr = FlightRecorder(capacity=8, vote_capacity=4)
    fr.record("step", height=1, round=0, step=1)
    for i in range(100):
        fr.record("vote", height=1, round=0, vote_type=1, val_index=i)
    fr.record("timeout", height=1, round=0, step=3)
    d = fr.dump()
    kinds = [e["kind"] for e in d["entries"]]
    assert kinds.count("step") == 1 and kinds.count("timeout") == 1
    assert kinds.count("vote") == 4  # newest votes, bounded by their ring
    assert d["votes_evicted"] == 96 and d["evicted"] == 0
    seqs = [e["seq"] for e in d["entries"]]
    assert seqs == sorted(seqs)  # merged dump keeps arrival order


def test_rpc_dump_consensus_trace_route():
    from cometbft_tpu.rpc.core import ROUTES, Environment

    rec = recorder()
    rec.clear()
    rec.record("timeout", height=3, round=1, step=4, stale=False)
    params, fn = ROUTES["dump_consensus_trace"]
    assert params == ""
    out = fn(Environment(None))  # handler touches no node state
    # >= rather than ==: the recorder is process-global and a lingering
    # background thread from an earlier test may also have recorded
    assert out["count"] >= 1
    assert any(
        e["kind"] == "timeout" and e["height"] == 3 for e in out["entries"]
    )
    json.dumps(out)
    rec.clear()


def test_crash_report_bundles_flight_recorder(tmp_path):
    from cometbft_tpu.utils import debugdump

    rec = recorder()
    rec.clear()
    rec.record("vote", height=7, round=0, step=0, val_index=3)
    path = debugdump.crash_report("test-crash-reason", directory=str(tmp_path))
    try:
        with open(path) as f:
            text = f.read()
        assert "test-crash-reason" in text
        assert '"kind": "vote"' in text
        assert "=== threads ===" in text and "thread" in text
    finally:
        rec.clear()
        os.unlink(path)


def test_ticker_fire_counts_step_metric():
    """Satellite: every fired timeout bumps the per-step counter."""
    from cometbft_tpu.consensus.ticker import TimeoutInfo, TimeoutTicker
    from cometbft_tpu.utils.metrics import hub

    fired = threading.Event()
    t = TimeoutTicker(lambda ti: fired.set())
    before = hub().cs_timeout_fired.value(step="3")
    t.schedule(TimeoutInfo(0.01, 1, 0, 3))
    assert fired.wait(5.0), "timeout must fire"
    t.stop()
    assert hub().cs_timeout_fired.value(step="3") == before + 1


# ------------------------------------------------- trace script smoke test


def test_trace_verify_pipeline_script_smoke(tmp_path, monkeypatch):
    """CI satellite: the synthetic-load script must produce a Chrome
    trace whose spans cover >= 5 distinct verify-pipeline phases.  Tiny
    scale, comb path forced (V=8 reuses the compiled shapes of
    test_comb_smoke / test_comb_pipeline, so a warm cache keeps this
    fast-tier)."""
    monkeypatch.setenv("COMETBFT_TPU_COMB_MIN", "4")
    monkeypatch.setenv("COMETBFT_TPU_DEVICE_BATCH_MIN", "1")
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_verify_pipeline",
        os.path.join(REPO, "scripts", "trace_verify_pipeline.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = str(tmp_path / "verify.trace.json")
    res = mod.run(n_validators=8, iters=2, out_path=out)
    assert res["events"] > 0 and res["path"] == out
    pipeline = {p for p in res["phases"] if p.startswith("verify.")}
    assert len(pipeline) >= 5, f"want >=5 verify phases, got {res['phases']}"
    with open(out) as f:
        doc = json.load(f)
    assert any(
        e["ph"] == "X" and e["name"] == "verify.device_wait"
        for e in doc["traceEvents"]
    ), "the device-wait phase must appear as a complete span"


# ------------------------------------------- one request, end to end


def test_one_request_names_every_stretch_of_its_path(monkeypatch):
    """A verify_commit through the verify service and the comb staging
    path (tables and program stood in, as in test_comb_resident): one
    trace id from the caller's preparation to its wake-up, every span
    with a parent, and each hand-off named by the span or interval
    that covers it."""
    import numpy as np
    from test_comb_resident import _stand_in_program
    from test_types import _keys, _signed_commit, _valset

    import cometbft_tpu.types as T
    from cometbft_tpu.models import comb_verifier as cv
    from cometbft_tpu.models import verifier
    from cometbft_tpu.verifysvc.service import reset_global_service

    def build(a):
        a = np.asarray(a)
        return a[None, :, 1].astype(np.int32), np.ones((a.shape[0],), bool)

    monkeypatch.setattr(cv, "_build_tables", build)
    monkeypatch.setattr(cv, "_GLOBAL_CACHE", cv.ValsetCombCache())
    monkeypatch.setattr(cv, "_device_verify", _stand_in_program)
    monkeypatch.setattr(verifier, "_COMB_PROGRAMS", {})
    keys = _keys(32)  # the comb floor and the device batch floor
    vals = _valset(keys)
    bid, commit = _signed_commit(keys, vals)
    reset_global_service()
    tracing.set_enabled(True)
    tracing.reset()
    root = tracing.new_context()
    try:
        with tracing.context_scope(root), tracing.span("test.request"):
            T.verify_commit("test-chain", vals, bid, 5, commit)
    finally:
        reset_global_service()
    ring = [e for e in tracing.chrome_trace_events() if e["ph"] == "X"]
    mine = [e for e in ring if e["args"].get("trace_id") == root.trace_id]
    by = {}
    for e in mine:
        assert e["name"] not in by, f"{e['name']} twice in one request"
        by[e["name"]] = e
    path = [
        "commit.prepare", "verify.table_bind", "commit.assemble",
        "commit.verify", "verify.svc.submit", "verify.svc.queued",
        "verify.sched.dispatch", "verify.sched.add", "verify.staging.queued",
        "verify.slab_fill", "verify.h2d_dispatch", "verify.sched.collect",
        "verify.staging_wait", "verify.device_wait", "verify.blame_unpack",
        "verify.sched.split", "verify.svc.wake", "commit.judge",
    ]
    assert set(path) <= set(by), sorted(set(path) - set(by))
    sids = {e["args"]["sid"]: e for e in ring}
    for name in path:  # every span and interval names a recorded parent
        assert by[name]["args"]["parent"] in sids, name

    def parent(name):
        return sids[by[name]["args"]["parent"]]["name"]

    assert parent("commit.prepare") == "test.request"
    assert parent("verify.table_bind") == "commit.prepare"
    assert parent("verify.svc.submit") == "commit.verify"
    for name in ("verify.svc.queued", "verify.sched.dispatch",
                 "verify.sched.collect", "verify.sched.split"):
        assert parent(name) == "verify.svc.submit", name  # handed over
    for name in ("verify.sched.add", "verify.staging.queued",
                 "verify.slab_fill", "verify.h2d_dispatch"):
        assert parent(name) == "verify.sched.dispatch", name
    assert parent("verify.device_wait") == "verify.sched.collect"
    assert parent("verify.svc.wake") == "commit.verify"
    waits = {e["name"] for e in mine if e["args"].get("wait")}
    assert waits == {"commit.verify", "verify.sched.collect",
                     "verify.staging_wait", "verify.device_wait"}

    def start(name):
        return by[name]["ts"]

    def end(name):
        return by[name]["ts"] + by[name]["dur"]

    slack = 1e-3  # the export's microseconds are floats
    assert end("commit.prepare") <= start("commit.assemble") + slack
    assert start("verify.svc.submit") <= start("verify.svc.queued")
    assert end("verify.svc.queued") <= start("verify.sched.dispatch") + slack
    assert start("verify.sched.dispatch") <= start("verify.sched.add")
    assert end("verify.sched.add") <= end("verify.sched.dispatch") + slack
    assert end("verify.staging.queued") <= start("verify.slab_fill") + slack
    assert start("verify.sched.split") <= start("verify.svc.wake")
    assert start("verify.svc.wake") <= end("verify.sched.split") + slack
    assert end("verify.svc.wake") <= end("commit.verify") + slack
    assert by["verify.svc.wake"]["tid"] == by["commit.verify"]["tid"]


def test_the_host_workers_queue_is_an_interval():
    """A batch the scheduler hands to the host worker (here a set under
    the device floor, answered on the host): the wait in the worker's
    queue is the interval verify.host.queued, under the request's trace
    and parent, ending where verify.sched.hostwork starts."""
    from test_types import _keys, _signed_commit, _valset

    import cometbft_tpu.types as T
    from cometbft_tpu.verifysvc.service import reset_global_service

    keys = _keys(4)
    vals = _valset(keys)
    bid, commit = _signed_commit(keys, vals)
    reset_global_service()
    tracing.set_enabled(True)
    tracing.reset()
    root = tracing.new_context()
    try:
        with tracing.context_scope(root):
            T.verify_commit("test-chain", vals, bid, 5, commit)
    finally:
        reset_global_service()
    by = {e["name"]: e for e in tracing.chrome_trace_events()
          if e["ph"] == "X" and e["args"].get("trace_id") == root.trace_id}
    queued, work = by["verify.host.queued"], by["verify.sched.hostwork"]
    assert queued["tid"] == work["tid"]
    assert queued["ts"] + queued["dur"] <= work["ts"] + 1e-3
    assert queued["ts"] >= by["verify.sched.dispatch"]["ts"]
    submit = by["verify.svc.submit"]["args"]["sid"]
    assert queued["args"]["parent"] == work["args"]["parent"] == submit
