"""The yardstick's arithmetic on inputs written by hand: percentiles
and sample counts, interval union, idle share and gap attribution on a
list of trace rows, and the readers on a recorded span-ring export."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import stats, xplane  # noqa: E402
from benchmarks.readers import (  # noqa: E402
    clock_difference, hub_histogram_mean, span_median, xplane_idle_share,
    xplane_module_time,
)


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 90, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([4, 1, 3, 2], 50, 2.5),
    (list(range(1, 101)), 90, 90.1),
    (list(range(1, 12)), 90, 10.0),
    ([10, 20], 0, 10.0),
    ([10, 20], 100, 20.0),
])
def test_percentile_interpolates_between_closest_ranks(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,q,want", [
    (100, 90, 10), (99, 90, 9), (112, 90, 11), (200, 95, 10), (0, 90, 0),
    (1000, 99, 10),
])
def test_samples_beyond_a_percentile(n, q, want):
    assert stats.samples_beyond(n, q) == want


@pytest.mark.parametrize("values,want", [
    # ten samples: 4 + 3 + 3, the first thirds take the remainder
    (list(range(1, 11)), [(4, 2.5, 3.7), (3, 6.0, 6.8), (3, 9.0, 9.8)]),
    # a slow start shows in the first third alone
    ([30.0, 30.0, 10.0, 10.0, 10.0, 10.0], [(2, 30.0, 30.0), (2, 10.0, 10.0),
                                            (2, 10.0, 10.0)]),
    # in the order taken, not sorted
    ([3.0, 1.0, 2.0], [(1, 3.0, 3.0), (1, 1.0, 1.0), (1, 2.0, 2.0)]),
    ([7.0, 9.0], [(1, 7.0, 7.0), (1, 9.0, 9.0)]),  # an empty third is left out
    ([], []),
])
def test_thirds_of_a_window_in_the_order_taken(values, want):
    got = stats.thirds(values)
    assert [(t["n"], t["p50"], t["p90"]) for t in got] == [
        (n, pytest.approx(p50), pytest.approx(p90)) for n, p50, p90 in want]
    assert sum(t["n"] for t in got) == len(values)


# one chip, 100 ns window [100, 200]: busy 110-130, 125-140 (overlap),
# 160-170, and one operation that straddles the window's end
OPS = [(110, 130, "fusion.1"), (125, 140, "fusion.2 = s32[22,256]{1,0} fusion()"),
       (160, 170, "copy.3"), (195, 230, "fusion.1 = s32[1] fusion(s32[1] %x)"),
       (20, 90, "before.the.window")]
REQUESTS = [(100, 150, xplane.REQUEST), (155, 200, xplane.REQUEST)]
MODULES = [(108, 141, "jit_verify_batch(123)"), (159, 171, "jit_verify_batch(123)"),
           (194, 231, "jit_other(9)")]


def test_merge_clips_and_unites():
    assert xplane.merge(OPS, 100, 200) == [(110, 140), (160, 170), (195, 200)]
    assert xplane.busy_ns(OPS, 100, 200) == 45
    assert xplane.merge([], 0, 10) == []


def test_gaps_are_the_complement():
    assert xplane.gaps(OPS, 100, 200) == [
        (100, 110), (140, 160), (170, 195)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]
    assert xplane.gaps([(0, 10, "all")], 0, 10) == []


def test_idle_share():
    assert xplane.idle_share(45, 100) == pytest.approx(55.0)
    assert xplane.idle_share(0, 100) == 100.0
    with pytest.raises(ValueError):
        xplane.idle_share(1, 0)


def test_gaps_are_split_at_the_requests_edges():
    pieces = xplane.split_gaps(xplane.gaps(OPS, 100, 200), REQUESTS, 100, 200)
    # 140-160 holds 140-150 and 155-160 inside requests, 150-155 between
    assert sorted(pieces["inside_request"]) == [5, 10, 10, 25]
    assert pieces["between_requests"] == [5]
    assert sum(sum(v) for v in pieces.values()) == 100 - 45


def test_module_time_and_top_names():
    assert xplane.module_ns(MODULES, ["jit_verify_batch"], 100, 200) == 33 + 12
    assert xplane.module_ns(MODULES, ["jit_other"], 100, 200) == 6
    assert xplane.module_ns(MODULES, ["jit_none"], 100, 200) == 0
    assert xplane.top_names(OPS, 100, 200, k=2) == [
        ["fusion.1", 25 / 1e9], ["fusion.2", 15 / 1e9]]


def _trace():
    return xplane.Trace(
        ops={"/device:TPU:0": OPS, "/device:TPU:1": []},
        modules={"/device:TPU:0": MODULES}, requests=REQUESTS,
    )


def test_reduce_averages_over_the_chips_that_ran_anything():
    got = xplane.reduce(_trace())
    assert got["busy_s"] == pytest.approx(45e-9)
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["requests"] == 2
    assert got["device_ops"][0] == ["fusion.1", 25e-9]
    rows = dict(got["idle_gaps"])
    assert rows["inside_request.total"] == pytest.approx(50e-9)
    assert rows["inside_request.longest"] == pytest.approx(25e-9)
    assert rows["between_requests.total"] == pytest.approx(5e-9)
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10


def test_a_trace_with_no_device_operation_or_no_request_is_refused():
    with pytest.raises(xplane.TraceError):
        xplane.reduce(xplane.Trace(ops={"/device:TPU:0": []}, requests=REQUESTS))
    with pytest.raises(xplane.TraceError):
        xplane.reduce(xplane.Trace(ops={"/device:TPU:0": OPS}))


def test_trace_readers():
    trace = _trace()
    sources = {"trace": trace, "trace_reduced": xplane.reduce(trace)}
    assert xplane_idle_share.read({}, sources) == pytest.approx(55.0)
    assert xplane_module_time.read(
        {"modules": ["jit__device_verify", "jit_verify_batch"]}, sources
    ) == pytest.approx(45 / 1e6 / 2)
    assert xplane_module_time.read({"modules": ["jit_none"]}, sources) is None


@pytest.fixture(scope="module")
def ring():
    """Three 8-signature commits through the uncached program, recorded
    from utils/tracing on the CPU backend (durations are the CPU's)."""
    with open(os.path.join(HERE, "span_ring_recorded.json")) as f:
        return [e for e in json.load(f) if e["ph"] == "X"]


def test_span_median_per_batch_on_a_recorded_ring(ring):
    waits = sorted(e["dur"] for e in ring if e["name"] == "verify.device_wait")
    assert len(waits) == 3
    got = span_median.read(
        {"per": "trace_id", "spans": ["verify.device_wait"]}, {"spans": ring})
    assert got == pytest.approx(waits[1] / 1e3)
    # host work of a batch: its spans are added before the median
    by_batch = {}
    for e in ring:
        if e["name"] in ("verify.uncached_assemble", "verify.h2d_dispatch"):
            tid = e["args"]["trace_id"]
            by_batch[tid] = by_batch.get(tid, 0.0) + e["dur"]
    got = span_median.read(
        {"per": "trace_id",
         "spans": ["verify.slab_fill", "verify.h2d_dispatch",
                   "verify.blame_unpack", "verify.uncached_assemble"]},
        {"spans": ring})
    assert got == pytest.approx(sorted(by_batch.values())[1] / 1e3)


def test_a_reader_that_finds_nothing_returns_nothing(ring):
    assert span_median.read(
        {"per": "span", "spans": ["blocksync.apply"]}, {"spans": ring}) is None
    assert clock_difference.read(
        {"outer": "request_s", "inner": "observer_s"},
        {"samples": {"request_s": [1.0, 2.0], "observer_s": [0.5]}}) is None
    assert hub_histogram_mean.read(
        {"metric": "cometbft_x_seconds"}, {"hub_before": "", "hub_after": ""}
    ) is None


def test_clock_difference_is_the_median_of_per_request_differences():
    got = clock_difference.read(
        {"outer": "request_s", "inner": "observer_s"},
        {"samples": {"request_s": [0.010, 0.020, 0.050],
                     "observer_s": [0.008, 0.015, 0.020]}})
    assert got == pytest.approx(5.0)


def test_hub_histogram_mean_reads_the_growth_over_the_window():
    before = (
        'cometbft_q_seconds_bucket{class="consensus",le="0.1"} 2\n'
        'cometbft_q_seconds_sum{class="consensus"} 0.5\n'
        'cometbft_q_seconds_count{class="consensus"} 2\n'
        'cometbft_q_seconds_total_sum 99\n'
    )
    after = (
        'cometbft_q_seconds_sum{class="consensus"} 0.9\n'
        'cometbft_q_seconds_count{class="consensus"} 5\n'
        'cometbft_q_seconds_sum{class="blocksync"} 0.2\n'
        'cometbft_q_seconds_count{class="blocksync"} 1\n'
        'cometbft_q_seconds_total_sum 1000\n'
    )
    got = hub_histogram_mean.read(
        {"metric": "cometbft_q_seconds"},
        {"hub_before": before, "hub_after": after})
    assert got == pytest.approx(1e3 * (0.9 + 0.2 - 0.5) / 4)
