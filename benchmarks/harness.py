"""One run of one cell: set-up, the measured window, the checks that
decide ``correct``, the reduction to metrics, the result line.

A driver (benchmarks/drivers/<name>.py) is one kind of traffic and has

    setup(cell, seed, log) -> state   data from the seed, the set bound,
                                      the correctness checks
    warm(state)                       the cell's own programs, warm
    run(state, window)                the traffic, until window.deadline
    finish(state) -> list[str]        stops what it started; what did
                                      not hold after the window
    end_to_end(state, window) -> dict the cell's end-to-end values

and everything else is here, the same for every cell.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

from . import checks, spec, xplane

TRACE_DIR = os.path.join(spec.HERE, ".trace")  # listed in .gitignore
PEAKS = os.path.join(spec.HERE, "peaks.json")  # keyed by device_kind


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class Window:
    """The measured window.  Drivers wrap each request in ``request()``
    and call ``tick()`` between requests; in a traced run the profiler
    runs from the first tick until ``trace_requests`` requests have
    ended (and for ``trace_max_s`` at most)."""

    def __init__(self, seconds: float, trace: bool, traffic: dict):
        self.seconds = seconds
        self.trace = trace
        self.trace_requests = int(traffic.get("trace_requests", 16))
        self.trace_max_s = float(traffic.get("trace_max_s", 10.0))
        self.attempted = 0
        self.failed = 0
        self.samples: dict[str, list[float]] = {}
        self.opened = self.opened_perf = self.deadline = self.closed = None
        self._profiling = False
        self._profile_done = False
        self._profile_t0 = 0.0
        self._profiled_requests = 0

    def open(self) -> None:
        self.opened = time.monotonic()
        self.opened_perf = time.perf_counter()
        self.deadline = self.opened + self.seconds

    def close(self) -> None:
        self.stop_profile()
        self.closed = time.monotonic()

    def expired(self) -> bool:
        return time.monotonic() >= self.deadline

    def sample(self, name: str, value: float) -> None:
        self.samples.setdefault(name, []).append(value)

    @contextlib.contextmanager
    def annotate(self):
        """One request as the profiler's trace shall see it (a
        TraceAnnotation from this file, while the profiler runs)."""
        import jax

        if not self._profiling:
            yield
            return
        with jax.profiler.TraceAnnotation(xplane.REQUEST):
            yield
        self._profiled_requests += 1

    @contextlib.contextmanager
    def request(self):
        """One request: annotated, counted as attempted, and as failed
        if it raises (the exception stops here: a failed request fails
        the run through ``failed``, and the traffic goes on)."""
        self.attempted += 1
        try:
            with self.annotate():
                yield
        except Exception as e:  # noqa: BLE001 - any failure is a failed request
            self.failed += 1
            log(f"request {self.attempted} failed: {type(e).__name__}: {e}")

    def tick(self) -> None:
        """Between requests: start or stop the profiler."""
        if not self.trace or self._profile_done:
            return
        if not self._profiling:
            self.start_profile()
        elif (self._profiled_requests >= self.trace_requests
              or time.monotonic() - self._profile_t0 >= self.trace_max_s):
            self.stop_profile()

    def start_profile(self) -> None:
        import shutil

        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # every Python call otherwise
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        self._profiling = True
        self._profile_t0 = time.monotonic()

    def stop_profile(self) -> None:
        import jax

        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
            self._profile_done = True


def device_record(devs) -> dict:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs
    ]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": max(peaks),
    }


def hub_text() -> str:
    """The hub as /metrics would expose it."""
    from cometbft_tpu.utils.metrics import hub

    return hub().registry.expose_text()


def read_layers(cell: spec.Cell, sources: dict) -> dict:
    """Each per-layer metric of the cell through the reader its file
    names; a reader that finds nothing returns nothing, and the metric
    is then left out."""
    values = {}
    for m in cell.per_layer:
        v = spec.module("readers", m["reader"]).read(m["args"], sources)
        if v is not None:
            values[m["name"]] = v
    return values


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devs) -> tuple[dict, dict]:
    """The whole run; returns the result line, and the facts that go on
    the line before it (counts, route, compiles, what did not hold)."""
    from cometbft_tpu.types import validation
    from cometbft_tpu.utils import compilecache, tracing
    from cometbft_tpu.verifysvc.service import reset_global_service

    cache_dir = compilecache.enable()
    events = checks.JaxEvents()
    events.install()
    # the span ring is on through set-up in every run: a batch routed to
    # the host leaves a span there (and, in every phase, a count), and the
    # route depends on widths that are the same before and inside the window
    tracing.set_enabled(True, ring_capacity=1 << 20)
    tracing.reset()
    problems: list[str] = []
    window = Window(seconds, trace, cell.traffic)
    driver = cell.driver
    state = driver.setup(cell, seed, log)
    log(f"set-up data and checks done at {time.monotonic() - t_start:.1f} s")
    try:
        if not trace:
            # the ring has seen set-up's batches; off before the warm-up,
            # which then runs as the window will (the host-route counter
            # covers both, checks.route_counters)
            problems += checks.span_fallbacks(
                checks.route_spans(tracing.chrome_trace_events())
            )
            tracing.set_enabled(False)
            tracing.reset()
        driver.warm(state)
        if trace:
            # the program's own hook around bv.verify(); traced runs
            # only, so that the other runs take the untouched path
            validation.VERIFY_LATENCY_OBSERVER = (
                lambda s: window.sample("observer_s", s)
            )
        hub_before = hub_text()
        window.open()
        setup_s = window.opened - t_start
        log(f"window opens, set-up {setup_s:.1f} s")
        driver.run(state, window)
        window.close()
    finally:
        validation.VERIFY_LATENCY_OBSERVER = None
        problems += driver.finish(state)
    hub_after = hub_text()
    compiled = events.compiled_since(window.opened)
    if compiled:
        problems.append(f"compiled inside the window: {compiled}")
    counters = checks.route_counters()
    spans = None
    ring: list[dict] = []
    if trace:
        ring = tracing.chrome_trace_events()
        spans = checks.route_spans(ring)
    problems += checks.route_failures(counters, spans)
    reset_global_service()  # everything of the program is read: stop it
    tracing.set_enabled(False)
    values = driver.end_to_end(state, window)
    if window.failed:
        problems.append(f"{window.failed} of {window.attempted} requests failed")
    facts = {
        "cell": cell.name, "seed": seed, "trace": trace,
        "attempted": window.attempted, "failed": window.failed,
        "samples": {k: len(v) for k, v in window.samples.items()},
        "window_s": window.closed - window.opened, "setup_s": setup_s,
        "end_to_end": values, "route": counters, "route_spans": spans,
        "compile": {"cache_dir": cache_dir, **events.summary()},
        "problems": problems,
    }
    device = device_record(devs)
    result = {
        "correct": not problems,
        "attempted": window.attempted,
        "failed": window.failed,
        "device": device,
    }
    if not trace:
        values["setup_s"] = setup_s
        wanted = cell.end_to_end
    else:
        profile = xplane.load(xplane.find_xplane(TRACE_DIR))
        reduced = xplane.reduce(profile)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
        values = read_layers(cell, {
            "spans": [
                e for e in ring
                if e.get("ph") == "X" and e["ts"] >= window.opened_perf * 1e6
            ],
            "hub_before": hub_before,
            "hub_after": hub_after,
            "samples": window.samples,
            "trace": profile,
            "trace_reduced": reduced,
        })
        wanted = cell.per_layer
    result["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in wanted if m["name"] in values
    }
    # what ``correct`` compared, each number beside its limit: last
    result["compared"] = compared(window, compiled, counters, problems)
    return result, facts


def compared(window: Window, compiled: list, counters: dict,
             problems: list) -> dict:
    """Every comparison is exact, so every limit is 0.  A verdict vector
    that differs from the reference's in set-up ends the run with no
    result; after the window it is one of ``checks_not_held``."""
    host = (sum(counters[c] for c in checks.HOST_COUNTERS)
            + counters["failover_trips"] + sum(counters["rejected"].values()))
    numbers = {
        "requests_failed": window.failed,
        "compiled_in_window": len(compiled),
        "batches_off_the_device": int(host),
        "backend_not_tpu": int(counters["backend_mode"] != "tpu"),
        "checks_not_held": len(problems),
    }
    return {k: {"value": v, "limit": 0} for k, v in numbers.items()}


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.resolve(args.workload)
    except spec.SpecError as e:
        log(str(e))
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"no TPU, or too few: JAX offers {len(devs)} x "
            f"{devs[0].platform!r} ({devs[0].device_kind}), the cell needs "
            f"{cell.chips} x 'tpu'")
        return 2
    if devs[0].device_kind not in spec.load_json(PEAKS):
        log(f"{devs[0].device_kind!r} is not in benchmarks/peaks.json: no "
            "number of this benchmark was ever taken on such a device")
        return 2
    try:
        result, facts = run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace), t_start, devs)
    except checks.CheckFailure as e:
        log(f"a check of set-up did not hold: {e}")
        return 1
    except xplane.TraceError as e:
        log(f"the profiler's trace cannot be reduced: {e}")
        return 1
    print("bench facts: " + json.dumps(facts))
    for p in facts["problems"]:
        log(f"not correct: {p}")
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']:g}, limit {c['limit']}")
    # the result line: last, and nothing after it
    print(json.dumps(result), flush=True)
    return 0
