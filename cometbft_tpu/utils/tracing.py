"""In-process span tracer for the verification plane and consensus core.

A low-overhead tracer in the spirit of the Chrome trace-event profile
format: call sites open monotonic-clock spans (`with tracing.span("x")`)
or drop instant markers (`tracing.instant("y")`); finished events land in
a per-thread buffer (appends touch no lock) that drains in chunks into
one process-global bounded ring, and the whole ring exports as Chrome
trace-event JSON — open the file in Perfetto (ui.perfetto.dev) or
chrome://tracing to see the VerifyCommit pipeline (slab fill, H2D,
kernel dispatch, device wait, collect) laid out across the caller,
staging, and blocksync threads.

Cost model: tracing is OFF by default and the disabled path is a single
module-bool check returning a shared no-op context manager — no
allocation, no clock read — so the hot paths stay instrumented in
production builds.  Enabled, a span is two perf_counter_ns reads plus a
tuple append; the ring bounds total memory however long the run.

On the profiler's clock: a recording span also enters a
``jax.profiler.TraceAnnotation`` of its name (name only; instants and
intervals are not mirrored), so while ``jax.profiler`` runs every
program span lands on ``/host:CPU`` of the profile, on the line of the
thread that ran it and on the same clock as the device's ``XLA Ops``.
JAX is imported when tracing is turned on, never at this module's
import, and a process without JAX keeps the ring alone.  The ring's
clock is ``perf_counter_ns``; the profile's is the profiler's own, offset
from it by a constant the program cannot read.  So the tracer writes a
clock anchor, ``verify.clock_anchor``, while the profiler records: a
zero-length ring event whose mirrored annotation lands in the profile,
carrying the perf and wall readings as args, written when the first
recording span ends after the profiler started and then at most once a
second.  A reader pairs an anchor's ring ``ts`` with its annotation's
start and so places every ring event on the profile's clock.

What caused what: every recorded span carries its own id (arg ``sid``)
and its parent's (``parent``): the enclosing span on the thread, or the
span that was current where :func:`carry_context` or a context from
:func:`handoff` passed the work to another thread.  A span marked
``wait=True`` (arg ``wait``) says the thread only waited in it.  An
:func:`interval` records a span whose start was stamped earlier,
possibly on another thread: a queue wait or a wake-up.

One timing convention: :func:`phase` takes ONE pair of clock reads and
feeds the ``verify_phase_seconds`` histogram (always) and the ring and
the annotation (tracing on) from it; the duration stays on the returned
object (``ns``).  The verifiers time their phases through it and in no
other way.

Enable with COMETBFT_TPU_TRACE=1 (drain via export_chrome_trace / the
API) or COMETBFT_TPU_TRACE=/path/to/out.trace.json to also auto-export
at interpreter exit.  COMETBFT_TPU_TRACE_RING sizes the ring (events,
default 65536).

Cross-process correlation: a :class:`SpanContext` (W3C-traceparent-
shaped trace_id/span_id pair) can be installed as the thread's current
context (:func:`context_scope`); every event recorded under a scope
carries ``trace_id``/``span_id`` args, and the context serializes to /
parses from a ``traceparent`` string so it can ride a wire field — the
verify plane's RPC layer propagates it, and ``scripts/trace_merge.py``
stitches the per-process exports into one timeline where client and
server spans of a remote verify share a trace_id.
COMETBFT_TPU_TRACE_CTX=0 turns propagation off (events stay local).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import weakref

from . import envknobs
from .metrics import hub as _metrics_hub

_OFF_VALUES = ("", "0", "false", "off", "no")
_ON_VALUES = ("1", "true", "on", "yes")

# events drain from thread-local buffers to the ring in chunks this big;
# small enough that an export misses at most a few dozen in-flight events
_CHUNK = 64
_DEFAULT_RING = 65536

_ENABLED = False
# jax.profiler.TraceAnnotation once tracing is on and JAX can be
# imported; None in a process without JAX.  _UNRESOLVED until the first
# look: COMETBFT_TPU_TRACE turns tracing on at import, and JAX must not
# be imported from here then.
_UNRESOLVED = object()
_ANNOTATION = _UNRESOLVED
_CTX_ENABLED = True  # COMETBFT_TPU_TRACE_CTX — span-context propagation
_EXPORT_PATH: str | None = None
# TraceAnnotation.is_enabled (whether the profiler records), or None
_PROFILING = None

# span ids: next() of a count is atomic under the GIL
_ids = itertools.count(1)

ANCHOR = "verify.clock_anchor"
_ANCHOR_EVERY_NS = 1_000_000_000
_anchor_at = 0  # perf ns of the last anchor
_anchor_profiling = False  # whether an anchor was written in this profile

_ring_mtx = threading.Lock()
_ring: list = []  # bounded manually (deque has no atomic bulk-swap)
_ring_cap = _DEFAULT_RING
_dropped = 0

_bufs_mtx = threading.Lock()
_bufs: list = []  # [(weakref-to-thread, buf list, tid), ...]
_thread_names: dict[int, str] = {}
# registration-time pruning threshold: beyond this many registered
# buffers, dead threads' buffers are flushed and dropped so per-peer
# thread churn can't grow _bufs/_thread_names for the process lifetime
_PRUNE_AT = 256

_tls = threading.local()


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool, ring_capacity: int | None = None) -> None:
    """Runtime switch (tests, the trace script, bench).  Turning tracing
    on never clears previously collected events; call reset() for a
    clean capture window."""
    global _ENABLED, _ring_cap
    if ring_capacity is not None:
        with _ring_mtx:
            _ring_cap = max(1, int(ring_capacity))
            del _ring[: max(0, len(_ring) - _ring_cap)]
    if on:
        _annotation()  # the JAX import, if any, is paid here and in no span
    _ENABLED = bool(on)


def _annotation():
    """``jax.profiler.TraceAnnotation``, or None without JAX; looked up
    once, when tracing is first on."""
    global _ANNOTATION, _PROFILING
    if _ANNOTATION is _UNRESOLVED:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # noqa: BLE001 - no JAX, or one that cannot load
            TraceAnnotation = None
        _ANNOTATION = TraceAnnotation
        _PROFILING = getattr(TraceAnnotation, "is_enabled", None)
    return _ANNOTATION


def reset() -> None:
    """Drop every buffered event (thread-local and ring); the next
    recording span writes a fresh clock anchor."""
    global _dropped, _anchor_profiling
    _anchor_profiling = False
    with _bufs_mtx:
        entries = list(_bufs)
    for _tref, buf, _tid in entries:
        del buf[:]
    with _ring_mtx:
        del _ring[:]
        _dropped = 0


def dropped_count() -> int:
    """Events evicted from the ring since the last reset()."""
    return _dropped


# ----------------------------------------------------------- span context


class SpanContext:
    """Propagable identity of one distributed trace: a 16-byte trace_id
    shared by every span of the trace (across processes) and an 8-byte
    span_id naming this hop.  Shaped after the W3C traceparent header
    (version 00, sampled flag always 01) so the wire form is a plain
    printable string any tracing stack recognizes.  ``parent``, set by
    :func:`handoff` and never on the wire, is the ``sid`` of the span
    that handed the work over: the parent of the receiver's spans."""

    __slots__ = ("trace_id", "span_id", "parent")

    def __init__(self, trace_id: str, span_id: str, parent: int | None = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent = parent

    def child(self) -> "SpanContext":
        """Same trace, fresh hop id — what a server installs so its
        spans link to the client's without claiming its span_id."""
        return SpanContext(self.trace_id, os.urandom(8).hex())

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    @classmethod
    def from_traceparent(cls, header: str) -> "SpanContext | None":
        """Parse a traceparent string; None on anything malformed — a
        bad context from a peer must degrade to 'unlinked', never raise
        into the request path."""
        parts = header.split("-")
        if len(parts) != 4:
            return None
        _ver, tid, sid, _flags = parts
        if len(tid) != 32 or len(sid) != 16:
            return None
        try:
            int(tid, 16)
            int(sid, 16)
        except ValueError:
            return None
        if tid == "0" * 32 or sid == "0" * 16:
            return None
        return cls(tid, sid)

    def __eq__(self, other):
        return (
            isinstance(other, SpanContext)
            and self.trace_id == other.trace_id
            and self.span_id == other.span_id
        )

    def __repr__(self):
        return f"SpanContext({self.to_traceparent()!r})"


def new_context() -> SpanContext:
    """A fresh root context (random trace_id + span_id)."""
    return SpanContext(os.urandom(16).hex(), os.urandom(8).hex())


def current_context() -> SpanContext | None:
    """The calling thread's installed context, if any."""
    return getattr(_tls, "ctx", None)


class _CtxScope:
    __slots__ = ("_ctx", "_prev", "_prev_cur", "_installed")

    def __init__(self, ctx):
        self._ctx = ctx
        self._installed = ctx is not None

    def __enter__(self):
        if self._installed:
            self._prev = getattr(_tls, "ctx", None)
            self._prev_cur = getattr(_tls, "cur", None)
            _tls.ctx = self._ctx
            if self._ctx.parent is not None:
                _tls.cur = self._ctx.parent
        return self._ctx

    def __exit__(self, *exc) -> bool:
        if self._installed:
            _tls.ctx = self._prev
            _tls.cur = self._prev_cur
        return False


def context_scope(ctx: SpanContext | None):
    """Install ``ctx`` as the thread's current context for the block:
    every span/instant recorded inside carries its trace_id/span_id
    args, and, where ``ctx`` came from :func:`handoff`, the handing
    span as its parent.  ``None`` leaves the current context untouched
    (so call sites can pass an optional context unconditionally)."""
    return _CtxScope(ctx if propagation_enabled() else None)


def handoff() -> SpanContext | None:
    """The calling thread's context for work another thread picks up
    later (a queued request): the same trace identity, and the span
    current here as the parent of the spans recorded under it there.
    None where no context is installed."""
    ctx = current_context()
    cur = getattr(_tls, "cur", None)
    if ctx is None or cur is None:
        return ctx
    return SpanContext(ctx.trace_id, ctx.span_id, cur)


def carry_context(fn):
    """``fn`` as a callable that runs under the CALLING thread's current
    context and with its current span as the parent: for work handed to
    another thread, whose spans then carry the trace identity of the
    request they serve and say which span handed them over.  ``fn``
    itself where tracing is off or there is nothing to carry."""
    if not _ENABLED:
        return fn
    ctx = current_context()
    cur = getattr(_tls, "cur", None)
    if ctx is None and cur is None:
        return fn

    def run(*args, **kwargs):
        prev = getattr(_tls, "ctx", None), getattr(_tls, "cur", None)
        _tls.ctx, _tls.cur = ctx, cur
        try:
            return fn(*args, **kwargs)
        finally:
            _tls.ctx, _tls.cur = prev

    return run


def propagation_enabled() -> bool:
    return _ENABLED and _CTX_ENABLED


# ------------------------------------------------------------- recording


_tid_counter = 0


def _buf() -> list:
    b = getattr(_tls, "buf", None)
    if b is None:
        global _tid_counter
        b = _tls.buf = []
        t = threading.current_thread()
        with _bufs_mtx:
            if len(_bufs) >= _PRUNE_AT:
                _prune_dead_locked()
            # synthetic per-thread track id: OS thread idents are recycled
            # after thread exit, which would merge a dead thread's events
            # onto a new thread's track in the export
            _tid_counter += 1
            _tls.tid = _tid_counter
            _bufs.append((weakref.ref(t), b, _tls.tid))
            _thread_names[_tls.tid] = t.name
    return b


def _prune_dead_locked() -> None:
    """Flush and drop buffers (and name entries) of exited threads —
    caller holds _bufs_mtx.  Ring events from pruned threads keep their
    synthetic tid; only the name label for the track is lost."""
    keep = []
    for tref, b, tid in _bufs:
        if tref() is not None:
            keep.append((tref, b, tid))
        else:
            if b:
                _flush(b)
            _thread_names.pop(tid, None)
    _bufs[:] = keep


def _flush(b: list) -> None:
    """Move a buffer's events into the bounded ring.  The copy+delete and
    the ring extend happen under ONE lock: the owner thread's chunk flush
    and an exporter's drain may race on the same buffer, and an unlocked
    copy would insert the same chunk twice."""
    global _dropped
    with _ring_mtx:
        items = b[:]
        del b[: len(items)]
        _ring.extend(items)
        overflow = len(_ring) - _ring_cap
        if overflow > 0:
            del _ring[:overflow]
            _dropped += overflow


def _emit(ph: str, name: str, ts_ns: int, dur_ns: int, labels,
          sid: int | None = None, parent: int | None = None,
          wait: bool = False) -> None:
    # the context rides along as it is: events recorded under a context
    # scope carry its trace identity as args (the cross-process link
    # trace_merge.py stitches on), written out at export
    b = _buf()
    b.append((ph, name, ts_ns, dur_ns, _tls.tid, labels,
              getattr(_tls, "ctx", None), sid, parent, wait))
    if len(b) >= _CHUNK:
        _flush(b)


def _maybe_anchor(now_ns: int) -> None:
    """While the profiler records: a clock anchor at the first span that
    ends after it started, and then where a second has passed since the
    last one, so that every profile holds one, however short."""
    global _anchor_at, _anchor_profiling
    if _PROFILING is None or not _PROFILING():
        if _anchor_profiling:
            _anchor_profiling = False
        return
    if _anchor_profiling and now_ns - _anchor_at < _ANCHOR_EVERY_NS:
        return
    _anchor_at, _anchor_profiling = now_ns, True
    ann = _annotation()
    if ann is not None:
        ann = ann(ANCHOR)
        ann.__enter__()
    # read as a span reads its start: inside the annotation
    perf = time.perf_counter_ns()
    wall = time.time_ns()
    if ann is not None:
        ann.__exit__(None, None, None)
    _emit("X", ANCHOR, perf, 0, {"perf_ns": perf, "wall_ns": wall},
          next(_ids), getattr(_tls, "cur", None))


class _Span:
    """One timed block: an 'X' (complete) trace event recorded at
    __exit__ with its id and its parent's, the profiler annotation of
    the same name around it (``record``), and for :func:`phase` the
    histogram, all from the one pair of clock reads; ``ns`` holds the
    duration once the block has ended."""

    __slots__ = ("_name", "_labels", "_record", "_hist", "_wait", "_t0",
                 "_ann", "_sid", "_parent", "ns")

    def __init__(self, name, labels, record=True, hist=None, wait=False):
        self._name = name
        self._labels = labels
        self._record = record
        self._hist = hist
        self._wait = wait

    def __enter__(self) -> "_Span":
        ann = None
        if self._record:
            self._parent = getattr(_tls, "cur", None)
            self._sid = _tls.cur = next(_ids)
            ann = _annotation()
            if ann is not None:
                ann = ann(self._name)
                ann.__enter__()
        self._ann = ann
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, *exc) -> bool:
        t0 = self._t0
        self.ns = dur = time.perf_counter_ns() - t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, *exc)
        if self._record:
            _tls.cur = self._parent
            _emit("X", self._name, t0, dur, self._labels, self._sid,
                  self._parent, self._wait)
            _maybe_anchor(t0 + dur)
        if exc_type is None and self._hist is not None:
            # a phase that raised has no duration worth a histogram bucket
            _metrics_hub().verify_phase_seconds.observe(
                dur / 1e9, phase=self._hist
            )
        return False


class _NopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOP = _NopSpan()


def span(name: str, labels: dict | None = None, wait: bool = False):
    """Context manager timing one pipeline phase.  Disabled: returns a
    shared no-op — the call site pays one bool check and no allocation
    (pass labels as a prebuilt dict, not kwargs, to keep that true).
    ``wait``: the thread only waits inside (for another thread, the
    device or a callee's work); exported as the arg ``wait``."""
    if not _ENABLED:
        return _NOP
    return _Span(name, labels, True, None, wait)


def phase(span_name: str | None, hist_phase: str | None = None,
          labels: dict | None = None, wait: bool = False):
    """Context manager timing one verifier phase with ONE pair of clock
    reads, and feeding from it everything that wants the duration:

    - ``verify_phase_seconds{phase=hist_phase}`` on the metrics hub,
      always (/metrics is the operator's use, tracing on or off);
    - the span ring and the profiler annotation under ``span_name``,
      when tracing is on (``labels`` and ``wait`` as for :func:`span`);
    - the returned object's ``ns``, once the block has ended.

    Either of the first two is left out by passing None.  A phase that
    raises still closes its span; the histogram takes completed phases
    only."""
    return _Span(span_name, labels, _ENABLED and span_name is not None,
                 hist_phase, wait)


def stamp() -> int:
    """``perf_counter_ns`` where tracing is on, else 0 (no clock read):
    the start of an :func:`interval` that is recorded later."""
    return time.perf_counter_ns() if _ENABLED else 0


def interval(name: str, t0_ns: int, t1_ns: int | None = None,
             labels: dict | None = None) -> None:
    """A span from ``t0_ns`` (``perf_counter_ns``, stamped earlier and
    possibly on another thread) to ``t1_ns`` or now, recorded in the
    ring only (the profiler cannot be told of a start in the past):
    a queue wait, a wake-up.  Its parent is the thread's current span.
    Tracing off, or no start stamped (0), it reads no clock and records
    nothing: call sites take ``t0_ns`` from :func:`stamp`."""
    if not _ENABLED or not t0_ns:
        return
    if t1_ns is None:
        t1_ns = time.perf_counter_ns()
    _emit("X", name, t0_ns, t1_ns - t0_ns, labels, next(_ids),
          getattr(_tls, "cur", None))


def instant(name: str, labels: dict | None = None) -> None:
    """A zero-duration marker (step transitions, timeout fires)."""
    if not _ENABLED:
        return
    _emit("i", name, time.perf_counter_ns(), 0, labels, None,
          getattr(_tls, "cur", None))


# --------------------------------------------------------------- export


def _drain_all() -> tuple[list, dict]:
    """Flush every thread buffer into the ring, prune buffers AND name
    entries of dead threads, and return (ring snapshot, thread-name
    snapshot).  The name snapshot is taken before the prune, so the
    export in progress still labels just-exited threads' tracks; later
    exports show their remaining ring events on an unnamed track — the
    cosmetic price of keeping _thread_names bounded under thread churn.
    The ring itself is not cleared: repeat exports see a superset."""
    with _bufs_mtx:
        entries = list(_bufs)
        names = dict(_thread_names)
        live = [(tr, b, tid) for tr, b, tid in entries if tr() is not None]
        for tr, _b, tid in entries:
            if tr() is None:
                _thread_names.pop(tid, None)
        _bufs[:] = live
    for _tref, buf, _tid in entries:
        if buf:
            _flush(buf)
    with _ring_mtx:
        return list(_ring), names


def chrome_trace_events() -> list[dict]:
    """The buffered events as Chrome trace-event dicts (plus thread-name
    metadata records), timestamp-sorted."""
    events, names = _drain_all()
    pid = os.getpid()
    # Wall-clock anchor: every event timestamp in this export is pure
    # perf_counter_ns, while flight-recorder entries and log lines carry
    # wall-clock time — one (wall_ns, perf_ns) pair sampled at export
    # time lets a consumer line all three up on one timeline:
    #   wall_ns(event) = wall_time_ns + (event.ts * 1000 - perf_counter_ns)
    wall_anchor_ns = time.time_ns()
    perf_anchor_ns = time.perf_counter_ns()
    out: list[dict] = [
        {
            "ph": "M",
            "name": "wall_clock_anchor",
            "pid": pid,
            "tid": 0,
            "args": {
                "wall_time_ns": wall_anchor_ns,
                "perf_counter_ns": perf_anchor_ns,
            },
        }
    ]
    out += [
        {
            "ph": "M",
            "name": "thread_name",
            "pid": pid,
            "tid": tid,
            "args": {"name": tname},
        }
        for tid, tname in sorted(names.items())
    ]
    for ph, name, ts_ns, dur_ns, tid, labels, ctx, sid, parent, wait in sorted(
        events, key=lambda e: e[2]
    ):
        args = dict(labels) if labels else {}
        if ctx is not None:
            args.setdefault("trace_id", ctx.trace_id)
            args.setdefault("span_id", ctx.span_id)
        if sid is not None:
            args["sid"] = sid
        if parent is not None:
            args["parent"] = parent
        if wait:
            args["wait"] = True
        e = {
            "ph": ph,
            "name": name,
            "cat": "cometbft",
            "pid": pid,
            "tid": tid,
            "ts": ts_ns / 1e3,  # trace-event timestamps are microseconds
        }
        if ph == "X":
            e["dur"] = dur_ns / 1e3
        elif ph == "i":
            e["s"] = "t"  # thread-scoped instant
        if args:
            e["args"] = {k: _jsonable(v) for k, v in args.items()}
        out.append(e)
    return out


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    return str(v)


def export_chrome_trace(path: str) -> int:
    """Write {"traceEvents": [...]} JSON; returns the number of span /
    instant events written (metadata records excluded)."""
    events = chrome_trace_events()
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return sum(1 for e in events if e["ph"] != "M")


# --------------------------------------------------- env-var resolution

def _atexit_export() -> None:
    try:
        export_chrome_trace(_EXPORT_PATH)
    except Exception:  # noqa: BLE001 — never traceback on interpreter exit
        pass


_v = envknobs.get_str(envknobs.TRACE)
if _v.lower() not in _OFF_VALUES:
    _ENABLED = True
    if _v.lower() not in _ON_VALUES and (os.sep in _v or _v.endswith(".json")):
        # unambiguously a path: auto-export the ring at process exit.
        # Other truthy values ("2", "debug", ...) just enable recording —
        # they must not turn into a stray file named after themselves.
        _EXPORT_PATH = _v
        import atexit

        atexit.register(_atexit_export)
_ring_cap = max(1, envknobs.get_int(envknobs.TRACE_RING))
_CTX_ENABLED = envknobs.get_bool(envknobs.TRACE_CTX)
del _v
