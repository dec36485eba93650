"""What the readers of the program's own names need from a profile and
``xplane.load`` does not keep: for each device operation the scope path
the program gave it (``jax.named_scope``, carried by the HLO ``op_name``),
and the program's spans as the profiler saw them (``utils/tracing``
mirrors each recording span as a ``jax.profiler.TraceAnnotation``, which
lands on ``/host:CPU`` on the line of the thread that ran it).  One
parse per profile, cached; the rows of ops, modules and requests stay
``xplane.load``'s.

Looked at by hand on a v5e profile (PERF.md section 3; ``python3 -m
benchmarks.profile_rows <trace dir>`` prints what was looked at): an
``XLA Ops`` event carries three stats (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``) and no ``op_name``;
it is named by its whole HLO line, ``%while.9955 = (...) while(...)``,
without the metadata.  The ``op_name`` is in the optimised HLO module
itself, which the profiler stores once per program in the plane
``/host:metadata``: an event metadata entry named like the ``XLA
Modules`` event, ``jit_verify_batch(<fingerprint>)``, whose stat ``Hlo
Proto`` holds the serialised ``HloProto``.  ``jax.profiler.ProfileData``
shows no event metadata, so that plane is read from the file's bytes
with the few lines of protobuf wire format below (field numbers from
tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto), and an
operation is joined to its instruction by the name before `` = ``.  An
``op_name`` reads ``jit(verify_batch)/scalar_mul/while/body/...``; its
components between the slashes are the scopes.

Everything below the loader works on plain rows and intervals, so the
arithmetic is tested on hand-written rows.
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field

from . import xplane

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
# the program's span namespaces (docs/observability.md)
PROGRAM_SPAN = re.compile(r"^(verify|commit|blocksync)\.[A-Za-z0-9_.]+$")

Interval = tuple[float, float]


@dataclass
class Rows:
    # XLA module name -> {HLO instruction name -> op_name}
    op_names: dict[str, dict[str, str]] = field(default_factory=dict)
    annotations: list[xplane.Row] = field(default_factory=list)
    memo: dict = field(default_factory=dict)  # reductions shared by metrics


_LOADED: dict[tuple[str, float], Rows] = {}


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message: a
    varint as int, a length-delimited field as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise xplane.TraceError(f"wire type {wire} in the profile")
            value = buf[i:i + size]
            i += size
        yield key >> 3, wire, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _sub(buf, number: int):
    """The length-delimited fields ``number`` of one message."""
    return [v for n, w, v in _fields(buf) if n == number and w == 2]


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """{instruction name: op_name} of a serialised HloProto:
    hlo_module(1) > computations(3) > instructions(2) > name(1),
    metadata(7) > op_name(2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for computation in _sub(module, 3):
            for instruction in _sub(computation, 2):
                name = op_name = ""
                for n, w, v in _fields(instruction):
                    if n == 1 and w == 2:
                        name = _text(v)
                    elif n == 7 and w == 2:
                        op_name = "".join(_text(x) for x in _sub(v, 2))
                out[name] = op_name
    return out


def module_op_names(data) -> dict[str, dict[str, str]]:
    """From the bytes of an .xplane.pb: for each program whose HLO the
    profiler stored, its name and {instruction name: op_name}.
    XSpace.planes(1) > XPlane name(2), event_metadata(4), stat_metadata(5);
    a map entry is key(1), value(2); XEventMetadata name(2), stats(5);
    XStatMetadata name(2); XStat metadata_id(1), bytes_value(6)."""
    out = {}
    for plane in _sub(data, 1):
        name, events, stats = "", [], []
        for n, w, v in _fields(plane):
            if n == 2 and w == 2:
                name = _text(v)
            elif n == 4 and w == 2:
                events.append(v)
            elif n == 5 and w == 2:
                stats.append(v)
        if name != METADATA_PLANE:
            continue
        hlo_stat = None
        for entry in stats:
            key = next((v for n, w, v in _fields(entry) if n == 1), None)
            if any(_text(x) == HLO_PROTO_STAT
                   for value in _sub(entry, 2) for x in _sub(value, 2)):
                hlo_stat = key
        for entry in events:
            for meta in _sub(entry, 2):
                module = "".join(_text(x) for x in _sub(meta, 2))
                for stat in _sub(meta, 5):
                    parts = list(_fields(stat))
                    if any(n == 1 and v == hlo_stat for n, w, v in parts):
                        for n, w, v in parts:
                            if n == 6 and w == 2:
                                out[module] = hlo_op_names(v)
    return out


def instruction(event_name: str) -> str:
    """``%while.9955 = (s32[]...) while(...)`` -> ``while.9955``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Rows:
    """The profile at ``path``, parsed once per file."""
    key = (path, os.path.getmtime(path))
    rows = _LOADED.get(key)
    if rows is None:
        from jax.profiler import ProfileData

        rows = Rows()
        with open(path, "rb") as f:
            data = f.read()
        rows.op_names = module_op_names(memoryview(data))
        host = ProfileData.from_serialized_xspace(data).find_plane_with_name(
            xplane.HOST_PLANE)
        for line in host.lines if host is not None else ():
            rows.annotations += [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events if PROGRAM_SPAN.match(e.name)
            ]
        rows.annotations.sort()
        _LOADED.clear()  # one profile a run: keep no other
        _LOADED[key] = rows
    return rows


def of(sources: dict) -> Rows | None:
    """The rows for a reader: what the test handed in, else the profile
    the harness wrote; None where there is none."""
    if "profile_rows" in sources:
        return sources["profile_rows"]
    from . import harness

    try:
        return load(xplane.find_xplane(harness.TRACE_DIR))
    except xplane.TraceError:
        return None


# ------------------------------------------------------------ arithmetic


def under(op_name: str, scopes) -> bool:
    """Whether one of ``scopes`` is a component of the scope path."""
    return not set(scopes).isdisjoint(op_name.split("/"))


def intersect(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """The parts common to two lists of disjoint sorted intervals."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def scope_ns(ops, op_names: dict, scopes, modules, prefixes,
             lo: float, hi: float, complement: bool = False):
    """Device time, in [lo, hi] and inside the runs of the modules named
    by ``prefixes``, of the operations under one of ``scopes``: the
    union of their intervals, so a ``while`` and the operations of its
    iterations, which lie inside it, count once.  ``op_names`` is
    {module name: {instruction name: op_name}}.  ``complement``: the
    modules' time under none of the scopes.  None where no operation of
    these modules lies under any of the scopes (a program without the
    names, or a profile without its HLO: nothing to read)."""
    prefixes = tuple(prefixes)
    runs = xplane.merge(
        [(s, e) for s, e, name in modules if name.startswith(prefixes)],
        lo, hi,
    )
    wanted = {
        name
        for module, paths in op_names.items() if module.startswith(prefixes)
        for name, path in paths.items() if under(path, scopes)
    }
    if not wanted:
        return None
    verdict: dict[str, bool] = {}  # per event name: 21,000 of them, not 2.6 million

    def is_wanted(event_name: str) -> bool:
        hit = verdict.get(event_name)
        if hit is None:
            hit = verdict[event_name] = instruction(event_name) in wanted
        return hit

    covered = intersect(
        xplane.merge([r for r in ops if is_wanted(r[2])], lo, hi), runs
    )
    if not covered:
        return None
    return total(runs) - total(covered) if complement else total(covered)


def idle_by_class(ops, requests, annotations, classes,
                  lo: float, hi: float) -> list[float] | None:
    """Each idle gap of the chip inside a request, cut at the edges of
    the program's annotations and given to the FIRST of ``classes``
    (lists of span names, in order of precedence) one of whose spans
    covers the piece, on whatever thread; what no class covers is the
    last entry.  Lengths in ns, ``len(classes) + 1`` of them, summing to
    the idle time inside requests.  None where the profile holds none of
    the classes' spans (a program that mirrors no span)."""
    left = intersect(xplane.gaps(ops, lo, hi), xplane.merge(requests, lo, hi))
    out = []
    seen = False
    for names in classes:
        names = set(names)
        spans = [r for r in annotations if r[2] in names]
        seen = seen or bool(spans)
        covered = xplane.merge(spans, lo, hi)
        out.append(total(intersect(left, covered)))
        left = intersect(left, xplane.gaps(covered, lo, hi))
    out.append(total(left))
    return out if seen else None


# ------------------------------------------------------- the hand look


def describe(path: str, per_line: int = 3) -> list[str]:
    """For the hand look: per plane and line a few events with every
    stat the binding shows, the longest device operations, the host
    lines that hold program spans, and per stored HLO module how many
    instructions lie under which first scope."""
    from jax.profiler import ProfileData

    def show(e):
        stats = {k: (v if not isinstance(v, str) else v[:120])
                 for k, v in e.stats}
        return f"{e.name[:120]!r} dur={e.duration_ns} stats={stats}"

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for n, line in enumerate(plane.lines):
            events = list(line.events)
            out.append(f"  line {n} {line.name!r}: {len(events)} events")
            if plane.name == xplane.HOST_PLANE:
                names: dict[str, int] = {}
                for e in events:
                    if PROGRAM_SPAN.match(e.name) or e.name == xplane.REQUEST:
                        names[e.name] = names.get(e.name, 0) + 1
                if names:
                    out.append(f"    program spans: {names}")
                continue
            picks = events[:per_line]
            if line.name == xplane.OPS_LINE:
                picks = picks + sorted(
                    events, key=lambda e: -e.duration_ns)[:per_line]
            out += ["    " + show(e) for e in picks]
    with open(path, "rb") as f:
        modules = module_op_names(memoryview(f.read()))
    for module, paths in modules.items():
        first: dict[str, int] = {}
        for op_name in paths.values():
            parts = op_name.split("/")
            key = "/".join(parts[:3]) if op_name else "(no op_name)"
            first[key] = first.get(key, 0) + 1
        top = sorted(first.items(), key=lambda kv: -kv[1])[:24]
        out.append(f"hlo of {module!r}: {len(paths)} instructions; {top}")
    return out


if __name__ == "__main__":
    import sys

    print("\n".join(describe(xplane.find_xplane(sys.argv[1]))))
