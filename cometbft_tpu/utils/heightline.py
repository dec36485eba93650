"""Per-height consensus timeline ledger — the answer to "why was
height H slow".

A bounded registry of the last N committed-or-in-progress heights, each
carrying the wall-clock time its consensus pipeline reached every
phase:

    start         entered the height (round-0 propose step)
    proposal      proposal message accepted
    full_block    every block part assembled (Block decoded)
    prevote_23    2/3 prevote majority observed
    precommit_23  2/3 precommit majority observed
    commit        entered commit step
    apply         block executed + state persisted

plus the height's verify attribution: how many verify-service batches
settled while the height was current, their total signature width, and
the wall time spent inside their collects — the vote/verify pipeline
dominates committee-based consensus latency (arXiv:2302.00418), so
"slow height" almost always decomposes into one of these phases plus
its verify wait.

Feeds: consensus/state marks the consensus phases, blocksync/reactor
marks full_block/commit/apply for fast-synced heights, and the verify
service's collector reports settled CONSENSUS-class batches (attributed
to the registry's *current* height — batch tickets don't carry heights;
blocksync attributes its own waits explicitly by height).

Every mark is cross-recorded into the consensus flight recorder (kind
``heightline``), which makes the ledger reconstructible: a fresh
registry replays the recorder ring (:func:`restore_from_flightrec`)
after a restart or a dump-driven post-mortem, so the timeline survives
the process that produced it losing its in-memory state.

Surfaces: ``consensus_height_phase_seconds{phase}`` Hub histogram
observations (the delta between consecutive phase marks), the
``/height_timeline`` RPC route, and the flight recorder's ``heightline`` kind.

Bounded by ``COMETBFT_TPU_HEIGHTLINE_CAP`` heights; disabled entirely
with ``COMETBFT_TPU_HEIGHTLINE=0`` (marks become no-ops, the RPC
answers empty).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict

from . import envknobs

# canonical phase order: a phase's histogram observation measures the
# delta from the latest EARLIER phase that was marked for the height
PHASES = (
    "start",
    "proposal",
    "full_block",
    "prevote_23",
    "precommit_23",
    "commit",
    "apply",
)
_PHASE_IDX = {p: i for i, p in enumerate(PHASES)}

# phases whose deltas are observed into the Hub histogram ("start" is
# the reference point, not a duration)
METRIC_PHASES = PHASES[1:]


class HeightlineRegistry:
    """Bounded height -> timeline map.  Thread-safe: consensus,
    blocksync, and the verify collector threads all feed it."""

    def __init__(self, capacity: int | None = None, enabled: bool | None = None):
        if capacity is None:
            capacity = envknobs.get_int(envknobs.HEIGHTLINE_CAP)
        self.capacity = max(8, int(capacity))
        self.enabled = (
            envknobs.get_bool(envknobs.HEIGHTLINE)
            if enabled is None else bool(enabled)
        )
        self._mtx = threading.Lock()
        self._heights: OrderedDict[int, dict] = OrderedDict()
        self._current: int = 0
        self._evicted = 0

    # ------------------------------------------------------------ feeding

    def _entry_locked(self, height: int) -> dict:
        e = self._heights.get(height)
        if e is None:
            e = {
                "height": height,
                "phases": {},  # phase -> wall_ns of FIRST occurrence
                "round": 0,
                "verify": {"batches": 0, "sigs": 0, "wait_s": 0.0},
            }
            self._heights[height] = e
            while len(self._heights) > self.capacity:
                self._heights.popitem(last=False)
                self._evicted += 1
        return e

    def mark(
        self,
        height: int,
        phase: str,
        wall_ns: int | None = None,
        round_: int = 0,
        _record: bool = True,
    ) -> None:
        """Record that ``height`` reached ``phase`` (first mark wins —
        a re-proposal after a round bump doesn't rewind the timeline,
        but the max round is kept).  Observes the phase-delta histogram
        and cross-records into the flight recorder unless replaying."""
        if not self.enabled or height <= 0 or phase not in _PHASE_IDX:
            return
        if wall_ns is None:
            wall_ns = time.time_ns()
        idx = _PHASE_IDX[phase]
        with self._mtx:
            e = self._entry_locked(height)
            if round_ > e["round"]:
                e["round"] = round_
            if phase in e["phases"]:
                return
            e["phases"][phase] = wall_ns
            prev_ns = None
            for p, t in e["phases"].items():
                if _PHASE_IDX[p] < idx and (prev_ns is None or t > prev_ns):
                    prev_ns = t
        if not _record:
            return
        if phase in METRIC_PHASES and prev_ns is not None:
            from .metrics import hub as _mhub

            _mhub().cs_height_phase.observe(
                max(0.0, (wall_ns - prev_ns) / 1e9), phase=phase
            )
        from .flightrec import recorder as _flightrec

        _flightrec().record(
            "heightline", height=height, round=round_,
            phase=phase, t_wall_ns=wall_ns,
        )

    def set_current(self, height: int) -> None:
        """The height consensus is working on NOW — the attribution
        target for verify batches (whose tickets don't carry heights)."""
        if self.enabled:
            self._current = height

    @property
    def current(self) -> int:
        return self._current

    def note_verify(
        self, nsigs: int, wait_s: float, height: int | None = None
    ) -> None:
        """Attribute one settled verify batch (``nsigs`` wide, its
        collect blocked ``wait_s``) to ``height`` — or to the current
        height when the caller doesn't know one (the service collector).
        Unattributable batches (no current height yet) are dropped."""
        if not self.enabled:
            return
        h = self._current if height is None else height
        if h <= 0:
            return
        with self._mtx:
            v = self._entry_locked(h)["verify"]
            v["batches"] += 1
            v["sigs"] += int(nsigs)
            v["wait_s"] += float(wait_s)

    # ------------------------------------------------------------ reading

    def snapshot(self, limit: int | None = None) -> dict:
        """JSON-ready view, heights ascending: per height the absolute
        wall_ns of each phase, per-phase deltas in seconds, and the
        verify attribution.  ``limit`` keeps only the newest N."""
        with self._mtx:
            entries = list(self._heights.values())
            current = self._current
            evicted = self._evicted
        entries.sort(key=lambda e: e["height"])
        if limit is not None and limit >= 0:
            entries = entries[len(entries) - min(limit, len(entries)):]
        out = []
        for e in entries:
            phases = dict(e["phases"])
            deltas = {}
            marked = sorted(phases.items(), key=lambda kv: _PHASE_IDX[kv[0]])
            for (p0, t0), (p1, t1) in zip(marked, marked[1:]):
                deltas[p1] = max(0.0, (t1 - t0) / 1e9)
            total = None
            if len(marked) >= 2:
                total = max(0.0, (marked[-1][1] - marked[0][1]) / 1e9)
            out.append({
                "height": e["height"],
                "round": e["round"],
                "phases_wall_ns": phases,
                "phase_seconds": deltas,
                "total_seconds": total,
                "verify": dict(e["verify"]),
            })
        return {
            "heights": out,
            "count": len(out),
            "current_height": current,
            "capacity": self.capacity,
            "evicted": evicted,
            "enabled": self.enabled,
        }

    def clear(self) -> None:
        with self._mtx:
            self._heights.clear()
            self._current = 0
            self._evicted = 0


def restore_from_flightrec(
    registry: HeightlineRegistry, rec=None
) -> int:
    """Rebuild a registry's phase marks from flight-recorder
    ``heightline`` entries (the live global recorder by default, or any
    dumped ``{"entries": [...]}`` trace) — original wall times, no
    re-observation into metrics, no re-recording.  Returns the number
    of marks replayed."""
    if rec is None:
        from .flightrec import recorder

        rec = recorder()
    entries = rec["entries"] if isinstance(rec, dict) else rec.dump()["entries"]
    n = 0
    top = 0
    for e in entries:
        if e.get("kind") != "heightline":
            continue
        d = e.get("detail", {})
        phase = d.get("phase")
        if phase not in _PHASE_IDX:
            continue
        registry.mark(
            e.get("height", 0), phase,
            wall_ns=d.get("t_wall_ns", e.get("wall_ns")),
            round_=e.get("round", 0) or 0,
            _record=False,
        )
        top = max(top, e.get("height", 0))
        n += 1
    if top:
        registry.set_current(top)
    return n


_REG = HeightlineRegistry()


def registry() -> HeightlineRegistry:
    """The process-global ledger (same sharing model as the flight
    recorder: multi-node test processes share one; entries carry
    heights, so interleaved nodes stay distinguishable)."""
    return _REG
