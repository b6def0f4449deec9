"""Trace clock (THAPI §3.1).

LTTng timestamps events with a monotonic ns clock and records a realtime
offset so traces from different nodes can be aligned during the muxing phase.
We reproduce that: ``now()`` is the hot-path monotonic ns clock, and
``ClockInfo`` captures the monotonic→realtime offset once per session, stored
in the trace metadata so the Muxer (plugins/intervals/babeltrace) can align
streams from different ranks/hosts.

A JAX profile keeps its own clock, counted from the profile's start, so the
realtime offset cannot place trace records on it.  The consumer thread opens
a ``thapi.clock`` profiler annotation (:data:`PROFILE_MARK`) once per tick
between two ``now()`` stamps; :func:`profile_clock` turns those anchors into
the offset from trace time to profile time.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Iterable, Optional, Tuple

# Hot path: a single C-level call, ~60ns. Bound at module level so generated
# tracepoints reference it directly (no attribute lookup chain).
now = time.monotonic_ns


@dataclasses.dataclass(frozen=True)
class ClockInfo:
    """Monotonic clock description persisted in trace metadata."""

    #: realtime_ns - monotonic_ns at capture; aligns streams across hosts.
    offset_ns: int
    #: monotonic timestamp when the session started (trace-local epoch).
    session_start_ns: int

    @staticmethod
    def capture() -> "ClockInfo":
        m = time.monotonic_ns()
        r = time.time_ns()
        return ClockInfo(offset_ns=r - m, session_start_ns=m)

    def to_realtime(self, ts_monotonic_ns: int) -> int:
        return ts_monotonic_ns + self.offset_ns

    def to_json(self) -> dict:
        return {"offset_ns": self.offset_ns, "session_start_ns": self.session_start_ns}

    @staticmethod
    def from_json(d: dict) -> "ClockInfo":
        return ClockInfo(offset_ns=int(d["offset_ns"]), session_start_ns=int(d["session_start_ns"]))


#: name of the profiler annotation each consumer tick opens; its ``ts``
#: argument is the trace-clock stamp taken just before it
PROFILE_MARK = "thapi.clock"


@dataclasses.dataclass(frozen=True)
class ProfileClock:
    """Trace time placed on a profile: ``profile_ns = trace_ns + offset_ns``."""

    offset_ns: float
    #: largest less smallest offset over the anchors used: the alignment error
    spread_ns: float
    #: anchors used, of ``given``
    anchors: int
    given: int


def profile_clock(anchors: Iterable[Tuple[float, int, int]]) -> Optional[ProfileClock]:
    """The offset from trace time to a profile's time, from anchors
    ``(profile start of a mark, trace stamp before it, trace stamp after
    it)``: the median of (mark start − mid-stamp), with their spread as the
    alignment error.  An anchor whose stamps lie more than twice the median
    bracket apart was held up between them (its thread waited for the GIL),
    so its mid-stamp stands for the mark to within no better than half that
    bracket: it is left out.  None without an anchor."""
    anchors = list(anchors)
    if not anchors:
        return None
    wide = 2 * statistics.median(b - a for _, a, b in anchors)
    offs = sorted(p - (a + b) / 2 for p, a, b in anchors if b - a <= wide)
    return ProfileClock(statistics.median(offs), offs[-1] - offs[0], len(offs), len(anchors))
