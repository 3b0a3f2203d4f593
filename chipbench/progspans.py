"""The program's own spans, as the metric readers need them.

In the traced run ``run.prof`` is the program's ``loopprof`` profile;
the served path appends spans to its ``spans`` list where the work
happens (``repro/serving/loopprof.py`` names them).  A span has host
start and end ``t0``/``t1`` (``perf_counter``; ``t1`` is None while
open), the serving clock ``now0``/``now1`` at both ends (window time
under the adapter), the index of its ``parent`` (-1 at top level), the
request id ``rid`` and ``attrs``.  A program that records no spans
gives every reader here nothing to read, and its metrics are left out.

The window's clock stands still over a profiler pause and resumes at
the pause's start ``t_a`` (``WallClock.pause``), so a wait or interval
whose serving-clock span holds a ``t_a`` also holds the pause's host
seconds: it is left out.
"""
from __future__ import annotations

import bisect
import statistics
from typing import List, Optional


def spans_of(run) -> Optional[list]:
    """The profile's spans, or None where there are none."""
    return getattr(run.prof, "spans", None) or None


def over_pause(run, a: float, b: float) -> bool:
    """Whether [a, b] of the serving clock holds a pause's start."""
    return any(a <= t_a <= b for t_a, _ in run.wall.pauses)


def prefill_waits(run) -> List[float]:
    """Seconds of every ``queue.prefill`` of the requests counted: from
    the hand-over to the prefill router to the step that took it."""
    sp = spans_of(run)
    if sp is None:
        return []
    counted = {it.rid for it in run.counted}
    return [s.t1 - s.t0 for s in sp
            if s.name == "queue.prefill" and s.t1 is not None
            and s.rid in counted and not over_pause(run, s.now0, s.now1)]


def insert_self_times(run) -> List[float]:
    """Seconds of every ``step.insert`` less its ``wait.drain``
    children: the host work of the P->D handoff itself."""
    sp = spans_of(run)
    if sp is None:
        return []
    waited = {}
    for s in sp:
        if s.name == "wait.drain" and s.parent >= 0 and s.t1 is not None:
            waited[s.parent] = waited.get(s.parent, 0.0) + (s.t1 - s.t0)
    return [s.t1 - s.t0 - waited.get(i, 0.0) for i, s in enumerate(sp)
            if s.name == "step.insert" and s.t1 is not None]


def decode_extras(run) -> List[float]:
    """Per pair of consecutive ``step.decode`` spans of one instance
    that share a request, the seconds from the start of the first to
    the start of the second beyond the median of such intervals that no
    ``step.prefill`` or ``step.insert`` started inside (0 for those)."""
    sp = spans_of(run)
    if sp is None:
        return []
    others = sorted(s.t0 for s in sp
                    if s.name in ("step.prefill", "step.insert"))
    steps = {}
    for s in sp:
        if s.name == "step.decode":
            steps.setdefault(s.attrs["inst"], []).append(s)
    intervals = []  # (seconds, interrupted)
    for seq in steps.values():
        for a, b in zip(seq, seq[1:]):
            if not set(a.attrs["rids"]) & set(b.attrs["rids"]):
                continue
            if over_pause(run, a.now0, b.now0):
                continue
            i = bisect.bisect_right(others, a.t0)
            intervals.append((b.t0 - a.t0,
                              i < len(others) and others[i] < b.t0))
    plain = [d for d, hit in intervals if not hit]
    if not plain:
        return []
    med = statistics.median(plain)
    return [d - med if hit else 0.0 for d, hit in intervals]


def prefill_steps(spans, since: float) -> dict:
    """Request id -> ``(computed, reused)`` tokens of each of its
    ``step.prefill`` spans that began at or after host time ``since``,
    in order: what each prefill the trace holds computed and took from
    the prefix cache."""
    out: dict = {}
    for s in spans or ():
        if s.name == "step.prefill" and s.t0 >= since and s.t1 is not None:
            out.setdefault(s.rid, []).append(
                (int(s.attrs["computed"]), int(s.attrs["reused"])))
    return out


def prefill_tokens(run) -> Optional[tuple]:
    """(reused, computed) prompt tokens over the ``step.prefill`` spans
    of the requests counted, or None where there are none."""
    sp = spans_of(run)
    if sp is None:
        return None
    counted = {it.rid for it in run.counted}
    steps = [s for s in sp if s.name == "step.prefill" and s.t1 is not None
             and s.rid in counted]
    if not steps:
        return None
    return (sum(s.attrs["reused"] for s in steps),
            sum(s.attrs["computed"] for s in steps))
