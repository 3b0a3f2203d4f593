"""From a profiler trace to device time, idle gaps and step work.

``extract`` reads a JAX profiler trace (``.xplane.pb``) into plain
lists of ``[name, start_s, duration_s]``: the device's programs (the
``XLA Modules`` line of the first TPU plane), its operations (``XLA
Ops``) and the host spans the wall-clock adapter wrote (names starting
``cb.``).  ``reduce`` works on those lists alone, so it is checked on a
small recorded trace without a chip.

The program's step programs all carry the module name that JAX gives a
``jit`` of a ``functools.partial`` (``STEP_MODULE``): the paged prefill
and the paged decode step are told apart by order.  The adapter drains
the device before the trace starts and before it stops, so the step
modules in the trace are exactly the step dispatches it logged, in the
same order (one device executes its programs in the order it was
given them).  Where the counts differ, no step metric is read.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

STEP_MODULE = re.compile(r"^jit__unknown(\W|$)")
SPAN_PREFIX = "cb."


def op_name(hlo: str) -> str:
    """``%fusion.177 = (...) fusion(...), kind=kOutput, ...`` ->
    ``fusion.177 kOutput``: the instruction and its kind, which stay the
    same across a layer scan's iterations."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    kind = re.search(r"kind=(k\w+)", hlo)
    return f"{head} {kind.group(1)}" if kind else head


def extract(trace_dir: str) -> dict:
    """The device plane's modules and ops, and the adapter's host spans."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return {"modules": [], "ops": [], "spans": [], "device": None,
                "planes": []}
    pd = ProfileData.from_file(str(files[-1]))
    out = {"modules": [], "ops": [], "spans": [], "device": None,
           "planes": []}
    for plane in pd.planes:
        out["planes"].append((plane.name, [ln.name for ln in plane.lines]))
        if plane.name.startswith("/device:TPU:") and out["device"] is None:
            out["device"] = plane.name
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = op_name(e.name) if key == "ops" else e.name
                    out[key].append([name, e.start_ns * 1e-9,
                                     e.duration_ns * 1e-9])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["spans"].append([e.name[len(SPAN_PREFIX):],
                                             e.start_ns * 1e-9,
                                             e.duration_ns * 1e-9])
    for k in ("modules", "ops", "spans"):
        out[k].sort(key=lambda x: x[1])
    return out


def union(intervals: List[tuple]) -> List[List[float]]:
    """Merged [start, end] of the busy intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Busy:
    """Device busy time inside any [a, b], from the merged intervals."""

    def __init__(self, merged: List[List[float]]):
        self.s = [x[0] for x in merged]
        self.e = [x[1] for x in merged]
        self.cum = np.concatenate([[0.0], np.cumsum(
            np.subtract(self.e, self.s))]) if merged else np.zeros(1)

    def within(self, a: float, b: float) -> float:
        if b <= a or not self.s:
            return 0.0
        i = bisect.bisect_right(self.e, a)  # first interval ending after a
        j = bisect.bisect_left(self.s, b)  # intervals starting before b
        if j <= i:
            return 0.0
        tot = self.cum[j] - self.cum[i]
        tot -= max(0.0, a - self.s[i])
        tot -= max(0.0, self.e[j - 1] - b)
        return float(tot)


def host_segments(spans: List[list], lo: float, hi: float) -> List[tuple]:
    """Piecewise host state over [lo, hi]: the innermost open span, or
    ``control`` outside every backend call."""
    pts = []
    for name, s, d in spans:
        pts.append((s, 1, name))
        pts.append((s + d, 0, name))
    pts.sort(key=lambda p: (p[0], p[1]))
    segs, stack, t = [], [], lo
    for x, opening, name in pts:
        x = min(max(x, lo), hi)
        if x > t:
            segs.append((t, x, stack[-1] if stack else "control"))
            t = x
        if opening:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
    if hi > t:
        segs.append((t, hi, stack[-1] if stack else "control"))
    return segs


def idle_by_host(merged, segs, lo: float, hi: float) -> Dict[str, float]:
    """Device-idle seconds in [lo, hi], put down to the host's state."""
    idle, t = [], lo
    for s, e in merged:
        if s > t:
            idle.append((t, min(s, hi)))
        t = max(t, e)
    if hi > t:
        idle.append((t, hi))
    out: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            s0, s1, name = segs[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
            k += 1
    return out


def reduce(ev: dict, dispatches: List[tuple], c: Optional[dict],
           peaks: Optional[dict]) -> dict:
    """Busy and idle time, the breakdown, and the work of each step.

    ``dispatches``: the adapter's log, ``("prefill", tokens)`` or
    ``("decode", contexts, rids)`` per step program, in dispatch order.
    ``c``/``peaks``: the configuration and the device's peaks, for the
    operations and least time of each step (None: times only)."""
    import work

    ops = [(s, s + d) for _, s, d in ev["ops"]]
    mods = ev["modules"]
    merged = union(ops + [(s, s + d) for _, s, d in mods])
    edges = [x for _, s, d in mods + ev["ops"] + ev["spans"]
             for x in (s, s + d)]
    lo, hi = (min(edges), max(edges)) if edges else (0.0, 0.0)
    busy = Busy(merged)
    out = {"busy_s": busy.within(lo, hi), "span_s": hi - lo,
           "steps": False, "prefill_s": 0.0, "prefill_flops": 0.0,
           "decode_s": 0.0, "decode_flops": 0.0, "decode_least_s": 0.0,
           "decode_gaps": [], "n_prefill": 0, "n_decode": 0}
    per_op: Dict[str, float] = {}
    for name, _, d in ev["ops"]:
        per_op[name] = per_op.get(name, 0.0) + d
    out["device_ops"] = sorted(([k, v] for k, v in per_op.items()),
                               key=lambda x: -x[1])[:10]
    segs = host_segments(ev["spans"], lo, hi)
    gaps = idle_by_host(merged, segs, lo, hi)
    out["idle_gaps"] = sorted(([k, v] for k, v in gaps.items()),
                              key=lambda x: -x[1])[:10]
    steps = [m for m in mods if STEP_MODULE.match(m[0])]
    out["n_step_modules"] = len(steps)
    if c is None or len(steps) != len(dispatches) or not steps:
        return out
    out["steps"] = True
    prev = None
    for (name, s, d), disp in zip(steps, dispatches):
        if disp[0] == "prefill":
            out["n_prefill"] += 1
            out["prefill_s"] += d
            out["prefill_flops"] += work.prefill_flops(c, disp[1])
            continue
        ctx, rids = disp[1], set(disp[2])
        f = work.decode_flops(c, ctx)
        b = work.decode_bytes(c, ctx)
        out["n_decode"] += 1
        out["decode_s"] += d
        out["decode_flops"] += f
        if peaks is not None:
            out["decode_least_s"] += max(f / peaks["flops_per_s"],
                                         b / peaks["bytes_per_s"])
        # idle between two steps of one running batch; a prefill or an
        # insert between them is busy time, not a gap
        if prev is not None and prev[2] & rids:
            out["decode_gaps"].append(
                (s - prev[1]) - busy.within(prev[1], s))
        prev = (s, s + d, rids)
    return out
