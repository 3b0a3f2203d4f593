"""Event-loop time-split instrumentation (opt-in, zero cost when off).

``install(cluster)`` wraps a :class:`~repro.serving.cluster.PDCluster`'s
engines and routers with ``perf_counter`` accounting and returns a
:class:`LoopProfile`; run the workload, then read ``profile.breakdown()``
for the per-phase wall split the benchmark harness publishes in
``BENCH_serving.json``:

* ``schedule`` — engine ``start_iteration`` minus its inner EcoFreq and
  backend shares: batch assembly, admission, chunk take selection.
* ``select``   — EcoFreq frequency-ladder scans (``controller.select``).
* ``route``    — router placement decisions (``Router.route`` on the
  cluster's prefill/decode routers).  The cluster's ``_route_*``
  wrappers are deliberately NOT the probe point: they also kick idle
  engines, whose iteration time is already accounted under
  schedule/select/dispatch — timing them here double-counted that work
  as routing.
* ``dispatch`` — backend iteration calls' host time (Sim: hwmodel
  pricing; Real: jit dispatch — *not* device completion, which the async
  backend defers).
* ``device_wait`` — host time truly blocked on device transfers: the
  sum of the real backends' ``wait.drain`` spans (below); 0 in pure
  simulation.
* ``metrics``  — ``finish_iteration`` bookkeeping + straggler-bias
  re-prediction at ``_D_DONE``.
* ``queue``    — event-heap pops (the loop's only unavoidable
  per-event cost; pushes land under the handler that issued them).
* ``bookkeeping`` — per-event handler wall *not* covered by the probes
  above: routing wrappers' kick logic, request lifecycle mutation,
  chaos/scale handling, heap pushes.  Round 3 made this measurable so
  the unaccounted residue is timer overhead, not folklore.

Decision-plane telemetry (round 2) rides along in the same dict:

* ``select_memo_hit_rate`` — fraction of ``controller.select`` calls
  answered from the quantized-state memo (aggregated over every
  instrumented controller, unwrapping ``IntervalFreq``).
* ``route_batch_rows_avg`` — mean what-if rows per batched predictor
  matrix call across the routers (1.0 means no batching was possible).
* ``pipeline_depth_avg`` — mean async-dispatch ring occupancy observed
  at dispatch across real backends (0 for pure simulation, which has
  nothing in flight).

Engines created *after* ``install`` (autoscaler / chaos scale-out) are
instrumented too: the installer registers itself on the cluster's
``_spawn_hooks``, so mid-run spawns get the same wrapping and the
breakdown's ``accounted_frac`` stays honest.  Wrapping costs a couple of
``perf_counter`` calls per iteration, so install it for breakdown runs,
not for the headline iterations/s row.

**Spans.**  ``install`` also sets the profile on every backend (and on
the cluster), and the served path appends :class:`Span` records to
``profile.spans`` where the work happens:

* ``queue.prefill`` — from the cluster handing a request to the prefill
  router (an arrival or a re-route) to the prefill step that takes it;
* ``step.prefill`` — ``RealBackend._real_prefill``; child
  ``wait.first_token``, the host blocked on the first token;
* ``step.insert`` — the P→D handoff into a decode slot, whole;
* ``step.decode`` — the dispatch of one decode (or draft–verify) step;
* ``wait.drain`` — the host blocked on an in-flight step's token ids,
  a child of whatever forced it (an insert, the next decode dispatch;
  top level under a release or the end-of-run flush).

Each span holds host start and end (``perf_counter``), the serving
clock (``cluster.now``) at both ends, its parent's index and the request
id or the step's rids.  Step spans (prefill, insert, decode) carry
``compiled``: whether an XLA backend compile ran inside them, counted
from JAX's ``/jax/core/compile/backend_compile_duration`` monitoring
event, which jitted steps and eager ops (the insert's page scatter)
both emit, persistent-cache loads included.  ``profile.compiles`` sums
them.  While a profile is attached each span is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so inside a
profiler trace the spans sit on the device trace's clock.
With no profile attached a site costs nothing but ``is None`` tests.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from time import perf_counter
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compiles = [0]  # XLA backend compiles since this module was imported


def _count_compile(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _compiles[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


class Span:
    """One timed piece of served work (see the module docstring)."""

    __slots__ = ("name", "t0", "t1", "now0", "now1", "parent", "rid",
                 "attrs", "_ann", "_c0")

    def __init__(self, name: str, parent: int, rid: Optional[int],
                 attrs: dict):
        self.name, self.parent, self.rid, self.attrs = (
            name, parent, rid, attrs)
        self.t1: Optional[float] = None  # None while open
        self.now1: Optional[float] = None

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, t0={self.t0}, t1={self.t1}, "
                f"parent={self.parent}, rid={self.rid}, {self.attrs})")


@dataclass
class LoopProfile:
    start_total_s: float = 0.0
    select_s: float = 0.0
    backend_s: float = 0.0
    finish_total_s: float = 0.0
    route_s: float = 0.0
    queue_s: float = 0.0        # event-heap pops (profiled drain)
    bookkeeping_s: float = 0.0  # handler wall minus the probes above
    iterations: int = 0
    spans: List[Span] = field(default_factory=list)
    compiles: int = 0  # XLA backend compiles inside step spans
    _engines: List = field(default_factory=list)   # live, grows on spawn
    _backends: List = field(default_factory=list)  # live, grows on spawn
    _routers: List = field(default_factory=list)
    _cluster: object = None  # serving clock: ``_cluster.now``
    _stack: List[int] = field(default_factory=list)  # open nested spans
    _queued: Dict[int, Span] = field(default_factory=dict)  # rid -> open
    _inst: Dict[int, str] = field(default_factory=dict)  # id(backend)

    # -- spans -----------------------------------------------------------
    def _start(self, sp: Span) -> Span:
        sp._ann = TraceAnnotation("repro." + sp.name)
        sp._ann.__enter__()
        sp.t0 = perf_counter()
        sp.now0 = self._cluster.now
        return sp

    def _stop(self, sp: Span) -> None:
        sp.t1 = perf_counter()
        sp._ann.__exit__(None, None, None)
        sp._ann = None
        sp.now1 = self._cluster.now

    def begin(self, name: str, rid: Optional[int] = None,
              **attrs) -> Span:
        """Open a span nested in the innermost open one."""
        sp = Span(name, self._stack[-1] if self._stack else -1, rid, attrs)
        self._stack.append(len(self.spans))
        self.spans.append(sp)
        return self._start(sp)

    def end(self, sp: Span, **attrs) -> Span:
        self._stop(sp)
        sp.attrs.update(attrs)
        while self._stack:  # a span an exception left open goes too
            if self.spans[self._stack.pop()] is sp:
                break
        return sp

    def begin_step(self, name: str, rid: Optional[int] = None,
                   **attrs) -> Span:
        """A step span: it also notes whether XLA compiled inside."""
        c0 = _compiles[0]
        sp = self.begin(name, rid, **attrs)
        sp._c0 = c0
        return sp

    def end_step(self, sp: Span, **attrs) -> Span:
        self.end(sp, **attrs)
        n = _compiles[0] - sp._c0
        sp.attrs["compiled"] = n > 0
        self.compiles += n
        return sp

    def instance(self, backend) -> Optional[str]:
        """The name of the engine that ``backend`` serves."""
        return self._inst.get(id(backend))

    def queued(self, rid: int) -> None:
        """A request goes to the prefill router: its ``queue.prefill``
        opens, at top level (it closes in a later event)."""
        sp = Span("queue.prefill", -1, rid, {})
        self.spans.append(sp)
        self._queued[rid] = self._start(sp)

    def taken(self, reqs) -> None:
        """A prefill step takes these requests: their waits close."""
        for r in reqs:
            sp = self._queued.pop(r.rid, None)
            if sp is not None:
                self._stop(sp)

    def _device_wait(self) -> float:
        return sum(s.t1 - s.t0 for s in self.spans
                   if s.name == "wait.drain" and s.t1 is not None)

    def _select_memo_rate(self) -> float:
        hits = misses = 0
        for eng in self._engines:
            c = getattr(eng, "controller", None)
            c = getattr(c, "base", c)  # IntervalFreq wraps the memo owner
            hits += getattr(c, "select_memo_hits", 0)
            misses += getattr(c, "select_memo_misses", 0)
        return hits / (hits + misses) if hits + misses else 0.0

    def _route_batch_avg(self) -> float:
        queries = rows = 0
        for r in self._routers:
            queries += getattr(r, "route_batch_queries", 0)
            rows += getattr(r, "route_batch_rows", 0)
        return rows / queries if queries else 0.0

    def _pipeline_depth_avg(self) -> float:
        n = sum(
            getattr(b, "pipeline_dispatches", 0) for b in self._backends
        )
        s = sum(
            getattr(b, "pipeline_depth_sum", 0) for b in self._backends
        )
        return s / n if n else 0.0

    def breakdown(self, wall_s: float = 0.0) -> Dict[str, float]:
        dev = self._device_wait()
        out = {
            "schedule_s": max(
                0.0, self.start_total_s - self.select_s - self.backend_s
            ),
            "select_s": self.select_s,
            "route_s": self.route_s,
            "dispatch_s": max(0.0, self.backend_s - dev),
            "device_wait_s": dev,
            "metrics_s": self.finish_total_s,
            "queue_s": self.queue_s,
            "bookkeeping_s": self.bookkeeping_s,
            "iterations": self.iterations,
            "select_memo_hit_rate": self._select_memo_rate(),
            "route_batch_rows_avg": self._route_batch_avg(),
            "pipeline_depth_avg": self._pipeline_depth_avg(),
        }
        if wall_s > 0:
            out["wall_s"] = wall_s  # denominator for phase *shares*
            out["accounted_frac"] = round(
                (out["schedule_s"] + out["select_s"] + out["route_s"]
                 + out["dispatch_s"] + out["device_wait_s"]
                 + out["metrics_s"] + out["queue_s"]
                 + out["bookkeeping_s"]) / wall_s, 4,
            )
        return {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in out.items()
        }


_BACKEND_ITERS = (
    "prefill_iter", "prefill_chunk", "decode_iter", "spec_decode_iter",
    "hybrid_iter",
)


def install(cluster) -> LoopProfile:
    """Wrap the cluster's engines/routers in place; returns the profile
    the wrappers accumulate into.  Registers on the cluster's
    ``_spawn_hooks`` so engines spawned mid-run (scale-out) are wrapped
    identically."""
    prof = LoopProfile(_cluster=cluster)

    def timed(fn, attr, count=False):
        def wrapper(*a, **k):
            t0 = perf_counter()
            try:
                return fn(*a, **k)
            finally:
                setattr(prof, attr, getattr(prof, attr)
                        + perf_counter() - t0)
                if count:
                    prof.iterations += 1
        return wrapper

    def instrument(eng):
        eng.start_iteration = timed(eng.start_iteration, "start_total_s")
        eng.finish_iteration = timed(eng.finish_iteration,
                                     "finish_total_s")
        eng.controller.select = timed(eng.controller.select, "select_s")
        for name in _BACKEND_ITERS:
            if hasattr(eng.backend, name):
                setattr(eng.backend, name,
                        timed(getattr(eng.backend, name), "backend_s",
                              count=True))
        prof._engines.append(eng)
        prof._backends.append(eng.backend)
        prof._inst[id(eng.backend)] = eng.energy.name
        eng._prof = eng.backend._prof = prof  # the spans

    for eng in (list(cluster.prefill) + list(cluster.decode)
                + list(cluster.hybrid)):
        instrument(eng)
    cluster.prefill_router.route = timed(cluster.prefill_router.route,
                                         "route_s")
    cluster.decode_router.route = timed(cluster.decode_router.route,
                                        "route_s")
    hooks = getattr(cluster, "_spawn_hooks", None)
    if hooks is not None:
        hooks.append(instrument)
    prof._routers = [cluster.prefill_router, cluster.decode_router]
    # the cluster's run loop switches to its profiled drain (heap-pop +
    # per-event residue timing) when a profile is attached
    cluster._prof = prof
    return prof
