"""Wall-clock adapter of an unchanged ``PDCluster`` over real backends.

The cluster is an event loop whose clock the hardware model advances:
on the chip it would sleep on modelled time and report modelled
latency.  This adapter, built only here, makes the loop run on the host
clock:

* every backend iteration returns, as its cost's ``time_s``, the host
  seconds the call took (``cost._replace``), not the modelled time;
* no event is handled before its wall time: the cluster's event handler
  first waits until ``t0`` + the event's time, then sets the cluster's
  clock to the wall clock, so what the handler schedules is on it too;
* a request is due at ``t0`` + its arrival time, and the lateness with
  which the loop picked each arrival up is kept (``lag``);
* each token is stamped with the host time at the end of the first
  backend call after which its request's ``output_tokens`` grew, which
  is when a streaming client could have it.

Host seconds and calls are counted per backend method, and around each
call a span of the same name can be written into the profiler's trace
(``annotate``), so that device idle time can be put down to what the
host was doing.  ``dispatches`` logs, in order, every step program the
backends launch (``("prefill", tokens)`` and ``("decode", contexts,
rids)``), for the trace reduction.
"""
from __future__ import annotations

import time
from collections import Counter
from typing import Callable, Dict, List, Optional

# backend iterations that return an IterCost, and the span each is in
ITERS = {"prefill_iter": "prefill", "prefill_chunk": "prefill",
         "decode_iter": "decode", "spec_decode_iter": "decode",
         "hybrid_iter": "decode"}
# other backend calls and their spans: the P->D handoff, the device wait
# behind an emission, and the slot release
CALLS = {"insert": "insert", "release": "release", "flush": "drain",
         "_drain_one": "drain"}


class WallClock:
    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.clock, self.sleep = clock, sleep
        self.t0: Optional[float] = None  # None: not gating (warm-up)
        self.live: Dict[int, object] = {}  # rid -> request being stamped
        self.stamps: Dict[int, List[float]] = {}  # s after t0, per token
        self.lag: List[float] = []  # arrival handled - due, s
        # (start, end) of pauses of the loop that are not the system's
        # (the profiler starting and stopping), s after t0 as it was
        # before each; the window's clock leaves them out (``pause``)
        self.pauses: List[tuple] = []
        self.host_s: Counter = Counter()  # span -> host seconds
        self.calls: Counter = Counter()  # span -> calls
        self.dispatches: List[tuple] = []
        self.log_dispatches = False
        self.annotate = False
        self.after_call: Optional[Callable[[float], None]] = None
        self.backends: List[tuple] = []  # (kind, backend)
        self._span = None  # innermost open span name

    # -- clock -------------------------------------------------------------
    def now(self) -> float:
        """Seconds since the window opened (host clock)."""
        return self.clock() - self.t0

    def start(self, requests) -> None:
        """Open the window: from here events wait for their wall time."""
        self.stamps = {r.rid: [] for r in requests}
        self.live = {}
        self.lag = []
        self.pauses = []
        self.host_s.clear()
        self.calls.clear()
        self.dispatches = []
        self.t0 = self.clock()

    def stop(self) -> None:
        self.t0 = None

    @property
    def paused_s(self) -> float:
        return sum(b - a for a, b in self.pauses)

    def pause(self, fn: Callable[[], None]) -> None:
        """Run ``fn`` outside the system's time: the window's clock
        stands still while it runs, so that every event still due,
        every arrival among them, moves later by as long as it took and
        no queue builds up behind it."""
        t_a = self.now()
        fn()
        t_b = self.now()
        self.pauses.append((t_a, t_b))
        self.t0 += t_b - t_a

    # -- cluster -----------------------------------------------------------
    def attach(self, cluster) -> None:
        """Gate the cluster's event handler on the wall clock."""
        from repro.serving.request import Request

        handle = cluster._handle_event

        def gated(kind, data):
            if self.t0 is not None:
                due = self.t0 + cluster.now
                w = self.clock()
                if w < due:
                    self.sleep(due - w)
                    w = self.clock()
                cluster.now = w - self.t0
                if isinstance(data, Request):  # an arrival
                    self.lag.append(cluster.now - data.arrival_s)
                    self.live[data.rid] = data
            return handle(kind, data)

        cluster._handle_event = gated

    # -- backends ----------------------------------------------------------
    def wrap_factory(self, factory):
        """A backend factory whose backends report host time and are
        stamped and counted by this adapter."""

        def make(kind, idx, hw, seed, tp=None):
            b = factory(kind, idx, hw, seed, tp=tp)
            self.wrap(b, kind)
            return b

        return make

    def wrap(self, b, kind: str) -> None:
        self.backends.append((kind, b))
        for name, span in ITERS.items():
            if hasattr(b, name):
                setattr(b, name, self._timed(getattr(b, name), span, True))
        for name, span in CALLS.items():
            if hasattr(b, name):
                setattr(b, name, self._timed(getattr(b, name), span, False))
        if hasattr(b, "_real_prefill"):
            b._real_prefill = self._logged_prefill(b._real_prefill)
        if hasattr(b, "_decode_jit"):
            b._decode_jit = self._logged_decode(b, b._decode_jit)

    def _timed(self, fn, span: str, iteration: bool):
        import jax

        def call(*a, **k):
            outer = self._span
            self._span = span
            t_a = self.clock()
            try:
                if self.annotate:
                    with jax.profiler.TraceAnnotation(f"cb.{span}"):
                        out = fn(*a, **k)
                else:
                    out = fn(*a, **k)
            finally:
                self._span = outer
            t_b = self.clock()
            if outer is None:  # nested calls are inside their parent's
                self.host_s[span] += t_b - t_a
                self.calls[span] += 1
                self._stamp(t_b)
                if self.after_call is not None and self.t0 is not None:
                    self.after_call(t_b - self.t0)
            if iteration:
                return out._replace(time_s=t_b - t_a)
            return out

        return call

    def _logged_prefill(self, fn):
        def call(r):
            if self.log_dispatches:
                n = r.prompt_len + (r.tokens_out if r.resuming else 0)
                self.dispatches.append(("prefill", n))
            return fn(r)

        return call

    def _logged_decode(self, b, fn):
        def call(*a, **k):
            if self.log_dispatches:
                rids = sorted(b.slot_of)
                ctx = [int(b.pos[b.slot_of[rid]]) for rid in rids]
                self.dispatches.append(("decode", ctx, tuple(rids)))
            return fn(*a, **k)

        return call

    def _stamp(self, t_b: float) -> None:
        if self.t0 is None:
            return
        t = t_b - self.t0
        done = []
        for rid, r in self.live.items():
            st = self.stamps[rid]
            n = len(r.output_tokens)
            if n > len(st):
                st.extend([t] * (n - len(st)))
            if r.finished and n >= r.decode_len + 1:
                done.append(rid)
        for rid in done:
            del self.live[rid]
