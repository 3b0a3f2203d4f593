"""The per-layer metrics read from the program's own spans: each reader
on hand-built spans, the traced cell, and the spans against the
profiler's trace and the adapter's token stamps on the CPU."""
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import model
import tiny
import traffic
from wallclock import WallClock

NEW = ("prefill_wait_p90_ms", "handoff_ms", "decode_interrupt_p95_ms")


def S(name, t0, t1, parent=-1, rid=None, now0=None, now1=None, **attrs):
    """A span as the program records it; the serving clock defaults to
    the host clock."""
    return SimpleNamespace(name=name, t0=t0, t1=t1, parent=parent, rid=rid,
                           now0=t0 if now0 is None else now0,
                           now1=t1 if now1 is None else now1, attrs=attrs)


def fake_run(spans, pauses=(), counted=()):
    return SimpleNamespace(
        prof=SimpleNamespace(spans=list(spans)),
        wall=SimpleNamespace(pauses=list(pauses)),
        counted=[SimpleNamespace(rid=r) for r in counted])


def read(name, run):
    return harness.reader(name).read(run)


def test_prefill_wait_by_hand():
    """p90 by nearest rank of the counted requests' waits; a wait over a
    pause's start, an open one and an uncounted one are left out."""
    spans = [S("queue.prefill", 0.0, 0.010, rid=1),
             S("queue.prefill", 0.1, 0.120, rid=2),
             S("queue.prefill", 0.2, 0.230, rid=3),
             # its serving clock holds the pause at 1.2: left out
             S("queue.prefill", 1.0, 1.500, rid=4, now0=1.0, now1=1.3),
             S("queue.prefill", 2.0, 2.040, rid=5),
             S("queue.prefill", 2.0, 2.090, rid=9),  # not counted
             S("queue.prefill", 3.0, None, rid=6),  # never taken
             S("step.prefill", 0.010, 0.015, rid=1)]
    run = fake_run(spans, pauses=[(1.2, 1.25)], counted=[1, 2, 3, 4, 5, 6])
    assert read("prefill_wait_p90_ms", run) == pytest.approx(40.0)


def test_handoff_by_hand():
    """Mean insert less its own drains; a drain of a decode step or of a
    release is nobody's insert."""
    spans = [S("step.insert", 0.000, 0.010, rid=1),
             S("wait.drain", 0.001, 0.007, parent=0, seq=0, rids=(0,)),
             S("step.insert", 0.020, 0.023, rid=2),
             S("step.decode", 0.030, 0.040, inst="decode-0", seq=1,
               rids=(1, 2)),
             S("wait.drain", 0.031, 0.039, parent=3, seq=1, rids=(1, 2)),
             S("wait.drain", 0.050, 0.058, seq=2, rids=(1, 2))]
    assert read("handoff_ms", fake_run(spans)) == pytest.approx(3.5)


def test_decode_interrupt_by_hand():
    """Uninterrupted intervals of one instance's steps (median 10 ms)
    read 0; one with a prefill inside reads 30 - 10, one with an insert
    inside 10 - 10; a pair with no request in common and one over a
    pause are left out."""
    d = lambda t, rids, inst="decode-0": S(  # noqa: E731
        "step.decode", t, t + 0.002, inst=inst, seq=0, rids=rids)
    spans = [d(0.000, (1,)), d(0.010, (1,)), d(0.020, (1, 3)),
             d(0.030, (1,)), S("step.prefill", 0.035, 0.045, rid=2),
             d(0.060, (1, 2)), S("step.insert", 0.062, 0.064, rid=2),
             d(0.070, (2,)),
             d(0.100, (4,)),  # shares nothing with the step before
             S("step.prefill", 0.120, 0.130, rid=5),
             d(0.200, (4,)),  # 0.1 -> 0.2 holds the pause at 0.15
             d(0.300, (7,), "decode-1"), d(0.311, (7,), "decode-1")]
    run = fake_run(spans, pauses=[(0.15, 0.16)])
    # extras: 0, 0, 0, 20, 0 (decode-0), 0 (decode-1): p95 is the 6th
    assert read("decode_interrupt_p95_ms", run) == pytest.approx(20.0)
    # without the pause the 0.1 -> 0.2 interval counts, 100 - 10
    assert read("decode_interrupt_p95_ms", fake_run(spans)) == \
        pytest.approx(90.0)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_without_spans(name):
    """The parent's profile has no spans, the untraced run none at all:
    no value, and no error."""
    for prof in (None, SimpleNamespace(), SimpleNamespace(spans=[])):
        run = SimpleNamespace(prof=prof, wall=SimpleNamespace(pauses=[]),
                              counted=[SimpleNamespace(rid=0)])
        assert read(name, run) is None


def test_traced_cell_reports_the_span_metrics():
    result = tiny.run("phi4-chat", "phi4-mini-3.8b", trace=True)
    assert result["correct"] is True
    m = result["metrics"]
    assert set(NEW) <= set(m)
    assert m["prefill_wait_p90_ms"]["value"] > 0
    assert m["handoff_ms"]["value"] > 0
    assert math.isfinite(m["decode_interrupt_p95_ms"]["value"])


# ---------------------------------------------------------------------------
# A served run with the program's spans, the adapter and the profiler
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    import jax

    from repro.serving import loopprof

    seed, seconds = 2**31 + 77, 2.0
    c, mix = tiny.config("phi4-mini-3.8b"), tiny.mix("phi4-chat")
    mcfg = model.program_config(c)
    weights = model.make_weights(c, seed)
    wall = WallClock()
    cluster = harness.build_cluster(c, mcfg, weights, mix, wall, seed)
    harness.warm_up(cluster, mix, c, seed)
    V = int(c["vocab_size"])
    reqs = harness.requests_of(traffic.generate(mix, seconds, seed, V), V)
    prof = loopprof.install(cluster)
    ends = []  # window time at the end of each outermost backend call
    wall.after_call = ends.append
    trace_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        wall.start(reqs)
        t0 = wall.t0
        cluster.run(reqs, max_time_s=seconds + float(mix["drain_s"]))
    finally:
        wall.stop()
        jax.profiler.stop_trace()
    assert all(r.finished for r in reqs)
    return SimpleNamespace(prof=prof, wall=wall, t0=t0, ends=ends,
                           reqs=reqs, trace_dir=trace_dir)


def test_annotations_match_spans_at_one_offset(served):
    """Every span has a ``repro.<name>`` twin in the trace's host plane,
    and every twin lies at the same offset from its span, at both ends,
    to under 1 ms."""
    from jax.profiler import ProfileData

    f = sorted(Path(served.trace_dir).rglob("*.xplane.pb"))[-1]
    twins = {}
    for plane in ProfileData.from_file(str(f)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    twins.setdefault(e.name[len("repro."):], []).append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9))
    mine = {}
    for s in served.prof.spans:
        mine.setdefault(s.name, []).append((s.t0, s.t1))
    assert {k: len(v) for k, v in twins.items()} == \
        {k: len(v) for k, v in mine.items()}
    assert set(mine) == {"queue.prefill", "step.prefill",
                         "wait.first_token", "step.insert", "step.decode",
                         "wait.drain"}
    offsets = []
    for name, spans in mine.items():
        for (a0, a1), (b0, b1) in zip(sorted(twins[name]), sorted(spans)):
            offsets += [a0 - b0, a1 - b1]
    assert max(offsets) - min(offsets) < 1e-3


def test_drain_emissions_precede_the_adapter_stamps(served):
    """A decode token is emitted at the end of the ``wait.drain`` that
    carries its request; the adapter stamps it at the end of the backend
    call that holds that drain: at or after the emission, and at the
    first call end after it."""
    emitted = {}
    for s in served.prof.spans:
        if s.name == "wait.drain":
            for rid in s.attrs["rids"]:
                emitted.setdefault(rid, []).append(s.t1 - served.t0)
    ends = sorted(served.ends)
    n = 0
    for r in served.reqs:
        stamps = served.wall.stamps[r.rid][1:]  # the first is the prefill's
        assert len(emitted[r.rid]) == len(stamps) == r.decode_len
        for e, st in zip(emitted[r.rid], stamps):
            assert e <= st
            assert st == next(x for x in ends if x >= e)
            n += 1
    assert n > 0
