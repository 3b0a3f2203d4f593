"""Spans of the served path (``repro.serving.loopprof``): what a profile
records on a tiny paged ``RealBackend`` cluster, and that without one
nothing is recorded and nothing changes."""
import dataclasses
import statistics
import time
from collections import Counter

import jax
import pytest

from repro.configs.registry import REGISTRY
from repro.core.power import A100
from repro.models import model as M
from repro.serving import ClusterConfig, PDCluster, loopprof, poisson_workload
from repro.serving.cluster import build_predictor
from repro.serving.engine import SimBackend
from repro.serving.realengine import (
    make_draft_config,
    make_real_backend_factory,
)
from repro.serving.workload import DatasetDist, LengthDist, attach_tokens

MODEL = REGISTRY["llama-3.1-8b"]
TINY = DatasetDist(
    "tiny",
    prefill=LengthDist(20.0, 8.0, hi=60),
    decode=LengthDist(6.0, 3.0, hi=12),
)
EXACT = ("iterations", "select_memo_hit_rate", "route_batch_rows_avg",
         "pipeline_depth_avg")


@pytest.fixture(scope="module")
def rc():
    return dataclasses.replace(MODEL.reduced(), dtype="float32")


@pytest.fixture(scope="module")
def rparams(rc):
    return M.init_params(rc, jax.random.key(0))


@pytest.fixture(scope="module")
def pred():
    return build_predictor(MODEL, A100, A100.freq_levels_2, kv_cap=400_000)


def _requests(rc, seed=3):
    reqs = poisson_workload(TINY, 2.0, 8.0, seed=seed)
    return attach_tokens(reqs, rc.vocab_size, seed=4)


def _cluster(rc, rparams, pred, **spec):
    """1P+1D over paged real backends; ``spec`` (``spec_k``,
    ``draft_cfg``, ``draft_params``) makes decode draft and verify."""
    speculate = dict(spec_decode=True, spec_k=spec["spec_k"]) if spec else {}
    return PDCluster(ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=1, policy="voltana",
        predictor=pred, paged=True, kv_page_size=16,
        kv_capacity_tokens=400_000, online_adapt=False,
        decode_max_running=4, seed=2, **speculate,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=4, max_len=128, paged=True, page_size=16,
            **spec),
    ))


def _backends(cl):
    return [e.backend for e in cl.prefill + cl.decode + cl.hybrid]


def _profiled_run(rc, rparams, pred):
    cl = _cluster(rc, rparams, pred)
    prof = loopprof.install(cl)
    reqs = _requests(rc)
    m = cl.run(reqs)
    assert m.finished_frac() == 1.0
    return cl, prof, reqs


@pytest.fixture(scope="module")
def served(rc, rparams, pred):
    _profiled_run(rc, rparams, pred)  # compiles every shape
    return _profiled_run(rc, rparams, pred)


def test_no_profile_records_nothing(rc, rparams, pred, served,
                                    monkeypatch):
    """Without a profile no span is made, and the token streams are
    bit-identical to those of the profiled run."""
    _, _, traced = served

    def no_span(*a, **k):
        raise AssertionError("a span was made with no profile attached")

    monkeypatch.setattr(loopprof.Span, "__init__", no_span)
    cl = _cluster(rc, rparams, pred)
    assert cl._prof is None
    assert all(b._prof is None for b in _backends(cl))
    reqs = _requests(rc)
    assert cl.run(reqs).finished_frac() == 1.0
    assert [r.output_tokens for r in reqs] == \
        [r.output_tokens for r in traced]


def test_one_wait_prefill_and_insert_per_request(served):
    _, prof, reqs = served
    for name in ("queue.prefill", "step.prefill", "step.insert"):
        n = Counter(s.rid for s in prof.spans if s.name == name)
        assert n == Counter(r.rid for r in reqs), name
    for s in prof.spans:
        assert s.t1 is not None and s.t0 <= s.t1, s
        assert s.now0 <= s.now1, s
    for s in prof.spans:
        if s.name == "queue.prefill":  # handed over, then taken later
            r = next(r for r in reqs if r.rid == s.rid)
            assert s.now0 == r.arrival_s
            assert s.now1 == r.t_prefill_start
        if s.name == "step.prefill":
            assert s.attrs["computed"] + s.attrs["reused"] == \
                next(r.prompt_len for r in reqs if r.rid == s.rid)
        if s.name == "step.insert":
            assert s.attrs["pages"] >= 1


def test_spans_nest(served):
    """``wait.first_token`` lies in ``step.prefill``; ``wait.drain`` in
    the ``step.insert`` or ``step.decode`` that forced it, or at top
    level (a slot release or the end-of-run flush); every child inside
    its parent's interval."""
    _, prof, _ = served
    sp = prof.spans
    parents = Counter()
    for s in sp:
        if s.name == "wait.first_token":
            assert sp[s.parent].name == "step.prefill"
        elif s.name == "wait.drain":
            parents[sp[s.parent].name if s.parent >= 0 else None] += 1
        else:
            assert s.parent == -1, s
        if s.parent >= 0:
            p = sp[s.parent]
            assert p.t0 <= s.t0 <= s.t1 <= p.t1
    assert set(parents) <= {"step.insert", "step.decode", None}
    assert parents["step.insert"] and parents["step.decode"] \
        and parents[None]
    # every dispatched decode step is drained exactly once, in order
    steps = [s for s in sp if s.name == "step.decode"]
    drains = [s for s in sp if s.name == "wait.drain"]
    assert [s.attrs["seq"] for s in steps] == \
        [s.attrs["seq"] for s in drains] == list(range(len(steps)))
    assert [s.attrs["rids"] for s in steps] == \
        [s.attrs["rids"] for s in drains]


def test_device_wait_is_the_sum_of_drains(served):
    _, prof, _ = served
    waits = [s.dur for s in prof.spans if s.name == "wait.drain"]
    assert waits and all(w >= 0 for w in waits)
    assert prof.breakdown()["device_wait_s"] == round(sum(waits), 4)


def test_compiled_marks_the_cold_shape_only(rc, rparams, pred):
    """``compiled`` is true on the first step of a shape never compiled
    before (a configuration of its own here), false on the warm rerun;
    ``compiles`` sums the XLA backend compiles inside step spans, the
    insert's eager page scatter included."""
    cold = dataclasses.replace(rc, name=rc.name + "-tracing-cold")
    first = {}
    for run in ("cold", "warm"):
        cl, prof, _ = _profiled_run(cold, rparams, pred)
        first[run] = {n: next(s.attrs["compiled"] for s in prof.spans
                              if s.name == n)
                      for n in ("step.prefill", "step.decode")}
        if run == "warm":
            assert prof.compiles == 0
            assert not any(s.attrs.get("compiled") for s in prof.spans)
        else:
            assert prof.compiles >= 2
    assert first == {"cold": {"step.prefill": True, "step.decode": True},
                     "warm": {"step.prefill": False, "step.decode": False}}


def test_compiled_counts_what_the_backend_compiles(rc, rparams, pred,
                                                   served):
    """``compiled`` reads the XLA backend compile event the chip
    benchmark counts, so an eager op compiled inside a step is seen too:
    here every full drain compiles one eager op of a new shape, and the
    drains inside ``step.insert`` mark it.  ``compiles`` equals the
    events that fell inside step spans (warm shapes otherwise)."""
    at = []
    on = [True]

    def seen(event, duration, **_):
        if on[0] and event == "/jax/core/compile/backend_compile_duration":
            at.append(time.perf_counter())

    cl = _cluster(rc, rparams, pred)
    prof = loopprof.install(cl)
    b = cl.decode[0].backend
    drain, shapes = b._drain, iter(range(1000, 2000))

    def drain_with_an_eager_op():
        jax.numpy.zeros(next(shapes)).block_until_ready()
        drain()

    b._drain = drain_with_an_eager_op
    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        assert cl.run(_requests(rc)).finished_frac() == 1.0
    finally:
        on[0] = False
    steps = [s for s in prof.spans if s.name.startswith("step.")]
    hit = {id(s) for s in steps for t in at if s.t0 <= t <= s.t1}
    assert [s.attrs["compiled"] for s in steps] == \
        [id(s) in hit for s in steps]
    assert {s.name for s in steps if id(s) in hit} == {"step.insert"}
    assert prof.compiles == sum(
        s.t0 <= t <= s.t1 for s in steps for t in at) > 0


def _breakdown(rc, rparams, pred, spans: bool) -> dict:
    cl = _cluster(rc, rparams, pred)
    prof = loopprof.install(cl)
    if not spans:  # the wrappers alone, no span sites
        for e in prof._engines:
            e._prof = e.backend._prof = None
        prof.queued = lambda rid: None
    assert cl.run(_requests(rc)).finished_frac() == 1.0
    assert bool(prof.spans) == spans
    return prof.breakdown()


def test_breakdown_reads_the_same_with_spans(rc, rparams, pred, served):
    """``breakdown()``'s counts are identical with the spans as without
    them, and its host seconds agree within timer noise (the median of
    three alternating runs each).  Without spans there is no
    ``wait.drain`` to sum, so the backends' host time is compared whole:
    ``dispatch_s`` plus ``device_wait_s``, whose split moves with the
    host work that runs before each drain."""
    runs = {True: [], False: []}
    for _ in range(3):
        for spans in (True, False):
            runs[spans].append(_breakdown(rc, rparams, pred, spans))
    for k in EXACT:
        assert len({r[k] for r in runs[True] + runs[False]}) == 1, k
    assert all(r["device_wait_s"] == 0 for r in runs[False])
    assert all(r["device_wait_s"] > 0 for r in runs[True])
    for r in runs[True] + runs[False]:
        r["backend_s"] = r["dispatch_s"] + r["device_wait_s"]
    for k in ("schedule_s", "select_s", "route_s", "backend_s",
              "metrics_s", "queue_s", "bookkeeping_s"):
        a = statistics.median(r[k] for r in runs[True])
        b = statistics.median(r[k] for r in runs[False])
        assert abs(a - b) <= 0.5 * max(a, b) + 0.01, (k, a, b)


def test_profile_reaches_backends_spawned_mid_run(pred):
    """Scale-out engines get the profile too; on the simulator the only
    span is the prefill queue wait, one per request."""
    reqs = poisson_workload(TINY, 10.0, 6.0, seed=5)
    cl = PDCluster(ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=1, policy="voltana",
        predictor=pred, kv_capacity_tokens=400_000, seed=1))
    cl.schedule_scale_out(2.0, "prefill")
    cl.schedule_scale_out(2.0, "decode")
    prof = loopprof.install(cl)
    assert SimBackend._prof is None
    assert cl.run(reqs).finished_frac() == 1.0
    assert len(cl.prefill) == len(cl.decode) == 2
    assert all(b._prof is prof for b in _backends(cl))
    assert {s.name for s in prof.spans} == {"queue.prefill"}
    assert Counter(s.rid for s in prof.spans) == \
        Counter(r.rid for r in reqs)
    assert set(prof._inst.values()) == {"prefill-0", "prefill-1",
                                        "decode-0", "decode-1"}


def test_draft_verify_steps_are_decode_spans(rc, rparams):
    """A draft-verify step is a ``step.decode`` too, drained once by a
    ``wait.drain`` that emits its accepted tokens."""
    dc = make_draft_config(rc)
    spec = dict(spec_k=2, draft_cfg=dc,
                draft_params=M.init_params(dc, jax.random.key(1)))
    spec_pred = build_predictor(MODEL, A100, A100.freq_levels_2,
                                kv_cap=400_000, spec_k=2)
    cl = _cluster(rc, rparams, spec_pred, **spec)
    prof = loopprof.install(cl)
    reqs = _requests(rc)
    assert cl.run(reqs).finished_frac() == 1.0
    assert cl.decode[0].backend.spec_k == 2
    steps = [s for s in prof.spans if s.name == "step.decode"]
    drains = [s for s in prof.spans if s.name == "wait.drain"]
    assert steps
    assert [(s.attrs["seq"], s.attrs["rids"]) for s in steps] == \
        [(s.attrs["seq"], s.attrs["rids"]) for s in drains]
    assert prof.breakdown()["device_wait_s"] == \
        round(sum(s.dur for s in drains), 4)
