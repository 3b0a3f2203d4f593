"""Backend parity: the promise in engine.py's docstring, enforced.

The same trace through :class:`SimBackend` and :class:`RealBackend`
(reduced model, zero measurement noise) must produce *identical*
latency/energy metrics and per-request completion order — the real
backend adds token content, never timing drift.  Covered paths:

* ``plain-pd``      — legacy whole-prompt FCFS prefill batching;
* ``chunked-pd``    — chunked prefill forced small so prompts actually
  split across iterations in both backends;
* ``hybrid-tiered`` — chunked prefill + a hybrid (decode+chunk)
  instance under SLO-tiered traffic: EDF/priority queues, tier-aware
  EcoFreq budgets and the tier-aware decode router must make identical
  decisions over identical virtual clocks;
* ``paged-pd``      — the paged KV path: page-padded decode admission/
  headroom, per-page migration pricing and (real side) a block-pool
  allocator + block-table decode must leave the virtual clock exactly
  where the Sim backend's page-granular accounting puts it.
"""
import dataclasses

import jax
import pytest

from repro.configs.registry import REGISTRY
from repro.core.power import A100
from repro.models import model as M
from repro.serving import (
    DEFAULT_TIERS,
    ClusterConfig,
    PDCluster,
    poisson_workload,
)
from repro.serving.cluster import build_predictor
from repro.serving.realengine import make_real_backend_factory
from repro.serving.workload import DatasetDist, LengthDist, attach_tokens

MODEL = REGISTRY["llama-3.1-8b"]


@pytest.fixture(scope="module")
def rc():
    return dataclasses.replace(MODEL.reduced(), dtype="float32")


@pytest.fixture(scope="module")
def rparams(rc):
    return M.init_params(rc, jax.random.key(0))


@pytest.fixture(scope="module")
def pred():
    return build_predictor(MODEL, A100, A100.freq_levels_2, kv_cap=400_000)


def _workload(rc, tiered: bool):
    tiny = DatasetDist(
        "tiny",
        prefill=LengthDist(24.0, 10.0, hi=60),
        decode=LengthDist(6.0, 3.0, hi=12),
    )
    reqs = poisson_workload(tiny, 2.5, 10.0, seed=21)
    if tiered:
        tiers = ("interactive", "standard", "batch")
        for r in reqs:
            r.tier = tiers[r.rid % 3]
    return attach_tokens(reqs, rc.vocab_size, seed=22)


SCENARIOS = {
    "plain-pd": dict(chunked_prefill=False, prefill_chunk_tokens=None),
    "chunked-pd": dict(prefill_chunk_tokens=32),
    "hybrid-tiered": dict(
        prefill_chunk_tokens=32, n_hybrid=1, slo_tiers=DEFAULT_TIERS
    ),
    # n_hybrid=1 also exercises the paged local decode join (prefill
    # chunk -> same instance's pool, no migration)
    "paged-pd": dict(prefill_chunk_tokens=32, paged=True, kv_page_size=16,
                     n_hybrid=1),
}

# backend-side knobs matching each scenario's memory model
BACKEND_KW = {
    "paged-pd": dict(paged=True, page_size=16),
}


def _cfg(pred, scenario, **kw):
    return ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=2,
        policy="voltana", predictor=pred, kv_capacity_tokens=400_000,
        online_adapt=False, decode_max_running=8, seed=4,
        noise_sigma=0.0,  # determinism: parity must be exact
        **SCENARIOS[scenario],
        **kw,
    )


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_sim_and_real_backends_agree(rc, rparams, pred, scenario):
    tiered = "tiered" in scenario
    reqs_sim = _workload(rc, tiered)
    reqs_real = _workload(rc, tiered)

    m_sim = PDCluster(_cfg(pred, scenario)).run(reqs_sim)
    m_real = PDCluster(_cfg(
        pred, scenario,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128,
            **BACKEND_KW.get(scenario, {}),
        ),
    )).run(reqs_real)

    assert m_sim.finished_frac() == m_real.finished_frac() == 1.0

    # identical per-request latency metrics and placement
    for rs, rr in zip(reqs_sim, reqs_real):
        assert rs.rid == rr.rid
        assert rs.t_prefill_start == pytest.approx(rr.t_prefill_start)
        assert rs.t_first_token == pytest.approx(rr.t_first_token)
        assert rs.t_join_decode == pytest.approx(rr.t_join_decode)
        assert rs.t_finish == pytest.approx(rr.t_finish)
        assert rs.prefill_instance == rr.prefill_instance
        assert rs.decode_instance == rr.decode_instance
        assert rs.max_itl_s == pytest.approx(rr.max_itl_s)
        assert rs.preemptions == rr.preemptions

    # identical completion order
    order_sim = [r.rid for r in sorted(reqs_sim, key=lambda r: r.t_finish)]
    order_real = [r.rid for r in sorted(reqs_real, key=lambda r: r.t_finish)]
    assert order_sim == order_real

    # identical energy, instance by instance
    assert len(m_sim.instances) == len(m_real.instances)
    for es, er in zip(m_sim.instances, m_real.instances):
        assert es.name == er.name
        assert es.busy_j == pytest.approx(er.busy_j, rel=1e-12)
        assert es.busy_s == pytest.approx(er.busy_s, rel=1e-12)
    assert m_sim.energy_j() == pytest.approx(m_real.energy_j(), rel=1e-9)
    assert m_sim.epot_j() == pytest.approx(m_real.epot_j(), rel=1e-9)

    # and the real side actually produced the tokens it priced
    for r in reqs_real:
        assert len(r.output_tokens) == r.decode_len + 1


@pytest.fixture(scope="module")
def draft(rc):
    from repro.serving.realengine import make_draft_config

    dc = make_draft_config(rc)
    return dc, M.init_params(dc, jax.random.key(1))


@pytest.fixture(scope="module")
def spec_pred():
    return build_predictor(MODEL, A100, A100.freq_levels_2,
                           kv_cap=400_000, spec_k=2)


def test_sim_and_real_agree_through_speculation(rc, rparams, draft,
                                                spec_pred):
    """Sim==Real parity through the draft–verify path: the acceptance
    realization is a control-plane stream, so both backends schedule
    identical variable-yield iterations — and the real side actually
    drafts, verifies in one k-token forward, and rolls rejected pages
    back (pool refcounts balance after drain)."""
    dc, dparams = draft
    reqs_sim = _workload(rc, tiered=False)
    reqs_real = _workload(rc, tiered=False)
    kw = dict(paged=True, kv_page_size=16, prefill_chunk_tokens=32,
              spec_decode=True, spec_k=2)

    def cfg(**extra):
        return ClusterConfig(
            model=MODEL, chip=A100, n_prefill=1, n_decode=2,
            policy="voltana", predictor=spec_pred,
            kv_capacity_tokens=400_000, online_adapt=False,
            decode_max_running=8, seed=4, noise_sigma=0.0, **kw, **extra,
        )

    cl_sim = PDCluster(cfg())
    m_sim = cl_sim.run(reqs_sim)
    cl_real = PDCluster(cfg(backend_factory=make_real_backend_factory(
        rc, rparams, slots=8, max_len=128, paged=True, page_size=16,
        spec_k=2, draft_cfg=dc, draft_params=dparams,
    )))
    m_real = cl_real.run(reqs_real)

    assert m_sim.finished_frac() == m_real.finished_frac() == 1.0
    assert m_sim.spec_iterations() > 0
    for rs, rr in zip(reqs_sim, reqs_real):
        assert rs.t_finish == pytest.approx(rr.t_finish)
        assert rs.max_itl_s == pytest.approx(rr.max_itl_s)
        assert rs.decode_instance == rr.decode_instance
        # identical acceptance realizations (the parity mechanism)
        assert rs.spec_iters == rr.spec_iters
        assert rs.spec_accepted == rr.spec_accepted
        # the real side delivered complete streams through speculation
        assert len(rr.output_tokens) == rr.decode_len + 1
    assert m_sim.energy_j() == pytest.approx(m_real.energy_j(), rel=1e-9)
    assert m_sim.acceptance_rate() == m_real.acceptance_rate()

    # no page leaks through rollback: every decode pool drains empty
    for e in cl_real.decode:
        e.backend.pool.assert_empty()
    # the real draft-verify step really ran (every decode step of a
    # speculating backend is one)
    assert all(e.backend.spec_k > 0 for e in cl_real.decode)
    assert sum(e.backend.pipeline_dispatches for e in cl_real.decode) > 0


def test_real_spec_at_slot_capacity(rc, rparams, draft, spec_pred):
    """A request whose context ends within spec_k tokens of the slot
    capacity must complete: the verify window clamps at max_len and the
    overflow rows (always rejected by the acceptance clip) scatter to
    the scratch page instead of aliasing live pages."""
    from repro.serving import Request

    dc, dparams = draft
    max_len = 64
    # context tops out exactly at max_len (prompt 40 + 1 first + 23
    # decode iters): the last iterations' windows overflow the slot
    reqs = [Request(0, 0.0, prompt_len=40, decode_len=24),
            Request(1, 0.05, prompt_len=33, decode_len=12)]
    attach_tokens(reqs, rc.vocab_size, seed=6)
    cfg = ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=1,
        policy="voltana", predictor=spec_pred,
        kv_capacity_tokens=400_000, online_adapt=False,
        decode_max_running=4, seed=4, noise_sigma=0.0,
        prefill_chunk_tokens=32, paged=True, kv_page_size=16,
        spec_decode=True, spec_k=2,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=4, max_len=max_len, paged=True,
            page_size=16, spec_k=2, draft_cfg=dc, draft_params=dparams,
        ),
    )
    cl = PDCluster(cfg)
    m = cl.run(reqs)
    assert m.finished_frac() == 1.0
    for r in reqs:
        assert r.kv_len == r.prompt_len + r.decode_len <= max_len
        assert len(r.output_tokens) == r.decode_len + 1
    cl.decode[0].backend.pool.assert_empty()


def test_real_spec_requires_paged(rc, rparams, draft):
    dc, dparams = draft
    from repro.serving.realengine import RealBackend
    from repro.core.hwmodel import HardwareModel

    with pytest.raises(AssertionError, match="paged"):
        RealBackend(
            HardwareModel(MODEL, A100), rc, rparams, slots=2,
            max_len=64, paged=False, spec_k=2, draft_cfg=dc,
            draft_params=dparams,
        )


def _pressure_workload(rc, n_batch=3, n_int=3):
    """Batch-tier long decodes occupy a tiny decode instance; an
    interactive burst lands while they hold the KV (forces preemption)."""
    from repro.serving import Request

    reqs = []
    for i in range(n_batch):
        reqs.append(Request(i, 0.01 * i, prompt_len=40, decode_len=80,
                            tier="batch"))
    for j in range(n_int):
        reqs.append(Request(n_batch + j, 0.4 + 0.01 * j, prompt_len=60,
                            decode_len=10, tier="interactive"))
    return attach_tokens(reqs, rc.vocab_size, seed=5)


def _pressure_cfg(pred, **kw):
    base = dict(
        model=MODEL, chip=A100, n_prefill=1, n_decode=1,
        policy="voltana", predictor=pred, kv_capacity_tokens=200,
        online_adapt=False, decode_max_running=8, seed=4,
        noise_sigma=0.0, prefill_chunk_tokens=32,
        slo_tiers=DEFAULT_TIERS, admission_control=False,
    )
    base.update(kw)
    return ClusterConfig(**base)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_real_backend_preemption_resume(rc, rparams, pred, paged):
    """The recompute-on-resume path must run over *real* compute: the
    resume prefill rebuilds KV from prompt + already-delivered ids, the
    first token is not re-emitted, and Sim/Real timing parity holds
    through preempt/resume.  On the paged path an eviction must also
    return the victim's pages to the pool (admission re-fits by pages)."""
    reqs_sim = _pressure_workload(rc)
    reqs_real = _pressure_workload(rc)

    kw = dict(paged=True, kv_page_size=16) if paged else {}
    bkw = dict(paged=True, page_size=16) if paged else {}
    m_sim = PDCluster(_pressure_cfg(pred, **kw)).run(reqs_sim)
    m_real = PDCluster(_pressure_cfg(
        pred, **kw,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128, **bkw
        ),
    )).run(reqs_real)

    assert m_sim.preemptions_total() > 0, "scenario never preempted"
    assert m_sim.preemptions_total() == m_real.preemptions_total()
    assert m_sim.finished_frac() == m_real.finished_frac() == 1.0
    for rs, rr in zip(reqs_sim, reqs_real):
        assert rs.preemptions == rr.preemptions
        assert rs.t_finish == pytest.approx(rr.t_finish)
        # delivered exactly decode_len + 1 ids, across preempt/resume
        assert len(rr.output_tokens) == rr.decode_len + 1
    assert m_sim.energy_j() == pytest.approx(m_real.energy_j(), rel=1e-9)


def _multiturn_reqs(rc, n_convs=2, n_turns=3, system_len=48):
    """Conversations sharing a system prompt, each turn a strict
    extension of the last — the zero-copy prefix-sharing workload."""
    import numpy as np

    from repro.serving import Request

    rng = np.random.default_rng(11)
    system = rng.integers(0, rc.vocab_size, system_len).tolist()
    hist = {c: list(system) for c in range(n_convs)}
    reqs, rid = [], 0
    for turn in range(n_turns):
        for c in range(n_convs):
            prompt = hist[c] + rng.integers(0, rc.vocab_size, 8).tolist()
            r = Request(rid, 2.0 * turn + 0.3 * c, prompt_len=len(prompt),
                        decode_len=4, conv_id=c, turn=turn)
            r.prompt_tokens = list(prompt)
            reqs.append(r)
            rid += 1
            hist[c] = prompt + [0] * 4  # prompt + synthetic outputs
    return reqs


def test_paged_real_multiturn_prefix_reuse_is_zero_copy(rc, rparams, pred):
    """Acceptance: a real multi-turn run over the paged backend reuses
    prefix KV *pages* — shared pages show refcount > 1 in the pool, the
    reused tokens never re-enter the forward pass, and the pool balances
    (no leak) once the run drains."""
    reqs = _multiturn_reqs(rc)
    cfg = ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=1,
        policy="voltana", predictor=pred, kv_capacity_tokens=400_000,
        online_adapt=False, decode_max_running=8, seed=4,
        noise_sigma=0.0, prefill_chunk_tokens=64,
        prefix_cache=True, prefix_cache_capacity=2_048,
        paged=True, kv_page_size=16,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128,
            paged=True, page_size=16, pool_pages=256,
        ),
    )
    cl = PDCluster(cfg)
    m = cl.run(reqs)
    assert m.finished_frac() == 1.0
    assert m.prefix_hit_rate and m.prefix_hit_rate > 0.3

    pb = cl.prefill[0].backend
    total_prompt = sum(r.prompt_len for r in reqs)
    # prefix-hit tokens skipped the forward entirely (zero recompute)
    assert pb.reused_tokens > 0
    assert pb.computed_tokens == total_prompt - pb.reused_tokens
    # sharing showed up as refcount > 1 (request + radix / two turns)
    assert pb.pool.stats.max_refcount > 1
    # every delivered stream is complete and real
    for r in reqs:
        assert len(r.output_tokens) == r.decode_len + 1
    # pool hygiene after drain: only radix-held pages remain, and the
    # pool's refcounts match the tree exactly (no leaked request refs)
    radix_pages = _radix_pages(cl.prefill[0].cache)
    assert pb.pool.in_use == len(set(radix_pages))
    assert all(pb.pool.refcount(p) == 1 for p in radix_pages)
    # decode side released everything
    db = cl.decode[0].backend
    db.pool.assert_empty()


def _radix_pages(cache):
    pages = []
    stack = [cache.root]
    while stack:
        n = stack.pop()
        stack.extend(n.children.values())
        pages.extend(n.pages)
    return pages


def test_paged_prefill_failure_releases_stashed_pages(rc, rparams, pred):
    """A prefill instance dying with work in flight must release the
    page references stashed for the radix attach (abort_prefill), and
    the survivors must still drain the trace with balanced pools."""
    reqs = _pressure_workload(rc)
    cfg = _pressure_cfg(
        pred, n_prefill=2, n_decode=2, kv_capacity_tokens=400_000,
        prefix_cache=True, prefix_cache_capacity=1_024,
        paged=True, kv_page_size=16,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128, paged=True, page_size=16,
        ),
    )
    cl = PDCluster(cfg)
    cl.schedule_failure(0.05, "prefill", 0)
    m = cl.run(reqs)
    assert m.finished_frac() == 1.0
    for r in reqs:
        assert len(r.output_tokens) == r.decode_len + 1
    # the dead instance's stash was aborted; its pool refcounts reduce
    # to exactly what its radix tree still holds
    dead = cl.prefill[0].backend
    assert not dead._pstash
    assert dead.pool.in_use == len(set(_radix_pages(cl.prefill[0].cache)))
    # decode pools fully drained
    for e in cl.decode:
        e.backend.pool.assert_empty()


def test_paged_off_is_default_and_token_granular():
    """paged=False (the default) keeps token-granular accounting: the
    page-padding helpers must be inert so pre-paged runs stay
    bit-exact."""
    from repro.serving.engine import DecodeEngine

    assert ClusterConfig.__dataclass_fields__["paged"].default is False
    assert DecodeEngine.__dataclass_fields__["page_size"].default == 0
    eng = DecodeEngine.__new__(DecodeEngine)
    eng.page_size = 0
    assert eng._kv_footprint(37) == 37
    eng.page_size = 16
    assert eng._kv_footprint(37) == 48


def test_real_backend_failure_restart_token_hygiene(rc, rparams, pred):
    """A failure restart regenerates from scratch: stale pre-failure ids
    must not survive in output_tokens (a later preemption resume rebuilds
    context from that list)."""
    reqs = _pressure_workload(rc)
    cfg = _pressure_cfg(
        pred, n_decode=2, kv_capacity_tokens=400_000,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128
        ),
    )
    cl = PDCluster(cfg)
    cl.schedule_failure(0.3, "decode", 0)
    m = cl.run(reqs)
    assert m.finished_frac() == 1.0
    assert any(r.restarts > 0 for r in reqs), "failure hit nobody"
    for r in reqs:
        assert len(r.output_tokens) == r.decode_len + 1, r
