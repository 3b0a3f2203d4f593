"""RealEngine integration: actual JAX tokens through the full
disaggregated control plane, cross-checked against a direct model loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import REGISTRY
from repro.core.power import A100
from repro.models import model as M
from repro.serving import ClusterConfig, PDCluster, poisson_workload
from repro.serving.cluster import build_predictor
from repro.serving.realengine import RealBackend, make_real_backend_factory
from repro.serving.workload import DatasetDist, LengthDist, attach_tokens

import dataclasses

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


def _tiny_workload(rc, n_seed=3):
    tiny = DatasetDist(
        "tiny",
        prefill=LengthDist(20.0, 8.0, hi=60),
        decode=LengthDist(6.0, 3.0, hi=12),
    )
    reqs = poisson_workload(tiny, 2.0, 8.0, seed=n_seed)
    return attach_tokens(reqs, rc.vocab_size, seed=4)


def test_real_cluster_end_to_end(rc, rparams, pred):
    reqs = _tiny_workload(rc)
    cfg = ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=2,
        policy="voltana", predictor=pred, kv_capacity_tokens=400_000,
        online_adapt=False, decode_max_running=8, seed=2,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128
        ),
    )
    m = PDCluster(cfg).run(reqs)
    assert m.finished_frac() == 1.0
    for r in reqs:
        assert len(r.output_tokens) == r.decode_len + 1


@pytest.mark.slow
def test_real_tokens_match_direct_model_loop(rc, rparams, pred):
    """The served greedy continuation equals a direct prefill+decode loop
    on the same weights — the serving layer adds no token-level drift."""
    reqs = _tiny_workload(rc, n_seed=7)[:3]
    cfg = ClusterConfig(
        model=MODEL, chip=A100, n_prefill=1, n_decode=1,
        policy="voltana", predictor=pred, kv_capacity_tokens=400_000,
        online_adapt=False, decode_max_running=8, seed=3,
        backend_factory=make_real_backend_factory(
            rc, rparams, slots=8, max_len=128
        ),
    )
    PDCluster(cfg).run(list(reqs))
    for r in reqs:
        toks = jnp.asarray(r.prompt_tokens, jnp.int32)[None]
        # pad like the engine (power-of-two bucket)
        pad = 16
        while pad < toks.shape[1]:
            pad *= 2
        buf = jnp.zeros((1, pad), jnp.int32).at[:, : toks.shape[1]].set(toks)
        logits, cache = M.prefill(
            rparams, rc, buf, jnp.array([toks.shape[1]], jnp.int32),
            max_len=128,
        )
        want = [int(jnp.argmax(logits[0]))]
        pos = jnp.array([toks.shape[1]], jnp.int32)
        for _ in range(r.decode_len):
            logits, cache = M.decode_step(
                rparams, rc, jnp.array([want[-1]], jnp.int32), cache, pos
            )
            want.append(int(jnp.argmax(logits[0])))
            pos = pos + 1
        assert r.output_tokens == want, f"req {r.rid} diverged"


def test_prefill_bucket_clamps_to_capacity(rc, rparams):
    """A prompt that *fits* the cache must never be rejected just
    because its power-of-two bucket overshoots ``max_len`` (70 tokens at
    max_len=96 used to raise: bucket 128 > 96)."""
    from repro.core.hwmodel import HardwareModel
    from repro.serving.request import Request

    hw = HardwareModel(MODEL, A100)
    be = RealBackend(hw, rc, rparams, slots=2, max_len=96)
    r = Request(0, 0.0, prompt_len=70, decode_len=2,
                prompt_tokens=list(np.arange(70) % rc.vocab_size))
    be.prefill_iter([r], 70, 1410.0)  # must not raise
    assert len(r.output_tokens) == 1


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_prefill_rejects_oversized_prompt(rc, rparams, paged):
    """A prompt larger than the cache capacity must fail loudly at
    admission instead of silently wrapping (and corrupting) the ring
    cache / overflowing the page pool."""
    from repro.core.hwmodel import HardwareModel
    from repro.serving.request import Request

    hw = HardwareModel(MODEL, A100)
    be = RealBackend(hw, rc, rparams, slots=2, max_len=96 if not paged
                     else 96 + 32, paged=paged, page_size=16)
    n = be.max_len + 1
    r = Request(0, 0.0, prompt_len=n, decode_len=2,
                prompt_tokens=list(np.arange(n) % rc.vocab_size))
    with pytest.raises(ValueError, match="exceeds the decode cache"):
        be.prefill_iter([r], n, 1410.0)


def test_bucket_helper():
    from repro.serving.realengine import _bucket

    assert _bucket(10) == 16
    assert _bucket(17) == 32
    assert _bucket(70, hi=96) == 96
    assert _bucket(5, hi=96) == 16


def test_real_backend_slot_reuse(rc, rparams):
    from repro.core.hwmodel import HardwareModel
    from repro.serving.request import Request

    hw = HardwareModel(MODEL, A100)
    be = RealBackend(hw, rc, rparams, slots=2, max_len=64)
    reqs = [
        Request(i, 0.0, prompt_len=8, decode_len=2,
                prompt_tokens=list(range(8)))
        for i in range(4)
    ]
    be.prefill_iter(reqs, 32, 1410.0)
    be.insert(reqs[0])
    be.insert(reqs[1])
    assert not be.free
    be.release(reqs[0])
    be.insert(reqs[2])  # reuses the freed slot
    assert be.slot_of[reqs[2].rid] in (0, 1)


def test_snapshot_is_not_an_alias_of_host_state():
    """Slot positions and block tables are host arrays the backend keeps
    mutating while the step they fed is still in flight.  A plain
    ``jnp.asarray`` of a NumPy array can alias it on the CPU backend
    (the in-flight step then reads the next iteration's tables); the
    snapshot must not."""
    from repro.serving.realengine import _snapshot

    for _ in range(20):
        host = np.zeros((64, 1024), np.int32)
        dev = _snapshot(host)
        host[:] = -1
        assert int(jnp.abs(dev).max()) == 0
