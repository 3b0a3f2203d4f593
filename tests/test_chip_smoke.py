"""CPU guard for ``chip_smoke.py``: its phases, not its ``main``.

The script's parity and serve phases run here on phi4-mini-3.8b's
reduced config, so a change that breaks the script's path fails on the
CPU before it costs chip time.  Only ``main`` checks the platform, and
here it must refuse to run.
"""
import importlib.util
import pathlib
import sys

import jax
import pytest

from repro.configs.registry import REGISTRY

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


@pytest.fixture(scope="module")
def cfg():
    return REGISTRY["phi4-mini-3.8b"].reduced()


@pytest.fixture(scope="module")
def params(smoke, cfg):
    return smoke.init_weights(cfg, 0)


def test_parity_phase_within_tolerance(smoke, cfg, params):
    err = smoke.parity_phase(cfg, params, smoke.Sizes(), seed=0)
    floor = err["floor"]
    assert 0 < floor["rel_l2"] <= smoke.FLOOR_MAX
    assert err["rel_l2"] <= smoke.FLOOR_FACTOR * floor["rel_l2"]
    assert err["max_abs"] <= smoke.FLOOR_FACTOR * floor["max_abs"]
    assert err["logits"].shape == (
        len(smoke.Sizes().parity_lens), smoke.Sizes().parity_steps,
        cfg.vocab_size,
    )


def test_parity_metric_sees_a_misaligned_position(smoke, cfg, params):
    sz = smoke.Sizes()
    prompts, forced = smoke.parity_inputs(cfg, sz, 0)
    got = smoke.decode_probe(cfg, params, sz, prompts, forced)
    ref, _, floor = smoke.bf16_floor(cfg, params, prompts, forced)
    err = smoke.logit_errors(got[:, 1:], ref[:, :-1])
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_within(err, floor, smoke.FLOOR_FACTOR, "shifted")
    # both metrics miss on their own, by far
    for k in ("rel_l2", "max_abs"):
        assert err[k] > 10 * smoke.FLOOR_FACTOR * floor[k]


def test_serve_phase_exact_tokens_and_warm_replay(smoke, cfg, params):
    out = smoke.serve_phase(cfg, params, smoke.Sizes(), seed=0)
    assert out["requests"] == smoke.Sizes().n_requests
    assert out["warm_recompiles"] == 0


def test_memory_budget_fits_one_chip(smoke):
    cfg = REGISTRY["phi4-mini-3.8b"]
    weights = 2 * cfg.param_count()  # bf16
    budget = smoke.memory_budget(cfg, smoke.Sizes(), weights)
    assert budget["total"] < 16e9


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs a forced host mesh: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)
def test_four_chip_phase_on_a_forced_host_mesh(smoke, cfg):
    out = smoke.four_chip_phase(cfg, smoke.Sizes(), seed=0)
    assert out["rel_l2"] <= smoke.FLOOR_MAX
    assert out["warm_recompiles"] == 0
