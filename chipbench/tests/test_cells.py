"""The cell end to end on the CPU at a reduced size, the faults the
output check must catch, and the control."""
import io
import json
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

CELLS = [("phi4-chat", "phi4-mini-3.8b")]


@pytest.mark.parametrize("cell,cfg", CELLS)
def test_cell_end_to_end(cell, cfg):
    result = tiny.run(cell, cfg)
    buf = io.StringIO()
    with redirect_stdout(buf):
        harness.print_result(result)
    last = json.loads(buf.getvalue().strip().splitlines()[-1])
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in last
    assert list(last)[-1] == "check"
    assert last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] > 0
    bench = harness.load_bench(harness.HERE.parent)
    want = {m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
    assert set(last["metrics"]) == want
    assert last["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell,cfg", CELLS)
def test_cell_traced(cell, cfg):
    """The traced run reports the per-layer metrics its counters and
    spans hold; the device-trace ones need a TPU plane and stay out."""
    result = tiny.run(cell, cfg, trace=True)
    assert result["correct"] is True
    got = set(result["metrics"])
    assert "ctrl_ms_per_step" in got
    assert not got & {"prefill_mfu", "decode_mfu", "decode_roofline",
                      "window_mfu", "step_gap_ms"}
    assert result["device"]["window_s"] > 0


def _decode_backend(backends):
    return [b for b in backends if b.slots > 1][0]


def state_unchanged(backends):
    b = _decode_backend(backends)
    step = b._decode_jit

    def broken(params, *, tokens, cache, lengths, block_tables):
        kept = jax.tree.map(jnp.copy, cache)  # the step donates cache
        ids, _ = step(params, tokens=tokens, cache=cache, lengths=lengths,
                      block_tables=block_tables)
        return ids, kept  # the step's K/V writes are lost

    b._decode_jit = broken


def half_batch(backends):
    b = _decode_backend(backends)
    step = b._decode_jit

    def broken(params, *, tokens, cache, lengths, block_tables):
        ids, new = step(params, tokens=tokens, cache=cache, lengths=lengths,
                        block_tables=block_tables)
        # half of the occupied rows (rounded up) is left out: they
        # repeat their input token instead of advancing
        busy = block_tables[:, 0] >= 0
        rank = jnp.cumsum(busy)
        out = busy & (2 * rank > busy.sum())
        return jnp.where(out, tokens, ids), new

    b._decode_jit = broken


def token_altered(backends):
    b = _decode_backend(backends)
    step = b._decode_jit
    calls = [0]

    def broken(params, *, tokens, cache, lengths, block_tables):
        ids, new = step(params, tokens=tokens, cache=cache, lengths=lengths,
                        block_tables=block_tables)
        calls[0] += 1
        if calls[0] % 7 == 0:
            ids = (ids + 1) % b.cfg.vocab_size
        return ids, new

    b._decode_jit = broken


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
@pytest.mark.parametrize("cell,cfg", CELLS)
def test_fault_is_not_correct(cell, cfg, fault):
    result = tiny.run(cell, cfg, patch_backends=fault)
    assert result["correct"] is False, result["check"]


@pytest.mark.parametrize("cell,cfg", CELLS)
def test_control_is_not_correct(cell, cfg):
    """The control (the program's int8 weight path at the same positions
    as the served tokens) goes through the same comparison as the served
    tokens and comes out not correct, where the sound run is correct."""
    result = tiny.run(cell, cfg, control=True)
    ctl = result["control"]
    assert result["correct"] is True
    assert ctl["correct"] is False, ctl["numbers"]
    assert ctl["numbers"]["sample_tokens"] == \
        ctl["sound_numbers"]["sample_tokens"]
