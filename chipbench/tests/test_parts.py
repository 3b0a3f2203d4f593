"""The harness's parts: lookup by name, work counts, peaks, the
wall-clock adapter and the trace reduction."""
import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import harness
import model
import traffic
import tracereduce
import work
from conftest import BENCH, ROOT

# ---------------------------------------------------------------------------
# Found by name, from files
# ---------------------------------------------------------------------------


def test_parts_found_by_name():
    bench = harness.load_bench(ROOT)
    for cell in bench["workloads"]:
        assert harness.cell_of(bench, cell["name"]) is cell
        c = model.load(cell["config"])
        assert c["name"] == cell["config"]
        traffic.load(cell["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]).read)
    with pytest.raises(harness.RunError):
        harness.cell_of(bench, "no-such-cell")
    with pytest.raises(harness.RunError):
        harness.reader("no_such_metric")


def test_cell_added_as_files_only(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell that
    uses them, added as files and BENCHMARK.json entries to a copy of
    the benchmark, run with no edit to the harness."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    cfg = model.load("phi4-mini-3.8b")
    cfg.update(name="toy-gqa", num_hidden_layers=2, hidden_size=64,
               intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=1, head_dim=16, vocab_size=131,
               serving=dict(slots=2, max_len=128, page_size=16,
                            decode_pool_pages=24, prefill_pool_pages=9),
               check={"gap_max": 0.02, "gap_mean": 0.002})
    (bench_dir / "configs" / "toy-gqa.json").write_text(json.dumps(cfg))
    mix = dict(traffic.load("phi4-chat"), rate_rps=3.0, check_tokens=30,
               prompt={"mean": 30, "std": 10, "lo": 8, "hi": 64},
               output={"mean": 10, "std": 5, "lo": 2, "hi": 32},
               trace_start_s=0.2, trace_seconds=0.5, drain_s=20)
    (bench_dir / "traffic" / "toy-steady.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "tokens_total.py").write_text(
        '"""Tokens stamped in the run."""\n\n\ndef read(run):\n'
        "    return sum(len(s) for s in run.wall.stamps.values())\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-gqa", "source": "test",
                             "file": "chipbench/configs/toy-gqa.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy", "config": "toy-gqa",
                               "traffic": "toy-steady", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "tokens_total", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "itl_p50_ms",
                               "workloads": ["toy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    code = (
        "import sys, time; from pathlib import Path\n"
        f"sys.path[:0] = [{str(bench_dir)!r}, {str(ROOT / 'src')!r}]\n"
        "import harness\n"
        "r = harness.run_cell('toy', 5, 2.0, True, t_proc=time.perf_counter(),"
        f" root=Path({str(tmp_path)!r}), require_tpu=False,"
        " peaks={'flops_per_s': 1e12, 'bytes_per_s': 1e11})\n"
        "harness.print_result(r)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["tokens_total"]["value"] > 0
    # metrics with a cell list that leaves the new cell out stay out
    assert set(last["metrics"]) == {"tokens_total"}


def test_run_refuses_without_the_program(tmp_path):
    """In a directory that holds only the benchmark, the command exits
    non-zero and prints no result."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "phi4-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_run_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "phi4-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


# ---------------------------------------------------------------------------
# Work counts and peaks
# ---------------------------------------------------------------------------


def test_work_counts_by_hand():
    c = model.load("phi4-mini-3.8b")
    # one layer: q 3072x3072, k and v 3072x1024, o 3072x3072, MLP 3x3072x8192
    per_layer = 3072 * 3072 * 2 + 3072 * 1024 * 2 + 3 * 3072 * 8192
    assert work.layer_matmul_params(c) == per_layer == 100_663_296
    head = 3072 * 200064
    # one decode row at context 100: 32 layers, logits, attention over 101
    want = 2 * 32 * per_layer + 2 * head + 32 * 4 * 3072 * 101
    assert work.decode_flops(c, [100]) == want
    # a 10-token prefill: causal attention over 55 query-key pairs
    want = 2 * 32 * per_layer * 10 + 32 * 4 * 3072 * 55 + 2 * head
    assert work.prefill_flops(c, 10) == want
    # K and V of one token: 2 x 32 layers x 8 heads x 128 x 2 bytes
    assert model.kv_bytes_per_token(c) == 131_072
    w = 2 * (32 * (per_layer + 2 * 3072) + head + 3072)
    assert work.weight_bytes(c) == w
    assert work.decode_bytes(c, [100, 5]) == w + 131_072 * (105 + 2)
    # MHA: 40 layers x 36 heads of 64 (minicpm-2b's shape)
    mha = dict(c, num_hidden_layers=40, num_key_value_heads=36, head_dim=64)
    assert model.kv_bytes_per_token(mha) == 368_640


def test_unknown_device_is_refused():
    assert harness.peaks_of("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(harness.RunError):
        harness.peaks_of("TPU v99 imaginary")


def test_weights_are_seeded_and_large_seeds_work():
    c = dict(model.load("phi4-mini-3.8b"), num_hidden_layers=1,
             hidden_size=32, intermediate_size=48, num_attention_heads=2,
             num_key_value_heads=1, head_dim=16, vocab_size=50)
    a = model.make_weights(c, 2**33 + 7)
    b = model.make_weights(c, 2**33 + 7)
    d = model.make_weights(c, 2**33 + 8)
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], d["embed"])


def test_seeds_share_the_schedule():
    """Every seed serves the same requests at the same due times; the
    seed draws the token ids, and the same seed draws the same ones."""
    mix = traffic.load("phi4-chat")
    a = traffic.generate(mix, 40.0, 1, 1000)
    b = traffic.generate(mix, 40.0, 2**32 + 5, 1000)
    shape = lambda items: [(i.due_s, len(i.prompt), i.output) for i in items]
    assert shape(a) == shape(b)
    assert len(a) == round(mix["rate_rps"] * 40.0)
    assert all(0 < i.due_s < 40.0 for i in a)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    a2 = traffic.generate(mix, 40.0, 1, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, a2))


# ---------------------------------------------------------------------------
# The wall-clock adapter
# ---------------------------------------------------------------------------


def _sim_cluster(wall, stall=None):
    """A PDCluster over hardware-model backends that emit one token per
    request per iteration; ``stall(call_no)`` seconds of host sleep are
    added to a decode call."""
    from repro.configs.registry import REGISTRY
    from repro.core.power import TPU_V5E
    from repro.serving import ClusterConfig, PDCluster
    from repro.serving.engine import SimBackend

    cfg = REGISTRY["phi4-mini-3.8b"].reduced()

    class Tokens(SimBackend):
        calls = 0

        def prefill_chunk(self, reqs, takes, n_new, n_ctx, f):
            for r, take in zip(reqs, takes):
                if take >= r.prefill_remaining:
                    r.output_tokens.append(1)
            return super().prefill_chunk(reqs, takes, n_new, n_ctx, f)

        def decode_iter(self, reqs, n_req, n_kv, f):
            Tokens.calls += 1
            if stall is not None:
                time.sleep(stall(Tokens.calls))
            for r in reqs:
                r.output_tokens.append(2)
            return super().decode_iter(reqs, n_req, n_kv, f)

    def factory(kind, idx, hw, seed, tp=None):
        return Tokens(hw, noise_sigma=0.0, seed=seed)

    cluster = PDCluster(ClusterConfig(
        model=cfg, chip=TPU_V5E, n_prefill=1, n_decode=1, policy="voltana",
        decode_max_running=8, kv_capacity_tokens=100_000, online_adapt=False,
        transfer_const_s=0.0, transfer_bw=math.inf,
        backend_factory=wall.wrap_factory(factory)))
    wall.attach(cluster)
    return cluster


def _reqs(due, decode_len=20):
    from repro.serving.request import Request

    return [Request(rid=i, arrival_s=t, prompt_len=32, decode_len=decode_len)
            for i, t in enumerate(due)]


def test_no_event_before_its_wall_time():
    wall = harness.WallClock()
    cluster = _sim_cluster(wall)
    due = [0.0, 0.25, 0.5, 0.75]
    reqs = _reqs(due)
    wall.start(reqs)
    t0 = time.perf_counter()
    cluster.run(reqs)
    took = time.perf_counter() - t0
    wall.stop()
    assert took >= 0.75  # the loop waited for the last arrival
    assert len(wall.lag) == 4 and min(wall.lag) >= 0.0
    for r, t in zip(reqs, due):
        st = wall.stamps[r.rid]
        assert len(st) == r.decode_len + 1
        assert st[0] >= t  # no token before the request was due
        assert st == sorted(st)


def test_stalled_backend_counts_from_due_time():
    """A 0.6 s stall of one decode call shows as a 0.6 s token gap, and a
    request due during it waits from its due time."""
    wall = harness.WallClock()
    cluster = _sim_cluster(wall, stall=lambda n: 0.6 if n == 3 else 0.0)
    reqs = _reqs([0.0, 0.05])
    wall.start(reqs)
    cluster.run(reqs)
    wall.stop()
    gaps = np.diff(wall.stamps[0])
    assert gaps.max() >= 0.6
    # the second request came due during the stall: its first token
    # waited for the loop, counted from its due time
    ttft = wall.stamps[1][0] - 0.05
    assert ttft >= 0.6 - 0.1
    assert max(wall.lag) >= 0.4


def test_pause_is_left_out_of_the_window():
    """A 0.6 s pause (the profiler starting or stopping) stops the
    window's clock: no token gap and no arrival's lag holds it."""
    wall = harness.WallClock()
    cluster = _sim_cluster(wall)
    calls = []

    def hook(t):
        calls.append(t)
        if len(calls) == 3:
            wall.pause(lambda: time.sleep(0.6))

    wall.after_call = hook
    reqs = _reqs([0.0, 0.05])
    wall.start(reqs)
    t0 = time.perf_counter()
    cluster.run(reqs)
    took = time.perf_counter() - t0
    wall.stop()
    assert took >= 0.6
    assert len(wall.pauses) == 1 and wall.paused_s >= 0.6
    for r in reqs:
        assert len(wall.stamps[r.rid]) == r.decode_len + 1
        assert np.diff(wall.stamps[r.rid]).max() < 0.3
    assert max(wall.lag) < 0.3


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------


def _toy_events():
    mods = [["jit__unknown", 0.000, 0.010], ["jit_gather", 0.011, 0.001],
            ["jit__unknown", 0.013, 0.020], ["jit__unknown", 0.040, 0.020]]
    ops = [["fusion.1", 0.000, 0.004], ["fusion.2", 0.005, 0.005],
           ["gather", 0.011, 0.001], ["fusion.3", 0.013, 0.020],
           ["fusion.3", 0.040, 0.020]]
    spans = [["prefill", -0.001, 0.0125], ["insert", 0.0115, 0.001],
             ["decode", 0.0125, 0.001], ["decode", 0.033, 0.008],
             ["drain", 0.033, 0.006]]
    return {"modules": mods, "ops": ops, "spans": spans, "device": "d"}


def test_reduce_toy_trace():
    c = model.load("phi4-mini-3.8b")
    peaks = harness.peaks_of("TPU v5 lite")
    disp = [("prefill", 100), ("decode", [100], (7,)),
            ("decode", [101], (7,))]
    t = tracereduce.reduce(_toy_events(), disp, c, peaks)
    assert t["steps"] and t["n_prefill"] == 1 and t["n_decode"] == 2
    assert t["prefill_s"] == pytest.approx(0.010)
    assert t["decode_s"] == pytest.approx(0.040)
    assert t["prefill_flops"] == work.prefill_flops(c, 100)
    assert t["decode_flops"] == (work.decode_flops(c, [100])
                                 + work.decode_flops(c, [101]))
    lo, hi = -0.001, 0.060
    assert t["span_s"] == pytest.approx(hi - lo)
    assert t["busy_s"] == pytest.approx(0.010 + 0.001 + 0.020 + 0.020)
    idle = dict(t["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(t["span_s"] - t["busy_s"])
    # 0.033-0.039 the host drains (innermost span), 0.039-0.040 decodes
    assert idle["drain"] == pytest.approx(0.006)
    assert t["decode_gaps"] == [pytest.approx(0.007)]
    least = sum(max(work.decode_flops(c, [x]) / peaks["flops_per_s"],
                    work.decode_bytes(c, [x]) / peaks["bytes_per_s"])
                for x in (100, 101))
    assert t["decode_least_s"] == pytest.approx(least)


def test_reduce_refuses_mismatched_steps():
    c = model.load("phi4-mini-3.8b")
    t = tracereduce.reduce(_toy_events(), [("prefill", 100)], c, None)
    assert not t["steps"] and t["decode_s"] == 0.0
    assert t["busy_s"] > 0


RECORDED = Path(__file__).parent / "data" / "trace_phi4_chat.json"


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_reduce_recorded_trace():
    """A slice of a trace recorded on one TPU v5e in the phi4-chat cell
    (``tracereduce.extract`` of a traced run, with the adapter's dispatch
    log, cut to a prefill and three decode steps): every step module
    matched to a logged dispatch, shares of the peak below 100%."""
    ev = json.loads(RECORDED.read_text())
    c = model.load("phi4-mini-3.8b")
    peaks = harness.peaks_of("TPU v5 lite")
    disp = [tuple(d) for d in ev.pop("dispatches")]
    t = tracereduce.reduce(ev, disp, c, peaks)
    assert t["steps"]
    assert t["n_prefill"] + t["n_decode"] == len(disp)
    assert 0 < t["busy_s"] <= t["span_s"]
    assert 0 < t["decode_least_s"] <= t["decode_s"]
    assert t["decode_flops"] / (t["decode_s"] * peaks["flops_per_s"]) < 1
    if t["n_prefill"]:
        assert t["prefill_flops"] / (t["prefill_s"]
                                     * peaks["flops_per_s"]) < 1
