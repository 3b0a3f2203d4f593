"""The harness's parts: lookup by name, work counts, peaks, the
traffic generators, the wall-clock adapter and the trace reduction."""
import hashlib
import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

import harness
import model
import traffic
import tracereduce
import work
from conftest import BENCH, ROOT

DATA = BENCH / "tests" / "data"  # the sessions mix of the tests

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
    with pytest.raises(ValueError, match=r"metrics/no_such_metric\.py"):
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
    last = _run_copy(tmp_path, "toy")
    assert last["correct"] is True
    assert last["metrics"]["tokens_total"]["value"] > 0
    # metrics with a cell list that leaves the new cell out stay out
    assert set(last["metrics"]) == {"tokens_total"}


def _run_copy(root: Path, cell: str) -> dict:
    """Run ``cell`` traced from the benchmark copy under ``root`` in a
    fresh process on the CPU; its last line."""
    code = (
        "import sys, time; from pathlib import Path\n"
        f"sys.path[:0] = [{str(root / 'chipbench')!r}, {str(ROOT / 'src')!r}]\n"
        "import harness\n"
        f"r = harness.run_cell({cell!r}, 5, 2.0, True,"
        " t_proc=time.perf_counter(),"
        f" root=Path({str(root)!r}), require_tpu=False,"
        " peaks={'flops_per_s': 1e12, 'bytes_per_s': 1e11})\n"
        "harness.print_result(r)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_architecture_and_arrivals_added_as_files_only(tmp_path):
    """A configuration of an architecture the harness has not seen and a
    mix of arrivals it has not seen, each with its module written as a
    file (here copies of ``archs/dense.py`` and ``arrivals/sessions.py``
    under new names), and a cell over them, run with no edit to the
    harness: prefix-cached sessions, correct, their reuse read."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    shutil.copy(BENCH / "archs" / "dense.py",
                bench_dir / "archs" / "toy_dense.py")
    shutil.copy(BENCH / "arrivals" / "sessions.py",
                bench_dir / "arrivals" / "toy_chats.py")
    cfg = dict(model.load("phi4-mini-3.8b"), name="toy-arch",
               architecture="toy_dense", num_hidden_layers=2,
               hidden_size=64, intermediate_size=96, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=131,
               serving=dict(slots=2, max_len=128, page_size=16,
                            decode_pool_pages=24, prefill_pool_pages=9),
               check={"gap_max": 0.02, "gap_mean": 0.002})
    (bench_dir / "configs" / "toy-arch.json").write_text(json.dumps(cfg))
    mix = dict(traffic.load("sessions", DATA), arrivals="toy_chats",
               sessions_rate=3.0, system_tokens=32, max_len=128,
               think_s={"min": 0.2, "mean_extra": 0.3},
               user={"mean": 12, "std": 6, "lo": 4, "hi": 24},
               output={"mean": 8, "std": 4, "lo": 2, "hi": 16},
               serving={"prefill_pool_pages": 24}, check_tokens=30,
               trace_start_s=0.2, trace_seconds=0.5, drain_s=20)
    del mix["name"]
    (bench_dir / "traffic" / "toy-chats.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-arch", "source": "test",
                             "file": "chipbench/configs/toy-arch.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy", "config": "toy-arch",
                               "traffic": "toy-chats", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "prefix_reuse", "unit": "%",
                               "better": "higher", "source": "program_span",
                               "layer": "prefix cache", "moves": "ttft_p90_s",
                               "workloads": ["toy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    last = _run_copy(tmp_path, "toy")
    assert last["correct"] is True, last["check"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert set(last["metrics"]) == {"prefix_reuse"}
    assert last["metrics"]["prefix_reuse"]["value"] > 0


def test_unknown_modules_fail_at_load(tmp_path):
    """A configuration whose architecture, or a mix whose arrivals, has
    no module fails as it is loaded, naming the module it looked for."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    cfg = dict(model.load("phi4-mini-3.8b"), architecture="no_such_arch")
    (tmp_path / "configs" / "x.json").write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=r"archs/no_such_arch\.py"):
        model.load("x", tmp_path)
    mix = dict(traffic.load("phi4-chat"), arrivals="no_such_arrivals")
    (tmp_path / "traffic" / "x.json").write_text(json.dumps(mix))
    with pytest.raises(ValueError, match=r"arrivals/no_such_arrivals\.py"):
        traffic.load("x", tmp_path)


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
    dense = model.arch(c)
    # one layer: q 3072x3072, k and v 3072x1024, o 3072x3072, MLP 3x3072x8192
    per_layer = 3072 * 3072 * 2 + 3072 * 1024 * 2 + 3 * 3072 * 8192
    assert dense.layer_matmul_params(c) == per_layer == 100_663_296
    head = 3072 * 200064
    # one decode row at context 100: 32 layers, logits, attention over 101
    want = 2 * 32 * per_layer + 2 * head + 32 * 4 * 3072 * 101
    assert work.decode_flops(c, [100]) == want
    # a 10-token prefill: causal attention over 55 query-key pairs
    want = 2 * 32 * per_layer * 10 + 32 * 4 * 3072 * 55 + 2 * head
    assert work.prefill_flops(c, 10, 0) == want
    # the same 10 tokens after 1024 reused ones: the same matmuls and
    # logits, each query also over the 1024 reused keys
    want = (2 * 32 * per_layer * 10 + 32 * 4 * 3072 * (10 * 1024 + 55)
            + 2 * head)
    assert work.prefill_flops(c, 10, 1024) == want
    # K and V of one token: 2 x 32 layers x 8 heads x 128 x 2 bytes
    assert model.kv_bytes_per_token(c) == 131_072
    w = 2 * (32 * (per_layer + 2 * 3072) + head + 3072)
    assert dense.weight_bytes(c) == w
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


def test_weights_bytes_are_pinned():
    """The weights of a fixed seed, leaf by leaf, hash to what they were
    before the dense layout moved into ``archs/dense.py``."""
    c = dict(model.load("phi4-mini-3.8b"), num_hidden_layers=2,
             hidden_size=32, intermediate_size=48, num_attention_heads=2,
             num_key_value_heads=1, head_dim=16, vocab_size=50)
    w = model.make_weights(c, 2**33 + 7)
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == ("8aabf18cb612e02c93a7eb519640e502"
                             "c80d45a29f585d9a80e35f8553a2e794")


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


def _turns(mix, seconds, seed, vocab=1000):
    """The sessions mix's items, keyed (conversation, turn), with each
    conversation's app and the schedule."""
    gen = traffic.arrivals(mix)
    convs = gen.schedule(mix, seconds)
    items = traffic.generate(mix, seconds, seed, vocab)
    order = sorted((due, c, k) for c, (_, conv) in enumerate(convs)
                   for k, (due, _, _) in enumerate(conv))
    assert len(order) == len(items)
    assert [i.rid for i in items] == list(range(len(items)))
    return {(c, k): items[i] for i, (_, c, k) in enumerate(order)}, convs


def test_sessions_seeds_share_the_schedule():
    """Every seed serves the same turns at the same due times with the
    same lengths; the seed draws the ids, the same seed the same ones."""
    mix = traffic.load("sessions", DATA)
    a = traffic.generate(mix, 51.0, 1, 1000)
    b = traffic.generate(mix, 51.0, 2**32 + 5, 1000)
    shape = lambda items: [(i.due_s, len(i.prompt), i.output) for i in items]
    assert shape(a) == shape(b)
    assert all(0 < i.due_s < 51.0 for i in a)
    assert [i.due_s for i in a] == sorted(i.due_s for i in a)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    a2 = traffic.generate(mix, 51.0, 1, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, a2))


def test_sessions_turn_extends_the_history():
    """Turn k+1's prompt is turn k's prompt, then as many reply tokens as
    turn k was served, then the user's message; a first turn starts
    with its app's system prompt, which no other app shares."""
    mix = traffic.load("sessions", DATA)
    turns, convs = _turns(mix, 51.0, 2**31 + 99)
    sys_len = mix["system_tokens"]
    systems = {}
    for c, (app, conv) in enumerate(convs):
        head = tuple(turns[c, 0].prompt[:sys_len])
        assert systems.setdefault(app, head) == head
        assert len(turns[c, 0].prompt) == sys_len + conv[0][1]
        for k in range(1, len(conv)):
            prev, cur = turns[c, k - 1], turns[c, k]
            assert np.array_equal(cur.prompt[:len(prev.prompt)], prev.prompt)
            assert len(cur.prompt) == (len(prev.prompt) + prev.output
                                       + conv[k][1])
            assert cur.output == conv[k][2]
            assert cur.due_s >= prev.due_s + mix["think_s"]["min"]
    assert len(set(systems.values())) == len(systems) == len(mix["apps"])
    assert max(len(conv) for _, conv in convs) == mix["turns"]["max"]


def test_sessions_fit_max_len():
    """No turn's prompt and output pass ``max_len``, however long the
    window; conversations are cut before such a turn."""
    mix = dict(traffic.load("sessions", DATA), max_len=1600)
    turns, _ = _turns(mix, 200.0, 7)
    assert max(len(t.prompt) + t.output for t in turns.values()) <= 1600
    full = traffic.arrivals(mix).schedule(dict(mix, max_len=10**6), 200.0)
    cut = traffic.arrivals(mix).schedule(mix, 200.0)
    assert sum(map(len, (c for _, c in cut))) < \
        sum(map(len, (c for _, c in full)))


def test_sessions_apps_follow_popularity():
    """Over many conversations each app's share of them is its stated
    popularity."""
    mix = traffic.load("sessions", DATA)
    convs = traffic.arrivals(mix).schedule(mix, 5000.0)
    got = np.bincount([app for app, _ in convs],
                      minlength=len(mix["apps"])) / len(convs)
    want = np.asarray(mix["apps"]) / sum(mix["apps"])
    assert np.abs(got - want).max() < 0.02
    # turns: geometric with the stated mean, capped
    n = np.asarray([len(c) for _, c in convs])
    assert n.min() >= 1 and n.max() == mix["turns"]["max"]


def test_warmup_prompts_share_the_system_prompt():
    """The sessions warm-up: one prompt per page count a turn can hand
    off, longest first, all starting with the same system prompt, so
    that after the first the cache hits and every suffix bucket of the
    window compiles; a Poisson mix's prompts share nothing."""
    mix = traffic.load("sessions", DATA)
    ps, sys_len = 16, mix["system_tokens"]
    warm = traffic.warmup_prompts(mix, ps, 3, 1000)
    pages = [-(-len(p) // ps) for p in warm]
    turns, _ = _turns(mix, 51.0, 3)
    used = {-(-len(t.prompt) // ps) for t in turns.values()}
    assert used <= set(pages) and pages == sorted(pages, reverse=True)
    assert pages == list(range(pages[0], pages[-1] - 1, -1))
    assert all(np.array_equal(p[:sys_len], warm[0][:sys_len]) for p in warm)
    chat = traffic.warmup_prompts(traffic.load("phi4-chat"), ps, 3, 1000)
    assert not np.array_equal(chat[0][:32], chat[1][:32])


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
    disp = [("prefill", 100, 7), ("decode", [100], (7,)),
            ("decode", [101], (7,))]
    t = tracereduce.reduce(_toy_events(), disp, c, peaks, {7: [(100, 0)]})
    assert t["steps"] and t["n_prefill"] == 1 and t["n_decode"] == 2
    assert t["prefill_s"] == pytest.approx(0.010)
    assert t["decode_s"] == pytest.approx(0.040)
    assert t["prefill_flops"] == work.prefill_flops(c, 100, 0)
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


def test_reduce_charges_what_a_prefill_computed():
    """A prefill that reused a cached prefix is charged its computed
    suffix after the reused tokens; one whose span is missing, or does
    not add up to the tokens dispatched, leaves the prefill work unread."""
    c = model.load("phi4-mini-3.8b")
    disp = [("prefill", 100, 7), ("decode", [100], (7,)),
            ("decode", [101], (7,))]
    t = tracereduce.reduce(_toy_events(), disp, c, None, {7: [(36, 64)]})
    assert t["prefill_flops"] == work.prefill_flops(c, 36, 64)
    assert t["prefill_flops"] < work.prefill_flops(c, 100, 0)
    for spans in ({}, {8: [(100, 0)]}, {7: [(36, 60)]}):
        t = tracereduce.reduce(_toy_events(), disp, c, None, spans)
        assert t["prefill_flops"] is None and t["prefill_s"] > 0
        assert t["decode_flops"] > 0


def test_reduce_refuses_mismatched_steps():
    c = model.load("phi4-mini-3.8b")
    t = tracereduce.reduce(_toy_events(), [("prefill", 100, 7)], c, None,
                           {7: [(100, 0)]})
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
    # the recorded log names no request; no prompt there reused a prefix
    disp = [tuple(d) + ((i,) if d[0] == "prefill" else ())
            for i, d in enumerate(ev.pop("dispatches"))]
    spans = {d[2]: [(d[1], 0)] for d in disp if d[0] == "prefill"}
    t = tracereduce.reduce(ev, disp, c, peaks, spans)
    assert t["steps"]
    assert t["n_prefill"] + t["n_decode"] == len(disp)
    assert 0 < t["busy_s"] <= t["span_s"]
    assert 0 < t["decode_least_s"] <= t["decode_s"]
    assert t["decode_flops"] / (t["decode_s"] * peaks["flops_per_s"]) < 1
    if t["n_prefill"]:
        assert t["prefill_flops"] / (t["prefill_s"]
                                     * peaks["flops_per_s"]) < 1
