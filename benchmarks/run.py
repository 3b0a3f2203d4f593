"""Benchmark driver: one module per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig16 fig6 # subset
    PYTHONPATH=src python -m benchmarks.run --quick    # cheap subset
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI smoke (<10 min)

Each module writes ``benchmarks/results/<name>.csv``; this driver prints
a one-line summary per module and a final manifest.  ``--smoke`` also
sets ``BENCH_SMOKE=1`` so serving modules shrink their traces, and
emits ``benchmarks/results/BENCH_serving.json`` — a machine-readable
perf snapshot (event-loop wall time, energy/token, SLO attainment, and
per-module status) that CI uploads so the serving perf trajectory is
comparable across PRs.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
import time
import traceback

MODULES = [
    ("fig1_5_ucurve", "Fig.1/5  U-shaped E-f curves + anchors"),
    ("fig2_3_workload_dynamics", "Fig.2/3  multi-timescale workload dynamics"),
    ("fig4_itl_sensitivity", "Fig.4    decode ITL sensitivity vs batch"),
    ("fig6_staircase", "Fig.6    tile-quantization staircase"),
    ("fig10_predictability", "Fig.10   latency predictability scatter"),
    ("fig13_state_space", "Fig.13   decode state-space freq regions"),
    ("fig16_main", "Fig.16   MAIN: SLO attainment + energy"),
    ("fig17_ablation", "Fig.17/28 EcoFreq-only vs full + phase split"),
    ("fig18_traces", "Fig.18/31 frequency/batch traces"),
    ("fig19_slo_profiles", "Fig.19   SLO profile sweep"),
    ("fig20_control_interval", "Fig.20   control-interval ablation"),
    ("fig21_ecopred_mae", "Fig.21   EcoPred offline vs online MAE"),
    ("fig22_gh200", "Fig.22   GH200 generalization"),
    ("fig25_throughput", "Fig.25   throughput comparison"),
    ("fig26_27_static_powercap", "Fig.26/27 static-intermediate + powercap"),
    ("fig29_30_levels_delta", "Fig.29/30 freq levels + delta sweep"),
    ("tab2_pd_ratio", "Tab.II   synthetic P/D-ratio workload"),
    ("fig34_cdfs", "Fig.34   TTFT/ITL CDFs at low/high RPS"),
    ("fig_hetero_autoscale", "EcoScale hetero fleet + autoscale vs static"),
    ("fig_prefix_cache", "Chunked prefill + radix prefix cache (multi-turn)"),
    ("fig_slo_tiers", "Multi-tenant SLO tiers vs single-tier baseline"),
    ("fig_specdec", "Speculative draft-verify decode vs single-token"),
    ("fig_traces_replay", "Scenario matrix replay + open-loop QPS knees"),
    ("roofline", "§Roofline table from dry-run records"),
    ("perf_iterations", "§Perf    hillclimb log from perf records"),
]

QUICK = {"fig1_5_ucurve", "fig4_itl_sensitivity", "fig6_staircase",
         "fig13_state_space", "fig20_control_interval", "roofline"}

# CI smoke: fast analytic sanity + the EcoScale serving scenario + the
# prefix-cache + SLO-tier scenarios (all read BENCH_SMOKE=1 and shrink
# their traces)
SMOKE = {"fig1_5_ucurve", "fig6_staircase", "fig_hetero_autoscale",
         "fig_prefix_cache", "fig_slo_tiers", "fig_specdec",
         "fig_traces_replay"}


def _write_bench_serving(module_status: dict) -> str:
    """Machine-readable perf snapshot for cross-PR tracking (CI
    artifact): the Sim event loop timed on a fixed reference scenario —
    legacy and paged KV accounting — plus each smoke module's status."""
    from benchmarks.perf_iterations import (
        event_loop_benchmark,
        real_mesh_benchmark,
    )

    bank = {}  # one EcoPred fit shared by both variants
    event_loop = {
        "dense": event_loop_benchmark(paged=False, predictor_bank=bank),
        "paged": event_loop_benchmark(paged=True, predictor_bank=bank),
        "spec_decode": event_loop_benchmark(
            paged=True, spec=True, predictor_bank=bank
        ),
        # real JAX execution on a tp=1 mesh slice: gates the mesh-keyed
        # jit cache (warm run must replay, recompiles == 0) and the
        # virtual-clock golden pin through the sharded code path
        "real_mesh_tp1": real_mesh_benchmark(tp=1),
    }
    payload = {
        "schema": 2,
        "generated_by": "benchmarks.run --smoke",
        "event_loop": event_loop,
        # Phase split of the dense loop (separate instrumented run; its
        # iters_per_s is NOT the headline number — wrappers cost time).
        "event_loop_breakdown": event_loop_benchmark(
            paged=False, predictor_bank=bank, breakdown=True
        ).get("breakdown"),
        # standing depth-K data for the K>1 default question (ROADMAP):
        # the same real tp=1 scenario with the async-dispatch ring at
        # each depth; k1 is the headline real_mesh_tp1 row itself
        "pipeline_depth_sweep": {
            "k1": event_loop["real_mesh_tp1"],
            "k2": real_mesh_benchmark(tp=1, pipeline_depth=2),
            "k4": real_mesh_benchmark(tp=1, pipeline_depth=4),
        },
        "modules": module_status,
    }
    replay_path = os.path.join(os.path.dirname(__file__), "results",
                               "fig_traces_replay.json")
    if os.path.exists(replay_path):  # scenario matrix + open-loop QPS
        # knees (written by fig_traces_replay earlier in this smoke run)
        with open(replay_path) as f:
            payload["trace_replay"] = json.load(f)
    base_path = os.path.join(os.path.dirname(__file__),
                             "BENCH_baseline.json")
    if os.path.exists(base_path):  # embed the committed pre-PR rows so
        # the artifact is self-describing (gate math lives in
        # tools/bench_gate.py, which re-reads the baseline itself)
        with open(base_path) as f:
            base = json.load(f)
        pre = base.get("pre_pr", {})
        payload["pre_pr"] = pre
        payload["speedup_vs_pre_pr"] = {
            k: round(event_loop[k]["iters_per_s"]
                     / pre[k]["iters_per_s"], 2)
            for k in event_loop
            if pre.get(k, {}).get("iters_per_s")
            and event_loop[k].get("iters_per_s")
        }
    out_dir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "BENCH_serving.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main() -> int:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    quick = "--quick" in sys.argv
    smoke = "--smoke" in sys.argv
    if smoke:
        os.environ["BENCH_SMOKE"] = "1"
    from repro.serving import jitcache

    jitcache.enable_compile_cache()
    failures = 0
    module_status = {}
    for name, desc in MODULES:
        if args and not any(a in name for a in args):
            continue
        if quick and name not in QUICK:
            continue
        if smoke and name not in SMOKE:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            rows = mod.run()
            n = len(rows) if rows is not None else 0
            module_status[name] = {
                "status": "ok", "rows": n,
                "wall_s": round(time.time() - t0, 1),
            }
            print(f"[ok]   {desc:45s} {n:4d} rows  {time.time()-t0:6.1f}s",
                  flush=True)
        except (Exception, SystemExit) as e:
            # SystemExit too: a script-style `sys.exit(0)` inside a
            # figure module must fail *this* module, not silently end
            # the whole sweep with a green exit code.
            failures += 1
            module_status[name] = {
                "status": "fail", "error": f"{type(e).__name__}: {e}",
            }
            print(f"[FAIL] {desc:45s} {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if smoke and not args:  # full smoke only: a filtered run would
        # masquerade as a complete perf snapshot
        try:
            path = _write_bench_serving(module_status)
            print(f"[ok]   BENCH_serving.json -> {path}", flush=True)
        except Exception as e:
            failures += 1
            print(f"[FAIL] BENCH_serving.json {type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc()
    print(f"\nbenchmarks done ({failures} failures); results in "
          "benchmarks/results/")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
