"""Chip benchmark of the served path: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for; one process per run.  The cell, its configuration, its
traffic mix and its metrics are found by name from ``BENCHMARK.json``
(see ``harness.py``).  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics, from a profiler trace of
part of the window.  Every line but the last is progress; the last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``: each number compared beside its limit, which also
end standard error).

The run exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for, when the device is not in
``peaks.json``, or when the program (``src/repro``) is not in the
checkout.  JAX's compile cache is the checkout's ``.jax_cache/``, or
``JAX_COMPILATION_CACHE_DIR`` where that is set.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "serving").is_dir():
        print(f"run.py: no program (src/repro) in {ROOT}", file=sys.stderr)
        return 2
    for p in (str(HERE), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from repro.serving import jitcache

    jitcache.enable_compile_cache()
    # every program the cell runs, small eager ones too, comes from the
    # cache after a cell's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_proc=T_PROC, root=ROOT)
    except harness.RunError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
