"""Readings that set a cell's output-check limits, on the chip.

    python3 chipbench/control.py --workload <cell> --seconds 15 --seeds 12 [--first-seed N]

For each seed, in one process (the first seed warms every shape up;
the later ones find them compiled): one run of the cell with a short window
at the cell's own load, then the output check of its sample twice: the
served tokens (the sound reading) and the tokens the control puts first
at the same positions (the program's own int8 weight path, one precision
below the served bf16).  Both go through the harness's own comparison
with the configuration's limits.  Prints a line per seed and, last, one
JSON object with the largest sound reading and the smallest control
reading of each number: a limit lies between them.  Exits 1 unless
every sound run reads correct and every control reads not correct.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)
    for p in (str(HERE), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from repro.serving import jitcache

    jitcache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             t_proc=time.perf_counter(), root=ROOT,
                             control=True, warm=i == 0)
        ctl = r["control"]
        row = {"seed": seed, "correct": r["correct"],
               "control_correct": ctl["correct"], **ctl["sound_numbers"],
               **{"control_" + k: v for k, v in ctl["numbers"].items()}}
        rows.append(row)
        print("[control-seed] " + json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "sound_all_correct": all(r["correct"] for r in rows),
               "control_none_correct": not any(r["control_correct"]
                                               for r in rows)}
    for k in rows[0]:
        if k.startswith("gap_"):
            summary[f"sound_max_{k}"] = max(r[k] for r in rows)
            summary[f"control_min_{k}"] = min(r["control_" + k]
                                              for r in rows)
    print(json.dumps(summary), flush=True)
    return 0 if (summary["sound_all_correct"]
                 and summary["control_none_correct"]) else 1


if __name__ == "__main__":
    sys.exit(main())
