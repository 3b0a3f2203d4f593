"""Reduced configurations and mixes for running the cells on the CPU.

The widths are cut to a toy so that a whole run takes seconds on the
CPU; every key the reference reads keeps its meaning.
"""
from __future__ import annotations

import json
import tempfile
import time
from pathlib import Path

import harness
import model
import traffic

DATA = Path(__file__).resolve().parent / "data"
# a cell of the tests alone, which the benchmark does not have: the
# sessions mix of ``data/traffic/sessions.json`` (shared system prompts,
# multi-turn chats, the prefix cache on) on phi4-mini-3.8b, reporting
# every metric and ``prefix_reuse``
SESSIONS = {"name": "sessions", "config": "phi4-mini-3.8b",
            "traffic": "sessions", "chips": 1, "why": "tests"}
PREFIX_REUSE = {"name": "prefix_reuse", "unit": "%", "better": "higher",
                "source": "program_span", "layer": "prefix cache",
                "moves": "ttft_p90_s", "workloads": ["sessions"]}

# set from CPU readings at this size (three seeds): sound runs read
# gap_max 0 to 0.0037, the int8 control 0.0024 to 0.0085, and each
# planted fault 0.55 or more.  Over seeds the control is not 3x above
# the sound runs at this toy size; on the tests' seed it reads 0.0085
# and the sound run 0.00092.  The chip readings at the cells' own sizes
# set the configurations' limits.
LIMITS = {"gap_max": 0.004}
PEAKS = {"flops_per_s": 1e12, "bytes_per_s": 1e11}


def config(name: str) -> dict:
    c = model.load(name)
    L, d = 2, 64
    c = dict(c, num_hidden_layers=L, hidden_size=d, intermediate_size=128,
             num_attention_heads=4, head_dim=16, vocab_size=257,
             num_key_value_heads=(2 if c["num_key_value_heads"]
                                  < c["num_attention_heads"] else 4),
             serving=dict(c["serving"], slots=4, max_len=256,
                          decode_pool_pages=64, prefill_pool_pages=17),
             check=dict(LIMITS))
    return c


def bench() -> dict:
    """``BENCHMARK.json`` with the tests' ``sessions`` cell added."""
    b = harness.load_bench(harness.HERE.parent)
    b["workloads"].append(SESSIONS)
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(SESSIONS["name"])
    b["per_layer"].append(PREFIX_REUSE)
    return b


def load_mix(name: str) -> dict:
    """A mix of the benchmark, or of the tests' data."""
    here = (DATA / "traffic" / f"{name}.json").is_file()
    return traffic.load(name, DATA) if here else traffic.load(name)


def mix(name: str) -> dict:
    m = dict(load_mix(name), trace_start_s=0.0, trace_seconds=0.5,
             check_tokens=60, check_requests=50, drain_s=20)
    if m["arrivals"] == "sessions":
        # 4-page system prompts, turns a few tenths of a second apart,
        # conversations cut at the toy's max_len; the prefill pool holds
        # one largest prompt and a 32-page prefix cache
        return dict(m, sessions_rate=3.0, system_tokens=64, max_len=256,
                    think_s={"min": 0.2, "mean_extra": 0.3},
                    user=dict(m["user"], mean=16, std=8, lo=8, hi=32),
                    output=dict(m["output"], mean=10, std=5, lo=2, hi=24),
                    serving={"prefill_pool_pages": 48})
    return dict(m, rate_rps=6.0,
                prompt=dict(m["prompt"], mean=40, std=30, lo=8, hi=128),
                output=dict(m["output"], mean=20, std=10, lo=2, hi=64))


def run(cell: str, cfg: str, seed: int = 2**31 + 12345, seconds: float = 2.0,
        trace: bool = False, **kw) -> dict:
    with tempfile.TemporaryDirectory() as root:
        (Path(root) / "BENCHMARK.json").write_text(json.dumps(bench()))
        return harness.run_cell(cell, seed, seconds, trace,
                                t_proc=time.perf_counter(), root=Path(root),
                                require_tpu=False, config=config(cfg),
                                mix=mix(cell), peaks=PEAKS, **kw)
