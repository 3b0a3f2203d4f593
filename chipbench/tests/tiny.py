"""Reduced configurations and mixes for running the cells on the CPU.

The widths are cut to a toy so that a whole run takes seconds on the
CPU; every key the reference reads keeps its meaning.
"""
from __future__ import annotations

import time

import harness
import model
import traffic

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


def mix(name: str) -> dict:
    m = traffic.load(name)
    m = dict(m, prompt=dict(m["prompt"], mean=40, std=30, lo=8, hi=128),
             output=dict(m["output"], mean=20, std=10, lo=2, hi=64),
             trace_start_s=0.0, trace_seconds=0.5, check_tokens=60,
             check_requests=50,
             drain_s=20)
    m["rate_rps"] = 6.0
    return m


def run(cell: str, cfg: str, seed: int = 2**31 + 12345, seconds: float = 2.0,
        trace: bool = False, **kw) -> dict:
    return harness.run_cell(cell, seed, seconds, trace,
                            t_proc=time.perf_counter(), require_tpu=False,
                            config=config(cfg), mix=mix(cell), peaks=PEAKS,
                            **kw)
