"""The one traffic generator: a mix file of parameters -> seeded requests.

A mix (``traffic/<name>.json``) fixes the *schedule*: how many
requests, and for each its due time and its prompt and output lengths,
all drawn once from the mix's own ``shape_seed``.  The run's ``--seed``
draws only the token ids.  So every seed serves the same requests at the
same times, and runs with different seeds differ by content, not by
load: the device's time per step does not depend on the token ids.

Keys of a mix:

* ``arrivals``: ``"poisson"``, open loop at ``rate_rps`` for the window:
  ``round(rate_rps * seconds)`` requests whose exponential gaps are
  scaled to end inside the window.
* ``prompt`` / ``output``: lognormal ``mean`` and ``std``, clipped to
  ``[lo, hi]``; ``output`` counts every token served, the prefill's
  first token included.
* ``drain_s``: requests due in the window are served to the end, up to
  this many seconds after it closes.
* ``prefix_cache``, ``check_tokens``, ``check_requests``,
  ``trace_start_s``, ``trace_seconds``: read by the harness.

The lognormal moment match follows ``LengthDist`` of the program's
``serving/workload.py``; it is copied here so that the yardstick does not
move with the program.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Item:
    """One request of a run: due time (s after the window opens), prompt
    token ids and the number of tokens to serve."""

    rid: int
    due_s: float
    prompt: np.ndarray
    output: int


def load(name: str, root: Path = HERE) -> dict:
    path = root / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = json.loads(path.read_text())
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"mix {name!r}: arrivals must be poisson")
    return mix


def lognormal(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """Lognormal moment-matched to (mean, std), rounded, clipped."""
    mean, std = float(spec["mean"]), float(spec["std"])
    sigma2 = math.log(1.0 + (std / mean) ** 2)
    mu = math.log(mean) - sigma2 / 2.0
    x = rng.lognormal(mu, math.sqrt(sigma2), n)
    return np.clip(np.round(x), int(spec["lo"]), int(spec["hi"])).astype(int)


def schedule(mix: dict, seconds: float):
    """(due times, prompt lengths, output lengths) of the mix, from its
    own ``shape_seed``: the same for every run seed."""
    n = max(1, int(round(float(mix["rate_rps"]) * seconds)))
    rng = np.random.default_rng(int(mix["shape_seed"]))
    prompt = lognormal(rng, mix["prompt"], n)
    output = lognormal(rng, mix["output"], n)
    gaps = rng.exponential(1.0, n)
    # the n-th arrival lands at seconds * n / (n + 1): n requests due
    # inside the window at a mean rate of about rate_rps
    gaps *= seconds * n / (n + 1) / gaps.sum()
    return np.cumsum(gaps), prompt, output


def generate(mix: dict, seconds: float, seed: int, vocab: int) -> List[Item]:
    """The run's requests: the mix's schedule with seed-drawn token ids."""
    due, prompt, output = schedule(mix, seconds)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    return [Item(i, float(due[i]),
                 rng.integers(0, vocab, int(prompt[i]), dtype=np.int32),
                 int(output[i]))
            for i in range(len(prompt))]


def warmup_lengths(mix: dict, page_size: int) -> List[int]:
    """One prompt length per page count the mix can hand off (every
    prefill bucket is among them): each page count compiles its own
    handoff gather and insert in the program."""
    lo = -(-int(mix["prompt"]["lo"]) // page_size)
    hi = -(-int(mix["prompt"]["hi"]) // page_size)
    return [min(p * page_size, int(mix["prompt"]["hi"]))
            for p in range(lo, hi + 1)]
