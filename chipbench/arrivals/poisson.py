"""Open-loop Poisson arrivals of independent requests.

Keys of the mix:

* ``rate_rps``: ``round(rate_rps * seconds)`` requests whose
  exponential gaps are scaled to end inside the window;
* ``prompt`` / ``output``: lognormal ``mean`` and ``std``, clipped to
  ``[lo, hi]``; ``output`` counts every token served, the prefill's
  first token included;
* ``shape_seed``: draws the schedule.

No two prompts share a prefix.
"""
from __future__ import annotations

from typing import List

import numpy as np

from traffic import Item, lognormal


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


def warmup_prompts(mix: dict, page_size: int, seed: int,
                   vocab: int) -> List[np.ndarray]:
    """One seeded prompt per page count the mix can hand off (every
    prefill bucket is among them): each page count compiles its own
    handoff gather and insert in the program."""
    lo = -(-int(mix["prompt"]["lo"]) // page_size)
    hi = -(-int(mix["prompt"]["hi"]) // page_size)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    return [rng.integers(0, vocab, min(p * page_size,
                                       int(mix["prompt"]["hi"])),
                         dtype=np.int32)
            for p in range(lo, hi + 1)]
