"""Traffic mixes: a mix file of parameters -> seeded requests.

A mix (``traffic/<name>.json``) fixes the *schedule*: how many
requests, and for each its due time and its prompt and output lengths,
all drawn once from the mix's own ``shape_seed``.  The run's ``--seed``
draws only the token ids.  So every seed serves the same requests at the
same times, and runs with different seeds differ by content, not by
load: the device's time per step does not depend on the token ids.

The mix's ``arrivals`` key names the generator, ``arrivals/<arrivals>.py``,
which reads the rest of the mix.  Such a module gives

* ``generate(mix, seconds, seed, vocab)``: the run's ``Item`` list;
* ``warmup_prompts(mix, page_size, seed, vocab)``: seeded prompts, in
  the order they are served, that compile every shape the mix's
  requests compile in the window (prefill buckets, prefix hits,
  handoff page counts).

Keys every mix has, read by the harness: ``drain_s`` (requests due in
the window are served to the end, up to this many seconds after it
closes), ``prefix_cache``, ``check_tokens``, ``check_requests``,
``trace_start_s``, ``trace_seconds``; and optionally ``serving``, whose
keys override the configuration's serving sizes for the mix's cells.

A new kind of arrivals is a new module there; nothing here names one.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

from byname import load_module

HERE = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Item:
    """One request of a run: due time (s after the window opens), prompt
    token ids and the number of tokens to serve."""

    rid: int
    due_s: float
    prompt: np.ndarray
    output: int


def arrivals(mix: dict):
    """The generator module of the mix's ``arrivals``."""
    return load_module(HERE / "arrivals", mix.get("arrivals"), "arrivals")


def load(name: str, root: Path = HERE) -> dict:
    path = root / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    mix = dict(json.loads(path.read_text()), name=name)
    arrivals(mix)
    return mix


def lognormal(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """Lognormal moment-matched to (mean, std), rounded, clipped.  The
    moment match follows ``LengthDist`` of the program's
    ``serving/workload.py``, copied so that the yardstick does not move
    with the program."""
    mean, std = float(spec["mean"]), float(spec["std"])
    sigma2 = math.log(1.0 + (std / mean) ** 2)
    mu = math.log(mean) - sigma2 / 2.0
    x = rng.lognormal(mu, math.sqrt(sigma2), n)
    return np.clip(np.round(x), int(spec["lo"]), int(spec["hi"])).astype(int)


def generate(mix: dict, seconds: float, seed: int, vocab: int) -> List[Item]:
    """The run's requests, in due order."""
    return arrivals(mix).generate(mix, seconds, seed, vocab)


def warmup_prompts(mix: dict, page_size: int, seed: int,
                   vocab: int) -> List[np.ndarray]:
    return arrivals(mix).warmup_prompts(mix, page_size, seed, vocab)
