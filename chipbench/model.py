"""A configuration file -> its architecture module, the program's model
config and the weights.

``configs/<name>.json`` holds a model's published configuration as it
is run here: the source's keys and values, a cut of scale listed in
``BENCHMARK.json``'s ``reduced``; under ``program_departures``, each key
the program computes differently from the source, with the source's
value and the value run (which ``load`` puts at the top level, so the
program and the reference both follow it); the serving sizes under
``serving`` and the limits of the output check under ``check``.

Its ``architecture`` key names the module ``archs/<architecture>.py``
that knows the layout, the reference and the work of that kind of
model.  Such a module gives

* ``program_config(c)``: the program's ``ModelConfig``;
* ``weight_shapes(c)`` and ``make_weights(c, seed)``: random weights
  from the run's seed, made on the device in one jitted call in the
  served dtype, in the layout the program reads;
* ``kv_bytes_per_token(c)``: K/V bytes of one token over every layer;
* ``gap_fn(c, T, chunk)``: the plain float32 reference's jitted gap
  function at padded length ``T`` (``reference.py`` says what a gap
  is), importing nothing of the program;
* ``prefill_flops(c, computed, ctx)``, ``decode_flops(c, contexts)``
  and ``decode_bytes(c, contexts)``: the operations and bytes the
  served work needs (``work.py``).

A new architecture is a new module there; nothing here names one.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from byname import load_module

HERE = Path(__file__).resolve().parent

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def arch(c: dict):
    """The module of configuration ``c``'s architecture."""
    return load_module(HERE / "archs", c.get("architecture"),
                       "architecture")


def load(name: str, root: Path = HERE) -> dict:
    path = root / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    c = json.loads(path.read_text())
    for k, d in c.get("program_departures", {}).items():
        if isinstance(d, dict):
            c[k] = d["run"]
    arch(c)
    return c


def program_config(c: dict):
    return arch(c).program_config(c)


def kv_bytes_per_token(c: dict) -> int:
    return arch(c).kv_bytes_per_token(c)


def make_weights(c: dict, seed: int):
    return arch(c).make_weights(c, seed)


def key_of(seed: int):
    """A JAX key from any whole-number seed (seeds may exceed 32 bits)."""
    import jax

    word = np.random.SeedSequence([int(seed), 2]).generate_state(1)[0]
    return jax.random.key(int(word))


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
