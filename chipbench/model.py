"""A configuration file -> the program's model config and the weights.

``configs/<name>.json`` holds a model's published configuration as it
is run here: the source's keys and values, a cut of scale listed in
``BENCHMARK.json``'s ``reduced``; under ``program_departures``, each key
the program computes differently from the source, with the source's
value and the value run (which ``load`` puts at the top level, so the
program and the reference both follow it); the serving sizes under
``serving`` and the limits of the output check under ``check``.

The weights are the benchmark's input to the program: random, from the
run's seed, made on the device in one jitted call in the served dtype,
in the layout the program's dense path reads (stacked per layer).  Norm
weights are stored as offsets from 1, as the program applies them.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def load(name: str, root: Path = HERE) -> dict:
    path = root / "configs" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    c = json.loads(path.read_text())
    for k, d in c.get("program_departures", {}).items():
        if isinstance(d, dict):
            c[k] = d["run"]
    if c.get("architecture") != "dense":
        raise ValueError(f"config {name!r}: only the dense architecture "
                         "has a weight layout and reference here")
    return c


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``."""
    from repro.configs.base import LayerSpec, ModelConfig

    return ModelConfig(
        name=c["name"],
        family="dense",
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        block_pattern=(LayerSpec(mixer="attn", ffn="mlp"),),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        act=c["hidden_act"],
        dtype=c["torch_dtype"],
    )


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token over every layer."""
    return (2 * int(c["num_hidden_layers"]) * int(c["num_key_value_heads"])
            * int(c["head_dim"]) * DTYPE_BYTES[c["torch_dtype"]])


def weight_shapes(c: dict) -> dict:
    """Leaf name -> shape, in the program's dense layout (block leaves
    stacked over layers)."""
    L, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    qd = int(c["num_attention_heads"]) * int(c["head_dim"])
    kvd = int(c["num_key_value_heads"]) * int(c["head_dim"])
    ff, V = int(c["intermediate_size"]), int(c["vocab_size"])
    s = {
        "embed": (V, d),
        "attn.norm": (L, d), "attn.wq": (L, d, qd), "attn.wk": (L, d, kvd),
        "attn.wv": (L, d, kvd), "attn.wo": (L, qd, d),
        "mlp.norm": (L, d), "mlp.w_gate": (L, d, ff), "mlp.w_in": (L, d, ff),
        "mlp.w_out": (L, ff, d),
        "final_norm": (d,),
    }
    if not c["tie_word_embeddings"]:
        s["lm_head"] = (d, V)
    return s


def key_of(seed: int):
    """A JAX key from any whole-number seed (seeds may exceed 32 bits)."""
    import jax

    word = np.random.SeedSequence([int(seed), 2]).generate_state(1)[0]
    return jax.random.key(int(word))


def make_weights(c: dict, seed: int):
    """Random weights from ``seed``, on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(c)
    dt = jnp.dtype(c["torch_dtype"])
    L = int(c["num_hidden_layers"])

    def init(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (name, shp) in zip(keys, sorted(shapes.items())):
            leaf = name.rsplit(".", 1)[-1]
            if leaf.endswith("norm"):
                std = 0.1  # offsets from 1
            elif leaf in ("embed", "lm_head"):
                std = 0.02
            else:  # fan-in scaled, output projections by depth too
                std = 1.0 / np.sqrt(shp[-2])
                if leaf in ("wo", "w_out"):
                    std /= np.sqrt(2 * L)
            flat[name] = (jax.random.normal(k, shp, dt) * std).astype(dt)
        p = {
            "embed": flat["embed"],
            "blocks": {"layer_0": {
                "attn": {n: flat["attn." + n]
                         for n in ("norm", "wq", "wk", "wv", "wo")},
                "mlp": {n: flat["mlp." + n]
                        for n in ("norm", "w_gate", "w_in", "w_out")},
            }},
            "final_norm": flat["final_norm"],
        }
        if "lm_head" in flat:
            p["lm_head"] = flat["lm_head"]
        return p

    return jax.block_until_ready(jax.jit(init)(key_of(seed)))


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
