"""Plain float32 reference of a served model, and the served-token gap.

The forward pass belongs to the configuration's architecture module
(``archs/<architecture>.py``, ``gap_fn``): the published equations of
the configuration file in ``jax.numpy`` and float32 at ``highest``
matmul precision, importing nothing of the program and reading the
weights the benchmark made.  What is here is free of architecture: the
padding of a sequence to a length bucket and the reading of its gaps.

For a served request the sequence is its prompt followed by the tokens
it was served, the last one left out.  At the position before each
served token the reference's logits give the **gap**: how far the served
token's logit lies below the reference's best.  Greedy decoding that is
right up to rounding serves tokens with a gap near 0 (a flip between two
near-equal logits); a wrong token lies a logit spread below the best.
"""
from __future__ import annotations

import numpy as np

from model import arch

BUCKETS = (512, 1024, 2048)
CHUNK = 256  # positions per block of logits


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens is longer than {BUCKETS[-1]}")


def served_gaps(c: dict, weights, prompt, served, alt=None):
    """Gaps of the ``served`` tokens (and of ``alt``, tokens another
    computation would put first at the same positions), float64 arrays
    of ``len(served)``."""
    import jax.numpy as jnp

    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    alt = served if alt is None else np.asarray(alt, np.int32)
    P, n = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]])
    T = bucket(len(seq))
    toks = np.zeros(T, np.int32)
    toks[: len(seq)] = seq
    sv = np.zeros(T, np.int32)
    al = np.zeros(T, np.int32)
    sv[P - 1: P - 1 + n] = served
    al[P - 1: P - 1 + n] = alt
    g_s, g_a = arch(c).gap_fn(c, T, CHUNK)(weights, jnp.asarray(toks),
                                           jnp.asarray(sv), jnp.asarray(al))
    sl = slice(P - 1, P - 1 + n)
    return (np.asarray(g_s, np.float64)[sl], np.asarray(g_a, np.float64)[sl])
