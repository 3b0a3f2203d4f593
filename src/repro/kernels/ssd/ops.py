"""Jit'd public wrapper for the SSD scan kernel (interpreted on CPU,
Mosaic on TPU, an error elsewhere)."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import default_interpret
from repro.kernels.ssd.kernel import ssd_scan as _kernel


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    *,
    chunk: int = 256,
    interpret: Optional[bool] = None,
):
    if interpret is None:
        interpret = default_interpret()
    return _kernel(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
