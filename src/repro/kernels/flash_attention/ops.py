"""Jit'd public wrapper for the flash-attention kernel.

Interprets the kernel on the CPU backend so tests validate its body
there; on TPU the same call lowers to Mosaic; any other backend raises.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention as _kernel


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    return _kernel(
        q, k, v,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
