"""Jit'd public wrappers for the flash-decode kernels.

Two conveniences over the raw kernels in ``kernel.py``:

* **auto-interpret** — the CPU backend runs the Pallas interpreter
  (pure-JAX semantics, bit-exact math), so the same call sites work on
  CPU tests and TPU serving; any other backend raises
  (:func:`repro.kernels.default_interpret`);
* **mesh sharding** — pass ``mesh=`` (an instance's slice, axes
  ("data", "model")) and the kernel runs under ``shard_map`` with the
  **head dimension partitioned over the model axis**: attention
  decomposes per KV head, so each shard runs the unmodified kernel on
  its ``Hkv / tp`` heads with zero cross-shard communication.  The
  scalar-prefetch block tables and lengths are replicated — a page id
  names the same page on every shard (each shard stores that page's
  slice of the heads), which keeps the host-side ``KVPool`` arithmetic
  shard-agnostic.  When heads don't divide the axis (or the axis is
  width 1) the wrappers fall back to the unsharded call — correct,
  just replicated.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels import default_interpret
from repro.kernels.decode_attention.kernel import decode_attention as _kernel
from repro.kernels.decode_attention.kernel import (
    paged_decode_attention as _paged_kernel,
)
from repro.kernels.decode_attention.kernel import (
    paged_verify_attention as _verify_kernel,
)


def _model_axis_size(mesh, axis: str) -> int:
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def _heads_shardable(mesh, axis: str, hq: int, hkv: int) -> bool:
    n = _model_axis_size(mesh, axis)
    return n > 1 and hq % n == 0 and hkv % n == 0


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    slot_pos: jax.Array,
    q_pos: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_c: int = 512,
    interpret: Optional[bool] = None,
    mesh=None,
    axis: str = "model",
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    call = functools.partial(
        _kernel, window=window, softcap=softcap, block_c=block_c,
        interpret=interpret,
    )
    if _heads_shardable(mesh, axis, q.shape[1], k_cache.shape[2]):
        call = jax.shard_map(
            call, mesh=mesh,
            in_specs=(
                P(None, axis, None),        # q (B, Hq, Dh)
                P(None, None, axis, None),  # k_cache (B, C, Hkv, Dh)
                P(None, None, axis, None),  # v_cache
                P(None, None),              # slot_pos (B, C) replicated
                P(None),                    # q_pos (B,) replicated
            ),
            out_specs=P(None, axis, None),
            check_vma=False,
        )
    return call(q, k_cache, v_cache, slot_pos, q_pos)


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    mesh=None,
    axis: str = "model",
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    call = functools.partial(
        _paged_kernel, window=window, softcap=softcap, interpret=interpret,
    )
    if _heads_shardable(mesh, axis, q.shape[1], k_pages.shape[2]):
        call = jax.shard_map(
            call, mesh=mesh,
            in_specs=(
                P(None, axis, None),        # q (B, Hq, Dh)
                P(None, None, axis, None),  # k_pages (P, ps, Hkv, Dh)
                P(None, None, axis, None),  # v_pages
                P(None, None),              # block_tables (B, Pmax) repl.
                P(None),                    # lengths (B,) replicated
            ),
            out_specs=P(None, axis, None),
            check_vma=False,
        )
    return call(q, k_pages, v_pages, block_tables, lengths)


def paged_verify_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: Optional[bool] = None,
    mesh=None,
    axis: str = "model",
) -> jax.Array:
    if interpret is None:
        interpret = default_interpret()
    call = functools.partial(
        _verify_kernel, window=window, softcap=softcap, interpret=interpret,
    )
    if _heads_shardable(mesh, axis, q.shape[2], k_pages.shape[2]):
        call = jax.shard_map(
            call, mesh=mesh,
            in_specs=(
                P(None, None, axis, None),  # q (B, T, Hq, Dh)
                P(None, None, axis, None),  # k_pages (P, ps, Hkv, Dh)
                P(None, None, axis, None),  # v_pages
                P(None, None),              # block_tables (B, Pmax) repl.
                P(None),                    # lengths (B,) replicated
            ),
            out_specs=P(None, None, axis, None),
            check_vma=False,
        )
    return call(q, k_pages, v_pages, block_tables, lengths)
