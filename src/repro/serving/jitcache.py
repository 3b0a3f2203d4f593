"""Process-wide shared jit entry points: donation + compile telemetry.

Every :class:`~repro.serving.realengine.RealBackend` used to build its
own ``jax.jit(partial(fn, cfg=cfg))`` wrappers — each instance owned a
private compile cache, so a 2-decode cluster traced and compiled every
entry point twice, and a second cluster over the same config recompiled
everything from scratch.  This module keys the jitted callable on
``(fn, cfg, statics, donated argnames, mesh fingerprint)`` —
:class:`ModelConfig` is a frozen, hashable dataclass, so two backends
with the same config resolve to the *same* callable and share its XLA
executable cache.

**Mesh identity is part of the key.**  Two backends with the same
``ModelConfig`` but different mesh slices (or different sharding
policies) must NOT share a callable: the jitted computation bakes in
the device assignment and the sharding constraints picked up at trace
time (the MoE expert-parallel constraint reads a ContextVar — a retrace
is never triggered by a context change, only by a cache miss).  The
fingerprint covers axis names, axis sizes, the concrete device ids of
the slice, and the sharding policy, so a collision is impossible by
construction; ``mesh=None`` (the single-device legacy path) keys
exactly as before.

It also centralizes the two serving-wide jit policies:

* **donation** — decode/draft/verify steps donate their ``cache``
  argument so ring/paged KV buffers update in place on accelerators
  (on CPU donation is a documented no-op, so tests stay bit-exact);
* **compile counting** — :func:`compile_count` sums the executable-cache
  sizes of every shared entry point; the cluster snapshots it around a
  run to report ``RunMetrics.recompiles``, and the perf-invariant tests
  pin the steady-state value at zero.

It also owns the persistent compilation cache
(:func:`enable_compile_cache`), which entry points turn on and tests
leave off.

``jax`` is imported lazily: a pure-:class:`SimBackend` process that only
ever *reads* the counter (every ``PDCluster.run``) never pays the jax
import.
"""
from __future__ import annotations

import os
from functools import partial
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

# the checkout holding this package (src/repro/serving/jitcache.py)
_CHECKOUT = Path(__file__).resolve().parents[3]

_CACHE: Dict[tuple, Callable] = {}  # key -> raw jax.jit object
# key -> the callable handed out (the jit itself, or its mesh-entering
# wrapper).  Kept separate so compile_count() only ever sees raw jits
# while identity stays stable: same key -> same returned object.
_HANDED: Dict[tuple, Callable] = {}


def mesh_fingerprint(mesh) -> Optional[tuple]:
    """Hashable identity of a mesh slice: axis names, axis sizes, and
    the concrete device ids.  Two slices over the same devices with the
    same axes are interchangeable (their computations compile to the
    same device assignment); anything else must not share executables.
    ``None`` stays ``None`` — the meshless key is its own family."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def shared_jit(fn: Callable, cfg, *, donate: Tuple[str, ...] = (),
               mesh=None, policy=None, **statics) -> Callable:
    """The process-wide jitted entry point for ``fn`` closed over
    ``cfg`` (and any keyword ``statics``), donating ``donate`` argnames.
    Idempotent: same key -> same callable -> shared compile cache.

    With ``mesh``, the returned callable enters
    :func:`repro.distributed.context.mesh_context` around every call so
    sharding constraints (MoE expert parallelism, SSD head sharding)
    resolve against the instance's slice at trace time, and the cache
    key grows the mesh fingerprint + ``policy`` (a hashable
    :class:`~repro.distributed.sharding.ShardingPolicy`) so distinct
    slices/layouts never collide on one executable."""
    key = (fn, cfg, tuple(sorted(statics.items())), tuple(donate),
           mesh_fingerprint(mesh), policy)
    handed = _HANDED.get(key)
    if handed is not None:
        return handed
    import jax

    j = jax.jit(
        partial(fn, cfg=cfg, **statics),
        donate_argnames=tuple(donate) or None,
    )
    _CACHE[key] = j
    if mesh is None:
        handed = j
    else:
        from repro.distributed.context import mesh_context

        def handed(*args, _jit=j, _mesh=mesh, **kwargs):
            with mesh_context(_mesh):
                return _jit(*args, **kwargs)

        handed._shared_jit = j  # telemetry/tests reach the raw jit
    _HANDED[key] = handed
    return handed


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives in the checkout's
    ``.jax_cache/`` (gitignored).  The directory must stay put between
    runs for entries to be found again, so it is never derived from a
    temporary name, a process id or the time.  Called by the program's
    entry points (``chip_smoke.py``, ``repro.launch.serve``,
    ``benchmarks/run.py``), never by the tests."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def compile_count() -> int:
    """Total XLA executables compiled across every shared entry point
    (a re-trace for a new input shape raises this by one)."""
    return sum(j._cache_size() for j in _CACHE.values())


def entry_count() -> int:
    """Number of distinct shared entry points (for telemetry/tests)."""
    return len(_CACHE)


def clear() -> None:
    """Drop every shared entry point and its compiled executables
    (tests use this to measure cold-start compile behavior)."""
    for j in _CACHE.values():
        j.clear_cache()
    _CACHE.clear()
    _HANDED.clear()
