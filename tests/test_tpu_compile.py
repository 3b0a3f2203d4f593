"""The served path's steps and kernels compile for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what the chip would refuse
(Mosaic tiling, VMEM limits, programs that do not fit HBM).  Widths are
phi4-mini-3.8b's published ones; depth is cut to 2 layers to keep each
compile to a few seconds.  Nothing runs, so these say nothing about
results or times.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and pytest-xdist
workers all import every test file.  These tests stay in this one file
so that one worker loads the library for all of them.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.configs.registry import REGISTRY
from repro.distributed import sharding as SH
from repro.distributed.meshslice import make_slice_mesh
from repro.models import model as M

HBM_BYTES = 16e9  # one v5e chip
SLOTS, PAGE, POOL_PAGES, MAX_LEN = 8, 16, 1024, 2048
PMAX = MAX_LEN // PAGE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    enabled = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            try:
                t = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:  # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described: {e}")
            yield t
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(REGISTRY["phi4-mini-3.8b"], n_layers=2)


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _paged_args(cfg, sharding, batch):
    params = _on(M.param_specs(cfg), sharding)
    cache = _on(
        jax.eval_shape(lambda: M.init_paged_cache(cfg, POOL_PAGES, PAGE)),
        sharding,
    )
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=sharding)
    return params, cache, i32


def test_decode_step_paged_compiles_at_published_widths(cfg, one_chip):
    params, cache, i32 = _paged_args(cfg, one_chip, SLOTS)
    compiled = jax.jit(
        partial(M.decode_step_paged_greedy, cfg=cfg),
        donate_argnames=("cache",),
    ).lower(
        params, tokens=i32((SLOTS,)), cache=cache, lengths=i32((SLOTS,)),
        block_tables=i32((SLOTS, PMAX)),
    ).compile()
    m = compiled.memory_analysis()
    # the pool is donated: the new pool aliases the old one
    assert m.alias_size_in_bytes > 0
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("S", [128, 1024])
def test_prefill_paged_compiles_at_published_widths(cfg, one_chip, S):
    params, cache, i32 = _paged_args(cfg, one_chip, 1)
    compiled = jax.jit(
        partial(M.prefill_paged_greedy, cfg=cfg),
        donate_argnames=("cache",),
    ).lower(
        params, tokens=i32((1, S)), lengths=i32((1,)), ctx_lens=i32((1,)),
        block_tables=i32((1, PMAX)), cache=cache,
    ).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_decode_step_paged_compiles_on_a_tp2_slice(cfg, topo):
    """The four-chip path's decode instance: weights by the Megatron
    rules and pages split over KV heads on a two-chip slice."""
    mesh = make_slice_mesh(list(topo.devices[2:4]))
    pol = SH.default_policy(mesh)
    shapes = M.param_specs(cfg)
    params = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shapes, SH.param_pspecs(cfg, shapes, mesh, pol),
    )
    raw = jax.eval_shape(lambda: M.init_paged_cache(cfg, POOL_PAGES, PAGE))
    cache = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        raw, SH.serving_cache_pspecs(raw, mesh, pol),
    )
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())
    i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32, sharding=rep)
    compiled = jax.jit(
        partial(M.decode_step_paged_greedy, cfg=cfg),
        donate_argnames=("cache",),
    ).lower(
        params, tokens=i32((SLOTS,)), cache=cache, lengths=i32((SLOTS,)),
        block_tables=i32((SLOTS, PMAX)),
    ).compile()
    assert "all-reduce" in compiled.as_text()
    # per chip: half the pool and (about) half the weights
    assert _device_bytes(compiled) < HBM_BYTES / 2


def test_paged_decode_kernel_compiles(one_chip):
    Hq, Hkv, Dh = 24, 8, 128  # phi4-mini's attention widths
    sd = partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pages = sd((POOL_PAGES + 1, PAGE, Hkv, Dh), jnp.bfloat16)
    from repro.kernels.decode_attention.ops import paged_decode_attention

    compiled = jax.jit(
        partial(paged_decode_attention, interpret=False)
    ).lower(
        sd((SLOTS, Hq, Dh), jnp.bfloat16), pages, pages,
        sd((SLOTS, PMAX), jnp.int32), sd((SLOTS,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not interpret
    assert _device_bytes(compiled) < HBM_BYTES
