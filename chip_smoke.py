"""Bring-up run of the served path on a TPU.

    python chip_smoke.py              # one chip: parity + serve phases
    python chip_smoke.py --chips 4    # four chips: tp=2 P/D slices vs one chip

Serves phi4-mini-3.8b at its published widths, in the config's bf16,
with random weights from ``--seed``, through the normal path:
``PDCluster`` -> prefill/decode engines -> ``RealBackend(paged=True)``
-> the jitted paged prefill and decode steps.

* parity: seeded prompts are prefilled on a prefill backend, handed to
  a decode backend (the P->D page handoff), and several tokens are
  teacher-forced through the decode pool; the decode logits are compared
  with ``M.forward`` over the whole sequence at the same widths, within
  a multiple of the bf16 floor (``M.forward`` in bf16 against float32
  activations).
* serve: about 16 seeded Poisson requests through a 1P+1D cluster; each
  must finish with exactly its token count, and an identical second run
  must compile nothing (``recompiles == 0``).
* ``--chips 4``: the one-chip decode logits, then the same probe and the
  same requests with each instance a tp=2 slice on its own chip pair,
  compared with the one-chip logits.

Every line but the last is progress.  The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
The script exits non-zero, with no such line, when JAX finds no TPU,
when ``src/repro`` is not beside it, or when any check fails (a failed
check is logged as ``[FAIL]`` and the later phases still run).  Every
phase runs in this one process: a chip belongs to one process at a time.

Latency and energy that the cluster reports come from its hardware
model's virtual clock, so they are printed as *modelled*; only the wall
seconds printed per phase are measured.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

MODEL = "phi4-mini-3.8b"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Serving shapes of the run.  None of them is a model width."""

    slots: int = 8  # decode slots == decode_max_running
    max_len: int = 2048  # per-request context (prompt + output)
    page_size: int = 16
    # pages per instance.  Both instances share the weights, and the
    # decode pool is held three times at its peak (the pool, the decode
    # scan's pool-sized temporaries, the insert's eager copy), so the
    # default (2*slots+8)*max_pages would not fit one 16 GB chip.
    pool_pages: int = 512
    n_requests: int = 16
    # prompt lengths stay within two prefill buckets (128, 256): each
    # bucket compiles another whole-depth prefill
    prompt_lo: int = 65
    prompt_hi: int = 256
    decode_lo: int = 8
    decode_hi: int = 48
    # parity prompts: two sit on page edges (128 % 16 == 0, 161 % 16 == 1)
    parity_lens: tuple = (97, 128, 161, 240)
    parity_steps: int = 8


# Parity tolerances.  The served decode logits and M.forward's are two
# bf16 computations of the same function that round the residual stream
# at different points (paged gather against a dense causal mask, batch-8
# decode against whole-sequence matmuls, other fusions).  How far bf16
# rounding alone moves these logits is measured, not guessed: the
# *floor* is M.forward in bf16 against M.forward of the same bf16
# weights with float32 activations.  If the served path is as accurate
# as the bf16 forward, each is within the floor of the float32 logits,
# so by the triangle inequality they are within twice the floor of each
# other.  A misplaced position or page gives errors of order 1 (the CPU
# test checks the metric sees one), far above this bound.  tp=2 against
# one chip is held to the same bound on the floor of the same prompts:
# the sharded matmuls add per-shard partial sums in another order, one
# more bf16 rounding difference of the same kind.
FLOOR_FACTOR = 2.0
# The floor itself must stay small: bf16 keeps 8 significant bits
# (unit roundoff 2**-9 ~ 2e-3); a relative difference of that size in
# each of 32 layers, adding like a random walk and amplified by the
# random weights, stays within a few 1e-2.  A floor of 0.1 would mean
# the forward itself is numerically broken, not the served path.
FLOOR_MAX = 0.1


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _import_program():
    """The program beside this script, never another copy."""
    if not (SRC / "repro" / "serving").is_dir():
        raise SystemExit(
            f"chip_smoke.py: no src/repro beside {ROOT}; run it from a "
            "checkout of the repository"
        )
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


class CompileLog:
    """Counts XLA backend compiles and their seconds (every compile in
    the process: jitted steps and eager ops alike)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.n += 1
            self.s += duration


# ---------------------------------------------------------------------------
# Weights and memory budget
# ---------------------------------------------------------------------------


def init_weights(cfg, seed: int, sharding=None):
    """``init_params`` from ``seed``, made on the device in the config's
    dtype (jitted, so no full-size float32 copy is ever materialised).
    ``sharding``: an out_shardings tree to make them born sharded."""
    import jax

    from repro.models import model as M

    fn = jax.jit(M.init_params, static_argnums=0, out_shardings=sharding)
    return jax.block_until_ready(fn(cfg, jax.random.key(seed)))


def tree_bytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def pool_bytes(cfg, sz: Sizes) -> int:
    """Bytes of one instance's paged K/V pool, from its shapes."""
    import jax

    from repro.models import model as M

    return tree_bytes(jax.eval_shape(
        lambda: M.init_paged_cache(cfg, sz.pool_pages, sz.page_size)
    ))


def memory_budget(cfg, sz: Sizes, weight_bytes: int) -> dict:
    """Device bytes the serve phase may hold at once, from shapes: the
    weights (shared by both instances), each instance's pool, the decode
    step's temporaries (the layer scan carries the pool as xs and ys, so
    they equal the pool in spite of donation), the eager copy of the
    decode pool that ``insert``'s ``.at[:, dst].set`` makes, and the
    prefill step's pool-sized temporaries plus its S x (C+S) f32 score
    and probability blocks and gathered pages at the largest bucket."""
    pool = pool_bytes(cfg, sz)
    S = sz.prompt_hi
    C = sz.max_len
    prefill_tmp = pool + 2 * S * (C + S) * cfg.n_heads * 4 \
        + 2 * C * cfg.n_kv_heads * cfg.head_dim * 2
    parts = {
        "weights": weight_bytes,
        "prefill_pool": pool,
        "decode_pool": pool,
        "decode_step_tmp": pool,
        "insert_copy": pool,
        "prefill_step_tmp": prefill_tmp,
    }
    parts["total"] = sum(parts.values())
    return parts


def _free_pools(*backends) -> None:
    """Release backends' pools now (weights stay: they are shared)."""
    import jax

    for b in backends:
        for x in jax.tree.leaves(b.kvcache):
            x.delete()
    gc.collect()


# ---------------------------------------------------------------------------
# Parity: paged prefill -> P->D handoff -> decode logits vs M.forward
# ---------------------------------------------------------------------------


def parity_inputs(cfg, sz: Sizes, seed: int):
    """Seeded prompts and the tokens teacher-forced after them."""
    import numpy as np

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in sz.parity_lens]
    forced = rng.integers(
        0, cfg.vocab_size, (len(prompts), sz.parity_steps)
    ).astype(np.int32)
    return prompts, forced


def decode_probe(cfg, params, sz: Sizes, prompts, forced, *, tp: int = 0,
                 devices=None, placement=None):
    """Decode logits after the P->D handoff, on the served backends.

    A prefill and a decode ``RealBackend`` come from
    ``make_real_backend_factory`` exactly as the cluster makes them
    (``tp > 0``: each a slice of ``devices``).  Each prompt is prefilled
    on the first, inserted into the second (page-stack handoff), and
    ``forced[i, j]`` is fed at position ``len(prompt) + j`` through the
    paged decode step.  Returns float32 logits ``(n, steps, V)``;
    ``placement(prefill, decode)`` runs on the live backends first."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.hwmodel import HardwareModel
    from repro.core.power import TPU_V5E
    from repro.models import model as M
    from repro.serving import jitcache
    from repro.serving.realengine import make_real_backend_factory
    from repro.serving.request import Request

    check(len(prompts) <= sz.slots, "more parity prompts than decode slots")
    hw = HardwareModel(cfg, TPU_V5E)
    factory = make_real_backend_factory(
        cfg, params, slots=sz.slots, max_len=sz.max_len, paged=True,
        page_size=sz.page_size, pool_pages=sz.pool_pages, tp=tp,
        devices=devices,
    )
    pre = factory("prefill", 0, hw, 0)
    dec = factory("decode", 0, hw, 0)
    mesh_kw = {} if dec.mesh is None else dict(
        mesh=dec.mesh, policy=dec.sharding_policy
    )
    step = jitcache.shared_jit(
        M.decode_step_paged, cfg, donate=("cache",), **mesh_kw
    )
    reqs = []
    for i, toks in enumerate(prompts):
        r = Request(rid=i, arrival_s=0.0, prompt_len=len(toks),
                    decode_len=sz.parity_steps,
                    prompt_tokens=[int(t) for t in toks])
        pre.prefill_iter([r], r.prompt_len, TPU_V5E.f_max)
        dec.insert(r)
        reqs.append(r)
    if placement is not None:
        placement(pre, dec)
    out = np.zeros((len(reqs), sz.parity_steps, cfg.vocab_size), np.float32)
    for j in range(sz.parity_steps):
        toks = np.zeros(sz.slots, np.int32)
        for r in reqs:
            s = dec.slot_of[r.rid]
            dec.reserve_pages(r, int(dec.pos[s]) + 1)
            toks[s] = forced[r.rid, j]
        logits, dec.kvcache = step(
            dec.params, tokens=jnp.asarray(toks), cache=dec.kvcache,
            lengths=jnp.asarray(dec.pos.copy()),
            block_tables=jnp.asarray(dec.block_tables.copy()),
        )
        logits = np.asarray(logits)
        for r in reqs:
            s = dec.slot_of[r.rid]
            out[r.rid, j] = logits[s]
            dec.pos[s] += 1
    for r in reqs:
        dec.release(r)
    pre.pool.assert_empty()
    dec.pool.assert_empty()
    _free_pools(pre, dec)
    return out


def reference_logits(cfg, params, prompts, forced, *, f32: bool = False):
    """``M.forward`` over prompt + forced tokens, one padded batch; the
    logits at the forced tokens' positions, ``(n, steps, V)`` float32.

    ``f32``: the same bf16 weights with float32 activations (each
    layer's weights are widened inside the layer scan, which is exact;
    no float32 copy of the whole model is made)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    rcfg = dataclasses.replace(cfg, dtype="float32", embed_inputs=False) \
        if f32 else cfg
    n, steps = forced.shape
    total = max(len(p) for p in prompts) + steps
    S = -(-total // 16) * 16
    toks = np.zeros((n, S), np.int32)
    valid = np.zeros((n, S), bool)
    idx = np.zeros((n, steps), np.int32)
    for i, p in enumerate(prompts):
        L = len(p)
        toks[i, :L] = p
        toks[i, L: L + steps] = forced[i]
        valid[i, : L + steps] = True
        idx[i] = L + np.arange(steps)

    def fn(params, tokens, valid, idx):
        if f32:
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
            h, _ = M.forward(params, rcfg, inputs_embeds=x, valid=valid)
        else:
            h, _ = M.forward(params, rcfg, tokens=tokens, valid=valid)
        h = jnp.take_along_axis(h, idx[..., None], axis=1)
        return M.lm_logits(params, rcfg, h)

    return np.asarray(jax.jit(fn)(params, toks, valid, idx), np.float32)


def logit_errors(got, ref) -> dict:
    """Worst per-position relative L2 and max-abs errors (the max-abs
    error over the position's largest reference logit), overall and per
    decode step (``got``/``ref``: ``(n, steps, V)``)."""
    import numpy as np

    check(bool(np.isfinite(got).all() and np.isfinite(ref).all()),
          "non-finite logits")
    d = got.astype(np.float64) - ref.astype(np.float64)
    r = ref.astype(np.float64)
    rel = np.linalg.norm(d, axis=-1) / np.linalg.norm(r, axis=-1)
    mx = np.abs(d).max(axis=-1) / np.abs(r).max(axis=-1)
    return {"rel_l2": float(rel.max()), "max_abs": float(mx.max()),
            "rel_l2_by_step": [float(x) for x in rel.max(axis=0)]}


def check_within(err: dict, floor: dict, factor: float, what: str) -> None:
    """``err`` within ``factor`` times the bf16 ``floor``, per metric."""
    for k in ("rel_l2", "max_abs"):
        tol = factor * floor[k]
        check(err[k] <= tol, f"{what} {k} {err[k]:.3e} > {factor} x floor "
                             f"{floor[k]:.3e} = {tol:.3e}")


def bf16_floor(cfg, params, prompts, forced) -> tuple:
    """(M.forward bf16 logits, their errors against float32
    activations): how far bf16 rounding alone moves these logits."""
    t0 = time.perf_counter()
    ref = reference_logits(cfg, params, prompts, forced)
    ref32 = reference_logits(cfg, params, prompts, forced, f32=True)
    floor = logit_errors(ref, ref32)
    log("parity:floor", vs="M.forward float32 activations",
        rel_l2=f"{floor['rel_l2']:.3e}", max_abs=f"{floor['max_abs']:.3e}",
        floor_max=FLOOR_MAX, wall_s=f"{time.perf_counter() - t0:.2f}")
    for k in ("rel_l2", "max_abs"):
        check(floor[k] <= FLOOR_MAX,
              f"bf16 forward {k} {floor[k]:.3e} > {FLOOR_MAX} off float32")
    return ref, ref32, floor


def parity_phase(cfg, params, sz: Sizes = Sizes(), seed: int = 0) -> dict:
    """Paged prefill + handoff + decode logits vs the full forward."""
    prompts, forced = parity_inputs(cfg, sz, seed)
    t0 = time.perf_counter()
    got = decode_probe(cfg, params, sz, prompts, forced)
    t1 = time.perf_counter()
    ref, ref32, floor = bf16_floor(cfg, params, prompts, forced)
    err = logit_errors(got, ref)
    err32 = logit_errors(got, ref32)
    log("parity", prompts=list(sz.parity_lens), steps=sz.parity_steps,
        vs="M.forward bf16", rel_l2=f"{err['rel_l2']:.3e}",
        max_abs=f"{err['max_abs']:.3e}", factor=FLOOR_FACTOR,
        rel_l2_tol=f"{FLOOR_FACTOR * floor['rel_l2']:.3e}",
        max_abs_tol=f"{FLOOR_FACTOR * floor['max_abs']:.3e}",
        rel_l2_by_step="/".join(f"{x:.2e}" for x in err["rel_l2_by_step"]),
        probe_wall_s=f"{t1 - t0:.2f}")
    log("parity", vs="M.forward float32 activations",
        rel_l2=f"{err32['rel_l2']:.3e}", max_abs=f"{err32['max_abs']:.3e}")
    check_within(err, floor, FLOOR_FACTOR, "parity")
    return {**err, "floor": floor, "logits": got}


# ---------------------------------------------------------------------------
# Serve: PDCluster -> engines -> RealBackend(paged=True)
# ---------------------------------------------------------------------------


def make_requests(cfg, sz: Sizes, seed: int):
    """The first ``n_requests`` of a seeded Poisson trace, with ids."""
    from repro.serving import poisson_workload
    from repro.serving.workload import DatasetDist, LengthDist, attach_tokens

    dist = DatasetDist(
        "chip-smoke",
        prefill=LengthDist(150.0, 60.0, lo=sz.prompt_lo, hi=sz.prompt_hi),
        decode=LengthDist(24.0, 12.0, lo=sz.decode_lo, hi=sz.decode_hi),
    )
    reqs = poisson_workload(dist, 4.0, 60.0, seed=seed)[: sz.n_requests]
    check(len(reqs) == sz.n_requests, "trace shorter than n_requests")
    return attach_tokens(reqs, cfg.vocab_size, seed=seed + 1)


def build_cluster_predictor(cfg, sz: Sizes, seed: int = 0):
    """EcoPred fit for the cluster (shared by both runs of a phase)."""
    from repro.core.power import TPU_V5E
    from repro.serving.cluster import build_predictor

    return build_predictor(
        cfg, TPU_V5E, TPU_V5E.freq_levels_2,
        kv_cap=sz.pool_pages * sz.page_size, max_running=sz.slots,
        seed=seed,
    )


def serve_once(cfg, params, sz: Sizes, seed: int, predictor, *, tp: int = 0,
               devices=None, placement=None):
    """One cluster run over fresh requests; returns (metrics, reqs,
    wall seconds).  Every request must finish with exactly its tokens."""
    import jax

    from repro.core.power import TPU_V5E
    from repro.serving import ClusterConfig, PDCluster
    from repro.serving.realengine import make_real_backend_factory

    reqs = make_requests(cfg, sz, seed)
    ccfg = ClusterConfig(
        model=cfg, chip=TPU_V5E, n_prefill=1, n_decode=1, tp=max(tp, 1),
        policy="voltana", paged=True, kv_page_size=sz.page_size,
        decode_max_running=sz.slots,
        kv_capacity_tokens=sz.pool_pages * sz.page_size,
        predictor=predictor, online_adapt=False, seed=seed,
        backend_factory=make_real_backend_factory(
            cfg, params, slots=sz.slots, max_len=sz.max_len, paged=True,
            page_size=sz.page_size, pool_pages=sz.pool_pages, tp=tp,
            devices=devices,
        ),
    )
    cluster = PDCluster(ccfg)
    backends = [e.backend for e in cluster.prefill + cluster.decode]
    if placement is not None:
        placement(*backends)
    t0 = time.perf_counter()
    m = cluster.run(reqs)
    jax.block_until_ready([b.kvcache for b in backends])
    wall = time.perf_counter() - t0
    for r in reqs:
        # total output = decode_len + 1 (the first token is prefill's)
        check(r.finished and len(r.output_tokens) == r.decode_len + 1,
              f"request {r.rid}: {len(r.output_tokens)} tokens, expected "
              f"{r.decode_len + 1}")
    for b in backends:
        b.pool.assert_empty()
    _free_pools(*backends)
    del cluster, backends
    gc.collect()
    return m, reqs, wall


def serve_phase(cfg, params, sz: Sizes = Sizes(), seed: int = 0, *,
                predictor=None, tp: int = 0, devices=None,
                placement=None) -> dict:
    """Cold run, then an identical warm run that must compile nothing."""
    predictor = predictor or build_cluster_predictor(cfg, sz, seed)
    runs = []
    for label in ("cold", "warm"):
        m, reqs, wall = serve_once(cfg, params, sz, seed, predictor, tp=tp,
                                   devices=devices, placement=placement)
        s = m.summary()
        tokens = sum(len(r.output_tokens) for r in reqs)
        log(f"serve:{label}", requests=len(reqs), tokens=tokens,
            finished_frac=s["finished_frac"], recompiles=m.recompiles,
            wall_s=f"{wall:.2f}",
            modelled_ttft_p50_ms=s["ttft_p50_ms"],
            modelled_itl_p50_ms=s["itl_p50_ms"],
            modelled_energy_j=s["energy_j"])
        runs.append((m, reqs, wall, tokens))
    (_, reqs0, _, tokens), (m1, reqs1, wall1, _) = runs
    check(m1.recompiles == 0,
          f"warm rerun compiled {m1.recompiles} executables")
    same = sum(a.output_tokens == b.output_tokens
               for a, b in zip(reqs0, reqs1))
    log("serve", warm_identical_streams=f"{same}/{len(reqs1)}")
    return {"requests": len(reqs1), "tokens": tokens,
            "warm_recompiles": m1.recompiles, "warm_wall_s": wall1,
            "outputs": [r.output_tokens for r in reqs1]}


# ---------------------------------------------------------------------------
# Four chips: tp=2 prefill and decode slices vs the one-chip path
# ---------------------------------------------------------------------------


def _devices_of(tree) -> set:
    import jax

    return {d for x in jax.tree.leaves(tree) for d in x.devices()}


def slice_placement(cfg, groups):
    """A placement check: backend ``i`` holds its weights and pages on
    exactly ``groups[i]``, its pages split over KV heads, and no two
    backends share a device."""

    def check_placement(*backends):
        import jax

        seen = set()
        for b, want in zip(backends, groups):
            want = set(want)
            check(_devices_of(b.params) == want,
                  f"weights on {_devices_of(b.params)}, expected {want}")
            check(_devices_of(b.kvcache) == want,
                  f"pages on {_devices_of(b.kvcache)}, expected {want}")
            leaf = jax.tree.leaves(b.kvcache)[0]
            shard = leaf.addressable_shards[0].data.shape
            check(shard[-2] == cfg.n_kv_heads // len(want),
                  f"pool shard {shard} not split over KV heads")
            check(not (seen & want), f"slices share devices {seen & want}")
            seen |= want

    return check_placement


def four_chip_phase(cfg, sz: Sizes = Sizes(), seed: int = 0) -> dict:
    """The tp=2 P->D path and what it is compared with."""
    import jax

    from repro.distributed import sharding as SH
    from repro.distributed.meshslice import make_slice_mesh
    from repro.models import model as M

    devs = jax.devices()[:4]
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    prompts, forced = parity_inputs(cfg, sz, seed)

    # the one-chip path: same weights, meshless backends on device 0
    t0 = time.perf_counter()
    params = init_weights(cfg, seed)
    one = decode_probe(cfg, params, sz, prompts, forced)
    _, _, floor = bf16_floor(cfg, params, prompts, forced)
    del params
    gc.collect()
    log("4chip:one-chip", wall_s=f"{time.perf_counter() - t0:.2f}")

    # tp=2: weights born sharded on the prefill slice (chips 0,1); the
    # decode slice (chips 2,3) receives its copy when its backend is made
    groups = (devs[:2], devs[2:4])
    mesh_p = make_slice_mesh(list(groups[0]))
    pspecs = SH.param_pspecs(cfg, M.param_specs(cfg), mesh_p)
    t0 = time.perf_counter()
    params = init_weights(cfg, seed, sharding=SH.named(pspecs, mesh_p))
    on_chip0 = sum(
        sh.data.nbytes for x in jax.tree.leaves(params)
        for sh in x.addressable_shards if sh.device == devs[0]
    )
    log("4chip:weights", bytes_on_chip0=on_chip0,
        wall_s=f"{time.perf_counter() - t0:.2f}")
    placement = slice_placement(cfg, groups)
    t0 = time.perf_counter()
    sharded = decode_probe(cfg, params, sz, prompts, forced, tp=2,
                           devices=devs, placement=placement)
    err = logit_errors(sharded, one)
    log("4chip:handoff-parity", vs="one-chip decode logits",
        rel_l2=f"{err['rel_l2']:.3e}", max_abs=f"{err['max_abs']:.3e}",
        factor=FLOOR_FACTOR,
        rel_l2_tol=f"{FLOOR_FACTOR * floor['rel_l2']:.3e}",
        max_abs_tol=f"{FLOOR_FACTOR * floor['max_abs']:.3e}",
        rel_l2_by_step="/".join(f"{x:.2e}" for x in err["rel_l2_by_step"]),
        wall_s=f"{time.perf_counter() - t0:.2f}")
    served = serve_phase(cfg, params, sz, seed, tp=2, devices=devs,
                         placement=placement)
    # checked after serving, so that one run shows both phases
    check_within(err, floor, FLOOR_FACTOR, "tp=2")
    return {**err, **served}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp=2 P->D slice path and the "
                         "one-chip path it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_program()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    from repro.configs.registry import REGISTRY
    from repro.serving import jitcache

    cache_dir = jitcache.enable_compile_cache()
    compiles = CompileLog()
    cfg = REGISTRY[MODEL]
    sz = Sizes()
    log("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    t_start = time.perf_counter()
    failed = []

    def phase(name, fn, *a, **kw):
        """Run a phase; a failed check is reported and fails the run at
        the end, after the later phases had their say."""
        try:
            return fn(*a, **kw)
        except SmokeFailure as e:
            log("FAIL", phase=name, why=repr(str(e)))
            failed.append(name)

    if args.chips == 4:
        phase("4chip", four_chip_phase, cfg, sz, args.seed)
    else:
        t0 = time.perf_counter()
        params = init_weights(cfg, args.seed)
        w = tree_bytes(params)
        log("weights", model=cfg.name, dtype=cfg.dtype, layers=cfg.n_layers,
            d_model=cfg.d_model, vocab=cfg.vocab_size, bytes=w,
            wall_s=f"{time.perf_counter() - t0:.2f}")
        phase("parity", parity_phase, cfg, params, sz, args.seed)
        phase("serve", serve_phase, cfg, params, sz, args.seed)
        log("memory:budget", **memory_budget(cfg, sz, w))

    used = devs[:4] if args.chips == 4 else devs[:1]
    for d in used:
        mem = d.memory_stats() or {}
        peak = mem.get("peak_bytes_in_use")
        limit = mem.get("bytes_limit")
        log("memory", device=d.id, peak_bytes_in_use=peak, bytes_limit=limit)
        phase("memory", check, peak is not None and limit is not None
              and peak < limit,
              f"device {d.id}: peak_bytes_in_use {peak} not below {limit}")
    log("compile", count=compiles.n, seconds=f"{compiles.s:.2f}",
        shared_jit_executables=jitcache.compile_count())
    log("total", wall_s=f"{time.perf_counter() - t_start:.2f}")
    if failed:
        print(f"chip_smoke.py: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
