"""RealBackend — actual JAX model execution behind the serving control plane.

Drop-in replacement for :class:`~repro.serving.engine.SimBackend`: the
cluster's schedulers/controllers/routers are untouched; this backend
additionally runs real forwards of a (reduced) model, so examples and
integration tests exercise tokens end-to-end.

Two memory models, selected by ``paged``:

* **dense** (``paged=False``, the legacy default, bit-exact with the
  pre-paged backend): prefill runs B=1 with the prompt padded to a
  power-of-two bucket (clamped to ``max_len``) and stashes a
  per-request dense KV cache for migration; decode scatters requests
  into slots of a ``slots × max_len`` ring cache.
* **paged** (``paged=True``): KV lives in a
  :class:`~repro.serving.kvpool.KVPool` of fixed-size pages backed by
  one physical ``(pool_pages, page_size, …)`` array set
  (:func:`repro.models.model.init_paged_cache`).  Prefill writes
  straight into pool pages; radix prefix-cache hits hand the request
  the *same* pages (refcount > 1, zero recomputation — see
  :class:`~repro.serving.radixcache.PagedRadixCache`); decode grows a
  per-slot block table page by page; P→D migration copies whole pages;
  release/preemption returns pages to the pool.

The **virtual clock still advances by the hardware model's time** — CPU
wall time is meaningless for TPU SLO semantics — so latency/energy results
are identical between backends; only token content differs (real here).

**Async dispatch.**  Decode/spec iterations never block on device
results at dispatch time: the jitted step returns *token ids* (argmax is
fused into the graph — see the ``*_greedy`` entry points in
``repro.models.model``), the id array stays device-resident as the next
iteration's input, and host emission into ``Request.output_tokens`` is
deferred until the values are actually consumed (the next backend call,
a slot ``release``, or the cluster's end-of-run ``flush``).  Everything
the event loop does between two backend calls — finish-iteration
bookkeeping, EcoPred recording, EcoFreq's ladder scan, EcoRoute, heap
ops — overlaps with the in-flight device step.  Control decisions never
read token *content* (requests finish by count; speculative acceptance
is the engine's seeded realization), so deferral cannot reorder
anything: Sim==Real parity is structural.  ``pipeline_depth`` bounds
how many iterations may be in flight at once: dispatch only blocks once
``pipeline_depth`` deferred emissions are queued (and then only on the
oldest), while slot insert/release and flush drain everything — depth 1
is the classic one-iteration-deep pipeline, bit-exact with prior
releases, and every depth replays the same jitted shapes
(``recompiles == 0`` holds regardless of depth).

Jitted entry points come from :mod:`repro.serving.jitcache`: instances
with the same config share one compile cache, decode/draft/verify jits
donate their KV ``cache`` argument (in-place updates on accelerators;
documented no-op on CPU), and the cluster reads the module's compile
counter to report ``RunMetrics.recompiles``.

**Mesh-sharded instances.**  With ``mesh`` set (a
:class:`~repro.distributed.meshslice.MeshSlicer` slice — see
``make_real_backend_factory(tp=...)``), the instance is a real
TP/EP-sharded unit: params are laid out by
:func:`repro.distributed.sharding.param_pspecs` (Megatron TP; MoE
experts ride the "model" axis via the mesh context), the dense ring /
paged page pool shards its KV heads over "model"
(:func:`~repro.distributed.sharding.serving_cache_pspecs`), every jit
entry point is keyed on the mesh fingerprint + policy (no cross-slice
executable collisions), and the P→D handoff reshards the migrated page
stack onto the destination slice with an explicit per-shard
``device_put`` gather/scatter.  Page arithmetic (``KVPool`` /
``BlockTable``) stays host-side and shard-agnostic: a page id means the
same page on every shard, each shard simply holds that page's slice of
the KV heads.  A ``tp=1`` mesh is bit-exact with the meshless path.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.hwmodel import HardwareModel
from repro.distributed import sharding as SH
from repro.distributed.meshslice import MeshSlicer
from repro.models import model as M
from repro.serving import jitcache
from repro.serving.engine import SimBackend
from repro.serving.kvpool import BlockTable, KVPool, PageAllocError
from repro.serving.radixcache import PagedRadixCache
from repro.serving.request import Request


def _bucket(n: int, lo: int = 16, hi: Optional[int] = None) -> int:
    """Power-of-two padding bucket, clamped to the cache capacity: a
    70-token prompt with ``max_len=96`` pads to 96, not to an impossible
    128 (capacity itself is checked separately, on the *token* count)."""
    b = lo
    while b < n:
        b *= 2
    if hi is not None:
        b = min(b, hi)
    return b


def _snapshot(a: np.ndarray) -> jax.Array:
    """Device copy of host state the backend keeps mutating (slot
    positions, block tables).  ``jnp.asarray`` of a NumPy array may
    alias its memory or copy it after returning, while the step it
    feeds runs asynchronously; the host then advances ``pos`` and grows
    or clears table rows for the next iteration, and the in-flight step
    would read those.  A private copy that nothing mutates is safe."""
    return jnp.asarray(a.copy())


class RealBackend(SimBackend):
    """Executes real JAX forwards; inherits Sim timing/energy accounting."""

    def __init__(
        self,
        hw: HardwareModel,
        cfg: ModelConfig,
        params,
        *,
        slots: int = 8,
        max_len: int = 256,
        noise_sigma: float = 0.0,
        seed: int = 0,
        paged: bool = False,
        page_size: int = 16,
        pool_pages: Optional[int] = None,
        spec_k: int = 0,
        draft_cfg: Optional[ModelConfig] = None,
        draft_params=None,
        donate_kv: bool = True,
        mesh=None,
        sharding_policy=None,
        pipeline_depth: int = 1,
    ):
        super().__init__(hw, noise_sigma, seed)
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.paged = paged
        self.donate_kv = donate_kv
        # mesh slice: this instance's devices.  None = legacy
        # single-device path, byte-for-byte identical jit keys.
        self.mesh = mesh
        self.sharding_policy = (
            (sharding_policy or SH.default_policy(mesh))
            if mesh is not None else None
        )
        jit_kw = (
            dict(mesh=mesh, policy=self.sharding_policy)
            if mesh is not None else {}
        )
        self._handoff_shardings = None  # per-leaf reshard of migrations
        don = ("cache",) if donate_kv else ()
        # decode slot state (both memory models batch decode over slots).
        # The token chain is device-resident: the previous iteration's
        # greedy ids feed the next step without a host round trip.
        self.slot_of: Dict[int, int] = {}  # rid -> slot
        self.free = list(range(slots))[::-1]
        self._next_dev = jnp.zeros(slots, jnp.int32)
        self.pos = np.zeros(slots, np.int32)
        # deferred emissions from in-flight decode/spec steps: a bounded
        # ring of up to ``pipeline_depth`` iterations.  Dispatch only
        # blocks (drains the oldest entry) once the ring is full;
        # insert/release/flush drain everything.  depth=1 reproduces the
        # single-slot behavior exactly.
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}"
            )
        self.pipeline_depth = pipeline_depth
        self._ring: Deque[Tuple] = deque()
        # occupancy telemetry: mean ring depth observed at dispatch
        self.pipeline_depth_sum = 0
        self.pipeline_dispatches = 0

        if paged:
            assert max_len % page_size == 0, (max_len, page_size)
            self.page_size = page_size
            self.max_pages = max_len // page_size
            # worst case: every decode slot at max_len, plus in-flight
            # prefill tables and radix-shared prefix pages
            self.pool_pages = pool_pages or (2 * slots + 8) * self.max_pages
            self.pool = KVPool(self.pool_pages, page_size)
            self.kvcache = M.init_paged_cache(cfg, self.pool_pages, page_size)
            if mesh is not None:
                self.params, (self.kvcache,), (kv_pspecs,) = \
                    SH.place_serving_state(
                        cfg, self.params, [self.kvcache], mesh,
                        self.sharding_policy,
                    )
                self._handoff_shardings = SH.named(kv_pspecs, mesh)
            self.block_tables = np.full(
                (slots, self.max_pages), -1, np.int32
            )
            self.table_of: Dict[int, BlockTable] = {}  # rid -> resident table
            # prefill tables awaiting the radix attach at iteration end
            self._pstash: Dict[int, List[int]] = {}
            self._radix: Optional[PagedRadixCache] = None
            # observability (acceptance: prefix hits skip real compute)
            self.reused_tokens = 0
            self.computed_tokens = 0
            self._prefill_jit = jitcache.shared_jit(
                M.prefill_paged_greedy, cfg, donate=don, **jit_kw
            )
            self._decode_jit = jitcache.shared_jit(
                M.decode_step_paged_greedy, cfg, donate=don, **jit_kw
            )
        else:
            self.cache = M.init_cache(cfg, slots, max_len)
            if mesh is not None:
                self.params, (self.cache,), (kv_pspecs,) = \
                    SH.place_serving_state(
                        cfg, self.params, [self.cache], mesh,
                        self.sharding_policy,
                    )
                self._handoff_shardings = SH.named(kv_pspecs, mesh)
            self._prefill_jit = jitcache.shared_jit(
                M.prefill_greedy, cfg, max_len=max_len, **jit_kw
            )
            self._decode_jit = jitcache.shared_jit(
                M.decode_step_greedy, cfg, donate=don, **jit_kw
            )

        # speculative draft–verify execution (needs the paged pool: the
        # rollback of rejected draft KV is page bookkeeping)
        self.spec_k = spec_k
        if spec_k > 0:
            assert paged, (
                "real speculative decoding requires paged=True — the "
                "draft–verify rollback is block-pool page bookkeeping"
            )
            assert draft_cfg is not None and draft_params is not None, (
                "spec_k > 0 needs a draft model (make_draft_config / "
                "caller-supplied draft_cfg + draft_params)"
            )
            assert draft_cfg.vocab_size == cfg.vocab_size, (
                "draft and target must share a vocabulary"
            )
            self.draft_cfg = draft_cfg
            self.draft_params = draft_params
            # the drafter keeps a dense ring cache per decode slot; its
            # "rollback" is implicit (stale positions are masked by the
            # per-slot position array until overwritten)
            self.draft_cache = M.init_cache(draft_cfg, slots, max_len)
            if mesh is not None:
                self.draft_params, (self.draft_cache,), _ = \
                    SH.place_serving_state(
                        draft_cfg, self.draft_params, [self.draft_cache],
                        mesh, self.sharding_policy,
                    )
            self._prev_dev = jnp.zeros(slots, jnp.int32)  # token at pos-1
            self._draft_prefill_jit = jitcache.shared_jit(
                M.prefill_greedy, draft_cfg, max_len=max_len, **jit_kw
            )
            self._draft_jit = jitcache.shared_jit(
                M.draft_step, draft_cfg, donate=don, **jit_kw
            )
            self._verify_jit = jitcache.shared_jit(
                M.verify_step_paged_greedy, cfg, donate=don, **jit_kw
            )

    # ------------------------------------------------------------------
    # Paged plumbing
    # ------------------------------------------------------------------
    def bind_prefix_cache(self, cache) -> None:
        """Wire the engine's radix cache to this backend's page pool so
        cache nodes can hold page refs (called by the cluster; no-op for
        dense backends or plain token-granular caches).

        The cache's capacity must fit the pool's spare room after every
        decode slot maxes out.  Silently shrinking it instead would make
        the Real side evict prefixes the Sim side keeps — breaking the
        Sim==Real parity contract — so a misfit fails loudly here.
        """
        if not self.paged or not isinstance(cache, PagedRadixCache):
            return
        budget = (self.pool_pages - self.slots * self.max_pages) \
            * self.page_size
        if cache.capacity_tokens > budget:
            raise ValueError(
                f"prefix cache capacity ({cache.capacity_tokens} tokens) "
                f"exceeds the page pool's spare room ({budget} tokens "
                f"after reserving {self.slots} slots × {self.max_len}); "
                "raise pool_pages on make_real_backend_factory or lower "
                "ClusterConfig.prefix_cache_capacity"
            )
        cache.pool = self.pool
        self._radix = cache

    def _evict_radix_for(self, n: int) -> bool:
        """Best-effort: shed cold radix-held pages so ``n`` more can be
        allocated (locked / in-flight pages are pinned and survive).
        Returns False when there is no radix cache to shed from."""
        if self._radix is None:
            return False
        cap0 = self._radix.capacity_tokens
        self._radix.capacity_tokens = max(
            0, self._radix.size_tokens - n * self.page_size
        )
        self._radix._evict_to_fit()
        self._radix.capacity_tokens = cap0
        return True

    def _alloc_pages(self, n: int) -> List[int]:
        """Pool allocation with the radix-shedding fallback.  If even
        that cannot free enough (everything pinned by in-flight work),
        the PageAllocError propagates: an under-provisioned pool is a
        sizing misconfiguration and must fail loudly, not wedge."""
        try:
            return self.pool.alloc(n)
        except PageAllocError:
            if not self._evict_radix_for(n):
                raise
            return self.pool.alloc(n)

    def prefix_inserted(self, r: Request, cache, now: float) -> None:
        """Engine hook: the prompt just entered the radix cache — attach
        its full pages (the cache takes its own refs), then release the
        in-flight references the prefill stashed."""
        if not self.paged:
            return
        table = self._pstash.pop(r.rid, None)
        if table is None:
            return
        if self._radix is not None and r.prompt_tokens:
            self._radix.attach_pages(r.prompt_tokens, table)
        self.pool.decref(table)

    def abort_prefill(self, reqs: List[Request]) -> None:
        """Engine hook: in-flight prefill lost (failure) — release the
        stashed page references before the requests re-route."""
        if not self.paged:
            return
        for r in reqs:
            table = self._pstash.pop(r.rid, None)
            if table:
                self.pool.decref(table)
            r.kv_handoff = None

    # ------------------------------------------------------------------
    # Deferred emission (async dispatch)
    # ------------------------------------------------------------------
    def _drain_one(self) -> None:
        """Materialize the *oldest* in-flight iteration's token ids and
        emit them into the requests' output streams.  This is the
        **only** place the host blocks on device results — called when
        the ring reaches ``pipeline_depth`` at dispatch, at a slot
        insert/release, or the end-of-run flush.  Oldest-first order
        keeps each request's stream append-ordered.  With a profile
        attached the wait is a ``wait.drain`` span."""
        p = self._ring.popleft()
        prof = self._prof
        if prof is not None:
            sp = prof.begin(
                "wait.drain",
                seq=self.pipeline_dispatches - len(self._ring) - 1,
                rids=tuple(x[0].rid for x in p[1]))
        if p[0] == "decode":
            _, pairs, ids = p
            nxt = np.asarray(ids)
            if prof is not None:
                prof.end(sp)
            for r, s in pairs:
                r.output_tokens.append(int(nxt[s]))
        else:  # spec: accepted draft prefix + bonus/correction token
            _, entries, drafts_dev, tgt_dev = p
            drafts = np.asarray(drafts_dev)
            tgt = np.asarray(tgt_dev)
            if prof is not None:
                prof.end(sp)
            for r, s, a in entries:
                r.output_tokens.extend(
                    int(drafts[s, j]) for j in range(a)
                )
                r.output_tokens.append(int(tgt[s, a]))

    def _drain(self) -> None:
        """Drain the whole ring: every deferred iteration is emitted, in
        dispatch order.  Full drain points (insert, release, flush) keep
        every request's stream complete before it is read or its slot
        reused."""
        while self._ring:
            self._drain_one()

    def flush(self) -> None:
        """Emit every deferred token (cluster end-of-run hook)."""
        self._drain()

    # ------------------------------------------------------------------
    # Prefill: real first token + cache stash
    # ------------------------------------------------------------------
    def _padded(self, toks: np.ndarray) -> np.ndarray:
        """The single pad policy for every prefill-shaped entry point
        (dense, paged-suffix, draft): power-of-two bucket clamped to the
        cache capacity, so steady state replays a bounded shape set."""
        pad = _bucket(len(toks), hi=self.max_len)
        buf = np.zeros((1, pad), np.int32)
        buf[0, : len(toks)] = toks
        return buf

    def _context_tokens(self, r: Request) -> np.ndarray:
        ctx = list(r.prompt_tokens)
        if r.resuming:
            # preemption resume: recompute the KV of prompt + the tokens
            # already delivered (their ids are real and kept); the first
            # token was emitted long ago and must not be re-emitted
            ctx += [int(t) for t in r.output_tokens[: r.tokens_out]]
        return np.asarray(ctx, np.int32)

    def _real_prefill(self, r: Request) -> None:
        toks = self._context_tokens(r)
        if len(toks) > self.max_len:
            raise ValueError(
                f"request {r.rid}: prompt+context of {len(toks)} tokens "
                f"exceeds the decode cache capacity ({self.max_len}); "
                "admission must reject or truncate it upstream"
            )
        prof = self._prof
        if prof is not None:
            sp = prof.begin_step("step.prefill", r.rid)
            reused0 = self.reused_tokens if self.paged else 0
        if self.paged:
            self._real_prefill_paged(r, toks)
        else:
            self._real_prefill_dense(r, toks)
        if prof is not None:
            reused = self.reused_tokens - reused0 if self.paged else 0
            prof.end_step(sp, computed=len(toks) - reused, reused=reused)

    def _first_token(self, ids) -> int:
        """The prefill's first token: the host blocks on it here."""
        prof = self._prof
        if prof is None:
            return int(ids[0])
        sp = prof.begin("wait.first_token")
        tok = int(ids[0])
        prof.end(sp)
        return tok

    def _real_prefill_dense(self, r: Request, toks: np.ndarray) -> None:
        ids, cache = self._prefill_jit(
            self.params,
            tokens=jnp.asarray(self._padded(toks)),
            lengths=jnp.asarray([len(toks)], jnp.int32),
        )
        if not r.resuming:
            r.output_tokens.append(self._first_token(ids))
        r.kv_handoff = cache  # migrates with the request (P -> D)

    def _real_prefill_paged(self, r: Request, toks: np.ndarray) -> None:
        """Prefill into pool pages.  A radix prefix hit contributes its
        resident pages (incref, zero recomputation); only the suffix
        runs the forward, writing its KV into freshly allocated pages."""
        L = len(toks)
        n_ctx, ctx_pages = 0, []
        if self._radix is not None:
            n_ctx, ctx_pages = self._radix.match_pages(toks.tolist())
        self.pool.incref(ctx_pages)
        try:
            new_pages = self._alloc_pages(
                self.pool.pages_for(L) - len(ctx_pages)
            )
        except PageAllocError:
            self.pool.decref(ctx_pages)
            raise
        table = list(ctx_pages) + new_pages
        S = L - n_ctx
        bt = np.full((1, self.max_pages), -1, np.int32)
        bt[0, : len(table)] = table
        ids, self.kvcache = self._prefill_jit(
            self.params,
            tokens=jnp.asarray(self._padded(toks[n_ctx:])),
            lengths=jnp.asarray([S], jnp.int32),
            ctx_lens=jnp.asarray([n_ctx], jnp.int32),
            block_tables=jnp.asarray(bt),
            cache=self.kvcache,
        )
        if not r.resuming:
            r.output_tokens.append(self._first_token(ids))
        # migration payload: the request's pages, gathered page-stack —
        # the decode side scatters them into its own pool
        idx = np.asarray(table)
        r.kv_handoff = (
            jax.tree.map(lambda x: x[:, idx], self.kvcache), L
        )
        self.reused_tokens += n_ctx
        self.computed_tokens += S
        if self._radix is not None and r.prompt_tokens:
            # refs live until the radix attach at iteration end (the
            # engine's prefix_inserted hook) or an abort on failure
            self._pstash[r.rid] = table
        else:
            # no radix to hand the pages to: the handoff copy is taken,
            # release them now
            self.pool.decref(table)

    def prefill_iter(self, reqs: List[Request], n_tok: int, f: float):
        for r in reqs:
            self._real_prefill(r)
        return super().prefill_iter(reqs, n_tok, f)

    def prefill_chunk(self, reqs: List[Request], takes, n_new: int,
                      n_ctx: int, f: float):
        """Chunked scheduling over real compute: the virtual clock/energy
        price each chunk, but the actual forward runs on the *final*
        chunk (dense: whole prompt; paged: the post-prefix suffix)."""
        for r, take in zip(reqs, takes):
            if take >= r.prefill_remaining:
                self._real_prefill(r)
        return super().prefill_chunk(reqs, takes, n_new, n_ctx, f)

    # ------------------------------------------------------------------
    # Decode: slot insert / batched step / release
    # ------------------------------------------------------------------
    def insert(self, req: Request) -> None:
        assert self.free, "no free decode slots (max_running too high?)"
        prof = self._prof
        if prof is not None:
            sp = prof.begin_step("step.insert", req.rid)
        self._drain()  # the joining token seeds the device chain below
        slot = self.free.pop()
        self.slot_of[req.rid] = slot
        handoff, req.kv_handoff = req.kv_handoff, None

        if self.paged:
            tree, L = handoff
            if self.mesh is not None:
                # per-shard gather/scatter: the page stack was gathered
                # on the prefill instance's slice; re-lay it out on OUR
                # slice (same head-over-model rule, our devices) so the
                # scatter below is shard-local.  Same-device slices
                # (tp=1 host) make this a no-op placement.
                tree = jax.device_put(tree, self._handoff_shardings)
            table = BlockTable(self.pool)
            table.adopt(self._alloc_pages(self.pool.pages_for(L)), L)
            dst = np.asarray(table.pages)

            def put(cache_leaf, src):
                # cache_leaf: (n_blocks, P+1, ps, ...); src: the
                # request's page stack (n_blocks, n_pages, ps, ...)
                return cache_leaf.at[:, dst].set(src)

            self.kvcache = jax.tree.map(put, self.kvcache, tree)
            self.table_of[req.rid] = table
            self.block_tables[slot] = -1
            self.block_tables[slot, : len(table.pages)] = table.pages
        else:
            cache = handoff
            if self.mesh is not None:
                cache = jax.device_put(cache, self._handoff_shardings)

            def put(dst_leaf, src):
                # dst: (n_blocks, slots, ...); src: (n_blocks, 1, ...)
                return dst_leaf.at[:, slot].set(src[:, 0])

            self.cache = jax.tree.map(put, self.cache, cache)
        self._next_dev = self._next_dev.at[slot].set(
            int(req.output_tokens[-1])
        )
        # resident context = prompt + tokens regenerated before a
        # preemption (fresh requests: tokens_out == 0)
        self.pos[slot] = req.prompt_len + req.tokens_out
        if self.spec_k > 0:
            self._draft_prefill(req, slot)
        if prof is not None:
            prof.end_step(sp, pages=len(self.table_of[req.rid].pages)
                          if self.paged else 0)

    def _draft_prefill(self, req: Request, slot: int) -> None:
        """Build the drafter's dense cache for a joining request: the
        draft model ingests the same context the target holds (prompt
        plus any regenerated tokens after a preemption resume)."""
        toks = self._context_tokens(req)
        _, dcache = self._draft_prefill_jit(
            self.draft_params,
            tokens=jnp.asarray(self._padded(toks)),
            lengths=jnp.asarray([len(toks)], jnp.int32),
        )

        def put(dst_leaf, src):
            return dst_leaf.at[:, slot].set(src[:, 0])

        self.draft_cache = jax.tree.map(put, self.draft_cache, dcache)
        self._prev_dev = self._prev_dev.at[slot].set(int(toks[-1]))

    def release(self, req: Request) -> None:
        # materialize in-flight tokens first: the released request's
        # stream is read immediately (finish, or preemption resume)
        self._drain()
        slot = self.slot_of.pop(req.rid)
        self.free.append(slot)
        if self.paged:
            table = self.table_of.pop(req.rid, None)
            if table is not None:
                table.release()
            self.block_tables[slot] = -1

    def _real_decode_step(self, reqs: List[Request]) -> None:
        prof = self._prof
        if prof is not None:
            sp = prof.begin_step(
                "step.decode", inst=prof.instance(self),
                seq=self.pipeline_dispatches,
                rids=tuple(r.rid for r in reqs))
        # bounded depth: block only once pipeline_depth iterations are
        # in flight, and then only on the oldest (depth=1: the previous)
        while len(self._ring) >= self.pipeline_depth:
            self._drain_one()
        if self.paged:
            # grow tail pages where the next write crosses a boundary
            for r in reqs:
                self.reserve_pages(r, int(self.pos[self.slot_of[r.rid]]) + 1)
            ids, self.kvcache = self._decode_jit(
                self.params,
                tokens=self._next_dev,
                cache=self.kvcache,
                lengths=_snapshot(self.pos),
                block_tables=_snapshot(self.block_tables),
            )
        else:
            ids, self.cache = self._decode_jit(
                self.params,
                tokens=self._next_dev,
                cache=self.cache,
                lengths=_snapshot(self.pos),
            )
        # chain on device; emission of `ids` waits for the next drain
        self._next_dev = ids
        pairs = []
        for r in reqs:
            s = self.slot_of[r.rid]
            pairs.append((r, s))
            self.pos[s] += 1
        self._ring.append(("decode", pairs, ids))
        self.pipeline_depth_sum += len(self._ring)
        self.pipeline_dispatches += 1
        if prof is not None:
            prof.end_step(sp)

    def decode_iter(self, reqs: List[Request], n_req: int, n_kv: int,
                    f: float):
        if reqs:
            self._real_decode_step(reqs)
        return super().decode_iter(reqs, n_req, n_kv, f)

    # ------------------------------------------------------------------
    # Speculative draft–verify (paged)
    # ------------------------------------------------------------------
    def _grow_for_verify(self, r: Request, k: int) -> None:
        """Reserve tail pages for the k+1 tokens the verify forward
        writes (the rejected suffix rolls back after acceptance).

        Near the slot capacity the window is clamped: speculative
        positions past ``max_len`` have no page and scatter to the
        scratch page instead.  That is always safe — an accepted token
        satisfies ``pos + a + 1 <= prompt + decode <= max_len`` (the
        caller's sizing contract), so only *rejected* rows can overflow,
        and no valid row ever attends an overflow position (its query
        position is below them).
        """
        s = self.slot_of[r.rid]
        self.reserve_pages(r, min(int(self.pos[s]) + k + 1, self.max_len))

    def reserve_pages(self, r: Request, need: int) -> None:
        """Grow ``r``'s block table to cover ``need`` tokens (shedding
        cold radix pages if the pool is short) and mirror the fresh
        tail pages into its slot's row of the device block table."""
        s = self.slot_of[r.rid]
        table = self.table_of[r.rid]
        try:
            fresh = table.ensure(need)
        except PageAllocError:
            short = self.pool.pages_for(need) - len(table.pages)
            if not self._evict_radix_for(short):
                raise
            fresh = table.ensure(need)
        if fresh:
            n = len(table.pages)
            self.block_tables[s, n - len(fresh): n] = fresh

    def _real_spec_step(self, reqs: List[Request], k: int,
                        accepts: List[int]) -> None:
        """One draft–verify iteration over the paged pool.

        Drafting is k+1 batched draft-model steps: a *sync* step that
        (re-)ingests the token at position ``pos-1`` — idempotent for
        slots already caught up, and exactly the missing ``d_k`` after a
        fully-accepted window — then k greedy proposal steps.  The
        target verifies all proposals in one ``verify_step_paged``
        forward; per request the engine's acceptance realization ``a``
        picks the emitted prefix ``d_1..d_a`` plus the verify pass's
        bonus/correction token, and the pages holding only rejected
        positions are returned to the pool (page-exact rollback).
        """
        prof = self._prof
        if prof is not None:
            sp = prof.begin_step(
                "step.decode", inst=prof.instance(self),
                seq=self.pipeline_dispatches,
                rids=tuple(r.rid for r in reqs))
        while len(self._ring) >= self.pipeline_depth:
            self._drain_one()
        for r in reqs:
            self._grow_for_verify(r, k)
        # drafting (batched over every slot; free slots write masked
        # garbage into their own rows, never read)
        _, _, self.draft_cache = self._draft_jit(
            self.draft_params,
            tokens=self._prev_dev,
            cache=self.draft_cache,
            lengths=jnp.asarray(np.maximum(self.pos - 1, 0)),
        )
        props = []
        cur = self._next_dev
        for j in range(k):
            # clamp so a near-capacity slot's ring never wraps: an
            # over-the-end write parks on the last slot, whose true
            # content the next iteration's sync step restores
            prop, _, self.draft_cache = self._draft_jit(
                self.draft_params,
                tokens=cur,
                cache=self.draft_cache,
                lengths=jnp.asarray(
                    np.minimum(self.pos + j, self.max_len - 1)
                ),
            )
            props.append(prop)
            cur = prop
        drafts = jnp.stack(props, axis=1)  # (slots, k), device-resident
        # verify: one multi-token forward of [pending, d_1..d_k]
        toks = jnp.concatenate([self._next_dev[:, None], drafts], axis=1)
        tgt, self.kvcache = self._verify_jit(
            self.params,
            tokens=toks,
            cache=self.kvcache,
            lengths=_snapshot(self.pos),
            block_tables=_snapshot(self.block_tables),
        )
        # chain update on device with the host-known acceptance
        # realization: prev <- last accepted draft (or the old pending
        # token when a == 0), next <- the verify pass's bonus/correction
        a_by_slot = np.zeros(self.slots, np.int64)
        occupied = np.zeros(self.slots, bool)
        entries = []
        for r, a in zip(reqs, accepts):
            s = self.slot_of[r.rid]
            a_by_slot[s] = a
            occupied[s] = True
            entries.append((r, s, a))
        rows = jnp.arange(self.slots)
        a_dev = jnp.asarray(a_by_slot)
        occ = jnp.asarray(occupied)
        new_prev = jnp.where(
            a_dev > 0,
            drafts[rows, jnp.maximum(a_dev - 1, 0)],
            self._next_dev,
        )
        self._prev_dev = jnp.where(occ, new_prev, self._prev_dev)
        self._next_dev = jnp.where(occ, tgt[rows, a_dev], self._next_dev)
        self._ring.append(("spec", entries, drafts, tgt))
        self.pipeline_depth_sum += len(self._ring)
        self.pipeline_dispatches += 1
        for r, s, a in entries:
            self.pos[s] += a + 1
            # page-exact rollback of the rejected suffix
            table = self.table_of[r.rid]
            table.shrink(int(self.pos[s]))
            self.block_tables[s, len(table.pages):] = -1
        if prof is not None:
            prof.end_step(sp)

    def spec_decode_iter(self, reqs: List[Request], n_req: int, n_kv: int,
                         k: int, accepts: List[int], draft_frac: float,
                         f: float):
        if reqs:
            self._real_spec_step(reqs, k, accepts)
        return super().spec_decode_iter(
            reqs, n_req, n_kv, k, accepts, draft_frac, f
        )

    def hybrid_iter(self, dec_reqs: List[Request], n_req: int, n_kv: int,
                    pre_reqs: List[Request], takes, n_new: int,
                    n_ctx: int, f: float):
        if dec_reqs:
            self._real_decode_step(dec_reqs)
        for r, take in zip(pre_reqs, takes):
            if take >= r.prefill_remaining:
                self._real_prefill(r)
        return super().hybrid_iter(
            dec_reqs, n_req, n_kv, pre_reqs, takes, n_new, n_ctx, f
        )


def make_draft_config(cfg: ModelConfig) -> ModelConfig:
    """A small same-vocab drafter for ``cfg`` (the serving config): one
    super-block at reduced width.  The vocabulary is shared — drafted
    ids must be the target's ids — and the family (block pattern) is
    kept so RoPE/windows line up position for position."""
    assert not cfg.has_mamba, "draft models cover attention configs"
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-draft",
        n_layers=len(cfg.block_pattern),
        d_model=32,
        n_heads=2,
        n_kv_heads=2,
        head_dim=16,
        d_ff=64 if cfg.d_ff else 0,
    )


def make_real_backend_factory(
    cfg: ModelConfig,
    params,
    *,
    slots: int = 8,
    max_len: int = 256,
    paged: bool = False,
    page_size: int = 16,
    pool_pages: Optional[int] = None,
    spec_k: int = 0,
    draft_cfg: Optional[ModelConfig] = None,
    draft_params=None,
    donate_kv: bool = True,
    tp: int = 0,
    devices=None,
    sharding_policy=None,
    pipeline_depth: int = 1,
):
    """Factory for ClusterConfig.backend_factory: every instance gets its
    own slot/pool state but shares the (read-only) weights *and* — via
    :mod:`repro.serving.jitcache` — the jitted entry points, so a second
    instance (or a second cluster) over the same config never recompiles.
    With ``spec_k > 0`` the decode instances run real draft–verify
    speculation (requires ``paged=True`` and a draft model).

    ``tp > 0`` turns each instance into a **mesh slice**: a
    :class:`~repro.distributed.meshslice.MeshSlicer` over ``devices``
    (default: all of ``jax.devices()``) carves a ``(1, tp)``
    ("data", "model") sub-mesh per instance, and the cluster's
    ``InstanceSpec.tp`` — passed through the factory's ``tp`` keyword —
    overrides the default degree per instance, so a heterogeneous fleet
    compiles heterogeneous shardings.  ``tp=0`` (default) is the legacy
    meshless single-device path, bit-exact with prior releases.

    ``pipeline_depth`` sets each instance's async-dispatch window: how
    many decode/spec iterations may be in flight before dispatch blocks
    on the oldest deferred emission (see the module docstring).  Token
    streams are identical at every depth; 1 is the classic behavior."""
    slicer = MeshSlicer(devices) if tp or devices is not None else None
    default_tp = tp

    def factory(kind: str, idx: int, hw: HardwareModel, seed: int,
                tp: Optional[int] = None):
        n_slots = slots if kind in ("decode", "hybrid") else 1
        # hybrids coalesce prefill chunks between decode steps and stay
        # single-token; only pure decode instances speculate
        k = spec_k if kind == "decode" else 0
        mesh = None
        if slicer is not None:
            degree = tp if tp else (default_tp or 1)
            mesh = slicer.slice(degree)
        return RealBackend(
            hw, cfg, params, slots=n_slots, max_len=max_len, seed=seed,
            paged=paged, page_size=page_size, pool_pages=pool_pages,
            spec_k=k, draft_cfg=draft_cfg if k else None,
            draft_params=draft_params if k else None,
            donate_kv=donate_kv, mesh=mesh,
            sharding_policy=sharding_policy,
            pipeline_depth=pipeline_depth,
        )

    return factory
