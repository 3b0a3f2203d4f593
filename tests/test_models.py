"""Per-arch smoke tests + model-level correctness invariants.

The strongest check is prefill/decode consistency: running the prompt
through ``prefill`` and then stepping ``decode_step`` must reproduce the
full-sequence ``forward`` logits at every generated position.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED, REGISTRY
from repro.models import layers as L
from repro.models import model as M

ARCHS = sorted(ASSIGNED)


def _fp32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward(arch):
    """Reduced config: one forward (or train-style) step, shapes + no NaN."""
    cfg = REGISTRY[arch].reduced()
    params = M.init_params(cfg, jax.random.key(0))
    B, S = 2, 64
    key = jax.random.key(1)
    if cfg.embed_inputs:
        h, aux = M.forward(
            params, cfg,
            tokens=jax.random.randint(key, (B, S), 0, cfg.vocab_size),
        )
    else:  # modality frontend stub provides embeddings
        h, aux = M.forward(
            params, cfg,
            inputs_embeds=jax.random.normal(
                key, (B, S, cfg.d_model), jnp.bfloat16
            ),
        )
    assert h.shape == (B, S, cfg.d_model)
    logits = M.lm_logits(params, cfg, h)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize(
    "arch", [a for a in ARCHS if REGISTRY[a].causal and
             REGISTRY[a].embed_inputs]
)
def test_prefill_decode_matches_forward(arch):
    """decode_step after prefill == full forward, token by token."""
    cfg = _fp32(REGISTRY[arch].reduced())
    params = M.init_params(cfg, jax.random.key(0))
    B, P_len, G_len = 2, 24, 4
    total = P_len + G_len
    toks = jax.random.randint(jax.random.key(2), (B, total), 0,
                              cfg.vocab_size)
    # reference: full forward logits
    h, _ = M.forward(params, cfg, tokens=toks)
    ref_logits = M.lm_logits(params, cfg, h)  # (B, total, V)

    lengths = jnp.full((B,), P_len, jnp.int32)
    logits, cache = M.prefill(params, cfg, toks[:, :P_len], lengths,
                              max_len=total)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits[:, P_len - 1]),
        rtol=2e-3, atol=2e-3,
    )
    pos = lengths
    for t in range(G_len):
        logits, cache = M.decode_step(
            params, cfg, toks[:, P_len + t], cache, pos
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits[:, P_len + t]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"{arch} diverges at decode step {t}",
        )
        pos = pos + 1


def test_paged_prefill_decode_matches_forward():
    """Paged prefill + paged decode against the full-forward oracle:
    pool pages + block tables must be an invisible re-layout."""
    cfg = _fp32(REGISTRY["llama-3.1-8b"].reduced())
    params = M.init_params(cfg, jax.random.key(0))
    B, P_len, G_len, ps = 2, 24, 4, 8
    total = P_len + G_len
    toks = jax.random.randint(jax.random.key(2), (B, total), 0,
                              cfg.vocab_size)
    h, _ = M.forward(params, cfg, tokens=toks)
    ref_logits = M.lm_logits(params, cfg, h)

    num_pages, Pmax = 16, 4
    cache = M.init_paged_cache(cfg, num_pages, ps)
    n = -(-P_len // ps)
    bt = np.full((B, Pmax), -1, np.int32)
    bt[0, :n] = np.arange(n)
    bt[1, :n] = np.arange(n) + 6  # non-contiguous on purpose
    lengths = jnp.full((B,), P_len, jnp.int32)
    logits, cache = M.prefill_paged(
        params, cfg, toks[:, :P_len], lengths,
        jnp.zeros((B,), jnp.int32), jnp.asarray(bt), cache,
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits[:, P_len - 1]),
        rtol=2e-3, atol=2e-3,
    )
    pos = lengths
    for t in range(G_len):
        need = -(-(P_len + t + 1) // ps)
        bt[0, :need] = np.concatenate([bt[0, :n], np.arange(n, need)])
        bt[1, :need] = np.concatenate([bt[1, :n], np.arange(n, need) + 6])
        logits, cache = M.decode_step_paged(
            params, cfg, toks[:, P_len + t], cache, pos, jnp.asarray(bt)
        )
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits[:, P_len + t]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"paged decode diverges at step {t}",
        )
        pos = pos + 1


def test_verify_step_paged_matches_sequential_decode():
    """The k-token verify forward is bit-identical to running the same
    tokens through k+1 sequential paged decode steps — speculation is a
    re-batching, never a numerics change."""
    cfg = _fp32(REGISTRY["llama-3.1-8b"].reduced())
    params = M.init_params(cfg, jax.random.key(0))
    B, P_len, ps, T = 2, 20, 8, 4
    toks = jax.random.randint(jax.random.key(3), (B, P_len), 0,
                              cfg.vocab_size)
    num_pages, Pmax = 16, 4
    cache = M.init_paged_cache(cfg, num_pages, ps)
    bt = np.full((B, Pmax), -1, np.int32)
    bt[0] = np.arange(Pmax)
    bt[1] = np.arange(Pmax) + 8
    lengths = jnp.full((B,), P_len, jnp.int32)
    logits, cache = M.prefill_paged(
        params, cfg, toks, lengths, jnp.zeros((B,), jnp.int32),
        jnp.asarray(bt), cache,
    )
    cur = jnp.argmax(logits, -1).astype(jnp.int32)
    seq_logits, seq_cache, chain = [], cache, [cur]
    for j in range(T):
        lg, seq_cache = M.decode_step_paged(
            params, cfg, chain[-1], seq_cache, lengths + j,
            jnp.asarray(bt),
        )
        seq_logits.append(lg)
        chain.append(jnp.argmax(lg, -1).astype(jnp.int32))
    ver_logits, ver_cache = M.verify_step_paged(
        params, cfg, jnp.stack(chain[:T], axis=1), cache, lengths,
        jnp.asarray(bt),
    )
    for j in range(T):
        np.testing.assert_array_equal(
            np.asarray(ver_logits[:, j]), np.asarray(seq_logits[j]),
            err_msg=f"verify row {j} != sequential decode step {j}",
        )
    for a, b in zip(jax.tree.leaves(ver_cache), jax.tree.leaves(seq_cache)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _paged_step_layer_by_layer(params, cfg, tokens, pool, lengths, bt):
    """The paged verify step as a plain Python loop over layers, with the
    pool as numpy pages: each layer writes its new K/V at ``(page,
    offset)`` of its own pool, then attends over its table's pages.
    Returns ``(logits (B, T, V), pool)``."""
    pool = {name: {kv: np.array(a) for kv, a in e.items()}
            for name, e in pool.items()}
    B, T = tokens.shape
    Pmax = bt.shape[1]
    scratch, ps = next(iter(pool.values()))["k"].shape[1:3]
    scratch -= 1
    positions = np.asarray(lengths)[:, None] + np.arange(T)[None]
    slot_pos = np.broadcast_to(np.arange(Pmax * ps), (B, Pmax * ps))
    x = params["embed"][tokens]
    for li in range(cfg.n_blocks):
        bp = M._dequant_tree(
            jax.tree.map(lambda a: a[li], params["blocks"]), M._dtype(cfg)
        )
        for i, spec in enumerate(cfg.block_pattern):
            lp, pages = bp[f"layer_{i}"], pool[f"layer_{i}"]
            h = L.rms_norm(x, lp["attn"]["norm"], cfg.norm_eps)
            q, k, v = M._attn_qkv(lp["attn"], cfg, h)
            if cfg.use_rope:
                sin, cos = L.rope_sincos(
                    jnp.asarray(positions), cfg.head_dim, cfg.rope_theta
                )
                q, k = L.apply_rope(q, sin, cos), L.apply_rope(k, sin, cos)
            for b in range(B):
                for t in range(T):
                    p = positions[b, t]
                    page = bt[b, p // ps] if p // ps < Pmax else -1
                    page = page if page >= 0 else scratch
                    pages["k"][li, page, p % ps] = k[b, t]
                    pages["v"][li, page, p % ps] = v[b, t]
            idx = np.maximum(bt, 0)
            kg = pages["k"][li][idx].reshape(B, Pmax * ps, *k.shape[2:])
            vg = pages["v"][li][idx].reshape(B, Pmax * ps, *v.shape[2:])
            o = L.verify_attention(
                q, jnp.asarray(kg), jnp.asarray(vg), jnp.asarray(slot_pos),
                jnp.asarray(positions), window=spec.window,
                softcap=cfg.attn_softcap,
            )
            x = x + o.reshape(B, T, cfg.q_dim) @ lp["attn"]["wo"]
            out, _ = M._ffn(lp, cfg, x)
            x = x + out
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return M.lm_logits(params, cfg, x), pool


@pytest.mark.parametrize("arch", ["llama-3.1-8b", "gemma2-27b"])
@pytest.mark.parametrize("T", [1, 3])
def test_paged_step_writes_each_layer_in_place(arch, T):
    """decode_step_paged (T=1) and verify_step_paged (T=3) over three
    super-blocks against a layer-by-layer loop: the logits match, each
    layer's written ``(page, offset)`` holds that layer's new K/V, and
    every other page of every layer — the scratch page aside — is
    bitwise the pool it was given."""
    base = REGISTRY[arch].reduced()
    cfg = _fp32(dataclasses.replace(
        base, n_layers=3 * len(base.block_pattern)
    ))
    params = M.init_params(cfg, jax.random.key(0))
    ps, num_pages = 8, 12
    pool = M.init_paged_cache(cfg, num_pages, ps)
    keys = iter(jax.random.split(jax.random.key(5), 64))
    pool = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape, a.dtype), pool
    )
    bt = np.asarray([
        [5, 2, 9, -1],  # the last window row runs past the table
        [-1, -1, -1, -1],  # an empty slot: every write goes to scratch
        [7, 0, 11, 3],
    ], np.int32)
    lengths = np.asarray([22, 0, 13], np.int32)
    tokens = jax.random.randint(jax.random.key(6), (3, T), 0,
                                cfg.vocab_size)
    ref_logits, ref_pool = _paged_step_layer_by_layer(
        params, cfg, tokens, pool, lengths, bt
    )
    if T == 1:
        logits, got = M.decode_step_paged(
            params, cfg, tokens[:, 0], pool, jnp.asarray(lengths),
            jnp.asarray(bt),
        )
        logits = logits[:, None]
    else:
        logits, got = M.verify_step_paged(
            params, cfg, tokens, pool, jnp.asarray(lengths),
            jnp.asarray(bt),
        )
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    written = np.zeros((num_pages + 1, ps), bool)
    for b in range(3):
        for p in lengths[b] + np.arange(T):
            page = bt[b, p // ps] if p // ps < bt.shape[1] else -1
            if page >= 0:
                written[page, p % ps] = True
    for name in pool:
        for kv in ("k", "v"):
            before = np.asarray(pool[name][kv])
            after = np.asarray(got[name][kv])
            want = ref_pool[name][kv]
            for li in range(cfg.n_blocks):
                np.testing.assert_allclose(
                    after[li][written], want[li][written],
                    rtol=1e-5, atol=1e-5,
                    err_msg=f"{name}.{kv} layer {li}: new K/V",
                )
                np.testing.assert_array_equal(
                    after[li, :-1][~written[:-1]],
                    before[li, :-1][~written[:-1]],
                    err_msg=f"{name}.{kv} layer {li}: a page it must "
                            "not touch changed",
                )


def test_accept_prefix_sampling():
    draft = jnp.asarray([[5, 6, 7], [5, 6, 7], [1, 2, 3], [9, 9, 9]])
    target = jnp.asarray([
        [5, 6, 7, 8],  # all accepted -> 3
        [5, 0, 7, 8],  # mismatch at row 1 -> 1
        [0, 2, 3, 4],  # mismatch at row 0 -> 0
        [9, 9, 0, 4],  # prefix of 2
    ])
    np.testing.assert_array_equal(
        np.asarray(M.accept_prefix(draft, target)), [3, 1, 0, 2]
    )


def test_paged_prefill_resumes_from_resident_prefix():
    """A prefill that only computes the suffix against resident prefix
    pages must equal the whole-prompt prefill (zero-recompute reuse)."""
    cfg = _fp32(REGISTRY["llama-3.1-8b"].reduced())
    params = M.init_params(cfg, jax.random.key(0))
    ps, L, ctx = 8, 21, 16  # ctx page-aligned, suffix 5 tokens
    toks = jax.random.randint(jax.random.key(9), (1, L), 0, cfg.vocab_size)
    whole = M.init_paged_cache(cfg, 8, ps)
    bt = jnp.asarray([[0, 1, 2, -1]], jnp.int32)
    ref, whole = M.prefill_paged(
        params, cfg, toks, jnp.array([L]), jnp.array([0]), bt, whole,
    )
    split = M.init_paged_cache(cfg, 8, ps)
    _, split = M.prefill_paged(
        params, cfg, toks[:, :ctx], jnp.array([ctx]), jnp.array([0]),
        jnp.asarray([[0, 1, -1, -1]], jnp.int32), split,
    )
    got, split = M.prefill_paged(
        params, cfg,
        jnp.pad(toks[:, ctx:], ((0, 0), (0, 3))),  # padded suffix
        jnp.array([L - ctx]), jnp.array([ctx]), bt, split,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # the resident pages were read, not rewritten: caches agree exactly
    # (the trailing scratch page absorbs padding writes — don't compare)
    for la, lb in zip(jax.tree_util.tree_leaves(whole),
                      jax.tree_util.tree_leaves(split)):
        np.testing.assert_allclose(
            np.asarray(la[:, :-1]), np.asarray(lb[:, :-1]),
            rtol=1e-5, atol=1e-5,
        )


def test_paged_cache_rejects_unsupported_configs():
    # real exceptions, not asserts: the rejection must survive python -O
    with pytest.raises(ValueError, match="Mamba"):
        M.init_paged_cache(REGISTRY["jamba-v0.1-52b"].reduced(), 8, 8)
    import dataclasses

    int8_kv = dataclasses.replace(
        REGISTRY["llama-3.1-8b"].reduced(), kv_dtype="int8"
    )
    with pytest.raises(ValueError, match="int8"):
        M.init_paged_cache(int8_kv, 8, 8)


def test_ragged_prefill_respects_lengths():
    """Shorter rows in a padded prefill batch must give the same result
    as unpadded single-row prefill."""
    cfg = _fp32(REGISTRY["phi4-mini-3.8b"].reduced())
    params = M.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(3), (1, 16), 0, cfg.vocab_size)
    solo, _ = M.prefill(params, cfg, toks, jnp.array([16]), max_len=32)
    padded = jnp.pad(toks, ((0, 0), (0, 16)))
    both, _ = M.prefill(
        params, cfg,
        jnp.concatenate([padded, padded]),
        jnp.array([16, 32]),
        max_len=32,
    )
    np.testing.assert_allclose(
        np.asarray(both[0]), np.asarray(solo[0]), rtol=2e-3, atol=2e-3
    )


def test_sliding_window_matches_masked_full():
    cfg = REGISTRY["gemma2-27b"].reduced()
    key = jax.random.key(4)
    q = jax.random.normal(key, (2, 128, 4, 16))
    k = jax.random.normal(jax.random.key(5), (2, 128, 4, 16))
    v = jax.random.normal(jax.random.key(6), (2, 128, 4, 16))
    banded = L.sliding_attention(q, k, v, window=32, q_chunk=32)
    full = L.chunked_attention(q, k, v, causal=True, window=32,
                               q_chunk=64, k_chunk=64)
    np.testing.assert_allclose(
        np.asarray(banded), np.asarray(full), rtol=2e-3, atol=2e-3
    )


def test_moe_sorted_matches_onehot_dispatch():
    """The sort-based dispatch must equal the one-hot capacity dispatch."""
    key = jax.random.key(7)
    T, d, E, ffe, k = 96, 32, 8, 16, 2
    x = jax.random.normal(key, (T, d))
    router = jax.random.normal(jax.random.key(8), (d, E)) * 0.1
    wg = jax.random.normal(jax.random.key(9), (E, d, ffe)) * 0.1
    wi = jax.random.normal(jax.random.key(10), (E, d, ffe)) * 0.1
    wo = jax.random.normal(jax.random.key(11), (E, ffe, d)) * 0.1
    y1, a1 = L.moe_ffn(x, router, wg, wi, wo, top_k=k, capacity_factor=8.0)
    y2, a2 = L.moe_ffn_sorted(x, router, wg, wi, wo, top_k=k,
                              capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(a1["load"]),
                                  np.asarray(a2["load"]))


def test_moe_capacity_drops_are_bounded():
    key = jax.random.key(12)
    T, d, E, ffe, k = 128, 16, 4, 8, 2
    x = jax.random.normal(key, (T, d))
    router = jax.random.normal(jax.random.key(13), (d, E))
    wg = jax.random.normal(jax.random.key(14), (E, d, ffe)) * 0.1
    wi = jax.random.normal(jax.random.key(15), (E, d, ffe)) * 0.1
    wo = jax.random.normal(jax.random.key(16), (E, ffe, d)) * 0.1
    y, aux = L.moe_ffn_sorted(x, router, wg, wi, wo, top_k=k,
                              capacity_factor=1.0)
    assert int(aux["dropped"]) <= T * k  # sane
    assert bool(jnp.isfinite(y).all())


def test_param_count_matches_actual():
    for arch in ("phi4-mini-3.8b", "qwen3-moe-30b-a3b", "mamba2-2.7b"):
        cfg = REGISTRY[arch].reduced()
        params = M.init_params(cfg, jax.random.key(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        declared = cfg.param_count()
        # accounting excludes norms/small vectors — within 3%
        assert abs(actual - declared) / actual < 0.03


def test_full_config_param_counts():
    """Sanity: full configs land near their nameplate sizes."""
    expect = {
        "phi4-mini-3.8b": (3.0e9, 4.6e9),
        "gemma2-27b": (24e9, 30e9),
        "command-r-plus-104b": (95e9, 115e9),
        "qwen3-moe-30b-a3b": (28e9, 33e9),
        "dbrx-132b": (120e9, 140e9),
        "mamba2-2.7b": (2.4e9, 3.0e9),
        "jamba-v0.1-52b": (48e9, 56e9),
    }
    for arch, (lo, hi) in expect.items():
        n = REGISTRY[arch].param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.1f}B outside [{lo},{hi}]"


def test_active_params_moe():
    cfg = REGISTRY["qwen3-moe-30b-a3b"]
    assert cfg.active_param_count() < 0.2 * cfg.param_count()
