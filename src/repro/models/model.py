"""Unified scan-based model: dense / MoE / Mamba2 / hybrid / encoder-only.

The model is ``n_blocks`` repetitions of a (possibly heterogeneous)
super-block; parameters are stacked along a leading block axis so the forward
pass is a single ``lax.scan`` — this keeps the lowered HLO size independent of
depth (critical for the 512-device dry-run compiles).

Three entry points:
  * ``forward``      — full-sequence hidden states (training / encoder).
  * ``prefill``      — forward + builds the decode cache (serving prefill).
  * ``decode_step``  — one token per sequence against the cache (serving decode).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import layers as L

Params = Dict[str, Any]
PyTree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig, dt):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.n_layers)
    p = {
        "norm": jnp.zeros((d,), dt),
        "wq": (jax.random.normal(k1, (d, qd)) * std).astype(dt),
        "wk": (jax.random.normal(k2, (d, kvd)) * std).astype(dt),
        "wv": (jax.random.normal(k3, (d, kvd)) * std).astype(dt),
        "wo": (jax.random.normal(k4, (qd, d)) * out_std).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.head_dim,), dt)
        p["k_norm"] = jnp.zeros((cfg.head_dim,), dt)
    return p


def _init_mamba(key, cfg: ModelConfig, dt):
    m = cfg.mamba
    d = cfg.d_model
    di = m.d_inner(d)
    nh = m.n_heads(d)
    conv_dim = di + 2 * m.d_state
    in_dim = 2 * di + 2 * m.d_state + nh  # z, x, B, C, dt
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.n_layers)
    # dt bias: inverse softplus of dt ~ U[1e-3, 0.1]
    dt0 = jnp.exp(
        jax.random.uniform(k3, (nh,)) * (math.log(0.1) - math.log(1e-3))
        + math.log(1e-3)
    )
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "norm": jnp.zeros((d,), dt),
        "in_proj": (jax.random.normal(k1, (d, in_dim)) * std).astype(dt),
        "conv_w": (jax.random.normal(k2, (conv_dim, m.d_conv)) * std).astype(dt),
        "A_log": jnp.log(
            jax.random.uniform(k4, (nh,), minval=1.0, maxval=16.0)
        ).astype(jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "gnorm": jnp.zeros((di,), dt),
        "out_proj": (jax.random.normal(key, (di, d)) * out_std).astype(dt),
    }


def _init_mlp(key, cfg: ModelConfig, dt):
    d, ff = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.n_layers)
    return {
        "norm": jnp.zeros((d,), dt),
        "w_gate": (jax.random.normal(k1, (d, ff)) * std).astype(dt),
        "w_in": (jax.random.normal(k2, (d, ff)) * std).astype(dt),
        "w_out": (jax.random.normal(k3, (ff, d)) * out_std).astype(dt),
    }


def _init_moe(key, cfg: ModelConfig, dt):
    e = cfg.moe
    d, ff = cfg.d_model, e.d_ff_expert
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.n_layers)
    return {
        "norm": jnp.zeros((d,), dt),
        "router": (jax.random.normal(k1, (d, e.num_experts)) * std).astype(dt),
        "w_gate": (
            jax.random.normal(k2, (e.num_experts, d, ff)) * std
        ).astype(dt),
        "w_in": (
            jax.random.normal(k3, (e.num_experts, d, ff)) * std
        ).astype(dt),
        "w_out": (
            jax.random.normal(k4, (e.num_experts, ff, d)) * out_std
        ).astype(dt),
    }


def _init_block(key, cfg: ModelConfig):
    dt = _dtype(cfg)
    p = {}
    keys = jax.random.split(key, 2 * len(cfg.block_pattern))
    for i, spec in enumerate(cfg.block_pattern):
        lp = {}
        if spec.mixer == "attn":
            lp["attn"] = _init_attn(keys[2 * i], cfg, dt)
        elif spec.mixer == "mamba":
            lp["mamba"] = _init_mamba(keys[2 * i], cfg, dt)
        if spec.ffn == "mlp":
            lp["mlp"] = _init_mlp(keys[2 * i + 1], cfg, dt)
        elif spec.ffn == "moe":
            lp["moe"] = _init_moe(keys[2 * i + 1], cfg, dt)
        p[f"layer_{i}"] = lp
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dt = _dtype(cfg)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    params: Params = {}
    if cfg.embed_inputs:
        params["embed"] = (
            jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model)) * 0.02
        ).astype(dt)
    block_keys = jax.random.split(k_blocks, cfg.n_blocks)
    params["blocks"] = jax.vmap(lambda k: _init_block(k, cfg))(block_keys)
    params["final_norm"] = jnp.zeros((cfg.d_model,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size)) * 0.02
        ).astype(dt)
    return params


def param_specs(cfg: ModelConfig) -> PyTree:
    """ShapeDtypeStruct pytree of the parameters (no allocation). With
    ``weight_dtype="int8"`` the tree is the quantized serving layout."""
    if cfg.weight_dtype == "int8":
        return jax.eval_shape(
            lambda k: quantize_params(init_params(cfg, k)),
            jax.random.key(0),
        )
    return jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))


# ---------------------------------------------------------------------------
# int8 serving weights (beyond-paper perf iteration: halves the per-token
# weight-read traffic that dominates small-batch/long-context decode)
# ---------------------------------------------------------------------------

_QUANT_LEAVES = {
    "wq", "wk", "wv", "wo", "w_gate", "w_in", "w_out",
    "in_proj", "out_proj", "router", "conv_w",
}


def quantize_params(params: Params) -> Params:
    """Per-output-channel symmetric int8 for the block weight matrices.

    Each quantized leaf becomes ``{"q8": int8, "sc": f32}``; norms, biases
    and the embedding/LM head stay in the original dtype. The forward
    paths dequantize at block entry (``_dequant_tree``) — XLA fuses the
    int8→bf16 convert into the consuming dot, so HBM reads stay int8.
    """

    def visit(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in _QUANT_LEAVES and x.ndim >= 2:
            xf = x.astype(jnp.float32)
            sc = jnp.max(jnp.abs(xf), axis=-2, keepdims=True) / 127.0 + 1e-9
            q = jnp.clip(jnp.round(xf / sc), -127, 127).astype(jnp.int8)
            return {"q8": q, "sc": jnp.squeeze(sc, axis=-2)}
        return x

    return jax.tree_util.tree_map_with_path(visit, params)


def _dequant_tree(t, dt):
    if isinstance(t, dict):
        if set(t.keys()) == {"q8", "sc"}:
            return (
                t["q8"].astype(jnp.float32)
                * t["sc"][..., None, :].astype(jnp.float32)
            ).astype(dt)
        return {k: _dequant_tree(v, dt) for k, v in t.items()}
    return t


def params_quantized(params: Params) -> bool:
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return any(
        getattr(p[-1], "key", None) == "q8" for p, _ in flat
    )


# ---------------------------------------------------------------------------
# Sub-layer applications
# ---------------------------------------------------------------------------


def _attn_qkv(p, cfg: ModelConfig, h: jax.Array):
    B, S, _ = h.shape
    q = jnp.einsum("bsd,de->bse", h, p["wq"]).reshape(
        B, S, cfg.n_heads, cfg.head_dim
    )
    k = jnp.einsum("bsd,de->bse", h, p["wk"]).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim
    )
    v = jnp.einsum("bsd,de->bse", h, p["wv"]).reshape(
        B, S, cfg.n_kv_heads, cfg.head_dim
    )
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _attn_full(
    p, cfg: ModelConfig, spec: LayerSpec, x, positions, valid
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention. Returns (residual_out, (k, v))."""
    B, S, _ = x.shape
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v = _attn_qkv(p, cfg, h)
    if cfg.use_rope:
        sin, cos = L.rope_sincos(positions, cfg.head_dim, cfg.rope_theta)
        q = L.apply_rope(q, sin, cos)
        k = L.apply_rope(k, sin, cos)
    if spec.window is not None and cfg.causal:
        o = L.sliding_attention(
            q, k, v, window=spec.window, softcap=cfg.attn_softcap,
            kv_valid=valid,
        )
    else:
        o = L.chunked_attention(
            q, k, v, causal=cfg.causal, window=spec.window,
            softcap=cfg.attn_softcap, q_positions=positions,
            kv_positions=positions, kv_valid=valid,
        )
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, cfg.q_dim), p["wo"])
    return out, (k, v)


def _attn_decode(p, cfg: ModelConfig, spec: LayerSpec, x, entry, q_pos,
                 write_slot):
    """One-token attention against a ring-buffer cache entry.

    x: (B, d); entry holds k/v (B, C, Hkv, Dh) [+ int8 scales], pos (B, C);
    q_pos: (B,) absolute position of the new token; write_slot: (B,).
    Returns (residual_out, new_entry).
    """
    B, _ = x.shape
    h = L.rms_norm(x[:, None, :], p["norm"], cfg.norm_eps)
    q, k, v = _attn_qkv(p, cfg, h)  # (B, 1, H, Dh)
    if cfg.use_rope:
        sin, cos = L.rope_sincos(q_pos[:, None], cfg.head_dim, cfg.rope_theta)
        q = L.apply_rope(q, sin, cos)
        k = L.apply_rope(k, sin, cos)
    bidx = jnp.arange(B)
    new = dict(entry)
    if cfg.kv_dtype == "int8":
        kq, ksc = quantize_kv(k[:, 0])
        vq, vsc = quantize_kv(v[:, 0])
        new["k"] = entry["k"].at[bidx, write_slot].set(kq)
        new["v"] = entry["v"].at[bidx, write_slot].set(vq)
        new["k_sc"] = entry["k_sc"].at[bidx, write_slot].set(ksc)
        new["v_sc"] = entry["v_sc"].at[bidx, write_slot].set(vsc)
        k_cache = dequantize_kv(new["k"], new["k_sc"], q.dtype)
        v_cache = dequantize_kv(new["v"], new["v_sc"], q.dtype)
    else:
        new["k"] = entry["k"].at[bidx, write_slot].set(k[:, 0])
        new["v"] = entry["v"].at[bidx, write_slot].set(v[:, 0])
        k_cache, v_cache = new["k"], new["v"]
    new["pos"] = entry["pos"].at[bidx, write_slot].set(q_pos)
    o = L.decode_attention(
        q[:, 0], k_cache, v_cache, new["pos"], q_pos,
        window=spec.window, softcap=cfg.attn_softcap,
    )
    out = jnp.einsum("be,ed->bd", o.reshape(B, cfg.q_dim), p["wo"])
    return out, new


def _mamba_inner_split(p, cfg: ModelConfig, h):
    m = cfg.mamba
    d = cfg.d_model
    di = m.d_inner(d)
    nh = m.n_heads(d)
    N = m.d_state
    proj = jnp.einsum("...d,de->...e", h, p["in_proj"])
    z = proj[..., :di]
    xbc = proj[..., di : di + di + 2 * N]
    dt_raw = proj[..., di + di + 2 * N :]
    return z, xbc, dt_raw, di, nh, N


def _mamba_full(p, cfg: ModelConfig, x, valid):
    """Full-sequence Mamba2 (SSD).

    Returns (residual_out, (final_ssm_state, conv_tail)) where conv_tail is
    the last (d_conv-1) *pre-conv* features per row — the decode-time conv
    ring state.
    """
    m = cfg.mamba
    B, S, _ = x.shape
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    z, xbc, dt_raw, di, nh, N = _mamba_inner_split(p, cfg, h)
    if valid is not None:  # zero padded positions so state is unpolluted
        xbc = xbc * valid[..., None].astype(xbc.dtype)
        lengths = valid.sum(axis=-1).astype(jnp.int32)  # (B,)
    else:
        lengths = jnp.full((B,), S, jnp.int32)
    # conv tail state: last (K-1) pre-conv inputs per row (zeros if short)
    K = m.d_conv
    tail_pos = lengths[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]  # (B,K-1)
    tail_ok = tail_pos >= 0
    tail = xbc[jnp.arange(B)[:, None], jnp.clip(tail_pos, 0, S - 1)]
    conv_tail = jnp.where(tail_ok[..., None], tail, 0).astype(xbc.dtype)
    xbc = jax.nn.silu(L.causal_conv1d(xbc, p["conv_w"]))
    xs = xbc[..., :di].reshape(B, S, nh, m.head_dim)
    from repro.distributed.context import ssd_head_pspec

    hspec = ssd_head_pspec(nh)
    if hspec is not None:  # keep the per-head (L,L) SSD working set sharded
        xs = jax.lax.with_sharding_constraint(xs, hspec)
    Bm = xbc[..., di : di + N]
    Cm = xbc[..., di + N :]
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + p["dt_bias"]
    )  # (B,S,nh)
    if valid is not None:
        dt = dt * valid[..., None].astype(dt.dtype)
    A = -jnp.exp(p["A_log"])
    y, state = L.ssd_chunked(xs, dt, A, Bm, Cm, chunk=m.chunk)
    y = y + xs * p["D"][None, None, :, None].astype(y.dtype)
    y = y.reshape(B, S, di)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                   p["gnorm"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, (state, conv_tail)


def _mamba_decode(p, cfg: ModelConfig, x, conv_state, ssm_state):
    """One-token Mamba2 step. x: (B, d)."""
    m = cfg.mamba
    B, _ = x.shape
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    z, xbc, dt_raw, di, nh, N = _mamba_inner_split(p, cfg, h)
    xbc_c, conv_state = L.conv_step(xbc, conv_state, p["conv_w"])
    xbc_c = jax.nn.silu(xbc_c)
    xs = xbc_c[..., :di].reshape(B, nh, m.head_dim)
    Bm = xbc_c[..., di : di + N]
    Cm = xbc_c[..., di + N :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,nh)
    A = -jnp.exp(p["A_log"])
    y, ssm_state = L.ssd_decode_step(xs, dt, A, Bm, Cm, ssm_state)
    y = y + xs * p["D"][None, :, None].astype(y.dtype)
    y = y.reshape(B, di)
    y = L.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                   p["gnorm"], cfg.norm_eps)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])
    return out, conv_state, ssm_state


def _ffn(lp, cfg: ModelConfig, x):
    """MLP or MoE FFN on (B, S, d) (or (B, d)). Returns (out, moe_aux|None)."""
    if "mlp" in lp:
        p = lp["mlp"]
        h = L.rms_norm(x, p["norm"], cfg.norm_eps)
        return L.glu_mlp(h, p["w_gate"], p["w_in"], p["w_out"], cfg.act), None
    p = lp["moe"]
    h = L.rms_norm(x, p["norm"], cfg.norm_eps)
    shp = h.shape
    flat = h.reshape(-1, shp[-1])
    from repro.distributed.context import expert_pspec

    out, aux = L.moe_ffn_sorted(
        flat, p["router"], p["w_gate"], p["w_in"], p["w_out"],
        top_k=cfg.moe.top_k, act=cfg.act,
        capacity_factor=cfg.moe.capacity_factor,
        expert_sharding=expert_pspec(),
        dispatch_dtype=cfg.moe.dispatch_dtype,
    )
    return out.reshape(shp), aux


# ---------------------------------------------------------------------------
# Full-sequence block application (training / prefill)
# ---------------------------------------------------------------------------


def _block_full(bp, cfg: ModelConfig, x, positions, valid, build_cache: bool):
    """Apply one super-block. Returns (x, cache_slices, moe_stats)."""
    bp = _dequant_tree(bp, _dtype(cfg))
    cache_out = {}
    moe_loss = jnp.zeros((), jnp.float32)
    for i, spec in enumerate(cfg.block_pattern):
        lp = bp[f"layer_{i}"]
        entry = {}
        if spec.mixer == "attn":
            out, (k, v) = _attn_full(lp["attn"], cfg, spec, x, positions, valid)
            x = x + out
            if build_cache:
                entry["k"], entry["v"] = k, v
        elif spec.mixer == "mamba":
            out, (state, conv_tail) = _mamba_full(lp["mamba"], cfg, x, valid)
            x = x + out
            if build_cache:
                entry["ssm"] = state
                entry["conv"] = conv_tail
        if spec.ffn != "none":
            out, aux = _ffn(lp, cfg, x)
            x = x + out
            if aux is not None:
                E = cfg.moe.num_experts
                f = aux["load"].astype(jnp.float32)
                f = f / jnp.maximum(f.sum(), 1.0)
                moe_loss = moe_loss + E * jnp.sum(f * aux["me"])
        cache_out[f"layer_{i}"] = entry
    return x, cache_out, moe_loss


def _scan_blocks(params, cfg: ModelConfig, x, positions, valid,
                 build_cache: bool, remat: bool):
    def body(carry, bp):
        def inner(c, bp):
            return _block_full(bp, cfg, c, positions, valid, build_cache)
        if remat:
            inner = jax.checkpoint(inner)
        xc, cache, moe_loss = inner(carry, bp)
        return xc, (cache, moe_loss)

    x, (cache, moe_losses) = lax.scan(
        body, x, params["blocks"], unroll=L.in_analysis_mode()
    )
    return x, cache, moe_losses.sum()


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array] = None,  # (B, S) int32
    inputs_embeds: Optional[jax.Array] = None,  # (B, S, d)
    valid: Optional[jax.Array] = None,  # (B, S) bool
    remat: bool = False,
):
    """Returns (hidden (B,S,d), moe_aux_loss)."""
    if cfg.embed_inputs:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = inputs_embeds.astype(_dtype(cfg))
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, _, moe_loss = _scan_blocks(
        params, cfg, x, positions, valid, build_cache=False, remat=remat
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, moe_loss


def lm_logits(params: Params, cfg: ModelConfig, hidden: jax.Array):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum(
        "...d,dv->...v", hidden, head, preferred_element_type=jnp.float32
    )
    if cfg.logit_softcap is not None:
        logits = L._softcap(logits, cfg.logit_softcap)
    return logits


# ---------------------------------------------------------------------------
# Serving: prefill and decode
# ---------------------------------------------------------------------------


def cache_capacity(cfg: ModelConfig, spec: LayerSpec, max_len: int) -> int:
    if spec.window is not None:
        return min(max_len, spec.window)
    return max_len


def _kv_store_dtype(cfg: ModelConfig):
    return jnp.int8 if cfg.kv_dtype == "int8" else _dtype(cfg)


def quantize_kv(x: jax.Array):
    """Per-(position, head) symmetric int8: x (..., Dh) -> (q8, scale)."""
    xf = x.astype(jnp.float32)
    sc = jnp.max(jnp.abs(xf), axis=-1) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(xf / sc[..., None]), -127, 127).astype(jnp.int8)
    return q, sc


def dequantize_kv(q: jax.Array, sc: jax.Array, dt) -> jax.Array:
    return (q.astype(jnp.float32) * sc[..., None]).astype(dt)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    """Empty decode cache (zeros; slot_pos=-1 marks empty slots)."""
    dt = _dtype(cfg)
    kv_dt = _kv_store_dtype(cfg)
    m = cfg.mamba
    n = cfg.n_blocks
    cache = {}
    for i, spec in enumerate(cfg.block_pattern):
        entry = {}
        if spec.mixer == "attn":
            cap = cache_capacity(cfg, spec, max_len)
            entry["k"] = jnp.zeros(
                (n, batch, cap, cfg.n_kv_heads, cfg.head_dim), kv_dt
            )
            entry["v"] = jnp.zeros_like(entry["k"])
            entry["pos"] = jnp.full((n, batch, cap), -1, jnp.int32)
            if cfg.kv_dtype == "int8":
                entry["k_sc"] = jnp.zeros(
                    (n, batch, cap, cfg.n_kv_heads), jnp.float32
                )
                entry["v_sc"] = jnp.zeros_like(entry["k_sc"])
        elif spec.mixer == "mamba":
            di = m.d_inner(cfg.d_model)
            conv_dim = di + 2 * m.d_state
            entry["conv"] = jnp.zeros((n, batch, m.d_conv - 1, conv_dim), dt)
            entry["ssm"] = jnp.zeros(
                (n, batch, m.n_heads(cfg.d_model), m.head_dim, m.d_state),
                jnp.float32,
            )
        cache[f"layer_{i}"] = entry
    return cache


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len))


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: Optional[jax.Array],  # (B, S) int32, left-aligned
    lengths: jax.Array,  # (B,) int32
    inputs_embeds: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
):
    """Run the prompt and build the decode cache.

    Returns (last_logits (B, V), cache). ``max_len`` is the decode cache
    capacity (defaults to S).
    """
    if cfg.embed_inputs:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = inputs_embeds.astype(_dtype(cfg))
    B, S = x.shape[:2]
    max_len = max_len or S
    pos_row = jnp.arange(S, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos_row, (B, S))
    valid = positions < lengths[:, None]

    x, cache_sl, _ = _scan_blocks(
        params, cfg, x, positions, valid, build_cache=True, remat=False
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    # logits at the last valid position of each row
    last = jnp.maximum(lengths - 1, 0)
    h_last = x[jnp.arange(B), last]
    logits = lm_logits(params, cfg, h_last)

    # build ring caches from the full-sequence K/V produced by the scan
    cache = init_cache(cfg, B, max_len)
    for i, spec in enumerate(cfg.block_pattern):
        key = f"layer_{i}"
        entry = cache[key]
        produced = cache_sl[key]
        if spec.mixer == "attn":
            cap = entry["k"].shape[2]
            # keep the last `cap` positions per row (ring layout: slot = pos % cap)
            # produced k/v: (n, B, S, Hkv, Dh)
            kfull, vfull = produced["k"], produced["v"]
            ksc = vsc = None
            if cfg.kv_dtype == "int8":
                kfull, ksc = quantize_kv(kfull)
                vfull, vsc = quantize_kv(vfull)
            take = jnp.arange(cap, dtype=jnp.int32)
            if cap >= S:
                # identity layout; slots >= S stay empty
                entry["k"] = entry["k"].at[:, :, :S].set(kfull)
                entry["v"] = entry["v"].at[:, :, :S].set(vfull)
                if ksc is not None:
                    entry["k_sc"] = entry["k_sc"].at[:, :, :S].set(ksc)
                    entry["v_sc"] = entry["v_sc"].at[:, :, :S].set(vsc)
                pos = jnp.where(
                    (pos_row[None] < lengths[:, None]), pos_row[None], -1
                ).astype(jnp.int32)
                n = entry["pos"].shape[0]
                entry["pos"] = entry["pos"].at[:, :, :S].set(
                    jnp.broadcast_to(pos[None], (n, B, S))
                )
            else:
                # last cap tokens per row, placed at slot = pos % cap
                start = jnp.maximum(lengths - cap, 0)  # (B,)
                src = start[:, None] + take[None, :]  # (B, cap) positions
                slot = src % cap
                bidx = jnp.arange(B)[:, None]
                kg = kfull[:, bidx, src]  # (n, B, cap, Hkv, Dh)
                vg = vfull[:, bidx, src]
                entry["k"] = entry["k"].at[:, bidx, slot].set(kg)
                entry["v"] = entry["v"].at[:, bidx, slot].set(vg)
                if ksc is not None:
                    entry["k_sc"] = entry["k_sc"].at[:, bidx, slot].set(
                        ksc[:, bidx, src]
                    )
                    entry["v_sc"] = entry["v_sc"].at[:, bidx, slot].set(
                        vsc[:, bidx, src]
                    )
                posv = jnp.where(src < lengths[:, None], src, -1)
                entry["pos"] = jnp.broadcast_to(
                    posv[None], entry["pos"].shape
                ).astype(jnp.int32)
        elif spec.mixer == "mamba":
            entry["ssm"] = produced["ssm"]
            entry["conv"] = produced["conv"]
        cache[key] = entry
    return logits, cache


# ---------------------------------------------------------------------------
# Paged serving: KV lives in pool pages, requests carry block tables
# ---------------------------------------------------------------------------


def _check_paged(cfg: ModelConfig) -> None:
    """Reject configs the paged cache cannot serve.  Raises ``ValueError``
    (never ``assert`` — an invalid user config must fail identically
    under ``python -O``).  ``ClusterConfig`` runs the same validation at
    construction so the misconfiguration surfaces before any backend or
    jit work; this copy guards direct model-layer callers."""
    if cfg.has_mamba:
        raise ValueError(
            f"model '{cfg.name}': paged KV covers attention caches only; "
            "recurrent (Mamba) state is constant-size per request and "
            "cannot resume mid-sequence from shared prefix pages — serve "
            "hybrid models with paged=False"
        )
    if cfg.kv_dtype == "int8":
        raise ValueError(
            f"model '{cfg.name}': paged cache does not carry int8 KV "
            "scales yet — set kv_dtype to a float dtype or serve with "
            "paged=False (which supports int8 KV)"
        )


def init_paged_cache(
    cfg: ModelConfig, num_pages: int, page_size: int
) -> PyTree:
    """Paged decode cache: per attention layer, a pool of
    ``(num_pages + 1, page_size, Hkv, Dh)`` K/V pages shared by every
    resident sequence.  Page ids are handed out by
    :class:`~repro.serving.kvpool.KVPool`; token position ``i`` of a
    sequence lives in ``block_table[i // page_size]`` at offset
    ``i % page_size``.  The extra page at index ``num_pages`` is a
    write scratch: masked/padded scatters land there instead of
    corrupting live pages.
    """
    _check_paged(cfg)
    kv_dt = _kv_store_dtype(cfg)
    n = cfg.n_blocks
    cache = {}
    for i, spec in enumerate(cfg.block_pattern):
        entry = {}
        if spec.mixer == "attn":
            entry["k"] = jnp.zeros(
                (n, num_pages + 1, page_size, cfg.n_kv_heads, cfg.head_dim),
                kv_dt,
            )
            entry["v"] = jnp.zeros_like(entry["k"])
        cache[f"layer_{i}"] = entry
    return cache


def _gather_pages(
    pages: jax.Array, block_tables: jax.Array, layer=None
) -> jax.Array:
    """(P+1, ps, H, D) pages × (B, Pmax) tables -> (B, Pmax*ps, H, D)
    dense per-sequence view (−1 table entries clamp to page 0; callers
    mask by length).  With ``layer``, ``pages`` is the whole stacked
    ``(n_blocks, P+1, ps, H, D)`` pool and only that layer's pages are
    read: one gather, no slice of the layer."""
    B, Pmax = block_tables.shape
    idx = jnp.maximum(block_tables, 0)
    g = pages[idx] if layer is None else pages[layer, idx]
    return g.reshape(B, Pmax * g.shape[2], *g.shape[3:])


def prefill_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, S) NEW suffix tokens, left-aligned
    lengths: jax.Array,  # (B,) int32 — number of new tokens
    ctx_lens: jax.Array,  # (B,) int32 — resident prefix (page-aligned)
    block_tables: jax.Array,  # (B, Pmax) page ids over ctx+new, -1 pad
    cache: PyTree,  # paged cache (init_paged_cache)
):
    """Prefill that **writes straight into pool pages**.

    The ``ctx_lens`` resident prefix (a radix prefix-cache hit, already
    in the pool) is *not* recomputed: its K/V pages are gathered for the
    new tokens' attention span, and only the suffix runs the forward.
    New K/V scatters into the pages ``block_tables`` assigns to
    positions ``ctx .. ctx+len``.  With ``ctx_lens == 0`` this is a
    whole-prompt prefill.  Returns ``(last_logits (B, V), cache)``.
    """
    _check_paged(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    B, S = x.shape[:2]
    scratch = jax.tree_util.tree_leaves(cache)[0].shape[1] - 1
    ps = jax.tree_util.tree_leaves(cache)[0].shape[2]
    Pmax = block_tables.shape[1]
    C = Pmax * ps
    pos_row = jnp.arange(S, dtype=jnp.int32)
    positions = ctx_lens[:, None] + pos_row[None]  # (B, S) absolute
    valid = pos_row[None] < lengths[:, None]
    ctx_pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    ctx_valid = ctx_pos < ctx_lens[:, None]
    bidx = jnp.arange(B)[:, None]
    # new token i of row b -> page block_tables[b, pos//ps], offset pos%ps
    pid = block_tables[bidx, positions // ps]  # (B, S)
    pid = jnp.where(valid & (pid >= 0), pid, scratch)
    off = positions % ps

    def body(carry, xs):
        xc = carry
        bp, cache_in = xs
        bp = _dequant_tree(bp, _dtype(cfg))
        cache_out = {}
        for i, spec in enumerate(cfg.block_pattern):
            lp = bp[f"layer_{i}"]
            ci = cache_in[f"layer_{i}"]
            co = {}
            if spec.mixer == "attn":
                h = L.rms_norm(xc, lp["attn"]["norm"], cfg.norm_eps)
                q, k, v = _attn_qkv(lp["attn"], cfg, h)
                if cfg.use_rope:
                    sin, cos = L.rope_sincos(
                        positions, cfg.head_dim, cfg.rope_theta
                    )
                    q = L.apply_rope(q, sin, cos)
                    k = L.apply_rope(k, sin, cos)
                # resident prefix pages join the attention span as-is —
                # this is the zero-recompute prefix reuse
                kg = _gather_pages(ci["k"], block_tables).astype(q.dtype)
                vg = _gather_pages(ci["v"], block_tables).astype(q.dtype)
                o = L.chunked_attention(
                    q,
                    jnp.concatenate([kg, k], axis=1),
                    jnp.concatenate([vg, v], axis=1),
                    causal=True, window=spec.window,
                    softcap=cfg.attn_softcap,
                    q_positions=positions,
                    kv_positions=jnp.concatenate(
                        [ctx_pos, positions], axis=1
                    ),
                    kv_valid=jnp.concatenate([ctx_valid, valid], axis=1),
                    q_chunk=S, k_chunk=C + S,
                )
                out = jnp.einsum(
                    "bse,ed->bsd", o.reshape(B, S, cfg.q_dim),
                    lp["attn"]["wo"],
                )
                xc = xc + out
                co["k"] = ci["k"].at[pid, off].set(k.astype(ci["k"].dtype))
                co["v"] = ci["v"].at[pid, off].set(v.astype(ci["v"].dtype))
            if spec.ffn != "none":
                out, _ = _ffn(lp, cfg, xc)
                xc = xc + out
            cache_out[f"layer_{i}"] = co
        return xc, cache_out

    x, cache = lax.scan(
        body, x, (params["blocks"], cache), unroll=L.in_analysis_mode()
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.maximum(lengths - 1, 0)
    logits = lm_logits(params, cfg, x[jnp.arange(B), last])
    return logits, cache


def decode_step_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B,) int32
    cache: PyTree,  # paged cache (init_paged_cache)
    lengths: jax.Array,  # (B,) int32 — resident tokens == new position
    block_tables: jax.Array,  # (B, Pmax) page ids, -1 pad
):
    """One decode iteration over the paged pool.  The new token's K/V is
    scattered into its sequence's tail page before attention; sequences
    whose table lacks the page (or empty slots, table all -1) write to
    the scratch page.  Returns ``(logits (B, V), new_cache)``.

    This is the ``T == 1`` case of :func:`verify_step_paged` (the tests
    pin the two bit-identical), kept as the single-token API.
    """
    logits, new_cache = verify_step_paged(
        params, cfg, tokens[:, None], cache, lengths, block_tables
    )
    return logits[:, 0], new_cache


def verify_step_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B, T) int32 — pending token + k draft proposals
    cache: PyTree,  # paged cache (init_paged_cache)
    lengths: jax.Array,  # (B,) int32 — resident tokens BEFORE this step
    block_tables: jax.Array,  # (B, Pmax) page ids covering lengths+T, -1 pad
):
    """Speculative *verify*: forward ``T = k + 1`` tokens per sequence in
    one pass over the paged pool.

    Row 0 is the sequence's pending (already-emitted) token, rows
    ``1..k`` its draft proposals; K/V for all ``T`` rows scatters into
    the tail pages ``block_tables`` must already cover, and attention is
    causal within the speculation window (row ``j`` sees positions
    ``<= lengths + j``).  Returns ``(logits (B, T, V), new_cache)`` —
    ``argmax(logits[:, j])`` is the target model's token after position
    ``lengths + j``, which is what accept-prefix sampling compares the
    drafts against.  Rollback of rejected rows is the caller's page
    bookkeeping (:meth:`~repro.serving.kvpool.BlockTable.shrink`): the
    rejected offsets inside kept pages are masked by ``lengths`` until
    the next accepted tokens overwrite them.

    With ``T == 1`` this is exactly :func:`decode_step_paged`.

    The pool travels in the layer scan's **carry**, and each layer's
    pages are written and read in it by layer index, so under donation
    the step updates the pool in place.  It must not ride in ``xs`` /
    ``ys``: the scan would then read one pool and write another, and XLA
    keeps them apart by copying the whole donated pool into the loop and
    slicing each layer out and back every step.
    """
    _check_paged(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, T, d)
    B, T = tokens.shape
    scratch = jax.tree_util.tree_leaves(cache)[0].shape[1] - 1
    ps = jax.tree_util.tree_leaves(cache)[0].shape[2]
    Pmax = block_tables.shape[1]
    C = Pmax * ps
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    bidx = jnp.arange(B)[:, None]
    pid = block_tables[bidx, jnp.minimum(positions // ps, Pmax - 1)]
    # rows past the table's coverage (a near-capacity speculation
    # window) must scatter to scratch, never alias the clamped last
    # page — only rejected rows can sit there (see the caller's
    # capacity contract), so their K/V is disposable by construction
    pid = jnp.where((pid >= 0) & (positions < C), pid, scratch)
    off = positions % ps
    slot_pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))

    def body(carry, xs):
        xc, pool = carry  # (B, T, d), the whole stacked pool
        bp, li = xs
        bp = _dequant_tree(bp, _dtype(cfg))
        pool = dict(pool)
        for i, spec in enumerate(cfg.block_pattern):
            lp = bp[f"layer_{i}"]
            if spec.mixer == "attn":
                pk, pv = pool[f"layer_{i}"]["k"], pool[f"layer_{i}"]["v"]
                h = L.rms_norm(xc, lp["attn"]["norm"], cfg.norm_eps)
                q, k, v = _attn_qkv(lp["attn"], cfg, h)  # (B, T, H, Dh)
                if cfg.use_rope:
                    sin, cos = L.rope_sincos(
                        positions, cfg.head_dim, cfg.rope_theta
                    )
                    q = L.apply_rope(q, sin, cos)
                    k = L.apply_rope(k, sin, cos)
                pk = pk.at[li, pid, off].set(k.astype(pk.dtype))
                pv = pv.at[li, pid, off].set(v.astype(pv.dtype))
                pool[f"layer_{i}"] = {"k": pk, "v": pv}
                kg = _gather_pages(pk, block_tables, li).astype(q.dtype)
                vg = _gather_pages(pv, block_tables, li).astype(q.dtype)
                o = L.verify_attention(
                    q, kg, vg, slot_pos, positions,
                    window=spec.window, softcap=cfg.attn_softcap,
                )
                xc = xc + jnp.einsum(
                    "bte,ed->btd", o.reshape(B, T, cfg.q_dim),
                    lp["attn"]["wo"],
                )
            if spec.ffn != "none":
                out, _ = _ffn(lp, cfg, xc)
                xc = xc + out
        return (xc, pool), None

    (x, new_cache), _ = lax.scan(
        body, (x, cache),
        (params["blocks"], jnp.arange(cfg.n_blocks, dtype=jnp.int32)),
        unroll=L.in_analysis_mode(),
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, cfg, x)  # (B, T, V)
    return logits, new_cache


def draft_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B,) int32
    cache: PyTree,
    lengths: jax.Array,  # (B,) int32 — write position per sequence
):
    """One draft-model proposal step (greedy): a thin wrapper over
    :func:`decode_step` that also returns the argmax proposals, so the
    drafting loop reads ``(proposal, logits, cache)`` per step."""
    logits, cache = decode_step(params, cfg, tokens, cache, lengths)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache


def accept_prefix(
    draft_tokens: jax.Array,  # (B, k) int32 — the k proposals
    target_tokens: jax.Array,  # (B, k+1) int32 — verify-pass argmaxes
) -> jax.Array:
    """Greedy accept-prefix sampling: accepted count per sequence is the
    longest prefix where the draft's proposal matches the target's
    argmax (``target_tokens[:, j]`` is the target's choice after
    position ``j``; ``target_tokens[:, a]`` is the bonus/correction
    token).  Returns (B,) int32 in ``[0, k]``."""
    match = (draft_tokens == target_tokens[:, :-1]).astype(jnp.int32)
    return jnp.sum(jnp.cumprod(match, axis=1), axis=1)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (B,) int32
    cache: PyTree,
    lengths: jax.Array,  # (B,) int32 — tokens generated so far (position)
):
    """One decode iteration. Returns (logits (B, V), new_cache)."""
    x = jnp.take(params["embed"], tokens, axis=0)  # (B, d)
    B = x.shape[0]
    q_pos = lengths

    new_cache = {}

    def body(carry, xs):
        xc = carry
        bp, cache_in = xs
        bp = _dequant_tree(bp, _dtype(cfg))
        cache_out = {}
        for i, spec in enumerate(cfg.block_pattern):
            lp = bp[f"layer_{i}"]
            ci = cache_in[f"layer_{i}"]
            co = {}
            if spec.mixer == "attn":
                cap = ci["k"].shape[1]  # (B, C, H, D) inside scan
                write_slot = q_pos % cap
                out, co = _attn_decode(
                    lp["attn"], cfg, spec, xc, ci, q_pos, write_slot,
                )
                xc = xc + out
            elif spec.mixer == "mamba":
                out, conv, ssm = _mamba_decode(
                    lp["mamba"], cfg, xc, ci["conv"], ci["ssm"]
                )
                xc = xc + out
                co = {"conv": conv, "ssm": ssm}
            if spec.ffn != "none":
                out, _ = _ffn(lp, cfg, xc)
                xc = xc + out
            cache_out[f"layer_{i}"] = co
        return xc, cache_out

    x, new_cache = lax.scan(
        body, x, (params["blocks"], cache), unroll=L.in_analysis_mode()
    )
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, cfg, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Greedy serving entry points (argmax fused into the jitted graph)
# ---------------------------------------------------------------------------
# The serving backend decodes greedily, so the host only ever needs the
# argmax token ids — returning them from inside the jit shrinks the
# device->host transfer from (B, V) logits to (B,) int32 and lets the
# event loop defer the blocking read to token-emission time (the async
# dispatch contract in repro.serving.realengine).


def prefill_greedy(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    lengths: jax.Array,
    max_len: Optional[int] = None,
):
    """:func:`prefill` returning (first token ids (B,), cache)."""
    logits, cache = prefill(params, cfg, tokens, lengths, max_len=max_len)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def prefill_paged_greedy(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    lengths: jax.Array,
    ctx_lens: jax.Array,
    block_tables: jax.Array,
    cache: PyTree,
):
    """:func:`prefill_paged` returning (first token ids (B,), cache)."""
    logits, cache = prefill_paged(
        params, cfg, tokens, lengths, ctx_lens, block_tables, cache
    )
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def decode_step_greedy(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: PyTree,
    lengths: jax.Array,
):
    """:func:`decode_step` returning (next token ids (B,), cache)."""
    logits, cache = decode_step(params, cfg, tokens, cache, lengths)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def decode_step_paged_greedy(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: PyTree,
    lengths: jax.Array,
    block_tables: jax.Array,
):
    """:func:`decode_step_paged` returning (next token ids (B,), cache)."""
    logits, cache = decode_step_paged(
        params, cfg, tokens, cache, lengths, block_tables
    )
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache


def verify_step_paged_greedy(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    cache: PyTree,
    lengths: jax.Array,
    block_tables: jax.Array,
):
    """:func:`verify_step_paged` returning (argmax ids (B, T), cache)."""
    logits, cache = verify_step_paged(
        params, cfg, tokens, cache, lengths, block_tables
    )
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
