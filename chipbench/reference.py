"""Plain float32 reference of the dense decoder, and the served-token gap.

The forward pass follows the published equations of the configuration
file (pre-norm RMSNorm, rotary positions on ``partial_rotary_factor``
of each head with the half-split rotation, causal grouped-query
attention, SwiGLU, MiniCPM's ``scale_emb`` / ``scale_depth`` /
``dim_model_base`` where the file has them) in ``jax.numpy`` and float32
at ``highest`` matmul precision.  It imports nothing of the program; it
reads the weights the benchmark made, one layer at a time (each layer is
widened to float32 inside the layer scan), one sequence at a time.

For a served request the sequence is its prompt followed by the tokens
it was served, the last one left out.  At the position before each
served token the reference's logits give the **gap**: how far the served
token's logit lies below the reference's best.  Greedy decoding that is
right up to rounding serves tokens with a gap near 0 (a flip between two
near-equal logits); a wrong token lies a logit spread below the best.
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

BUCKETS = (512, 1024, 2048)
CHUNK = 256  # positions per block of logits


def bucket(n: int) -> int:
    for b in BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"sequence of {n} tokens is longer than {BUCKETS[-1]}")


def _rope(x, pos, rot: int, theta: float):
    """Half-split rotation of the first ``rot`` dims of each head.
    x: (T, H, Dh) float32; pos: (T,)."""
    import jax.numpy as jnp

    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


@lru_cache(maxsize=None)
def _gap_fn(key: tuple, T: int):
    """Jitted (weights, tokens (T,), served (T,), alt (T,)) -> (gap of
    served, gap of alt), each (T,) float32; ``served[t]`` is the token
    served after position t."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    c = dict(key)
    L, d = c["num_hidden_layers"], c["hidden_size"]
    Hq, Hkv, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    G = Hq // Hkv
    eps = c["rms_norm_eps"]
    rot = int(round(c.get("partial_rotary_factor", 1.0) * Dh))
    emb_scale = float(c.get("scale_emb", 1.0))
    res_scale = (float(c["scale_depth"]) / math.sqrt(L)
                 if "scale_depth" in c else 1.0)
    logit_scale = (float(c["dim_model_base"]) / d
                   if "dim_model_base" in c else 1.0)
    hi = lax.Precision.HIGHEST
    f32 = jnp.float32

    def norm(x, w):
        x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return x * (1.0 + w.astype(f32))

    def mm(x, w):
        return jnp.matmul(x, w.astype(f32), precision=hi)

    pos = jnp.arange(T, dtype=jnp.int32)
    causal = pos[:, None] >= pos[None, :]

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = norm(x, a["norm"])
        q = mm(h, a["wq"]).reshape(T, Hq, Dh)
        k = mm(h, a["wk"]).reshape(T, Hkv, Dh)
        v = mm(h, a["wv"]).reshape(T, Hkv, Dh)
        q, k = _rope(q, pos, rot, c["rope_theta"]), _rope(k, pos, rot,
                                                          c["rope_theta"])
        k = jnp.repeat(k, G, axis=1)  # query head h reads kv head h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=hi) / math.sqrt(Dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v,
                       precision=hi)
        x = x + res_scale * mm(o.reshape(T, Hq * Dh), a["wo"])
        h = norm(x, m["norm"])
        g = jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_in"])
        return x + res_scale * mm(g, m["w_out"]), None

    def fn(w, tokens, served, alt):
        x = jnp.take(w["embed"], tokens, axis=0).astype(f32) * emb_scale
        x, _ = lax.scan(layer, x, w["blocks"]["layer_0"])
        h = norm(x, w["final_norm"]) * logit_scale
        head = w["embed"] if c["tie_word_embeddings"] else w["lm_head"].T

        def block(args):
            hb, sb, ab = args
            lg = jnp.matmul(hb, head.astype(f32).T, precision=hi)  # (C, V)
            best = lg.max(axis=-1)
            pick = lambda t: jnp.take_along_axis(lg, t[:, None], 1)[:, 0]
            return best - pick(sb), best - pick(ab)

        n = T // CHUNK
        gs, ga = lax.map(block, (h.reshape(n, CHUNK, d),
                                 served.reshape(n, CHUNK),
                                 alt.reshape(n, CHUNK)))
        return gs.reshape(T), ga.reshape(T)

    return jax.jit(fn)


def config_key(c: dict) -> tuple:
    keep = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "tie_word_embeddings", "rope_theta",
            "partial_rotary_factor", "rms_norm_eps", "scale_emb",
            "scale_depth", "dim_model_base")
    return tuple(sorted((k, c[k]) for k in keep if k in c))


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
    g_s, g_a = _gap_fn(config_key(c), T)(weights, jnp.asarray(toks),
                                         jnp.asarray(sv), jnp.asarray(al))
    sl = slice(P - 1, P - 1 + n)
    return (np.asarray(g_s, np.float64)[sl], np.asarray(g_a, np.float64)[sl])
