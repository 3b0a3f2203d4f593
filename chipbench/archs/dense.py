"""The dense decoder: pre-norm GQA attention and a SwiGLU MLP per layer.

A configuration whose ``architecture`` is ``"dense"`` is run through
this module: the program's ``ModelConfig``, the weights in the layout
the program's dense path reads, the plain float32 reference of its
forward pass, and the operations and bytes its served work needs.

**Weights.**  Random, from the run's seed, made on the device in one
jitted call in the served dtype, block leaves stacked over layers.
Norm weights are stored as offsets from 1, as the program applies them.

**Reference.**  The published equations of the configuration file
(pre-norm RMSNorm, rotary positions on ``partial_rotary_factor`` of
each head with the half-split rotation, causal grouped-query attention,
SwiGLU, MiniCPM's ``scale_emb`` / ``scale_depth`` / ``dim_model_base``
where the file has them) in ``jax.numpy`` and float32 at ``highest``
matmul precision.  It imports nothing of the program; it reads the
weights the benchmark made, one layer at a time (each layer is widened
to float32 inside the layer scan), one sequence at a time.

**Work.**  What the algorithm requires, not what an implementation
happens to do: a decode row attends over its real context (not the
pages a step gathers), a prefill computes its real new tokens (not the
padded bucket; not a prefix it reuses) and the logits of its last
position only.  A multiply-add counts as two operations.
"""
from __future__ import annotations

import math
from functools import lru_cache

import numpy as np

from model import DTYPE_BYTES, key_of

# ---------------------------------------------------------------------------
# The program's config and the weights
# ---------------------------------------------------------------------------


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``."""
    from repro.configs.base import LayerSpec, ModelConfig

    return ModelConfig(
        name=c["name"],
        family="dense",
        n_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        block_pattern=(LayerSpec(mixer="attn", ffn="mlp"),),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        act=c["hidden_act"],
        dtype=c["torch_dtype"],
    )


def kv_bytes_per_token(c: dict) -> int:
    """K and V of one token over every layer."""
    return (2 * int(c["num_hidden_layers"]) * int(c["num_key_value_heads"])
            * int(c["head_dim"]) * DTYPE_BYTES[c["torch_dtype"]])


def weight_shapes(c: dict) -> dict:
    """Leaf name -> shape, in the program's dense layout (block leaves
    stacked over layers)."""
    L, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    qd = int(c["num_attention_heads"]) * int(c["head_dim"])
    kvd = int(c["num_key_value_heads"]) * int(c["head_dim"])
    ff, V = int(c["intermediate_size"]), int(c["vocab_size"])
    s = {
        "embed": (V, d),
        "attn.norm": (L, d), "attn.wq": (L, d, qd), "attn.wk": (L, d, kvd),
        "attn.wv": (L, d, kvd), "attn.wo": (L, qd, d),
        "mlp.norm": (L, d), "mlp.w_gate": (L, d, ff), "mlp.w_in": (L, d, ff),
        "mlp.w_out": (L, ff, d),
        "final_norm": (d,),
    }
    if not c["tie_word_embeddings"]:
        s["lm_head"] = (d, V)
    return s


def make_weights(c: dict, seed: int):
    """Random weights from ``seed``, on the device, in one jitted call."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(c)
    dt = jnp.dtype(c["torch_dtype"])
    L = int(c["num_hidden_layers"])

    def init(key):
        keys = jax.random.split(key, len(shapes))
        flat = {}
        for k, (name, shp) in zip(keys, sorted(shapes.items())):
            leaf = name.rsplit(".", 1)[-1]
            if leaf.endswith("norm"):
                std = 0.1  # offsets from 1
            elif leaf in ("embed", "lm_head"):
                std = 0.02
            else:  # fan-in scaled, output projections by depth too
                std = 1.0 / np.sqrt(shp[-2])
                if leaf in ("wo", "w_out"):
                    std /= np.sqrt(2 * L)
            flat[name] = (jax.random.normal(k, shp, dt) * std).astype(dt)
        p = {
            "embed": flat["embed"],
            "blocks": {"layer_0": {
                "attn": {n: flat["attn." + n]
                         for n in ("norm", "wq", "wk", "wv", "wo")},
                "mlp": {n: flat["mlp." + n]
                        for n in ("norm", "w_gate", "w_in", "w_out")},
            }},
            "final_norm": flat["final_norm"],
        }
        if "lm_head" in flat:
            p["lm_head"] = flat["lm_head"]
        return p

    return jax.block_until_ready(jax.jit(init)(key_of(seed)))


# ---------------------------------------------------------------------------
# The plain float32 reference
# ---------------------------------------------------------------------------


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
def _gap_fn(key: tuple, T: int, chunk: int):
    """Jitted (weights, tokens (T,), served (T,), alt (T,)) -> (gap of
    served, gap of alt), each (T,) float32; ``served[t]`` is the token
    served after position t.  Logits in blocks of ``chunk`` positions."""
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

        n = T // chunk
        gs, ga = lax.map(block, (h.reshape(n, chunk, d),
                                 served.reshape(n, chunk),
                                 alt.reshape(n, chunk)))
        return gs.reshape(T), ga.reshape(T)

    return jax.jit(fn)


def config_key(c: dict) -> tuple:
    keep = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size",
            "vocab_size", "tie_word_embeddings", "rope_theta",
            "partial_rotary_factor", "rms_norm_eps", "scale_emb",
            "scale_depth", "dim_model_base")
    return tuple(sorted((k, c[k]) for k in keep if k in c))


def gap_fn(c: dict, T: int, chunk: int):
    """The reference's jitted gap function at padded length ``T``."""
    return _gap_fn(config_key(c), T, chunk)


# ---------------------------------------------------------------------------
# Operations and bytes of the served work
# ---------------------------------------------------------------------------


def _dims(c: dict):
    d = int(c["hidden_size"])
    qd = int(c["num_attention_heads"]) * int(c["head_dim"])
    kvd = int(c["num_key_value_heads"]) * int(c["head_dim"])
    return d, qd, kvd, int(c["intermediate_size"]), int(c["vocab_size"])


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, qd, kvd, ff, _ = _dims(c)
    return d * qd + 2 * d * kvd + qd * d + 3 * d * ff


def head_params(c: dict) -> int:
    d, _, _, _, V = _dims(c)
    return d * V


def attn_flops(c: dict, q_pos_sum: int) -> int:
    """Scores and weighted values of one layer, for queries that attend
    ``q_pos_sum`` keys in all (each query sees its own position too)."""
    qd = int(c["num_attention_heads"]) * int(c["head_dim"])
    return 4 * qd * q_pos_sum


def prefill_flops(c: dict, computed: int, ctx: int) -> int:
    """A prefill that computes ``computed`` new tokens after ``ctx``
    reused ones: every layer over the new tokens, their queries over the
    reused context and causally over the new tokens, and the logits of
    the last position.  With ``ctx`` 0 this is a whole-prompt prefill."""
    L = int(c["num_hidden_layers"])
    keys = computed * ctx + computed * (computed + 1) // 2
    return (2 * L * layer_matmul_params(c) * computed
            + L * attn_flops(c, keys) + 2 * head_params(c))


def decode_flops(c: dict, contexts) -> int:
    """One decode step: each row reads ``ctx`` resident tokens and its
    new one, and computes its logits."""
    L = int(c["num_hidden_layers"])
    rows = len(contexts)
    keys = sum(int(x) + 1 for x in contexts)
    return (rows * (2 * L * layer_matmul_params(c) + 2 * head_params(c))
            + L * attn_flops(c, keys))


def weight_bytes(c: dict) -> int:
    """Bytes of every weight a decode step reads once: the layers, the
    norms and the output head (the embedding rows of the input tokens
    are negligible and left out)."""
    L, d = int(c["num_hidden_layers"]), int(c["hidden_size"])
    per = layer_matmul_params(c) + 2 * d
    return DTYPE_BYTES[c["torch_dtype"]] * (L * per + head_params(c) + d)


def decode_bytes(c: dict, contexts) -> int:
    """One decode step: the weights once, the K/V of each row's real
    context, and the K/V written for each row's new token."""
    kv = kv_bytes_per_token(c)
    return (weight_bytes(c) + kv * sum(int(x) for x in contexts)
            + kv * len(contexts))
