"""Operations and bytes that the served work needs, from the shapes.

These count what the algorithm requires, not what an implementation
happens to do: a decode row attends over its real context (not the
pages a step gathers), a prefill computes its real prompt tokens (not
the padded bucket) and the logits of its last position only.  A later
change that gathers fewer pages or pads less therefore shows as a
higher share of the peak, and these counts do not go stale.

A multiply-add counts as two operations.
"""
from __future__ import annotations

from model import DTYPE_BYTES, kv_bytes_per_token


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


def prefill_flops(c: dict, n_tokens: int) -> int:
    """A whole-prompt prefill of ``n_tokens``: every layer over every
    token, causal attention, and the logits of the last position."""
    L = int(c["num_hidden_layers"])
    causal = n_tokens * (n_tokens + 1) // 2
    return (2 * L * layer_matmul_params(c) * n_tokens
            + L * attn_flops(c, causal) + 2 * head_params(c))


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
