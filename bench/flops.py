"""Operations and bytes a dense decoder's work needs, from its shapes.

Counted by what the algorithm needs, not by what today's code does:
attention reads only the keys and values of live tokens, prefill counts
the real prompt tokens under a causal mask, and nothing counts padding.
``m`` is a configuration's ``model`` block (``bench/configs/*.json``).
"""

from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Weights multiplied per token: attention and MLP of every layer, and
    the output head. The embedding table is gathered, not multiplied."""
    d, f = m["d_model"], m["d_ff"]
    q = m["n_heads"] * m["head_dim"]
    kv = m["n_kv_heads"] * m["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return m["n_layers"] * per_layer + d * m["vocab"]


def param_count(m: dict) -> int:
    """Every parameter: the multiplied ones, the embedding table and the
    RMSNorm weights (two per layer and the final one)."""
    d = m["d_model"]
    return matmul_params(m) + d * m["vocab"] + (2 * m["n_layers"] + 1) * d


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """Keys and values of one token in every layer."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def token_flops(m: dict, context: int) -> int:
    """One new token attending to ``context`` positions (itself included):
    2 per multiplied weight, and 2 x 2 per head dimension and position for
    the scores and the weighted sum of values."""
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * context
    return 2 * matmul_params(m) + attn


def prefill_flops(m: dict, n: int) -> int:
    """A prompt of ``n`` real tokens, causal: position i attends to i + 1."""
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * n * (n + 1) // 2
    return 2 * matmul_params(m) * n + attn


def decode_bytes(m: dict, contexts: list[int], itemsize: int = 2) -> int:
    """Least HBM traffic of one decode call over live slots.

    ``contexts`` are the positions each slot attends, its new token
    included. Every weight is read once, and the embedding row of each new
    token; the keys and values of each slot's earlier positions are read
    once and the new token's are written once."""
    d = m["d_model"]
    weights = (matmul_params(m) + (2 * m["n_layers"] + 1) * d) * itemsize
    kv = kv_bytes_per_token(m, itemsize)
    return weights + len(contexts) * d * itemsize + kv * sum(contexts)
