"""Required work, counted from shapes: the floating-point operations that
the mathematics of the model needs, independent of how the program
implements it (no padding, no recomputation).

Conventions: a multiply-add is 2 operations; ``ctx`` is the number of key
positions a query attends to (itself included).
"""
from __future__ import annotations


# -- dense GQA (InternLM2 / Llama layout) -----------------------------------

def dense_matmul_params(m: dict) -> int:
    """Parameters that multiply each token: every layer's projections and
    MLP, and the output head (the embedding is a lookup)."""
    d, H, Hkv, hd, ff, V, L = (m["d"], m["H"], m["Hkv"], m["hd"], m["ff"],
                               m["V"], m["L"])
    attn = d * (H + 2 * Hkv) * hd + H * hd * d
    return L * (attn + 3 * d * ff) + d * V


def dense_attention_flops(m: dict, ctx: int) -> int:
    """Scores and weighted values of one query over ``ctx`` positions."""
    return m["L"] * 4 * m["H"] * m["hd"] * ctx


def causal_sum(n: int, start: int = 0) -> int:
    """sum of ctx over n consecutive positions start..start+n-1
    (ctx = position + 1)."""
    return n * start + n * (n + 1) // 2

