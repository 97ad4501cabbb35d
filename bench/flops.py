"""Operations the algorithm needs, counted from shapes.

Every count is of real work: real tokens and real context lengths, no
padding lanes, no unmapped cache blocks, no recomputation.  ``m`` is a model's
sizes as ``weights.dims_of`` gives them.  A multiply-add counts as 2
operations.
"""
from __future__ import annotations

def matmul_params(m: dict, head: bool) -> int:
    """Weights a token multiplies with in one forward (embedding lookup
    excluded; the output head included when ``head``)."""
    d, H, Hkv, hd, f = m["d"], m["H"], m["Hkv"], m["hd"], m["f"]
    per_layer = d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * f
    return m["L"] * per_layer + (d * m["V"] if head else 0)


def attention_flops(m: dict, ctx_sum: int) -> int:
    """QK^T and PV of all layers, summed over query tokens whose key counts
    (each token's own context, itself included) add up to ``ctx_sum``."""
    return 4 * m["L"] * m["H"] * m["hd"] * ctx_sum


def forward_flops(m: dict, n_tokens: int, ctx_sum: int, head: bool = True) -> int:
    """One forward over ``n_tokens`` query tokens: the matmuls of every token
    plus attention over their contexts."""
    return 2 * matmul_params(m, head) * n_tokens + attention_flops(m, ctx_sum)


def prefill_flops(m: dict, T: int) -> int:
    """Causal prefill of ``T`` real tokens; only the last hidden state is
    used, so no output head."""
    return forward_flops(m, T, T * (T + 1) // 2, head=False)
