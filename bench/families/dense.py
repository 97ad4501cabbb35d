"""The dense family: a decoder of grouped-query attention and SwiGLU layers,
the program's ``arch_type`` ``dense`` (``repro.models.transformer``).

What the harness needs of a family, as a module of its own (see
``bench/run.py``):

- ``dims(block)``: the sizes, from a configuration or draft block in Hugging
  Face key names; hashable values, ``V`` among them;
- ``program_dims(cfg)``: the same sizes read from the program's
  ``ModelConfig``;
- ``weights(key, m, dtype)``: random weights in the program's layout;
- ``forward_flops``, ``prefill_flops``: the real work of a forward;
- optionally ``warm_up(eng, traffic)``; the dense family uses the harness's.

Weights, in the layout ``init_params`` gives a dense model:

    {"embed": (V, d), "final_ln": (d,), ["lm_head": (d, V)],
     "blocks": {"ln1", "ln2": (L, d),
                "attn": {"wq": (L, d, H*hd), "wk"/"wv": (L, d, Hkv*hd),
                         "wo": (L, H*hd, d)},
                "mlp": {"w_gate"/"w_up": (L, d, f), "w_down": (L, f, d)}}}

Scales follow the program's own initialiser (N(0, 1/din) matrices,
N(0, 0.02^2) embedding); the norm scales, which the program starts at zero,
are drawn N(0, 0.1^2) here so that a model that ignored them would not
agree.

Work is counted from shapes, of real work only: real tokens and real
context lengths, no padding lanes, no unmapped cache blocks, no
recomputation.  A multiply-add counts as 2 operations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    return {"d": d, "L": c["num_hidden_layers"], "H": H,
            "Hkv": c["num_key_value_heads"], "hd": c.get("head_dim") or d // H,
            "f": c["intermediate_size"], "V": c["vocab_size"],
            "tied": bool(c["tie_word_embeddings"]), "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"])}


def program_dims(c) -> dict:
    return {"d": c.d_model, "L": c.n_layers, "H": c.n_heads, "Hkv": c.n_kv_heads,
            "hd": c.hd, "f": c.d_ff, "V": c.vocab, "tied": c.tie_embeddings,
            "theta": c.rope_theta, "eps": c.norm_eps}


def weights(key, m: dict, dtype) -> dict:
    d, L, H, Hkv, hd, f, V = (m[k] for k in ("d", "L", "H", "Hkv", "hd", "f", "V"))
    ks = iter(jax.random.split(key, 12))

    def mat(shape, din):
        return jax.random.normal(next(ks), shape, dtype) * jnp.asarray(1.0 / np.sqrt(din), dtype)

    def norm(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * 0.1

    p = {
        "embed": jax.random.normal(next(ks), (V, d), dtype) * jnp.asarray(0.02, dtype),
        "final_ln": norm((d,)),
        "blocks": {
            "ln1": norm((L, d)),
            "ln2": norm((L, d)),
            "attn": {"wq": mat((L, d, H * hd), d), "wk": mat((L, d, Hkv * hd), d),
                     "wv": mat((L, d, Hkv * hd), d), "wo": mat((L, H * hd, d), H * hd)},
            "mlp": {"w_gate": mat((L, d, f), d), "w_up": mat((L, d, f), d),
                    "w_down": mat((L, f, d), f)},
        },
    }
    if not m["tied"]:
        p["lm_head"] = mat((d, V), d)
    return p


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
