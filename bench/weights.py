"""Random weights of a dense decoder, made on the device from the seed.

One jitted call builds every leaf of the target and the draft, directly in
the dtype they are served in (bfloat16 matrices, float32 norm scales), in
the layout ``repro.models.transformer.init_params`` gives a dense model:

    {"embed": (V, d), "final_ln": (d,), ["lm_head": (d, V)],
     "blocks": {"ln1", "ln2": (L, d),
                "attn": {"wq": (L, d, H*hd), "wk"/"wv": (L, d, Hkv*hd),
                         "wo": (L, H*hd, d)},
                "mlp": {"w_gate"/"w_up": (L, d, f), "w_down": (L, f, d)}}}

The plain reference regenerates the same arrays with the same call after the
program's state is freed, so it takes nothing the program made.  Scales
follow the program's own initialiser (N(0, 1/din) matrices, N(0, 0.02^2)
embedding); the norm scales, which the program starts at zero, are drawn
N(0, 0.1^2) here so that a model that ignored them would not agree.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def dims_of(c: dict) -> dict:
    """The sizes the weights and the reference need, from a configuration
    block in Hugging Face key names."""
    d = c["hidden_size"]
    H = c["num_attention_heads"]
    return {"d": d, "L": c["num_hidden_layers"], "H": H,
            "Hkv": c["num_key_value_heads"], "hd": c.get("head_dim") or d // H,
            "f": c["intermediate_size"], "V": c["vocab_size"],
            "tied": bool(c["tie_word_embeddings"]), "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"])}


def key_of(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (beyond 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _tree(key, m: dict, dtype) -> dict:
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


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, target: tuple, draft: tuple):
    kt, kd = jax.random.split(key)
    return (_tree(kt, dict(target), jnp.bfloat16), _tree(kd, dict(draft), jnp.bfloat16))


def make_weights(seed: int, target: dict, draft: dict):
    """(target params, draft params) for ``seed``, built on the default
    device in one compiled call."""
    return _make(key_of(seed), tuple(sorted(target.items())), tuple(sorted(draft.items())))
