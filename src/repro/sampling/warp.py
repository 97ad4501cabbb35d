"""Logit/probability warping: temperature + nucleus (top-p) sampling.

All verification algorithms in this framework operate on *warped* target and
draft distributions: the paper evaluates temperatures {0.2..1.2} and nucleus
{0.9, 0.99}.  Losslessness is always w.r.t. the warped target distribution.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def warp_logits(logits: jax.Array, temperature: float = 1.0, top_p: float = 1.0) -> jax.Array:
    """Apply temperature then nucleus filtering to logits.  Returns probabilities.

    Works on any leading batch shape; the last axis is the vocabulary.
    temperature==0 is greedy (one-hot argmax).
    """
    if temperature == 0.0:
        return jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1], dtype=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32) / temperature, axis=-1)
    return warp_probs(probs, top_p=top_p)


def warp_probs(probs: jax.Array, top_p: float = 1.0) -> jax.Array:
    """Nucleus-filter a probability vector (last axis), renormalising.

    Keeps the smallest prefix of the sorted distribution whose mass is
    >= top_p (the token that crosses the threshold is kept, matching HF
    semantics).
    """
    if top_p >= 1.0:
        return probs
    sort_idx = jnp.argsort(probs, axis=-1)[..., ::-1]
    sorted_p = jnp.take_along_axis(probs, sort_idx, axis=-1)
    csum = jnp.cumsum(sorted_p, axis=-1)
    # keep tokens whose *preceding* cumulative mass is < top_p
    keep_sorted = (csum - sorted_p) < top_p
    keep = jnp.zeros_like(keep_sorted)
    keep = jnp.put_along_axis(keep, sort_idx, keep_sorted, axis=-1, inplace=False)
    filtered = jnp.where(keep, probs, 0.0)
    return filtered / jnp.sum(filtered, axis=-1, keepdims=True)


def sample_categorical(key: jax.Array, probs: jax.Array) -> jax.Array:
    """Sample token indices from probability vectors (last axis = vocab)."""
    # Gumbel trick on log-probs; robust to zeros.
    logp = jnp.log(jnp.clip(probs, 1e-30, None))
    g = jax.random.gumbel(key, probs.shape, dtype=jnp.float32)
    g = jnp.where(probs > 0, g, -jnp.inf)
    return jnp.argmax(logp + g, axis=-1)


def stream_key(seed: int) -> np.ndarray:
    """A stream's threefry key, ``(2,)`` uint32: its seed's high and low
    32-bit words, the key ``jax.random.PRNGKey(seed)`` builds from a 64-bit
    seed.  Seeds are any integer below 2**64 (the benchmark's reach 2**62),
    so both words count."""
    s = int(seed) % (1 << 64)
    return np.asarray([s >> 32, s & 0xFFFFFFFF], np.uint32)


def draw_keyed(q: jax.Array, key: jax.Array, C: jax.Array, node: jax.Array) -> jax.Array:
    """Draw one draft token per row from warped distributions ``q`` (rows, V).

    Row r's key is ``fold_in(fold_in(key[r], C[r]), node[r])``: ``key``
    (rows, 2) is the row's stream key (``stream_key``), ``C`` the stream's
    committed length when the step began and ``node`` the drafted node's
    index in the delayed-tree layout (``core.trees.delayed_layout``).  The
    draw is Gumbel-max on ``log q`` (``sample_categorical``), with the noise
    built per row from its own key, so a row's token depends on its key and
    its q alone — never on which other rows share the call.  Tokens of zero
    mass are never drawn.  Both engines draw every draft token through this
    one primitive, which is what keeps their drafts token-identical."""

    def one(q_r, k, c, n):
        return sample_categorical(jax.random.fold_in(jax.random.fold_in(k, c), n), q_r)

    return jax.vmap(one)(q, key, C, node).astype(jnp.int32)
