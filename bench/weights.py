"""Random weights made on the device from the seed, in the layout of the
configuration's family (``bench/families/<family>.py``: its ``weights``).

One jitted call builds every leaf of the target and the draft, directly in
the dtype they are served in (bfloat16 matrices; what else the family's
layout holds in float32 stays float32).  The plain reference regenerates
the same arrays with the same call after the program's state is freed, so
it takes nothing the program made.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (beyond 32 bits too)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


@partial(jax.jit, static_argnums=(0, 2, 3))
def _make(tree, key, target: tuple, draft: tuple):
    kt, kd = jax.random.split(key)
    return (tree(kt, dict(target), jnp.bfloat16), tree(kd, dict(draft), jnp.bfloat16))


def make_weights(family, seed: int, target: dict, draft: dict):
    """(target params, draft params) for ``seed``, built by ``family``'s
    ``weights`` on the default device in one compiled call."""
    return _make(family.weights, key_of(seed), tuple(sorted(target.items())),
                 tuple(sorted(draft.items())))
