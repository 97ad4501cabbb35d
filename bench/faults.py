"""Faults planted under the timed path, each a way a serving cell can go
wrong.  ``bench/tests/test_control.py`` drives a whole run with each and
expects ``correct`` to come out false; ``bench/run.py --fault <name>``
plants one on the chip to read the number it has to fail.

Each takes a pool engine after it is built and patches it in place; the
harness plants it in every shard's engine of a sharded one."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def commit_leaves_state_unchanged(eng):
    """The fused commit writes nothing: the KV pool keeps its old lanes."""
    eng._commit_tree_batch = lambda active, node_paths, Tpad: None


def tree_pass_rows_swapped(eng):
    """Each pool row gets another row's target distributions."""
    for name in ("_target_tree_dispatch", "_target_tree_dispatch_ragged"):
        orig = getattr(eng, name)

        def swapped(*a, _orig=orig):
            p, hid = _orig(*a)
            return jnp.roll(p, 1, axis=0), hid

        setattr(eng, name, swapped)


def served_token_altered(eng):
    """Each step's last served token is replaced by its successor id."""
    orig = eng._advance_stream

    def altered(slot, *a, **k):
        st = eng.streams[slot]
        ev = orig(slot, *a, **k)
        st["out"][-1] = (st["out"][-1] + 1) % eng.tc.vocab
        return ev

    eng._advance_stream = altered


def accept_all_drafts(eng):
    """A lossy verifier: every step accepts the tree's deepest path of draft
    tokens and adds one token drawn from the target there.  The KV pool
    stays consistent with what is served; only the served tokens'
    distribution is wrong."""
    from repro.serving.engine import SpeculativeEngine

    orig = eng.verify_step

    def verify_step(pending):
        v = orig(pending)
        for s in v.accepted:
            tree = pending.trees[s]
            leaf = int(np.argmax(tree.depth))
            p = np.asarray(tree.p[leaf], np.float64)
            v.accepted[s] = tree.path_tokens(leaf)
            v.corr[s] = int(eng.streams[s]["rng"].choice(p.shape[-1], p=p / p.sum()))
            if v.node_paths is not None:
                v.node_paths[s] = SpeculativeEngine._accepted_nodes(tree, v.accepted[s])
        return v

    eng.verify_step = verify_step


FAULTS = {f.__name__: f for f in (commit_leaves_state_unchanged, tree_pass_rows_swapped,
                                  served_token_altered, accept_all_drafts)}
