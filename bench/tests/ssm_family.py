"""A family for the program's ``ssm`` arch (Mamba-2, ``models/ssm.py``),
brought as a module of its own the way a configuration of another
architecture brings one: the harness runs it with no file of its own
edited.  Test-only: no cell uses it, and it has no plain reference.

Sizes, in Hugging Face ``Mamba2Config`` key names: ``d`` hidden_size, ``L``
num_hidden_layers, ``N`` state_size, ``P`` head_dim, ``E`` expand, ``G``
n_groups, ``K`` conv_kernel, ``chunk`` chunk_size, ``V`` vocab_size, ``tied``
tie_word_embeddings, ``eps`` layer_norm_epsilon.

The program serves a recurrent target by replay (``batch_engine``'s
``_target_replay``, ``_commit_replay``): its programs are shaped by how a
step's rows split into groups of equal length, and its prefill by the exact
prompt length, so ``warm_up`` walks every such split and every prompt length
the traffic draws.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from traffic import quantiles


def dims(c: dict) -> dict:
    return {"d": c["hidden_size"], "L": c["num_hidden_layers"], "N": c["state_size"],
            "P": c["head_dim"], "E": c["expand"], "G": c["n_groups"], "K": c["conv_kernel"],
            "chunk": c["chunk_size"], "V": c["vocab_size"],
            "tied": bool(c["tie_word_embeddings"]), "eps": float(c["layer_norm_epsilon"])}


def program_dims(c) -> dict:
    return {"d": c.d_model, "L": c.n_layers, "N": c.ssm_state, "P": c.ssm_headdim,
            "E": c.ssm_expand, "G": c.ssm_groups, "K": c.ssm_conv, "chunk": c.ssm_chunk,
            "V": c.vocab, "tied": c.tie_embeddings, "eps": c.norm_eps}


def _widths(m: dict) -> tuple[int, int, int]:
    di = m["E"] * m["d"]
    return di, di // m["P"], di + 2 * m["G"] * m["N"]   # inner width, heads, conv channels


def weights(key, m: dict, dtype) -> dict:
    d, L, V = m["d"], m["L"], m["V"]
    di, H, conv = _widths(m)
    ks = iter(jax.random.split(key, 12))

    def mat(shape, din):
        return jax.random.normal(next(ks), shape, dtype) * jnp.asarray(1.0 / np.sqrt(din), dtype)

    def f32(shape, scale):
        return jax.random.normal(next(ks), shape, jnp.float32) * scale

    p = {
        "embed": jax.random.normal(next(ks), (V, d), dtype) * jnp.asarray(0.02, dtype),
        "final_ln": f32((d,), 0.1),
        "blocks": {
            "ln": f32((L, d), 0.1),
            "ssm": {"w_in": mat((L, d, 2 * di + 2 * m["G"] * m["N"] + H), d),
                    "conv_w": mat((L, m["K"], conv), m["K"]),
                    "conv_b": jax.random.normal(next(ks), (L, conv), dtype) * jnp.asarray(0.1, dtype),
                    "A_log": jnp.log(jax.random.uniform(next(ks), (L, H), jnp.float32, 1.0, 16.0)),
                    "D": 1.0 + f32((L, H), 0.1),
                    "dt_bias": f32((L, H), 0.1),
                    "w_out": mat((L, di, d), di),
                    "norm_z": f32((L, di), 0.1)},
        },
    }
    if not m["tied"]:
        p["lm_head"] = mat((d, V), d)
    return p


def forward_flops(m: dict, n_tokens: int, ctx_sum: int, head: bool = True) -> int:
    """One forward over ``n_tokens`` tokens: per layer the input and output
    projections, the causal convolution, and the state's update and read
    (a multiply-add per state entry each); no attention, so ``ctx_sum``
    adds nothing."""
    d = m["d"]
    di, H, conv = _widths(m)
    per_layer = (2 * d * (2 * di + 2 * m["G"] * m["N"] + H) + 2 * di * d
                 + 2 * m["K"] * conv + 4 * di * m["N"])
    return n_tokens * (m["L"] * per_layer + (2 * d * m["V"] if head else 0))


def prefill_flops(m: dict, T: int) -> int:
    return forward_flops(m, T, 0, head=False)


def compositions(a: int, most: int):
    """Every ordered split of ``a`` rows into at most ``most`` groups."""
    if a == 0:
        yield ()
    elif most > 0:
        for g in range(1, a + 1):
            for rest in compositions(a - g, most - 1):
                yield (g, *rest)


def warm_up(eng, traffic: dict) -> None:
    """Compile every program the replay strategy reaches under ``traffic``:
    the prefill of every prompt length it draws, then a step's programs for
    each split of the active rows into groups by the tokens a row ingests
    and commits (1 .. 1 + L1 + L2), each group length on the whole pool.
    The pools are left as they were found."""
    from repro.sampling import stream_key

    e = traffic["engine"]
    for T in sorted(set(quantiles(traffic["prompt"], traffic["requests_per_client"]).tolist())):
        for cfg, params, name in ((eng.tc, eng.tp, "tgt"), (eng.dc, eng.dp, "drf")):
            eng._prefill_row(cfg, params, [0] * (T - 1), name)
    act = (e["K"], e["L1"], e["L2"])
    pads = eng._bucket_actions({0: act})
    most = 1 + e["L1"] + e["L2"]
    n = eng.n_slots
    plans = [((L, n),) for L in range(1, most + 1)]
    plans += [tuple(zip(range(1, most + 1), c)) for a in range(1, n + 1)
              for c in compositions(a, most)]
    saved_streams, saved_pool = eng.streams, jax.tree.map(jnp.copy, eng.tpool.cache)
    key = stream_key(0)
    for plan in plans:
        lens = [L for L, g in plan for _ in range(g)]
        rows = list(range(len(lens)))
        eng.streams = {s: {"draft_delta": [0] * L, "key": key, "committed": [0] * 8,
                           "pending": 0} for s, L in zip(rows, lens)}
        acts = {s: act for s in rows}
        if eng.pipeline:
            eng.dpool.begin_frame()
        q0, _ = eng._ingest_deltas(rows)
        trees, tok_dev, _ = eng._draft_trees(rows, acts, q0, pads)
        eng._fill_tokens(trees, tok_dev)
        if eng.pipeline:
            eng.dpool.rollback_frame()
        snapshot, _ = eng._target_replay(rows, trees, acts, pads[0])
        eng._commit_replay(rows, snapshot, {s: [0] * (L - 1) for s, L in zip(rows, lens)})
    eng.streams, eng.tpool.cache = saved_streams, saved_pool
    jax.block_until_ready((eng.tpool.cache, eng.dpool.cache))
