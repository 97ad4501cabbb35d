"""Fused-commit equivalence: the device-resident commit path's contract.

Property tests (vendored _propcheck shim) that the one-call batched commit
(serve_step.make_pool_commit_step + kernels/commit_kv) leaves the pool
bit-identical to the per-row PR-1 commit chain
(serve_step.commit_row_reference) across random accepted paths, ring-wrap
positions and mixed active/idle slots — for the tree strategy's scatter and
for the replay strategy's fused row write-back — plus the engine-level
guarantee that the commit path issues exactly ONE jitted call per step()
regardless of the active-stream count.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
from _propcheck import given, settings, strategies as st

from repro.core.trees import tree_ancestor_mask
from repro.kernels.commit_kv import commit_kv
from repro.kernels.ref import commit_kv_ref
from repro.models.cache import concat_streams, scatter_streams
from repro.models.config import ModelConfig
from repro.models.transformer import init_params
from repro.serving.batch_engine import BatchedSpeculativeEngine
from repro.serving.engine import EngineConfig, SpeculativeEngine
from repro.serving.serve_step import (
    commit_row_reference,
    device_ancestor_mask,
    make_pool_commit_step,
    next_pow2,
)

L, B, S, H, HD = 2, 4, 16, 2, 4


def _rand_pool(rng):
    return {
        "attn": {
            "k": jnp.asarray(rng.normal(size=(L, B, S, H, HD)).astype(np.float32)),
            "v": jnp.asarray(rng.normal(size=(L, B, S, H, HD)).astype(np.float32)),
            "pos": jnp.asarray(rng.integers(-1, 4 * S, size=(B, S)).astype(np.int32)),
            "len": jnp.asarray(rng.integers(0, 4 * S, size=(B,)).astype(np.int32)),
        }
    }


def _rand_case(rng, Tpad):
    """Random per-row commit inputs honouring the index contract: accepted
    node indices strictly increasing in (0, Tpad), C anywhere in the ring
    (including past S, exercising the modulo wrap)."""
    paths, Cs, act = {}, {}, {}
    for b in range(B):
        act[b] = bool(rng.integers(2))
        tau = int(rng.integers(0, Tpad))
        paths[b] = sorted(rng.choice(np.arange(1, Tpad), size=tau, replace=False).tolist()) if tau else []
        Cs[b] = int(rng.integers(1, 3 * S))
    return paths, Cs, act


def _fused(pool, paths, Cs, act, Tpad, attention_impl):
    cfg = types.SimpleNamespace(attention_impl=attention_impl)
    P = next_pow2(max([len(p) for b, p in paths.items() if act[b]] + [1]))
    npath = np.zeros((B, P), np.int32)
    plen = np.zeros((B,), np.int32)
    C = np.zeros((B,), np.int32)
    active = np.zeros((B,), np.bool_)
    for b in range(B):
        if not act[b]:
            continue
        npath[b, : len(paths[b])] = paths[b]
        plen[b] = len(paths[b])
        C[b] = Cs[b]
        active[b] = True
    commit = make_pool_commit_step(cfg, Tpad)
    return commit(pool, jnp.asarray(npath), jnp.asarray(plen), jnp.asarray(C),
                  jnp.asarray(active))


def _assert_pools_equal(got, want):
    for key in ("k", "v", "pos", "len"):
        assert np.array_equal(np.asarray(got["attn"][key]), np.asarray(want["attn"][key])), key


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 8))
def test_fused_commit_matches_per_row(seed, Tpad):
    rng = np.random.default_rng(seed)
    pool = _rand_pool(rng)
    paths, Cs, act = _rand_case(rng, Tpad)
    ref = pool
    for b in range(B):
        if act[b]:
            ref = commit_row_reference(ref, b, Cs[b], paths[b], Tpad)
    got = _fused(pool, paths, Cs, act, Tpad, "xla")
    _assert_pools_equal(got, ref)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 6))
def test_fused_commit_pallas_kernel_path(seed, Tpad):
    """The Pallas commit_kv route (interpret mode) is bit-identical too."""
    rng = np.random.default_rng(seed)
    pool = _rand_pool(rng)
    paths, Cs, act = _rand_case(rng, Tpad)
    ref = pool
    for b in range(B):
        if act[b]:
            ref = commit_row_reference(ref, b, Cs[b], paths[b], Tpad)
    got = _fused(pool, paths, Cs, act, Tpad, "pallas")
    _assert_pools_equal(got, ref)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
def test_commit_kv_kernel_matches_ref(seed, P):
    """kernels/commit_kv (sequential in-place grid) == gather-then-scatter
    oracle on hazard-free index tables (src disjoint from other dsts)."""
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(L, B, S, H, HD)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(L, B, S, H, HD)).astype(np.float32))
    src = np.zeros((B, P), np.int32)
    dst = np.zeros((B, P), np.int32)
    for b in range(B):
        C = int(rng.integers(0, 3 * S))
        tau = int(rng.integers(0, P + 1))
        nodes = np.sort(rng.choice(np.arange(1, S), size=tau, replace=False)) if tau else []
        for j in range(P):
            if j < tau:  # strictly-increasing nodes from 1 => nodes[j] >= j+1
                src[b, j] = (C + int(nodes[j])) % S
                dst[b, j] = (C + 1 + j) % S
            else:
                src[b, j] = dst[b, j] = C % S
    ko, vo = commit_kv(k, v, jnp.asarray(src), jnp.asarray(dst), interpret=True)
    kr, vr = commit_kv_ref(k, v, jnp.asarray(src), jnp.asarray(dst))
    assert np.array_equal(np.asarray(ko), np.asarray(kr))
    assert np.array_equal(np.asarray(vo), np.asarray(vr))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 12))
def test_device_ancestor_mask_matches_host(seed, T):
    """Device-composed eye/ancestor masks == host tree_ancestor_mask per row,
    with padding rows (parent = -1 everywhere) as isolated roots."""
    rng = np.random.default_rng(seed)
    parents = np.full((B, T), -1, np.int32)
    want = np.zeros((B, T, T), bool)
    for b in range(B):
        n = int(rng.integers(1, T + 1))
        par = [-1] + [int(rng.integers(0, i)) for i in range(1, n)]
        parents[b, :n] = par
        want[b] = np.eye(T, dtype=bool)
        want[b, :n, :n] = tree_ancestor_mask(np.asarray(par))
    got = np.asarray(device_ancestor_mask(jnp.asarray(parents)))
    assert np.array_equal(got, want)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_fused_row_scatter_matches_sequential(seed):
    """Replay-strategy commit write: concat_streams + one scatter_streams ==
    the PR-1 per-group scatter chain (mixed row groups, ssm-style cache)."""
    rng = np.random.default_rng(seed)
    pool = {
        "state": jnp.asarray(rng.normal(size=(L, B, 3, 5)).astype(np.float32)),
        "conv": jnp.asarray(rng.normal(size=(L, B, 2, 7)).astype(np.float32)),
        "len": jnp.asarray(rng.integers(0, 50, size=(B,)).astype(np.int32)),
    }
    rows = [int(r) for r in rng.permutation(B)[: int(rng.integers(1, B + 1))]]
    cut = int(rng.integers(0, len(rows) + 1))
    groups = [g for g in (rows[:cut], rows[cut:]) if g]
    subs = []
    for g in groups:
        subs.append({
            "state": jnp.asarray(rng.normal(size=(L, len(g), 3, 5)).astype(np.float32)),
            "conv": jnp.asarray(rng.normal(size=(L, len(g), 2, 7)).astype(np.float32)),
            "len": jnp.asarray(rng.integers(0, 50, size=(len(g),)).astype(np.int32)),
        })
    seq = pool
    for g, sub in zip(groups, subs):
        seq = scatter_streams(seq, sub, g)
    combined = subs[0] if len(subs) == 1 else concat_streams(subs)
    fused = scatter_streams(pool, combined, [r for g in groups for r in g])
    for key in pool:
        assert np.array_equal(np.asarray(fused[key]), np.asarray(seq[key])), key


# ------------------------------------------------------- engine-level ---

V = 32
DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")


def test_one_commit_call_per_step():
    """Acceptance: the commit path issues exactly one jitted call per step()
    regardless of the active-stream count — counted both by the engine's
    commit counter and by its jit cache (one entry per shape bucket, not one
    per stream)."""
    tc, dc = DENSE_T, DENSE_D
    tp = init_params(tc, jax.random.PRNGKey(0))
    dp = init_params(dc, jax.random.PRNGKey(1))
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    for prompts in ([[1, 2, 3]], [[1, 2, 3], [4, 5], [6, 7, 8, 9]]):
        beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4)
        for i, p in enumerate(prompts):
            beng.submit(p, max_new=12, seed=20 + i)
        n_steps = 0
        while beng.queue or beng.streams:
            if beng.step():
                n_steps += 1
        assert beng.counters["commit_calls"] == n_steps
        commit_entries = [k for k in beng._jit_cache if k.startswith("commit_")]
        # shape buckets only — independent of how many streams were resident
        assert 1 <= len(commit_entries) <= 3, commit_entries
        assert beng.counters["steps_begun"] == n_steps


def test_single_engine_commit_routed_through_primitive():
    """SpeculativeEngine commits through the same fused primitive: its jit
    cache gains commit_* entries and generation still works."""
    tc, dc = DENSE_T, DENSE_D
    tp = init_params(tc, jax.random.PRNGKey(0))
    dp = init_params(dc, jax.random.PRNGKey(1))
    eng = SpeculativeEngine(tc, tp, dc, dp,
                            EngineConfig(verifier="specinfer", K=2, L1=1, L2=1,
                                         max_cache=128, seed=5))
    out = eng.generate([1, 2, 3], max_new=8)
    assert len(out) >= 8
    assert any(k.startswith("commit_") for k in eng._jit_cache)
