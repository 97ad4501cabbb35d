"""Paged KV pool: token-identity with the PR-1 ring pool + block lifecycle.

The paged pool (models/cache.py paged layout) must be a pure indirection:
with ring-equivalent capacity the engine's scheduling is unchanged and the
emitted tokens are identical to the ring pool for every verifier and both
target-pass strategies, across admissions, capacity evictions and commit
ring-wraps.  On top of that, the block lifecycle — admission gating on the
free list, dead-tail reclamation, LIFO pressure eviction — must let long
and short streams co-reside in an arena the ring design could not share.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.models.cache import (
    PagedCachePool,
    concat_streams,
    fork_streams,
    gather_streams,
    init_paged_attn_cache,
    merge_streams,
    scatter_streams,
)
from repro.models.config import ModelConfig
from repro.models.transformer import init_cache, init_params
from repro.serving.batch_engine import BatchedSpeculativeEngine
from repro.serving.engine import EngineConfig
from repro.serving.serve_step import make_pool_commit_step, next_pow2

V = 32
DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
HYB_CFG = ModelConfig(name="h", arch_type="hybrid", n_layers=5, d_model=48, n_heads=4,
                      n_kv_heads=1, d_ff=96, vocab=V, local_window=32, dtype="float32")

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
SEEDS = [20, 21, 22]


@pytest.fixture(scope="module")
def dense_models():
    return (DENSE_T, init_params(DENSE_T, jax.random.PRNGKey(0)),
            DENSE_D, init_params(DENSE_D, jax.random.PRNGKey(1)))


def _outputs(tc, tp, dc, dp, ecfg, prompts, seeds, max_new, selector=None, **pool_kw):
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, selector=selector,
                                   n_slots=4, **pool_kw)
    return eng, eng.generate_batch(prompts, max_new=max_new, seeds=seeds)


# ------------------------------------------------------ engine token-identity ---


@pytest.mark.parametrize("verifier", ["specinfer", "traversal"])
def test_paged_matches_ring_tree_strategy(dense_models, verifier):
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    _, ring = _outputs(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, 16, paged=False)
    peng, paged = _outputs(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, 16,
                           paged=True, block_size=8)
    assert peng.paged and isinstance(peng.tpool, PagedCachePool)
    assert paged == ring
    # the pool never materialized the ring-equivalent footprint
    assert 0 < peng.counters["blocks_peak"] < peng.pool_blocks


@pytest.mark.slow
@pytest.mark.parametrize("verifier", ["specinfer", "traversal"])
def test_paged_matches_ring_replay_strategy(verifier):
    """Hybrid arch: the replay strategy's grouped gathers/scatters and forks
    route through the paged attn component (recurrent state stays dense)."""
    params = init_params(HYB_CFG, jax.random.PRNGKey(0))
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    reng, ring = _outputs(HYB_CFG, params, HYB_CFG, params, ecfg, PROMPTS, SEEDS, 10,
                          paged=False)
    peng, paged = _outputs(HYB_CFG, params, HYB_CFG, params, ecfg, PROMPTS, SEEDS, 10,
                           paged=True, block_size=16)
    assert reng.strategy == peng.strategy == "replay"
    assert peng.paged
    assert paged == ring


@pytest.mark.slow
def test_paged_matches_ring_under_capacity_eviction(dense_models):
    """A stream that outgrows its logical ring is evicted at the same point
    with the same partial output under both layouts."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=24)
    ring = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2, paged=False)
    rid = ring.submit([1, 2, 3], max_new=64, seed=7)
    ring_info = ring.run()[rid]
    paged = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                     paged=True, block_size=8)
    rid = paged.submit([1, 2, 3], max_new=64, seed=7)
    info = paged.run()[rid]
    assert ring_info["reason"].startswith("evicted")
    assert info == ring_info


# -------------------------------------------------------- commit equivalence ---

L, B, S, H, HD = 2, 4, 16, 2, 4
BLK = 4
NB_PER = S // BLK


def _paired_pools(rng):
    """A dense per-stream pool and a paged pool with identical logical
    content: every row fully mapped through a random disjoint block table."""
    kd = rng.normal(size=(L, B, S, H, HD)).astype(np.float32)
    vd = rng.normal(size=(L, B, S, H, HD)).astype(np.float32)
    pos = rng.integers(-1, 4 * S, size=(B, S)).astype(np.int32)
    ln = rng.integers(0, 4 * S, size=(B,)).astype(np.int32)
    dense = {"attn": {"k": jnp.asarray(kd), "v": jnp.asarray(vd),
                      "pos": jnp.asarray(pos), "len": jnp.asarray(ln)}}
    perm = rng.permutation(np.arange(1, B * NB_PER + 1))
    tbl = perm.reshape(B, NB_PER).astype(np.int32)
    ka = np.zeros((L, B * NB_PER + 1, BLK, H, HD), np.float32)
    va = np.zeros_like(ka)
    for b in range(B):
        for i in range(NB_PER):
            ka[:, tbl[b, i]] = kd[:, b, i * BLK:(i + 1) * BLK]
            va[:, tbl[b, i]] = vd[:, b, i * BLK:(i + 1) * BLK]
    paged = {"attn": {"k": jnp.asarray(ka), "v": jnp.asarray(va),
                      "block_tbl": jnp.asarray(tbl), "pos": jnp.asarray(pos),
                      "len": jnp.asarray(ln)}}
    return dense, paged


def _logical(cache):
    got = gather_streams(cache, np.arange(B))["attn"]
    return {key: np.asarray(got[key]) for key in ("k", "v", "pos", "len")}


def _commit_args(rng, Tpad):
    paths, Cs, act = {}, {}, {}
    for b in range(B):
        act[b] = bool(rng.integers(2))
        tau = int(rng.integers(0, Tpad))
        paths[b] = (sorted(rng.choice(np.arange(1, Tpad), size=tau, replace=False).tolist())
                    if tau else [])
        Cs[b] = int(rng.integers(1, 3 * S))  # C past S exercises the ring wrap
    P = next_pow2(max([len(p) for b, p in paths.items() if act[b]] + [1]))
    npath = np.zeros((B, P), np.int32)
    plen = np.zeros((B,), np.int32)
    C = np.zeros((B,), np.int32)
    active = np.zeros((B,), np.bool_)
    for b in range(B):
        if act[b]:
            npath[b, :len(paths[b])] = paths[b]
            plen[b] = len(paths[b])
            C[b] = Cs[b]
            active[b] = True
    return tuple(jnp.asarray(a) for a in (npath, plen, C, active))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 8))
def test_paged_commit_matches_dense(seed, Tpad):
    """The fused commit through the block table leaves the paged pool's
    LOGICAL view bit-identical to the dense per-stream commit — including
    C > Smax ring wraps and idle rows."""
    rng = np.random.default_rng(seed)
    dense, paged = _paired_pools(rng)
    args = _commit_args(rng, Tpad)
    cfg = types.SimpleNamespace(attention_impl="xla")
    commit = make_pool_commit_step(cfg, Tpad)
    want = _logical(commit(dense, *args))
    got = _logical(commit(paged, *args))
    for key in want:
        assert np.array_equal(got[key], want[key]), key


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 6))
def test_paged_commit_pallas_kernel_path(seed, Tpad):
    """The Pallas commit_kv route over the flattened arena agrees too."""
    rng = np.random.default_rng(seed)
    dense, paged = _paired_pools(rng)
    args = _commit_args(rng, Tpad)
    xla = types.SimpleNamespace(attention_impl="xla")
    pal = types.SimpleNamespace(attention_impl="pallas")
    want = _logical(make_pool_commit_step(xla, Tpad)(dense, *args))
    got = _logical(make_pool_commit_step(pal, Tpad)(paged, *args))
    for key in want:
        assert np.array_equal(got[key], want[key]), key


# ------------------------------------------------------------ stream algebra ---


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_paged_stream_algebra_matches_dense(seed):
    """gather (dense view), scatter round-trip, fork and merge on a paged
    pool reproduce the dense pool's logical state exactly — including rows
    with different mapped-block counts fused by concat_streams."""
    rng = np.random.default_rng(seed)
    dense, paged = _paired_pools(rng)
    # unmap a random tail per row: rows now hold DIFFERENT block counts
    tbl = np.asarray(paged["attn"]["block_tbl"]).copy()
    pos = np.asarray(paged["attn"]["pos"]).copy()
    kd = np.asarray(dense["attn"]["k"]).copy()
    vd = np.asarray(dense["attn"]["v"]).copy()
    for b in range(B):
        keep = int(rng.integers(1, NB_PER + 1))
        tbl[b, keep:] = -1
        pos[b, keep * BLK:] = -1  # unmapped slots carry no live tokens
        kd[:, b, keep * BLK:] = 0  # dense mirror: zero the dropped content
        vd[:, b, keep * BLK:] = 0
    paged["attn"]["block_tbl"] = jnp.asarray(tbl)
    paged["attn"]["pos"] = jnp.asarray(pos)
    dense["attn"]["pos"] = jnp.asarray(pos)

    rows = [int(r) for r in rng.permutation(B)[: int(rng.integers(2, B + 1))]]
    cut = int(rng.integers(1, len(rows)))
    ga, gb = gather_streams(paged, rows[:cut]), gather_streams(paged, rows[cut:])
    # dense sub-rows of a paged pool concat like any other (different mapped
    # counts just mean trailing pos = -1 padding)
    combined = concat_streams([ga, gb])
    back = scatter_streams(paged, combined, rows)
    gl = _logical(back)
    # scatter of self-gathered rows is the identity on mapped lanes
    pos_np = np.asarray(paged["attn"]["pos"])
    assert np.array_equal(gl["pos"], pos_np)
    mapped = np.repeat(tbl >= 0, BLK, axis=1)  # (B, S)
    want_k = np.asarray(gather_streams(paged, np.arange(B))["attn"]["k"])
    assert np.array_equal(gl["k"][:, mapped], want_k[:, mapped])

    # fork materializes the dense view, replicated K times
    fork = fork_streams(paged, 2)
    dview = gather_streams(paged, np.arange(B))
    assert fork["attn"]["k"].shape[1] == 2 * B
    assert np.array_equal(np.asarray(fork["attn"]["k"][:, 0::2]),
                          np.asarray(dview["attn"]["k"]))

    # merge freezes non-keep rows at block granularity
    keep = rng.integers(0, 2, size=B).astype(bool)
    keep[int(rng.integers(B))] = True
    new = {"attn": dict(paged["attn"])}
    new["attn"]["k"] = paged["attn"]["k"] + 1.0
    new["attn"]["v"] = paged["attn"]["v"] + 1.0
    new["attn"]["pos"] = paged["attn"]["pos"] + 1
    merged = merge_streams(new, paged, keep)
    ml = _logical(merged)
    base = _logical(paged)
    for b in range(B):
        sel = mapped[b]
        if keep[b]:
            assert np.array_equal(ml["k"][:, b, sel], base["k"][:, b, sel] + 1.0)
            assert np.array_equal(ml["pos"][b], base["pos"][b] + 1)
        else:
            assert np.array_equal(ml["k"][:, b, sel], base["k"][:, b, sel])
            assert np.array_equal(ml["pos"][b], base["pos"][b])


# ---------------------------------------------------------- block lifecycle ---


def test_pool_block_bookkeeping():
    cfg = DENSE_T
    attn = init_paged_attn_cache(cfg, cfg.n_layers, 2, 6, 4, 16, jnp.float32)
    pool = PagedCachePool({"attn": attn}, 2)
    assert pool.total_blocks == 6 and pool.free_blocks == 6
    row = init_cache(cfg, 1, 16, per_stream=True)
    s0 = pool.admit(row, ctx_len=5)  # 2 blocks
    s1 = pool.admit(row, ctx_len=1)  # 1 block
    assert (pool.free_blocks, pool.used_blocks) == (3, 3)
    assert pool.missing_blocks(s0, 13) == 2 and pool.ensure(s0, 13)
    assert pool.free_blocks == 1
    assert not pool.ensure(s1, 16)  # needs 3 more, only 1 free — refused whole
    assert pool.free_blocks == 1
    assert pool.reclaim_tail(s0, 7) == 2  # frontier back to 2 blocks
    assert pool.ensure(s1, 9)
    occ = pool.occupancy({s0: 7, s1: 9})
    assert occ["blocks_used"] == 5 and occ["blocks_free"] == 1
    assert 0.0 <= occ["fragmentation"] < 1.0
    pool.release(s0)
    assert pool.free_blocks == 3
    # the trash block is never handed out
    assert 0 not in pool._free_blocks


def test_admission_blocks_until_blocks_free(dense_models):
    """Satellite: a request whose context + speculation bucket exceeds the
    free list stays queued (not admitted, not lost) and is admitted once a
    resident stream releases its blocks — outputs unchanged vs. the ring."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    ring = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2, paged=False)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [8, 7, 6, 5, 4, 3, 2, 1]]
    seeds, max_news = [30, 31], [4, 4]
    rids = [ring.submit(p, max_new=m, seed=sd) for p, sd, m in zip(prompts, seeds, max_news)]
    want = ring.run()
    # 2 blocks of 8: admission asks for ceil((8 + Tpad0)/8) = 2 blocks per
    # stream, so the second request must wait until the first releases —
    # but each stream alone fits the arena, so nothing is ever evicted
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                   paged=True, block_size=8, pool_blocks=2)
    rids_p = [eng.submit(p, max_new=m, seed=sd) for p, sd, m in zip(prompts, seeds, max_news)]
    eng.step()
    assert len(eng.streams) == 1, "second stream must wait for blocks"
    assert eng.counters["admit_blocked"] > 0
    got = eng.run()
    assert eng.counters["evicted"] == 0
    assert [got[r]["tokens"] for r in rids_p] == [want[r]["tokens"] for r in rids]
    assert eng.tpool.free_blocks == eng.tpool.total_blocks


def test_midstream_tail_reclaim_keeps_output_exact(dense_models):
    """Satellite: when a selector shrinks a stream's speculation bucket, the
    blocks its earlier bigger bucket mapped become dead tail — a queued
    request's admission pressure recycles them (no stream dies) and every
    token still matches the ring run."""
    tc, tp, dc, dp = dense_models

    def selector(stream, engine):
        # big first tree, small afterwards: the first bucket maps tail
        # blocks the later frontiers do not cover
        return (2, 2, 2) if len(stream["committed"]) <= 4 else (1, 1, 1)

    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    # two short streams go big-then-small; a long third prompt queues behind
    # them (its admission needs 6 of 7 blocks) and its pressure reclaims the
    # dead tails the big first buckets left behind
    prompts = [[1, 2, 3], [7, 6, 5], list(range(1, 18))]
    seeds, max_news = [40, 41, 42], [8, 8, 4]
    ring = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, selector=selector,
                                    n_slots=3, paged=False)
    rids = [ring.submit(p, max_new=m, seed=s)
            for p, s, m in zip(prompts, seeds, max_news)]
    wout = ring.run()
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, selector=selector,
                                   n_slots=3, paged=True, block_size=4,
                                   pool_blocks=7)
    rp = [eng.submit(p, max_new=m, seed=s)
          for p, s, m in zip(prompts, seeds, max_news)]
    got = eng.run()
    assert [got[r]["tokens"] for r in rp] == [wout[r]["tokens"] for r in rids]
    assert eng.counters["blocks_reclaimed"] > 0
    assert eng.counters["admit_blocked"] > 0
    assert eng.counters["evicted"] == 0


def test_lifo_pressure_eviction_under_exhaustion(dense_models):
    """When reclamation cannot cover a step's block demand, the most
    recently admitted stream is finished (reason evicted:pool_blocks) and
    the survivors continue unperturbed."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    ring = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2, paged=False)
    first = ring.generate_batch([[1, 2, 3]], max_new=24, seeds=[50])[0]
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                   paged=True, block_size=4, pool_blocks=8)
    r0 = eng.submit([1, 2, 3], max_new=24, seed=50)
    r1 = eng.submit([4, 5, 6], max_new=24, seed=51)
    out = eng.run()
    assert out[r0]["tokens"] == first, "the older stream must be untouched"
    assert out[r0]["reason"] == "length"
    assert out[r1]["reason"] == "evicted:pool_blocks"
    assert 0 < len(out[r1]["tokens"]) < 24


def test_coresidency_beats_ring_footprint(dense_models):
    """Acceptance: 1 long + 7 short streams co-resident in an arena smaller
    than TWO ring slots — the ring design could hold at most the long
    stream alone in the same HBM."""
    tc, tp, dc, dp = dense_models
    smax, bs, pool_blocks = 64, 8, 12
    assert pool_blocks * bs < 2 * smax  # ring-equivalent capacity: 1 stream
    ecfg = EngineConfig(verifier="specinfer", K=1, L1=1, L2=1, max_cache=smax)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=8,
                                   paged=True, block_size=bs, pool_blocks=pool_blocks)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, V, size=12).tolist(), max_new=40, seed=60)  # long
    for i in range(7):
        eng.submit(rng.integers(0, V, size=3).tolist(), max_new=4, seed=61 + i)
    peak = 0
    while eng.queue or eng.streams:
        eng.step()
        peak = max(peak, len(eng.streams))
    assert peak == 8, f"expected 8 co-resident streams, saw {peak}"
    assert eng.counters["blocks_peak"] <= pool_blocks


# ------------------------------------------------------------ paged kernels ---


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_paged_attention_kernels_match_dense(seed):
    """Block-table kernels == dense kernels at matching KV block granularity
    (bit-identical: same online-softmax accumulation order), with the
    kernels/ref.py gather oracle providing the logical view."""
    from repro.kernels.decode_attention import decode_attention, paged_decode_attention
    from repro.kernels.ref import paged_gather_kv_ref
    from repro.kernels.tree_attention import paged_tree_attention, tree_attention

    rng = np.random.default_rng(seed)
    NB, BSZ, HKV, HDIM = 9, 8, 1, 16
    NROW, NBLK_PER = 3, 4  # logical capacity 32 slots
    ka = jnp.asarray(rng.normal(size=(NB, BSZ, HKV, HDIM)).astype(np.float32))
    va = jnp.asarray(rng.normal(size=(NB, BSZ, HKV, HDIM)).astype(np.float32))
    free = list(rng.permutation(np.arange(1, NB)))
    tbl = np.full((NROW, NBLK_PER), -1, np.int32)
    for b in range(NROW):
        for i in range(int(rng.integers(1, NBLK_PER + 1))):
            if free:
                tbl[b, i] = free.pop()
    tblj = jnp.asarray(tbl)
    S = NBLK_PER * BSZ
    kd, vd = paged_gather_kv_ref(ka, va, tblj)
    kf, vf = kd[:, :, 0], vd[:, :, 0]  # (NROW, S, HDIM): BH layout, H = 1

    T = 8
    q = jnp.asarray(rng.normal(size=(NROW, T, HDIM)).astype(np.float32))
    mapped = np.repeat(tbl >= 0, BSZ, axis=1)
    mask = np.asarray(rng.integers(0, 2, size=(NROW, T, S)), bool) & mapped[:, None, :]
    mask[:, :, 0] = mapped[:, 0:1]  # at least one admitted slot per query
    maskj = jnp.asarray(mask)
    want = tree_attention(q, kf, vf, maskj, block_k=BSZ, interpret=True)
    got = paged_tree_attention(q, ka[:, :, 0], va[:, :, 0], jnp.clip(tblj, 0),
                               maskj, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))

    lens = np.asarray([int((tbl[b] >= 0).sum()) * BSZ - int(rng.integers(0, BSZ))
                       for b in range(NROW)], np.int32)
    lens = np.maximum(lens, 1)
    qd = jnp.asarray(np.broadcast_to(
        rng.normal(size=(NROW, 1, HDIM)).astype(np.float32), (NROW, 8, HDIM)))
    wantd = decode_attention(qd, kf, vf, jnp.asarray(lens)[:, None], block_k=BSZ,
                             interpret=True)
    gotd = paged_decode_attention(qd, ka[:, :, 0], va[:, :, 0], jnp.clip(tblj, 0),
                                  jnp.asarray(lens), interpret=True)
    assert np.array_equal(np.asarray(gotd), np.asarray(wantd))


def test_paged_pallas_engine_generates():
    """End-to-end: a paged engine with attention_impl=pallas routes the tree
    pass through gqa_paged_tree_attention (interpret mode) and still decodes."""
    tc = DENSE_T.replace(attention_impl="pallas", head_dim=16)
    dc = DENSE_D.replace(attention_impl="pallas", head_dim=16)
    tp = init_params(tc, jax.random.PRNGKey(0))
    dp = init_params(dc, jax.random.PRNGKey(1))
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=32)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                   paged=True, block_size=8)
    outs = eng.generate_batch([[1, 2, 3], [4, 5]], max_new=4, seeds=[20, 21])
    assert all(len(o) == 4 for o in outs)
