"""Sharding-rule unit tests (tiny mesh; the production mesh is exercised by
launch/dryrun.py which this suite does not re-run), plus the pool-sharding
property suite: the sharded continuous-batching engine must emit
token-identical output to the unsharded pool for the same arrival order
(both strategies x both verifiers, synchronous and pipelined, including a
capacity-eviction-under-pressure scenario), and its admission/eviction
decisions must be shard-local."""
import logging

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_smoke
from repro.launch import sharding as sharding_mod
from repro.launch.mesh import shard_meshes
from repro.launch.sharding import (
    _spec_for,
    batch_shardings,
    pad_slots,
    param_shardings,
    pool_specs,
)
from repro.models.config import ModelConfig
from repro.models.transformer import init_cache, init_params
from repro.serving.batch_engine import (
    BatchedSpeculativeEngine,
    ShardedBatchedSpeculativeEngine,
)
from repro.serving.engine import EngineConfig, SpeculativeEngine

V = 32

DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
SSM_CFG = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=48, vocab=V,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8, dtype="float32")

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [3, 1]]
SEEDS = [20, 21, 22, 23]


@pytest.fixture(scope="module")
def dense_models():
    return (DENSE_T, init_params(DENSE_T, jax.random.PRNGKey(0)),
            DENSE_D, init_params(DENSE_D, jax.random.PRNGKey(1)))


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 8}


def test_rule_specs():
    m = FakeMesh()
    assert _spec_for("embed", (1024, 512), m) == P("model", "data")
    assert _spec_for("blocks/attn/wq", (12, 512, 1024), m) == P(None, "data", "model")
    assert _spec_for("blocks/attn/wo", (12, 1024, 512), m) == P(None, "model", "data")
    assert _spec_for("blocks/mlp/w_down", (12, 2048, 512), m) == P(None, "model", "data")
    # MoE 4D expert tensors: experts -> model
    assert _spec_for("blocks/mlp/w_gate", (12, 16, 512, 128), m) == P(None, "model", "data", None)
    assert _spec_for("blocks/ln1", (12, 512), m) == P()


def test_divisibility_guard_drops_axes():
    m = FakeMesh()
    # vocab 49155 not divisible by 8 -> replicated on that dim
    assert _spec_for("embed", (49155, 512), m) == P(None, "data")
    assert _spec_for("lm_head", (512, 49155), m) == P("data", None)
    # odd hidden: both dropped
    assert _spec_for("blocks/attn/wq", (2, 511, 1023), m) == P(None, None, None)


def test_param_shardings_cover_tree():
    dev = jax.devices()[0]
    mesh = Mesh(np.asarray([[dev]]), ("data", "model"))
    cfg = get_smoke("qwen3-moe-235b-a22b")
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    sh = param_shardings(mesh, shapes)
    n_params = len(jax.tree.leaves(shapes))
    n_sh = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_params == n_sh


def test_batch_shardings_guard():
    dev = jax.devices()[0]
    mesh = Mesh(np.asarray([[dev]]), ("data", "model"))
    batch = {
        "tokens": jax.ShapeDtypeStruct((8, 16), np.int32),
        "labels": jax.ShapeDtypeStruct((8, 16), np.int32),
    }
    sh = batch_shardings(mesh, batch)
    assert all(hasattr(s, "spec") for s in jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))


def test_divisibility_drop_logs_once(caplog):
    """Silently replicating half the model is the bug class the guard log
    exists for: the drop must be reported, but only once per param class."""
    sharding_mod._logged_drops.clear()
    m = FakeMesh()
    with caplog.at_level(logging.WARNING, logger="repro.launch.sharding"):
        assert _spec_for("embed", (49155, 512), m) == P(None, "data")
        assert _spec_for("embed", (49155, 512), m) == P(None, "data")
    drops = [r for r in caplog.records if "drops axis" in r.getMessage()]
    assert len(drops) == 1, [r.getMessage() for r in caplog.records]


# ------------------------------------------------------- pool stream axis ---


def test_pool_specs_stream_axis():
    ring = init_cache(DENSE_T, 8, 32, per_stream=True)
    sp = pool_specs({"data": 4, "model": 2}, ring)
    assert sp["attn"]["k"] == P(None, "data", None, None, None)
    assert sp["attn"]["pos"] == P("data", None)
    assert sp["attn"]["len"] == P("data")

    paged = init_cache(DENSE_T, 8, 32, per_stream=True, page=(8, 8))
    sp = pool_specs({"data": 4}, paged)
    # the arena has no stream axis (and an odd trash block): replicated —
    # the sharded engine gives each shard a private arena instead
    assert sp["attn"]["k"] == P()
    assert sp["attn"]["block_tbl"] == P("data", None)
    assert sp["attn"]["pos"] == P("data", None)

    ssm = init_cache(SSM_CFG, 8, 32, per_stream=True)
    sp = pool_specs({"data": 2}, ssm)
    assert sp["state"] == P(None, "data", None, None, None)
    assert sp["conv"] == P(None, "data", None, None)
    assert sp["len"] == P("data")


def test_pool_stream_axis_must_divide():
    """Unlike param rules the stream axis never silently drops: pad n_slots
    up instead of replicating a pool shard."""
    ring = init_cache(DENSE_T, 3, 32, per_stream=True)
    with pytest.raises(AssertionError, match="pad n_slots"):
        pool_specs({"data": 2}, ring)
    assert pad_slots(3, 2) == 4
    assert pad_slots(4, 2) == 4
    assert pad_slots(1, 4) == 4
    assert pad_slots(5, 1) == 5


def test_sharded_pools_carry_named_shardings(dense_models):
    """Every shard's pool arrays are committed to its mesh slice: the
    stream axis carries a NamedSharding over the shard's data axis."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2)
    assert [sh.n_slots for sh in eng.shards] == [2, 2]
    for sh in eng.shards:
        tbl = sh.tpool.cache["attn"]["block_tbl"]
        assert isinstance(tbl.sharding, NamedSharding)
        assert tuple(tbl.sharding.spec) == ("data", None)
        assert "data" in tbl.sharding.mesh.axis_names
    # n_slots pads UP to a shard multiple rather than replicating a shard
    odd = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=3,
                                          data_shards=2)
    assert odd.n_slots == 4 and [sh.n_slots for sh in odd.shards] == [2, 2]
    assert len(shard_meshes(3)) == 3


# -------------------------------------- sharded == unsharded token identity ---


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
@pytest.mark.parametrize("verifier", ["specinfer", "traversal", "univer", "greedy_mpbv"])
def test_sharded_matches_unsharded_tree(dense_models, verifier, pipeline):
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    base = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                    pipeline=pipeline)
    ref = base.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2, pipeline=pipeline)
    assert eng.strategy == "tree"
    assert eng.generate_batch(PROMPTS, max_new=12, seeds=SEEDS) == ref
    # the scheduler spread the four streams across both shards
    assert all(sh.counters["blocks"] > 0 for sh in eng.shards)


@pytest.mark.slow
@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
@pytest.mark.parametrize("verifier", ["specinfer", "traversal", "univer", "greedy_mpbv"])
def test_sharded_matches_unsharded_replay(verifier, pipeline):
    params = init_params(SSM_CFG, jax.random.PRNGKey(0))
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    base = BatchedSpeculativeEngine(SSM_CFG, params, SSM_CFG, params, ecfg,
                                    n_slots=4, pipeline=pipeline)
    ref = base.generate_batch(PROMPTS, max_new=8, seeds=SEEDS)
    eng = ShardedBatchedSpeculativeEngine(SSM_CFG, params, SSM_CFG, params, ecfg,
                                          n_slots=4, data_shards=2,
                                          pipeline=pipeline)
    assert eng.strategy == "replay"
    assert eng.generate_batch(PROMPTS, max_new=8, seeds=SEEDS) == ref


@pytest.mark.slow
def test_sharded_continuous_admission_exact(dense_models):
    """More requests than total slots: per-shard FIFOs admit as their own
    rows free up, and outputs still match the unsharded pool (admission
    *timing* may differ across schedulers; tokens may not)."""
    tc, tp, dc, dp = dense_models
    prompts = [[i + 1, i + 2] for i in range(6)]
    max_news = [6, 14, 10, 8, 12, 9]
    seeds = [30 + i for i in range(6)]
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    base = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4)
    ref = {}
    for p, sd, mn in zip(prompts, seeds, max_news):
        ref[base.submit(p, max_new=mn, seed=sd)] = None
    outs = base.run()
    ref = [outs[r]["tokens"] for r in sorted(outs)]
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2, pipeline=True)
    rids = [eng.submit(p, max_new=mn, seed=sd)
            for p, sd, mn in zip(prompts, seeds, max_news)]
    sout = eng.run()
    assert [sout[r]["tokens"] for r in rids] == ref
    # fully drained: every shard's rows are free again
    assert all(sh.tpool.free_slots == sh.n_slots for sh in eng.shards)


def test_sharded_eviction_identity(dense_models):
    """Capacity eviction under pressure fires at the SAME step in both
    engines: with a homogeneous action the eviction bound C-1+Tpad is a
    pure per-stream condition (Dp <= Tpad for (2,1,1)), so shard-local
    vs global shape bucketing cannot shift it — tokens AND truncation
    reasons are identical."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=24)
    prompts, seeds = [[1, 2, 3], [4, 5]], [7, 9]
    base = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2)
    brids = [base.submit(p, max_new=64, seed=sd) for p, sd in zip(prompts, seeds)]
    bouts = base.run()
    assert all(bouts[r]["reason"].startswith("evicted") for r in brids)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                          data_shards=2)
    srids = [eng.submit(p, max_new=64, seed=sd) for p, sd in zip(prompts, seeds)]
    assert [eng.shard_of(r) for r in srids] == [0, 1]
    souts = eng.run()
    assert [souts[r] for r in srids] == [bouts[r] for r in brids]
    assert sum(sh.counters["evicted"] for sh in eng.shards) == 2


# ------------------------------------------------------ shard-local decisions ---


def test_pressure_eviction_is_shard_local(dense_models):
    """Block pressure in one shard evicts from THAT shard's streams only
    (LIFO within the shard); the other shard's streams are untouched and
    emit exactly their independent single-engine output."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2, block_size=16,
                                          pool_blocks=10)  # 5 per shard < 2 rings
    # routing (least-loaded, ties to shard 0): A->0, B->1, C->0, D->1
    rid_a = eng.submit([1, 2, 3], max_new=64, seed=40)
    rid_b = eng.submit([4, 5], max_new=4, seed=41)
    rid_c = eng.submit([6, 7], max_new=64, seed=42)
    rid_d = eng.submit([8, 9], max_new=4, seed=43)
    assert [eng.shard_of(r) for r in (rid_a, rid_b, rid_c, rid_d)] == [0, 1, 0, 1]
    outs = eng.run()
    # shard 0 hit block pressure: its LATEST stream (C) was the LIFO victim,
    # and the survivor (A) later hit its ring capacity
    assert outs[rid_c]["reason"] == "evicted:pool_blocks"
    assert outs[rid_a]["reason"].startswith("evicted")
    assert eng.shards[0].counters["evicted"] == 2
    # shard 1 never felt shard 0's pressure
    assert eng.shards[1].counters["evicted"] == 0
    assert eng.shards[1].counters["blocks_reclaimed"] == 0
    for rid, prompt, seed in ((rid_b, [4, 5], 41), (rid_d, [8, 9], 43)):
        single = SpeculativeEngine(
            tc, tp, dc, dp,
            EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64,
                         seed=seed))
        assert outs[rid]["tokens"] == single.generate(prompt, max_new=4)


def test_admission_routes_around_exhausted_shard(dense_models):
    """One shard's block free list is exhausted while the other has blocks:
    the scheduler routes the new request to the shard that can admit it,
    instead of queueing it behind an arena it does not need."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2, block_size=16,
                                          pool_blocks=8)  # 4 per shard
    long_prompt = [(i % (V - 2)) + 1 for i in range(44)]
    rid_a = eng.submit(long_prompt, max_new=8, seed=50)
    assert eng.shard_of(rid_a) == 0
    eng.step()  # admit A: its context maps 3 of shard 0's 4 blocks
    s0 = eng.shards[0]
    assert s0.tpool.free_slots > 0, "exhaustion must come from blocks, not rows"
    assert all(p.free_blocks < 2 for p in s0._paged_pools())
    rid_b = eng.submit([3, 1, 4, 1] * 5, max_new=4, seed=51)  # needs 2 blocks
    assert eng.shard_of(rid_b) == 1, "scheduler must route around the dry shard"
    outs = eng.run()
    assert len(outs[rid_b]["tokens"]) == 4
    # shard 0 never queued the request it could not serve
    assert s0.counters["admit_blocked"] == 0


def test_multi_shard_abort_rewinds_all(dense_models):
    """``abort_pipeline`` with SEVERAL shards begun-ahead must rewind every
    one of them: each shard restores its own rng snapshots and pool writes,
    so the continued run still emits the synchronous sharded token stream.
    (A partial rewind would replay one shard's randomness against another's
    already-consumed state — the regression this pins down.)"""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    base = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                           data_shards=2)
    want = base.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2, pipeline=True)
    rids = [eng.submit(list(p), max_new=12, seed=sd)
            for p, sd in zip(PROMPTS, SEEDS)]
    eng.step()  # steady state: BOTH shards leave a step begun-ahead
    assert sum(sh._pending_next is not None for sh in eng.shards) == 2
    assert eng.abort_pipeline() == 2
    assert all(sh._pending_next is None for sh in eng.shards)
    assert not any(sh.dpool.frame_held for sh in eng.shards)
    assert eng.abort_pipeline() == 0  # idempotent once quiescent
    outs = eng.run()
    assert [outs[r]["tokens"] for r in rids] == want


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ag = bf16[2048,4096]{1,0} all-gather(%x), replica_groups={}
  %ar = f32[128]{0} all-reduce(%y), to_apply=%sum
  %fused = f32[16]{0} fusion(%z), kind=kLoop
  %a2a = bf16[64,32]{1,0} all-to-all(%w)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 2048 * 4096 * 2
    assert out["all-reduce"] == 128 * 4
    assert out["all-to-all"] == 64 * 32 * 2
    assert out["reduce-scatter"] == 0


def test_bin_packing_groups_similar_actions(dense_models):
    """Selector-aware routing: alternating big/thin action hints land
    big-with-big and thin-with-thin, so each shard's pool-wide speculation
    bucket stays tight instead of every shard stepping at the big Tpad."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2)
    big, thin = (4, 2, 4), (1, 1, 0)
    hints = [big, thin, big, thin]
    rids = [eng.submit(list(p), max_new=4, seed=sd, action_hint=h)
            for p, sd, h in zip(PROMPTS, SEEDS, hints)]
    shards = [eng.shard_of(r) for r in rids]
    assert shards[0] == shards[2], "both big-bucket streams must co-reside"
    assert shards[1] == shards[3], "both thin-bucket streams must co-reside"
    assert shards[0] != shards[1], "big and thin buckets must not mix"
    outs = eng.run()
    assert all(len(outs[r]["tokens"]) == 4 for r in rids)


def test_bin_packing_deterministic_and_output_invariant(dense_models):
    """The schedule is a pure function of arrival order and hints: two
    identical engines place identically and emit identical tokens — and the
    hints steer PLACEMENT only, so a hint-free engine serving the same
    arrivals emits the same per-request tokens from (possibly) different
    shards."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    hints = [(4, 2, 4), (1, 1, 0), (1, 1, 0), (4, 2, 4)]

    def serve(with_hints):
        eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                              data_shards=2)
        rids = [eng.submit(list(p), max_new=8, seed=sd,
                           action_hint=(h if with_hints else None))
                for p, sd, h in zip(PROMPTS, SEEDS, hints)]
        placed = [eng.shard_of(r) for r in rids]
        outs = eng.run()
        return placed, [outs[r]["tokens"] for r in rids]

    placed_a, outs_a = serve(True)
    placed_b, outs_b = serve(True)
    assert placed_a == placed_b, "same arrivals + hints must place identically"
    assert outs_a == outs_b
    # heterogeneous hints produced a non-least-loaded grouping…
    assert placed_a == [0, 1, 1, 0]
    placed_free, outs_free = serve(False)
    # …while hint-free routing stays the original least-loaded round-robin
    assert placed_free == [0, 1, 0, 1]
    assert outs_free == outs_a, "hints must never change emitted tokens"


def test_bin_packing_homogeneous_hints_degrade_to_least_loaded(dense_models):
    """With every hint in the same bucket all pack costs are 0 and routing
    is EXACTLY the original least-loaded rule (the pinned placements above
    this suite rely on that degradation)."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4,
                                          data_shards=2)
    rids = [eng.submit(list(p), max_new=4, seed=sd, action_hint=(2, 1, 1))
            for p, sd in zip(PROMPTS, SEEDS)]
    assert [eng.shard_of(r) for r in rids] == [0, 1, 0, 1]
    eng.run()


_FOUR_DEVICE_CHILD = """
import json, jax
from repro.models.config import ModelConfig
from repro.models.transformer import init_params
from repro.serving.batch_engine import BatchedSpeculativeEngine, ShardedBatchedSpeculativeEngine
from repro.serving.engine import EngineConfig
t = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, d_ff=96, vocab=32, dtype="float32")
d = t.replace(name="d", n_layers=1, d_model=32)
tp, dp = init_params(t, jax.random.PRNGKey(0)), init_params(d, jax.random.PRNGKey(1))
ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
prompts, seeds = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [3, 1]], [20, 21, 22, 23]
one = BatchedSpeculativeEngine(t, tp, d, dp, ecfg, n_slots=4)
eng = ShardedBatchedSpeculativeEngine(t, tp, d, dp, ecfg, n_slots=4, data_shards=4)
same = eng.generate_batch(prompts, 6, seeds) == one.generate_batch(prompts, 6, seeds)
print(json.dumps({"devices": len(jax.devices()), "placement": eng.placement(),
                  "unsharded": one.placement(), "same": same}))
"""


@pytest.fixture(scope="module")
def four_device_run():
    """The 4-shard engine on 4 virtual CPU devices.  The device count is
    fixed when a process first touches the backend, so the run goes to a
    child process with the flag set; this process keeps its one device."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4").strip(),
               PYTHONPATH=os.pathsep.join([src, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_CHILD], env=env,
                         capture_output=True, text=True, timeout=300, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_shards_hold_params_and_pools_on_own_device(four_device_run):
    """Each shard's target/draft params and pools sit on that shard's own
    device (placed once at construction, so no step moves weights across
    devices), the shards cover four distinct devices, and the tokens equal
    the unsharded engine's on device 0."""
    run = four_device_run
    assert run["devices"] == 4
    for i, placed in enumerate(run["placement"]):
        assert placed == {"target_params": [i], "draft_params": [i],
                          "target_pool": [i], "draft_pool": [i]}
    assert set(run["unsharded"]["target_pool"]) == {0}
    assert run["same"]
