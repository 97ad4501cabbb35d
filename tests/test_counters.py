"""Counter accuracy for the bench surface.

The 9 -> 17 ``commit_calls`` regression on the sharded bench row shipped
silently because nothing tested the counters themselves — the bench gates
compare counter values, so a counter that drifts from the work it claims
to measure silently re-opens the regression it gates.  These tests pin
each reported counter to ground truth from an instrumented run:

  * ``commit_calls`` == the number of commit dispatches that actually
    reached the jit cache (single-engine ``commit_T*`` keys, engine-level
    ``gcommit_*`` keys for the grouped cross-shard commit);
  * the grouped commit really regroups: 2-shard ``commit_calls`` stays
    within ``single-shard + shards`` (the bench_smoke.sh gate, at unit
    scale);
  * ``steps_begun`` == the steps actually taken, and on the sharded engine
    it shows how many shard steps each grouped commit carried;
  * per-shard ``blocks_peak`` (the bench's ``shard_blocks_peak`` column)
    equals the observed per-shard used-block maximum;
  * ``pipeline_iterations`` == steps actually taken, and the overlap
    invariant ``pipeline_ahead + pipeline_stalls == pipeline_iterations``
    holds on the numbers benchmarks/batch_throughput.py reports.
"""
import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import benchmarks.batch_throughput as bt

from repro.models.config import ModelConfig
from repro.models.transformer import init_params
from repro.serving.batch_engine import (
    BatchedSpeculativeEngine,
    ShardedBatchedSpeculativeEngine,
)
from repro.serving.engine import EngineConfig

V = 32

DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [3, 1]]
SEEDS = [20, 21, 22, 23]


@pytest.fixture(scope="module")
def dense_models():
    return (DENSE_T, init_params(DENSE_T, jax.random.PRNGKey(0)),
            DENSE_D, init_params(DENSE_D, jax.random.PRNGKey(1)))


def _count_commit_jits(obj, tally, prefixes):
    """Wrap ``obj._jit`` so every invocation of a commit-dispatch callable
    increments ``tally`` — ground truth independent of the counters."""
    orig = obj._jit

    def counting(name, fn, donate_argnums=None):
        f = orig(name, fn, donate_argnums)
        if name.startswith(prefixes):
            def wrapped(*a, **kw):
                tally[0] += 1
                return f(*a, **kw)
            return wrapped
        return f

    obj._jit = counting


class _CountingSingle(BatchedSpeculativeEngine):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.true_commits = [0]
        self.true_steps = 0
        _count_commit_jits(self, self.true_commits, ("commit_T",))

    def step(self):
        self.true_steps += 1
        return super().step()


class _CountingSharded(ShardedBatchedSpeculativeEngine):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.true_commits = [0]
        self.true_blocks_peak = [0] * self.data_shards
        _count_commit_jits(self, self.true_commits, ("gcommit_",))
        for si, sh in enumerate(self.shards):
            _count_commit_jits(sh, self.true_commits, ("commit_T",))
            self._track_peak(si, sh)

    def _track_peak(self, si, sh):
        begin0, outer = sh.begin_step, self

        def begin(*a, **kw):
            pending = begin0(*a, **kw)
            # sample at the point of maximum mapping: speculative blocks
            # are live right after the dispatch, before commit trims them
            if hasattr(sh.tpool, "used_blocks"):  # paged arenas only
                outer.true_blocks_peak[si] = max(outer.true_blocks_peak[si],
                                                 sh.tpool.used_blocks)
            return pending
        sh.begin_step = begin


def test_single_engine_commit_counters(dense_models):
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    eng = _CountingSingle(tc, tp, dc, dp, ecfg, n_slots=4)
    eng.generate_batch(PROMPTS, max_new=10, seeds=SEEDS)
    assert eng.counters["commit_calls"] == eng.true_commits[0] > 0
    assert eng.counters["steps_begun"] == eng.counters["commit_calls"] == eng.true_steps


def test_sharded_commit_counters_and_grouping(dense_models):
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    single = _CountingSingle(tc, tp, dc, dp, ecfg, n_slots=4)
    want = single.generate_batch(PROMPTS, max_new=10, seeds=SEEDS)
    eng = _CountingSharded(tc, tp, dc, dp, ecfg, n_slots=4, data_shards=2)
    assert eng.generate_batch(PROMPTS, max_new=10, seeds=SEEDS) == want
    # the summed counter equals the dispatches that actually happened...
    assert eng.counters["commit_calls"] == eng.true_commits[0] > 0
    # ...the grouped path really fired (engine-level, belongs to no shard)...
    grouped = eng._counters["commit_calls"]
    assert grouped > 0
    # ...each grouped commit carried both shards' steps, every other commit one
    assert eng.counters["steps_begun"] == \
        (eng.counters["commit_calls"] - grouped) + eng.data_shards * grouped
    # ...and regrouping holds the bench gate at unit scale: sharding may
    # add at most one straggler dispatch per shard over the single engine
    assert eng.counters["commit_calls"] <= \
        single.counters["commit_calls"] + eng.data_shards


def test_bench_surface_sharded_counters(dense_models, monkeypatch):
    """prepare_batched must report counters that match the instrumented
    engine underneath it — per-shard block peaks included."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    monkeypatch.setattr(bt, "ShardedBatchedSpeculativeEngine", _CountingSharded)
    eng, workload, commit_stats, occ, warm = bt.prepare_batched(
        tc, tp, dc, dp, ecfg, None, PROMPTS, 10, SEEDS, data_shards=2)
    assert commit_stats["commit_calls"] == eng.true_commits[0] > 0
    assert eng.counters["admitted"] == len(PROMPTS)
    assert commit_stats["shard_blocks_peak"] == eng.true_blocks_peak
    assert occ and occ["target"]["blocks_used"] > 0
    # the compile-hygiene surface: the warmup pass compiled something, and
    # the census sums every shard's cache (>= the grouped-commit entry alone)
    assert warm["compile_count"] == eng.jit_compile_count() > 0
    assert warm["warmup_secs"] > 0
    # the timed-pass counters start from zero, not the warmup's tallies
    assert eng.counters["commit_calls"] == 0


def test_bench_surface_overlap_invariant(dense_models, monkeypatch):
    """The overlap counters the bench prints describe one workload pass:
    iterations == steps actually taken, ahead + stalls == iterations."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    monkeypatch.setattr(bt, "BatchedSpeculativeEngine", _CountingSingle)
    eng, workload, _, _, _ = bt.prepare_batched(
        tc, tp, dc, dp, ecfg, None, PROMPTS, 10, SEEDS, pipeline=True)
    eng.true_steps = 0
    workload()
    c = eng.counters
    assert c["pipeline_iterations"] == eng.true_steps > 0
    assert c["pipeline_ahead"] + c["pipeline_stalls"] == c["pipeline_iterations"]