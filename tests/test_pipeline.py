"""Pipelined stepping: the two-phase engine's exactness and overlap contract.

``BatchedSpeculativeEngine(pipeline=True)`` splits every iteration into
``begin_step`` (scheduling boundary + dispatch) and ``finish_step`` (verify,
fused commit, retire) and lets ``finish_step`` begin the next iteration
before its own retirement tail.  These tests pin the contract from
docs/serving.md "Pipelined stepping":

  * token identity with the synchronous engine for both target-pass
    strategies x both verifiers — including under admission stalls, paged
    block-pressure reclaim, and LIFO/capacity evictions landing at the
    begin_step boundary while a finished step's retirement is deferred;
  * the overlap really happens: the draft for step i+1 is dispatched before
    step i's verify phase (finish_step) completes (call-order probe, same
    style as test_commit_fused.py's one-commit-per-step assertion);
  * stall-and-drain: iterations that retire a stream never pipeline ahead,
    and a begun step can be aborted (draft pool + speculative target
    writes rewound) without perturbing the token stream: begun again, it
    drafts the same tokens from the same keys;
  * drafting reads nothing back: ``draft_syncs`` stays 0 on the tree
    strategy, for the padded and the ragged layout.
"""
import jax
import pytest

from repro.models.config import ModelConfig
from repro.models.transformer import init_params
from repro.serving.batch_engine import BatchedSpeculativeEngine
from repro.serving.engine import EngineConfig, SpeculativeEngine
from repro.serving.serve_step import StagingBuffers

V = 32

DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
SSM_CFG = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=48, vocab=V,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8, dtype="float32")

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
SEEDS = [20, 21, 22]


@pytest.fixture(scope="module")
def dense_models():
    return (DENSE_T, init_params(DENSE_T, jax.random.PRNGKey(0)),
            DENSE_D, init_params(DENSE_D, jax.random.PRNGKey(1)))


def _singles(tc, tp, dc, dp, ecfg, prompts, seeds, max_new):
    outs = []
    for p, sd in zip(prompts, seeds):
        eng = SpeculativeEngine(
            tc, tp, dc, dp,
            EngineConfig(verifier=ecfg.verifier, K=ecfg.K, L1=ecfg.L1, L2=ecfg.L2,
                         max_cache=ecfg.max_cache, seed=sd))
        outs.append(eng.generate(list(p), max_new=max_new))
    return outs


# ------------------------------------------------------- token identity ---


@pytest.mark.parametrize("verifier", ["specinfer", "traversal"])
def test_pipeline_matches_sync_tree_strategy(dense_models, verifier):
    """Tree strategy: pipelined == synchronous == per-stream singles, and the
    pipeline actually ran ahead at least once."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    singles = _singles(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, max_new=16)
    sync = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4)
    assert sync.generate_batch(PROMPTS, max_new=16, seeds=SEEDS) == singles
    pipe = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4, pipeline=True)
    assert pipe.strategy == "tree"
    assert pipe.generate_batch(PROMPTS, max_new=16, seeds=SEEDS) == singles
    assert pipe.counters["pipeline_ahead"] > 0
    # drained: nothing left in flight, pool fully released
    assert pipe._pending_next is None
    assert pipe.tpool.free_slots == 4 and pipe.dpool.free_slots == 4
    assert not pipe.dpool.frame_held


@pytest.mark.slow
@pytest.mark.parametrize("verifier", ["specinfer", "traversal"])
def test_pipeline_matches_sync_replay_strategy(verifier):
    """Replay strategy (recurrent target): the host-interleaved target pass
    rides the same begin/finish split, token-identically."""
    params = init_params(SSM_CFG, jax.random.PRNGKey(0))
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    sync = BatchedSpeculativeEngine(SSM_CFG, params, SSM_CFG, params, ecfg, n_slots=2)
    assert sync.strategy == "replay"
    want = sync.generate_batch(PROMPTS[:2], max_new=10, seeds=SEEDS[:2])
    pipe = BatchedSpeculativeEngine(SSM_CFG, params, SSM_CFG, params, ecfg,
                                    n_slots=2, pipeline=True)
    assert pipe.generate_batch(PROMPTS[:2], max_new=10, seeds=SEEDS[:2]) == want
    assert pipe.counters["pipeline_ahead"] > 0


def test_pipeline_admission_stalls_exact(dense_models):
    """More requests than slots: every finished stream stalls the pipeline
    (slot release feeds the next admission), queued requests are admitted at
    the boundary, and outputs still match the synchronous engine."""
    tc, tp, dc, dp = dense_models
    prompts = [[i + 1, i + 2] for i in range(5)]
    max_news = [6, 14, 10, 8, 12]
    seeds = [30 + i for i in range(5)]
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)

    def run(pipeline):
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                       pipeline=pipeline)
        rids = [eng.submit(p, max_new=mn, seed=sd)
                for p, sd, mn in zip(prompts, seeds, max_news)]
        outs = eng.run()
        return [outs[r]["tokens"] for r in rids], eng

    want, _ = run(False)
    got, pipe = run(True)
    assert got == want
    assert pipe.counters["pipeline_stalls"] > 0, "finishing streams must stall"
    assert pipe.counters["pipeline_ahead"] > 0, "steady state must overlap"
    assert pipe.tpool.free_slots == 2 and not pipe.streams and not pipe.queue


def test_pipeline_paged_pressure_reclaim_exact(dense_models):
    """Paged arena under pressure mid-pipeline: dead-tail reclamation (a
    selector shrinks its speculation bucket; a queued long prompt's
    admission recycles the dead tails) happens at the begin_step boundary
    and the token stream matches the synchronous paged engine."""
    tc, tp, dc, dp = dense_models

    def selector(stream, engine):
        return (2, 2, 2) if len(stream["committed"]) <= 4 else (1, 1, 1)

    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    prompts = [[1, 2, 3], [7, 6, 5], list(range(1, 18))]
    seeds, max_news = [40, 41, 42], [8, 8, 4]

    def run(pipeline):
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, selector=selector,
                                       n_slots=3, paged=True, block_size=4,
                                       pool_blocks=7, pipeline=pipeline)
        rids = [eng.submit(p, max_new=m, seed=s)
                for p, s, m in zip(prompts, seeds, max_news)]
        outs = eng.run()
        return [(outs[r]["tokens"], outs[r]["reason"]) for r in rids], eng

    want, sync = run(False)
    got, pipe = run(True)
    assert got == want
    assert pipe.counters["blocks_reclaimed"] > 0
    assert pipe.counters["blocks_reclaimed"] == sync.counters["blocks_reclaimed"]
    assert pipe.counters["evicted"] == 0


def test_pipeline_evictions_exact(dense_models):
    """LIFO block-pressure eviction and ring-capacity eviction land at the
    begin_step boundary of a running pipeline; victims, reasons and every
    survivor's tokens match the synchronous engine."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)

    def run_paged(pipeline):
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                       paged=True, block_size=4, pool_blocks=8,
                                       pipeline=pipeline)
        r0 = eng.submit([1, 2, 3], max_new=24, seed=50)
        r1 = eng.submit([4, 5, 6], max_new=24, seed=51)
        outs = eng.run()
        return [(outs[r]["tokens"], outs[r]["reason"]) for r in (r0, r1)]

    got, want = run_paged(True), run_paged(False)
    assert got == want
    assert got[0][1] == "length" and got[1][1] == "evicted:pool_blocks"

    ecfg_small = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=24)

    def run_ring(pipeline):
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg_small, n_slots=2,
                                       pipeline=pipeline)
        rid = eng.submit([1, 2, 3], max_new=64, seed=7)
        out = eng.run()[rid]
        return out["tokens"], out["reason"]

    got_ring, want_ring = run_ring(True), run_ring(False)
    assert got_ring == want_ring
    assert got_ring[1] == "evicted:cache_full"


# ------------------------------------------------------ overlap probing ---


class _ProbedEngine(BatchedSpeculativeEngine):
    """Records the interleaving of draft dispatches and finish completions —
    the call-order probe for the pipeline-ahead guarantee."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.calls = []

    def _ingest_deltas(self, active):
        self.calls.append("draft_dispatch")
        return super()._ingest_deltas(active)

    def finish_step(self, pending, pipeline_ahead=None):
        events = super().finish_step(pending, pipeline_ahead)
        self.calls.append("finish_done")
        return events


def test_draft_dispatched_before_verify_completes(dense_models):
    """Acceptance probe: in pipelined mode the draft ingest for step i+1 is
    dispatched inside step i's finish_step — i.e. BEFORE the verify phase
    completes — while the synchronous engine strictly alternates."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)

    def trace(pipeline):
        eng = _ProbedEngine(tc, tp, dc, dp, ecfg, n_slots=4, pipeline=pipeline)
        outs = eng.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
        return eng, outs

    sync, outs_s = trace(False)
    # synchronous: every draft dispatch strictly follows the previous finish
    assert sync.calls == ["draft_dispatch", "finish_done"] * (len(sync.calls) // 2)

    pipe, outs_p = trace(True)
    assert outs_p == outs_s
    # pipelined: at least one step's draft is dispatched before the previous
    # finish completes — consecutive draft dispatches with no finish between
    ahead = any(a == b == "draft_dispatch"
                for a, b in zip(pipe.calls, pipe.calls[1:]))
    assert ahead, f"no overlapped dispatch in call trace {pipe.calls}"
    assert pipe.counters["pipeline_ahead"] > 0


def test_stalled_iterations_do_not_run_ahead(dense_models):
    """Every iteration that finishes a stream must stall: pipeline_ahead +
    pipeline_stalls partitions the finished iterations, and with a single
    stream of homogeneous length the final iteration always stalls."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=1, pipeline=True)
    eng.submit([1, 2, 3], max_new=12, seed=20)
    eng.run()
    c = eng.counters
    assert c["pipeline_stalls"] >= 1  # the finishing iteration stalled
    assert c["pipeline_ahead"] + c["pipeline_stalls"] > 0
    assert eng._pending_next is None


# ----------------------------------------------------- drain and abort ---


def test_abort_step_rewinds_exactly(dense_models):
    """A begun step can be abandoned: the draft pool's ingest writes are
    erased, and the target rows' speculative writes are invalidated — a
    subsequent run emits exactly the untouched token stream."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    want = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4) \
        .generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4, pipeline=True)
    for p, sd in zip(PROMPTS, SEEDS):
        eng.submit(p, max_new=12, seed=sd)
    pending = eng.begin_step()  # dispatches ingest + draft + tree pass
    eng.abort_step(pending)     # ...and rewinds all of it
    assert not eng.dpool.frame_held
    rids = sorted(st["rid"] for st in eng.streams.values())
    outs = eng.run()
    assert [outs[r]["tokens"] for r in rids] == want


def test_rebegun_step_drafts_identical_tokens(dense_models):
    """Draft draws are keyed by (seed, committed length, node), not drawn
    from a consumed rng: a step aborted and begun again drafts bit-identical
    tokens and node distributions."""
    import numpy as np

    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4, pipeline=True)
    for p, sd in zip(PROMPTS, SEEDS):
        eng.submit(p, max_new=12, seed=sd)
    eng.step()  # leaves step 2 begun ahead, ingesting step 1's tokens
    first = eng._pending_next
    drafted = [np.asarray(first.tok_dev), np.asarray(first.q_dev)]
    eng.abort_pipeline()
    again = eng.begin_step()
    np.testing.assert_array_equal(np.asarray(again.tok_dev), drafted[0])
    np.testing.assert_array_equal(np.asarray(again.q_dev), drafted[1])


@pytest.mark.parametrize("ragged", [False, "always"], ids=["padded", "ragged"])
def test_tree_strategy_drafts_without_host_reads(dense_models, ragged):
    """On the tree strategy no step blocks on the device between its draft
    ingest and its tree dispatch: ``draft_syncs`` stays 0 while the steps'
    drafts are still read back (once, in verify_step)."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=2, L2=2, max_cache=128)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4, pipeline=True,
                                   ragged=ragged)
    eng.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    c = eng.counters
    assert c["steps_begun"] >= 4 and c["readback_bytes"] > 0
    assert c["tree_calls_ragged" if ragged else "tree_calls_padded"] == c["target_calls"]
    assert c["draft_syncs"] == 0


def test_drain_pipeline_finishes_pending(dense_models):
    """drain_pipeline retires the begun-ahead step without beginning another
    — the engine is then quiescent (safe for out-of-band mutations) and the
    remaining run still matches."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    want = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4) \
        .generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4, pipeline=True)
    rids = [eng.submit(p, max_new=12, seed=sd) for p, sd in zip(PROMPTS, SEEDS)]
    eng.step()  # leaves the next step begun-ahead (steady state)
    assert eng._pending_next is not None
    eng.drain_pipeline()
    assert eng._pending_next is None
    assert eng.drain_pipeline() == []  # idempotent no-op when quiescent
    outs = eng.run()
    for r in rids:
        assert outs[r]["tokens"] == want[rids.index(r)]


def test_submit_mid_pipeline_admits_like_sync(dense_models):
    """A submit() landing while a step is begun-ahead must not slip its
    admission by one iteration: the pending step is aborted (pools
    rewound) so the request joins at exactly the boundary the synchronous
    engine would, and every stream's tokens match the same call trace with
    pipeline=False."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)

    def run(pipeline):
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2,
                                       pipeline=pipeline)
        r0 = eng.submit([1, 2, 3], max_new=12, seed=20)
        eng.step()
        eng.step()  # pipelined: leaves step 3 begun-ahead without r1
        r1 = eng.submit([4, 5], max_new=8, seed=21)
        outs = eng.run()
        return [outs[r]["tokens"] for r in (r0, r1)]

    got = run(True)
    assert got == run(False)

    # with zero free rows admission is provably unchanged: the begun-ahead
    # step is kept (no aborted device work), and the queued request still
    # matches its synchronous run
    def run_full(pipeline):
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=1,
                                       pipeline=pipeline)
        r0 = eng.submit([1, 2, 3], max_new=12, seed=20)
        eng.step()
        pending = eng._pending_next
        r1 = eng.submit([4, 5], max_new=8, seed=21)
        if pipeline:
            assert eng._pending_next is pending, \
                "no free row: the dispatched step must be kept"
        outs = eng.run()
        return [outs[r]["tokens"] for r in (r0, r1)]

    assert run_full(True) == run_full(False)


def test_staging_banks_isolated():
    """StagingBuffers: a flipped bank never hands back the buffer the
    previous bank's arrays were staged in (the pipelined no-overwrite
    contract); a single bank reuses storage."""
    import numpy as np

    two = StagingBuffers(banks=2)
    a = two.get("toks", (4,), np.int32)
    a[:] = 7
    two.flip()
    b = two.get("toks", (4,), np.int32)
    assert b is not a and a[0] == 7  # bank 0's staging untouched
    two.flip()
    assert two.get("toks", (4,), np.int32) is a  # round-robin reuse

    one = StagingBuffers(banks=1)
    x = one.get("toks", (4,), np.int32)
    one.flip()
    assert one.get("toks", (4,), np.int32) is x
