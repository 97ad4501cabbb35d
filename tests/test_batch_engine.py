"""Batch-vs-single exactness: the continuous-batching engine's contract.

For identical per-stream seeds and prompts, ``BatchedSpeculativeEngine`` with
N resident streams must emit token-identical output to N independent
``SpeculativeEngine`` runs — across verifiers, across both target-pass
strategies ("tree" for attention archs, "replay" for recurrent archs),
under heterogeneous prompt lengths, selector-driven heterogeneous tree
shapes, and continuous admission (more requests than pool slots).
"""
import jax
import pytest

from repro.models.config import ModelConfig
from repro.models.transformer import init_params
from repro.serving.batch_engine import BatchedSpeculativeEngine
from repro.serving.engine import EngineConfig, SamplingParams, SpeculativeEngine

V = 32

DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
SSM_CFG = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=48, vocab=V,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8, dtype="float32")
HYB_CFG = ModelConfig(name="h", arch_type="hybrid", n_layers=5, d_model=48, n_heads=4,
                      n_kv_heads=1, d_ff=96, vocab=V, local_window=32, dtype="float32")

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
SEEDS = [20, 21, 22]


@pytest.fixture(scope="module")
def dense_models():
    return (DENSE_T, init_params(DENSE_T, jax.random.PRNGKey(0)),
            DENSE_D, init_params(DENSE_D, jax.random.PRNGKey(1)))


def _single_outputs(tc, tp, dc, dp, ecfg, prompts, seeds, max_new, sampling=None, selector=None):
    outs = []
    for p, sd in zip(prompts, seeds):
        eng = SpeculativeEngine(
            tc, tp, dc, dp,
            EngineConfig(verifier=ecfg.verifier, K=ecfg.K, L1=ecfg.L1, L2=ecfg.L2,
                         max_cache=ecfg.max_cache, seed=sd),
            sampling, selector=selector,
        )
        outs.append(eng.generate(list(p), max_new=max_new))
    return outs


@pytest.mark.parametrize("verifier", ["specinfer", "traversal", "univer", "greedy_mpbv"])
def test_batch_matches_single_tree_strategy(dense_models, verifier):
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    singles = _single_outputs(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, max_new=16)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4)
    assert beng.strategy == "tree"
    outs = beng.generate_batch(PROMPTS, max_new=16, seeds=SEEDS)
    assert outs == singles


@pytest.mark.parametrize("action", [(2, 2, 2), (2, 0, 2), (3, 1, 3)])
def test_batch_matches_single_multi_step_branches(dense_models, action):
    """Branches of L2 >= 2 tokens: each branch's next token is drawn from its
    newest node's draft distribution in both engines, so outputs stay
    token-identical past the first branch step."""
    tc, tp, dc, dp = dense_models
    K, L1, L2 = action
    ecfg = EngineConfig(verifier="specinfer", K=K, L1=L1, L2=L2, max_cache=128)
    singles = _single_outputs(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, max_new=16)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4)
    assert beng.generate_batch(PROMPTS, max_new=16, seeds=SEEDS) == singles


@pytest.mark.slow
@pytest.mark.parametrize("verifier", ["specinfer", "traversal", "univer", "greedy_mpbv"])
@pytest.mark.parametrize("cfg", [SSM_CFG, HYB_CFG], ids=["ssm", "hybrid"])
def test_batch_matches_single_replay_strategy(cfg, verifier):
    params = init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128)
    singles = _single_outputs(cfg, params, cfg, params, ecfg, PROMPTS, SEEDS, max_new=10)
    beng = BatchedSpeculativeEngine(cfg, params, cfg, params, ecfg, n_slots=4)
    assert beng.strategy == "replay"
    outs = beng.generate_batch(PROMPTS, max_new=10, seeds=SEEDS)
    assert outs == singles


@pytest.mark.slow
def test_continuous_admission_exact(dense_models):
    """More requests than slots: queued requests join as slots free up, and
    every stream still matches its independent single-engine run."""
    tc, tp, dc, dp = dense_models
    prompts = [[i + 1, i + 2] for i in range(5)]
    # staggered lengths so slots free at different times
    max_news = [6, 14, 10, 8, 12]
    seeds = [30 + i for i in range(5)]
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    singles = [
        _single_outputs(tc, tp, dc, dp, ecfg, [p], [sd], max_new=mn)[0]
        for p, sd, mn in zip(prompts, seeds, max_news)
    ]
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2)
    rids = [beng.submit(p, max_new=mn, seed=sd)
            for p, sd, mn in zip(prompts, seeds, max_news)]
    outs = beng.run()
    assert [outs[r]["tokens"] for r in rids] == singles
    # the pool is fully drained and reusable; run() handed over every result
    assert beng.tpool.free_slots == 2
    assert beng.dpool.free_slots == 2
    assert not beng.streams and not beng.queue and not beng.finished


@pytest.mark.slow
def test_heterogeneous_selector_actions_exact(dense_models):
    """Per-stream NDE-style selector decisions: tree shapes differ across
    streams in one iteration (exercising the shape buckets), yet outputs
    still match the single-engine runs with the same selector."""
    tc, tp, dc, dp = dense_models

    def selector(stream, engine):
        # deterministic function of stream state, available in both engines
        return (1 + len(stream["committed"]) % 2, len(stream["committed"]) % 2, 1)

    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    singles = _single_outputs(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, max_new=12,
                              selector=selector)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, selector=selector, n_slots=4)
    outs = beng.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    assert outs == singles


@pytest.mark.slow
def test_sampling_params_exact(dense_models):
    """Temperature/nucleus warping flows through the batched path."""
    tc, tp, dc, dp = dense_models
    sampling = SamplingParams(temperature=0.8, top_p=0.9)
    ecfg = EngineConfig(verifier="traversal", K=2, L1=1, L2=1, max_cache=128)
    singles = _single_outputs(tc, tp, dc, dp, ecfg, PROMPTS, SEEDS, max_new=12,
                              sampling=sampling)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, sampling, n_slots=4)
    outs = beng.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    assert outs == singles


@pytest.mark.slow
def test_eviction_on_cache_pressure(dense_models):
    """A stream whose ring cannot hold another speculation block finishes
    early (evicted) instead of corrupting its cache."""
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=24)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2)
    rid = beng.submit([1, 2, 3], max_new=64, seed=7)
    info = beng.run()[rid]
    assert info["reason"].startswith("evicted")
    assert 0 < len(info["tokens"]) < 64
    assert beng.counters["evicted"] == 1
    # slot was released — the pool accepts new work afterwards, and the second
    # drain only returns the second request
    rid2 = beng.submit([3, 2], max_new=4, seed=8)
    out = beng.run()
    assert list(out) == [rid2]
    assert len(out[rid2]["tokens"]) == 4


def test_pooled_peeks_match_single_engine(dense_models):
    """The pooled peek oracles (a gathered row, functionally decoded) score
    the same distributions as the single-stream engine's peeks."""
    import numpy as np

    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64, seed=5)
    single = SpeculativeEngine(tc, tp, dc, dp, ecfg)
    stream = single.new_stream([1, 2, 3])
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=2)
    beng.submit([1, 2, 3], max_new=8, seed=5)
    # advance both one block with identical rng state, then peek
    beng.step()
    single.step(stream)
    bstream = next(iter(beng.streams.values()))
    assert bstream["committed"] == stream["committed"]
    for ctx in ([], [7], [7, 11]):
        np.testing.assert_allclose(beng.peek_target_dist(bstream, ctx),
                                   single.peek_target_dist(stream, ctx),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(beng.peek_draft_dist(bstream, ctx),
                                   single.peek_draft_dist(stream, ctx),
                                   rtol=1e-5, atol=1e-6)
    # peeks are functional: the pool state they read is unchanged
    assert bstream["committed"] == stream["committed"]


@pytest.mark.slow
def test_analytic_selector_runs_batched(dense_models):
    """AnalyticSelector's Eq. 9 argmax runs under continuous batching now
    that the engine provides pooled peek oracles (it used to be rejected
    at construction and silently unusable on pooled streams)."""
    from repro.core.delayed import LatencyModel
    from repro.serving.nde import AnalyticSelector

    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=64)
    sel = AnalyticSelector([(1, 1, 0), (2, 1, 1)],
                           LatencyModel(1e-4, 0.0, 1e-3, 0.0), "specinfer", s=1)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, selector=sel, n_slots=2)
    outs = beng.generate_batch([[1, 2, 3], [4, 5]], max_new=4, seeds=[1, 2])
    assert [len(o) for o in outs] == [4, 4]


def test_analytic_selector_fails_loud_without_peeks():
    """An engine without peek oracles must raise, not silently degrade the
    selection to a default action."""
    from repro.core.delayed import LatencyModel
    from repro.serving.nde import AnalyticSelector

    sel = AnalyticSelector([(2, 1, 1)], LatencyModel(1e-4, 0.0, 1e-3, 0.0),
                           "specinfer", s=1)
    with pytest.raises(TypeError, match="peek_draft_dist"):
        sel({"committed": [1, 2]}, object())


def test_long_prompt_prefill_does_not_wrap(dense_models):
    """Prompt-pad bucketing must cap at the ring size (regression: a
    21-token prompt in a 24-slot ring padded to 32 and wrapped onto its own
    committed prefix, silently corrupting the context), and prompts that
    cannot fit at all are rejected at submit."""
    tc, tp, dc, dp = dense_models
    prompt = list(range(1, 22))
    ecfg = EngineConfig(verifier="specinfer", K=1, L1=0, L2=1, max_cache=24)
    singles = _single_outputs(tc, tp, dc, dp, ecfg, [prompt], [7], max_new=2)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=1)
    assert beng.generate_batch([prompt], max_new=2, seeds=[7]) == singles
    with pytest.raises(ValueError):
        beng.submit(list(range(24)), max_new=2)


def test_counters_coherent(dense_models):
    tc, tp, dc, dp = dense_models
    ecfg = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)
    beng = BatchedSpeculativeEngine(tc, tp, dc, dp, ecfg, n_slots=4)
    beng.generate_batch(PROMPTS, max_new=12, seeds=SEEDS)
    c = beng.counters
    assert c["blocks"] > 0
    assert c["target_calls"] > 0
    # one padded tree pass per iteration advances every active stream:
    # strictly fewer target calls than blocks (the batching win)
    assert c["target_calls"] < c["blocks"]
    assert 0 <= c["accepted"] <= c["blocks"] * 3
