"""End-to-end speculative engine tests.

Invariants:
  * self-drafting (draft == target) accepts every drafted token for every
    verifier/strategy — block efficiency is exactly the tree depth + 1;
  * the engine's emitted first-token distribution matches direct target
    sampling (statistical, integration-level losslessness);
  * delayed expansion produces valid trees; counters are coherent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.transformer import forward, init_params
from repro.sampling import warp_logits
from repro.serving.engine import EngineConfig, SamplingParams, SpeculativeEngine

V = 32


def _dense(nl=2, dm=48, name="t", vocab=V):
    return ModelConfig(name=name, arch_type="dense", n_layers=nl, d_model=dm, n_heads=4,
                       n_kv_heads=2, d_ff=96, vocab=vocab, dtype="float32")


@pytest.fixture(scope="module")
def models():
    tc = _dense(2, 64)
    dc = _dense(1, 32, "d")
    return tc, init_params(tc, jax.random.PRNGKey(0)), dc, init_params(dc, jax.random.PRNGKey(1))


SSM_CFG = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=48, vocab=V,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8, dtype="float32")
HYB_CFG = ModelConfig(name="h", arch_type="hybrid", n_layers=5, d_model=48, n_heads=4,
                      n_kv_heads=1, d_ff=96, vocab=V, local_window=32, dtype="float32")


@pytest.mark.slow
@pytest.mark.parametrize("cfg", [_dense(2, 48), SSM_CFG, HYB_CFG], ids=["dense", "ssm", "hybrid"])
@pytest.mark.parametrize("verifier,K,L1,L2,expect", [
    ("naive_single", 1, 0, 3, 4.0),
    ("bv", 1, 1, 2, 4.0),
    ("traversal", 2, 1, 1, 3.0),
    ("specinfer", 2, 1, 1, 3.0),
])
def test_self_draft_full_acceptance(cfg, verifier, K, L1, L2, expect):
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = SpeculativeEngine(cfg, params, cfg, params,
                            EngineConfig(verifier=verifier, K=K, L1=L1, L2=L2, max_cache=128))
    eng.generate([1, 2, 3], max_new=18)
    be = eng.counters["accepted"] / eng.counters["blocks"] + 1
    assert abs(be - expect) < 1e-6, be


@pytest.mark.slow
@pytest.mark.parametrize("verifier", ["specinfer", "traversal", "spectr", "khisti", "nss"])
def test_engine_first_token_distribution(models, verifier):
    """The first emitted token across many seeds must follow the warped target."""
    tc, tp, dc, dp = models
    prompt = [3, 1, 4]
    temp, topp = 0.9, 1.0
    # direct target distribution at the prompt
    logits, _, _ = forward(tp, tc, jnp.asarray([prompt]), mode="full")
    p_direct = np.asarray(warp_logits(logits[0, -1], temp, topp))

    n = 260
    counts = np.zeros(V)
    eng = SpeculativeEngine(tc, tp, dc, dp,
                            EngineConfig(verifier=verifier, K=2, L1=1, L2=1, max_cache=128),
                            SamplingParams(temp, topp))
    for seed in range(n):
        eng.rng = np.random.default_rng(seed)
        stream = eng.new_stream(list(prompt), seed=seed)
        toks = eng.step(stream)
        counts[toks[0]] += 1
    freq = counts / n
    # generous statistical tolerance (binomial std ~ sqrt(p/n) ~ 0.03)
    assert np.abs(freq - p_direct).max() < 0.09, np.abs(freq - p_direct).max()


@pytest.mark.slow
def test_counters_and_block_structure(models):
    tc, tp, dc, dp = models
    eng = SpeculativeEngine(tc, tp, dc, dp, EngineConfig(verifier="spectr", K=3, L1=2, L2=2, max_cache=256))
    out = eng.generate([5, 6], max_new=25)
    assert len(out) == 25
    c = eng.counters
    assert c["blocks"] == c["target_calls"]
    # every block drafts L1 + K*L2 tokens (+ delta ingestion)
    assert c["draft_tokens"] >= c["blocks"] * (2 + 3 * 2)
    assert 0 <= c["accepted"] <= c["blocks"] * 8


@pytest.mark.slow
def test_greedy_temperature_zero(models):
    """temperature=0 -> engine output equals greedy target decoding exactly."""
    tc, tp, dc, dp = models
    eng = SpeculativeEngine(tc, tp, dc, dp,
                            EngineConfig(verifier="specinfer", K=2, L1=1, L2=2, max_cache=128),
                            SamplingParams(temperature=0.0))
    out = eng.generate([2, 7], max_new=12)
    # direct greedy
    ctx = [2, 7]
    for _ in range(12):
        lg, _, _ = forward(tp, tc, jnp.asarray([ctx]), mode="full")
        ctx.append(int(jnp.argmax(lg[0, -1])))
    assert out == ctx[2:], (out, ctx[2:])


@pytest.mark.slow
def test_nucleus_sampling_support(models):
    """top_p < 1: emitted tokens must stay within the warped support."""
    tc, tp, dc, dp = models
    eng = SpeculativeEngine(tc, tp, dc, dp,
                            EngineConfig(verifier="traversal", K=2, L1=1, L2=1, max_cache=256),
                            SamplingParams(1.0, 0.7))
    stream = eng.new_stream([1, 2, 3])
    for _ in range(6):
        ctx = list(stream["committed"])
        toks = eng.step(stream)
        # each emitted token must lie in the nucleus of the target at its prefix
        for i, t in enumerate(toks):
            lg, _, _ = forward(tp, tc, jnp.asarray([ctx + toks[:i]]), mode="full")
            dist = np.asarray(warp_logits(lg[0, -1], 1.0, 0.7))
            assert dist[t] > 0, (t, i)


@pytest.mark.slow
def test_analytic_selector_runs(models):
    from repro.core.delayed import LatencyModel
    from repro.serving.nde import AnalyticSelector

    tc, tp, dc, dp = models
    sel = AnalyticSelector([(1, 1, 0), (2, 1, 1)], LatencyModel(1e-4, 0, 1e-3, 0), "specinfer")
    eng = SpeculativeEngine(tc, tp, dc, dp, EngineConfig(verifier="specinfer", max_cache=256),
                            selector=sel)
    out = eng.generate([1, 2], max_new=8)
    assert len(out) == 8
