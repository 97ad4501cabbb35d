"""Sampling tests: temperature/nucleus warp semantics and the keyed draft draw."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st
from scipy import stats

from repro.sampling import draw_keyed, sample_categorical, stream_key, warp_logits, warp_probs


def test_temperature_zero_is_greedy():
    logits = jnp.asarray([0.1, 2.0, -1.0])
    d = warp_logits(logits, 0.0)
    np.testing.assert_array_equal(np.asarray(d), [0, 1, 0])


def test_temperature_scales_entropy():
    logits = jnp.asarray([1.0, 0.0, -1.0])
    hot = warp_logits(logits, 2.0)
    cold = warp_logits(logits, 0.5)

    def H(d):
        d = np.clip(np.asarray(d), 1e-12, None)
        return -(d * np.log(d)).sum()

    assert H(hot) > H(cold)


def test_nucleus_keeps_threshold_token():
    probs = jnp.asarray([0.5, 0.3, 0.15, 0.05])
    out = np.asarray(warp_probs(probs, top_p=0.6))
    # 0.5 < 0.6 so the second token (crossing the threshold) is kept
    assert out[0] > 0 and out[1] > 0 and out[2] == 0 and out[3] == 0
    assert abs(out.sum() - 1) < 1e-6


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(0.1, 0.999))
def test_nucleus_mass_and_renorm(seed, top_p):
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.ones(8)).astype(np.float32)
    out = np.asarray(warp_probs(jnp.asarray(p), top_p=top_p))
    assert abs(out.sum() - 1) < 1e-5
    kept = out > 0
    # kept mass under the ORIGINAL distribution covers top_p
    assert p[kept].sum() >= top_p - 1e-6


def test_sample_categorical_distribution():
    key = jax.random.PRNGKey(0)
    probs = jnp.asarray([0.7, 0.0, 0.3])
    keys = jax.random.split(key, 4000)
    s = jax.vmap(lambda k: sample_categorical(k, probs))(keys)
    counts = np.bincount(np.asarray(s), minlength=3) / 4000
    assert counts[1] == 0
    assert abs(counts[0] - 0.7) < 0.03


# ------------------------------------------- keyed draft draw (draw_keyed) ---

KEY = stream_key(2**62 + 12345)  # the benchmark's seeds reach 2**62


def _rows(n, key=KEY, C=7, node0=0):
    """(key, C, node) of ``n`` draws of one stream at one step, one node each."""
    return (np.repeat(key[None], n, axis=0), np.full(n, C, np.int32),
            np.arange(node0, node0 + n, dtype=np.int32))


@pytest.mark.parametrize("temperature,top_p", [(1.0, 0.8), (0.7, 1.0)])
def test_keyed_draw_follows_q(temperature, top_p):
    """Draws keyed by distinct nodes follow q (chi-square), and a token the
    nucleus cut to zero mass is never drawn."""
    logits = jnp.asarray([1.2, 0.4, -0.3, 0.9, -1.5, 0.0], jnp.float32)
    q = np.asarray(warp_logits(logits, temperature, top_p))
    n = 20000
    toks = np.asarray(jax.jit(draw_keyed)(jnp.broadcast_to(jnp.asarray(q), (n, q.size)),
                                          *_rows(n)))
    counts = np.bincount(toks, minlength=q.size)
    support = q > 0
    assert counts[~support].sum() == 0
    if top_p < 1:
        assert not support.all()
    q64 = q[support].astype(np.float64)
    chi2 = stats.chisquare(counts[support], n * q64 / q64.sum())
    assert chi2.pvalue > 1e-3, (counts, q)


@pytest.mark.parametrize("batch", [1, 6])
def test_keyed_draw_is_a_function_of_key_and_row(batch):
    """The same key gives the same token, and a row's token is the same
    drawn alone as among other rows (its noise is built from its own key)."""
    rng = np.random.default_rng(batch)
    q = jnp.asarray(rng.dirichlet(np.ones(40), size=batch), jnp.float32)
    key = np.stack([stream_key(s) for s in rng.integers(0, 2**62, size=batch)])
    C = rng.integers(1, 500, size=batch).astype(np.int32)
    node = rng.integers(0, 7, size=batch).astype(np.int32)
    draw = jax.jit(draw_keyed)
    toks = np.asarray(draw(q, key, C, node))
    np.testing.assert_array_equal(toks, np.asarray(draw(q, key, C, node)))
    for r in range(batch):
        alone = draw(q[r:r + 1], key[r:r + 1], C[r:r + 1], node[r:r + 1])
        assert int(alone[0]) == toks[r]
    # another node, committed length or seed is another key: over many
    # draws from a flat q the tokens differ
    many = 64
    flat = jnp.full((many, 40), 1 / 40, jnp.float32)
    base = np.asarray(draw(flat, *_rows(many)))
    assert (base != np.asarray(draw(flat, *_rows(many, node0=many)))).any()
    assert (base != np.asarray(draw(flat, *_rows(many, C=8)))).any()
    assert (base != np.asarray(draw(flat, *_rows(many, key=stream_key(3))))).any()


def test_keyed_draw_at_temperature_zero_is_argmax():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(9, 17)), jnp.float32)
    q = warp_logits(logits, 0.0)
    toks = np.asarray(draw_keyed(q, *_rows(9)))
    np.testing.assert_array_equal(toks, np.argmax(np.asarray(logits), axis=-1))
