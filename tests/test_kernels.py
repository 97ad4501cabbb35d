"""Pallas kernel validation (interpret mode) against the pure-jnp oracles:
shape/dtype sweeps with assert_allclose, plus seeded property checks
(the vendored _propcheck shim)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels.ops import gqa_decode_attention, gqa_tree_attention
from repro.kernels.ref import decode_attention_ref, tree_attention_ref


def _mk(key, B, T, H, Hkv, D, S, dtype):
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    mask = jax.random.bernoulli(ks[3], 0.5, (B, T, S)).at[:, :, 0].set(True)
    return q, k, v, mask


def _ref_tree(q, k, v, mask):
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, D)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, D)
    mr = jnp.broadcast_to(mask[:, None], (B, H, T, S)).reshape(B * H, T, S)
    return tree_attention_ref(qr, kr, vr, mr).reshape(B, H, T, D).transpose(0, 2, 1, 3)


@pytest.mark.slow
@pytest.mark.parametrize("T", [1, 5, 8, 17])
@pytest.mark.parametrize("S,block_k", [(64, 128), (96, 128), (256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tree_attention_sweep(T, S, block_k, dtype):
    q, k, v, mask = _mk(jax.random.PRNGKey(hash((T, S)) % 2**31), 2, T, 4, 2, 128, S, dtype)
    out = gqa_tree_attention(q, k, v, mask, block_k=block_k, interpret=True)
    ref = _ref_tree(q, k, v, mask)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (4, 1)])
def test_tree_attention_gqa_groups(H, Hkv):
    q, k, v, mask = _mk(jax.random.PRNGKey(0), 1, 6, H, Hkv, 128, 128, jnp.float32)
    out = gqa_tree_attention(q, k, v, mask, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref_tree(q, k, v, mask)), atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("S,lengths", [(128, (7, 128)), (256, (250, 1))])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(S, lengths, window, dtype):
    B, H, Hkv, D = 2, 4, 2, 128
    key = jax.random.PRNGKey(hash((S, lengths, window)) % 2**31)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    ln = jnp.asarray(lengths, jnp.int32)
    out = gqa_decode_attention(q, k, v, ln, block_k=128, window=window, interpret=True)
    G = H // Hkv
    qr = jnp.broadcast_to(q.transpose(0, 2, 1, 3), (B, H, 1, D)).reshape(B * H, 1, D)
    kr = jnp.repeat(k.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, D)
    vr = jnp.repeat(v.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, D)
    lr = jnp.broadcast_to(ln[:, None], (B, H)).reshape(B * H, 1)
    ref = decode_attention_ref(qr, kr, vr, lr, window=window)
    ref = ref.reshape(B, H, 1, D).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.integers(1, 10), st.integers(1, 200), st.integers(0, 2**31 - 1))
def test_tree_attention_property(T, S, seed):
    """Arbitrary (T, S): kernel == oracle after the wrapper's padding."""
    q, k, v, mask = _mk(jax.random.PRNGKey(seed), 1, T, 2, 1, 128, S, jnp.float32)
    out = gqa_tree_attention(q, k, v, mask, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref_tree(q, k, v, mask)), atol=3e-5)


def test_tree_attention_equals_engine_attention():
    """The kernel must agree with the model's jnp gqa_attend on a tree mask."""
    from repro.models.layers import gqa_attend

    q, k, v, mask = _mk(jax.random.PRNGKey(5), 2, 7, 4, 2, 128, 64, jnp.float32)
    out_k = gqa_tree_attention(q, k, v, mask, block_k=128, interpret=True)
    out_m = gqa_attend(q, k, v, mask[:, None])
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m), atol=3e-5)


def test_pallas_attention_impl_in_model():
    """cfg.attention_impl='pallas' must reproduce the XLA path end-to-end
    (full pass and cached decode)."""
    import numpy as np
    from repro.models.config import ModelConfig
    from repro.models.transformer import forward, init_cache, init_params

    cfg = ModelConfig(name="t", n_layers=2, d_model=256, n_heads=2, n_kv_heads=1,
                      d_ff=256, vocab=64, dtype="float32", head_dim=128)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)), jnp.int32)
    lg_x, _, _ = forward(params, cfg, toks, mode="full")
    lg_p, _, _ = forward(params, cfg.replace(attention_impl="pallas"), toks, mode="full")
    np.testing.assert_allclose(np.asarray(lg_x), np.asarray(lg_p), atol=1e-4)

    c1 = init_cache(cfg, 2, 32)
    _, c1, _ = forward(params, cfg, toks, mode="full", cache=c1)
    d1, _, _ = forward(params, cfg, toks[:, :1], mode="decode", cache=c1)
    cfg_p = cfg.replace(attention_impl="pallas")
    c2 = init_cache(cfg_p, 2, 32)
    _, c2, _ = forward(params, cfg_p, toks, mode="full", cache=c2)
    d2, _, _ = forward(params, cfg_p, toks[:, :1], mode="decode", cache=c2)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-4)


def _mk_ragged(seed, segs, H=4, Hkv=2, D=128, nb=4, block=16, tail=0):
    """A ragged node-major attention problem: ``segs`` 8-row Q tiles per
    stream (``tail`` trims rows off the last stream's final tile, exercising
    the wrapper's pad-and-slice), a paged arena with per-stream block
    tables (-1 = unmapped; unmapped logical slots masked False)."""
    import numpy as np
    from repro.kernels.ops import gqa_ragged_tree_attention  # noqa: F401

    B = len(segs)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    N = 8 * sum(segs) - tail
    owner = np.repeat(np.arange(B, dtype=np.int32), [8 * s for s in segs])[:N]
    NBLK = 1 + B * nb  # block 0 is the trash block unmapped entries clamp to
    k_arena = jax.random.normal(ks[0], (NBLK, block, Hkv, D), jnp.float32)
    v_arena = jax.random.normal(ks[1], (NBLK, block, Hkv, D), jnp.float32)
    rng = np.random.default_rng(seed)
    tbl = np.full((B, nb), -1, np.int32)
    perm = rng.permutation(np.arange(1, NBLK, dtype=np.int32))
    taken = 0
    for b in range(B):
        nmap = int(rng.integers(1, nb + 1))
        tbl[b, :nmap] = perm[taken:taken + nmap]
        taken += nmap
    q = jax.random.normal(ks[2], (N, H, D), jnp.float32)
    mask = np.array(jax.random.bernoulli(ks[3], 0.5, (N, nb * block)))
    mask &= np.repeat(tbl >= 0, block, axis=1)[owner]  # unmapped slots False
    mask[:, 0] = True  # slot 0 is always mapped (tbl[:, 0] >= 0 above)
    return (q, k_arena, v_arena, jnp.asarray(tbl), jnp.asarray(owner),
            jnp.asarray(mask))


def test_ragged_tree_attention_matches_oracle():
    """The scalar-prefetched owner steering reads each tile's OWN stream's
    arena blocks: kernel == pure-jnp gather oracle across a 3-stream ragged
    buffer with distinct per-stream block tables."""
    from repro.kernels.ops import gqa_ragged_tree_attention
    from repro.kernels.ref import ragged_tree_attention_ref

    args = _mk_ragged(0, segs=[1, 2, 1])
    out = gqa_ragged_tree_attention(*args, interpret=True)
    ref = ragged_tree_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_ragged_tree_attention_partial_tail_tile():
    """N not a multiple of 8: the wrapper pads with all-False mask rows and
    slices them back off; the padded tail must not perturb real rows."""
    from repro.kernels.ops import gqa_ragged_tree_attention
    from repro.kernels.ref import ragged_tree_attention_ref

    args = _mk_ragged(1, segs=[1, 1, 2], tail=5)
    assert args[0].shape[0] % 8 != 0
    out = gqa_ragged_tree_attention(*args, interpret=True)
    ref = ragged_tree_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.integers(0, 7), st.integers(0, 2**31 - 1))
def test_ragged_tree_attention_property(n_streams, tail, seed):
    """Arbitrary stream counts, segment lengths, ragged tails and sparse
    block tables: kernel == oracle."""
    from repro.kernels.ops import gqa_ragged_tree_attention
    from repro.kernels.ref import ragged_tree_attention_ref

    segs = np.random.default_rng(seed).integers(1, 4, size=n_streams).tolist()
    tail = min(tail, 8 * segs[-1] - 1)
    args = _mk_ragged(seed, segs=segs, H=2, Hkv=1, nb=3, tail=tail)
    out = gqa_ragged_tree_attention(*args, interpret=True)
    ref = ragged_tree_attention_ref(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_interpret_mode_follows_backend():
    """Interpretation is decided once, from the backend: on the CPU backend
    (every test run) kernels are interpreted; on a TPU they compile."""
    from repro.kernels.ops import interpret_mode
    from repro.models.config import ModelConfig

    assert jax.default_backend() == "cpu"
    assert interpret_mode() is True
    # no config knob can pin interpretation on for a TPU run
    assert not hasattr(ModelConfig(), "kernel_interpret")


def test_kernel_wrappers_take_interpret_explicitly():
    """No public kernel entry point defaults ``interpret``: every call site
    states it, so none inherits interpretation silently."""
    import inspect

    from repro.kernels import commit_kv, decode_attention, ops, tree_attention

    wrappers = [ops.pool_commit_kv, ops.gqa_tree_attention, ops.gqa_paged_tree_attention,
                ops.gqa_ragged_tree_attention, ops.gqa_paged_decode_attention,
                ops.gqa_decode_attention, commit_kv.commit_kv,
                tree_attention.tree_attention, tree_attention.paged_tree_attention,
                tree_attention.ragged_paged_tree_attention,
                decode_attention.decode_attention, decode_attention.paged_decode_attention]
    for fn in wrappers:
        param = inspect.signature(fn).parameters["interpret"]
        assert param.kind is inspect.Parameter.KEYWORD_ONLY, fn
        assert param.default is inspect.Parameter.empty, fn
