"""Every Pallas kernel compiles for a TPU v5e at granite-3-2b's widths.

The kernels are compiled (never run) for a v5e chip that is described, not
attached: the TPU compiler refuses here what the chip would refuse, such as
a block not aligned to the tiling.  Shapes are those of the serving path at
granite-3-2b: 32 query heads over 8 KV heads of width 64, 40 layers, a
4-stream pool with a 1024-slot logical ring, at KV block sizes 64 (the
serving default) and 128.  A compiled kernel shows up as a Mosaic custom
call in the program; an interpreted one would not.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.commit_kv import commit_kv

L, H, HKV, D = 40, 32, 8, 64        # granite-3-2b: layers, heads, kv heads, head_dim
STREAMS, SMAX, T, P = 4, 1024, 8, 4  # pool rows, logical ring, tree block, commit path
KERNELS = ["tree_attention", "decode_attention", "paged_tree_attention",
           "ragged_paged_tree_attention", "paged_decode_attention", "commit_kv"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single-device sharding on the described chip, with JAX's persistent
    compilation cache off: a compile for a chip that is not attached can be
    written to the cache but never read back."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _problem(kernel: str, block: int, sds):
    """(fn, argument shapes) for one kernel through its public wrapper."""
    bf, i32 = jnp.bfloat16, jnp.int32
    nb = SMAX // block
    arena = (STREAMS * nb + 1, block, HKV, D)  # usable blocks + the trash block
    paged = (sds(arena, bf), sds(arena, bf), sds((STREAMS, nb), i32))
    dense = (sds((STREAMS, SMAX, HKV, D), bf), sds((STREAMS, SMAX, HKV, D), bf))
    if kernel == "tree_attention":
        return (lambda q, k, v, m: ops.gqa_tree_attention(q, k, v, m, block_k=block,
                                                          interpret=False),
                (sds((STREAMS, T, H, D), bf), *dense, sds((STREAMS, T, SMAX), jnp.bool_)))
    if kernel == "decode_attention":
        return (lambda q, k, v, n: ops.gqa_decode_attention(q, k, v, n, block_k=block,
                                                            interpret=False),
                (sds((STREAMS, 1, H, D), bf), *dense, sds((STREAMS,), i32)))
    if kernel == "paged_tree_attention":
        return (lambda q, k, v, t, m: ops.gqa_paged_tree_attention(q, k, v, t, m,
                                                                   interpret=False),
                (sds((STREAMS, T, H, D), bf), *paged, sds((STREAMS, T, SMAX), jnp.bool_)))
    if kernel == "ragged_paged_tree_attention":
        n = STREAMS * T
        return (lambda q, k, v, t, o, m: ops.gqa_ragged_tree_attention(q, k, v, t, o, m,
                                                                       interpret=False),
                (sds((n, H, D), bf), *paged, sds((n,), i32), sds((n, SMAX), jnp.bool_)))
    if kernel == "paged_decode_attention":
        return (lambda q, k, v, t, n: ops.gqa_paged_decode_attention(q, k, v, t, n,
                                                                     interpret=False),
                (sds((STREAMS, 1, H, D), bf), *paged, sds((STREAMS,), i32)))
    # the pool commit step views the paged arena as one row of
    # (usable + trash) * block lanes and commits every row's path in one call
    lanes = (L, 1, arena[0] * block, HKV, D)
    return (lambda k, v, s, d: commit_kv(k, v, s, d, interpret=False),
            (sds(lanes, bf), sds(lanes, bf), sds((1, STREAMS * P), i32),
             sds((1, STREAMS * P), i32)))


@pytest.mark.parametrize("block", [64, 128])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, block):
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    fn, args = _problem(kernel, block, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes > 0
