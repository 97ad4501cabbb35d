"""Flash-decode — Pallas TPU kernel for single-token decode attention.

The memory-bound core of decode_32k / long_500k: one query row per (batch,
head) against a KV cache of S slots.  No mask tensor: validity is computed
in-register from a streamed iota against the scalar cache length (and an
optional sliding window), so HBM traffic is exactly the KV bytes — the
roofline floor for decode.

TPU adaptation of GPU flash-decode: the split-K + cross-SM reduction becomes
a sequential grid walk over KV blocks with VMEM-resident (m, l, acc); the
8-sublane minimum tile means the single query row is padded to 8 rows (the
wrapper slices row 0 back out).

Layouts: q (BH, 8, D);  k, v (BH, S, D);  lengths (BH, 1) int32 in SMEM.

``paged_decode_attention`` is the block-table variant for the paged KV
pool: same kernel body, with the K/V index maps chasing a scalar-prefetched
block table (docs/kernels.md "Block-table attention").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, block_k, window):
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    q = q_ref[0].astype(jnp.float32)  # (8, D)
    k = k_ref[0].astype(jnp.float32)  # (Bk, D)
    v = v_ref[0].astype(jnp.float32)

    d = q.shape[-1]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / (d**0.5)  # (8, Bk)
    slot = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = slot < length
    if window:
        valid = valid & (slot >= length - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                         *, block, window):
    del tbl_ref  # consumed by the K/V index maps
    _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   block_k=block, window=window)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_arena, v_arena, tbl, lengths, *, window: int = 0,
                           interpret: bool):
    """Flash-decode over a paged KV pool: KV streams through the block table.

    q (BH, 8, D); k_arena, v_arena (NBLK, block, D); tbl (BH, max_blocks)
    int32 physical block ids (pre-clamped — unmapped logical blocks point at
    the trash block, which in-register validity already excludes because a
    stream's mapped blocks always cover slots [0, len)); lengths (BH,)
    int32.  Returns (BH, 8, D).

    Same kernel body as ``decode_attention``: the minor grid axis j is the
    logical block index, so the streamed iota validity (slot = j*block +
    lane < length, optionally windowed) is untouched; only the K/V index
    maps chase the scalar-prefetched table.  Oracle: kernels/ref.py
    ``paged_gather_kv_ref`` composed with ``decode_attention_ref``."""
    BH, R, D = q.shape
    nblk, block = k_arena.shape[0], k_arena.shape[1]
    nb = tbl.shape[1]
    kernel = functools.partial(_paged_decode_kernel, block=block, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nb),
        in_specs=[
            pl.BlockSpec((1, R, D), lambda i, j, tbl, lens: (i, 0, 0)),
            pl.BlockSpec((1, block, D), lambda i, j, tbl, lens: (tbl[i, j], 0, 0)),
            pl.BlockSpec((1, block, D), lambda i, j, tbl, lens: (tbl[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, D), lambda i, j, tbl, lens: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, R, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(tbl, lengths.reshape(BH), q, k_arena, v_arena)


@functools.partial(jax.jit, static_argnames=("block_k", "window", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k: int = 1024, window: int = 0, interpret: bool):
    """q (BH, 8, D) (query broadcast over 8 sublanes, row 0 real);
    k, v (BH, S, D); lengths (BH, 1) int32.  Returns (BH, 8, D)."""
    BH, R, D = q.shape
    S = k.shape[1]
    assert S % block_k == 0, (S, block_k)
    grid = (BH, S // block_k)
    kernel = functools.partial(_decode_kernel, block_k=block_k, window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, R, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, D), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, R, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(lengths.reshape(BH), q, k, v)
