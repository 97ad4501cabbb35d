"""Masked flash attention for the speculative tree pass — Pallas TPU kernel.

The target pass of multi-path speculative decoding attends T tree tokens
against (a) a long committed prefix and (b) the speculation block itself with
an arbitrary ancestor mask.  On GPU this is a gather + custom-mask Flash
kernel (DeFT-style); the TPU-native formulation here:

  * queries: the whole (padded) tree block lives in VMEM for the entire
    kernel — T is tiny (<= 128), so the online-softmax state (m, l, acc)
    stays in VMEM scratch with no HBM round-trips;
  * keys/values stream HBM -> VMEM in ``block_k`` chunks along the grid's
    sequential minor axis (TPU grids execute in order, so cross-block
    accumulation needs no atomics — the GPU split-k reduction disappears);
  * the boolean mask streams with the same blocking, as (T, block_k)
    tiles of a block-major view (``_block_major``); MXU matmuls are
    (T, D) x (D, block_k) with D = head_dim.

Layouts: q (BH, T, D);  k, v (BH, S, D);  mask (BH, T, S).  The ops.py
wrapper folds batch x heads and broadcasts GQA groups.

``paged_tree_attention`` is the block-table variant for the paged KV pool:
same kernel body, with the K/V index maps chasing a scalar-prefetched block
table (docs/kernels.md "Block-table attention").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_tile_body(q_ref, k_ref, v_ref, mask, o_ref, m_ref, l_ref, acc_ref, j, nk):
    """One K/V-block step of the online softmax; ``mask`` is this block's
    loaded (T, Bk) bool tile, j the sequential minor grid axis (0-based), nk
    its extent."""

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (T, D)
    k = k_ref[0].astype(jnp.float32)  # (Bk, D)
    v = v_ref[0].astype(jnp.float32)  # (Bk, D)

    d = q.shape[-1]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) / (d**0.5)  # (T, Bk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (T, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (T, Bk); rows that are fully masked give exp(NEG_INF - m)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _tree_attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref):
    _attn_tile_body(q_ref, k_ref, v_ref, mask_ref[0, 0], o_ref, m_ref, l_ref, acc_ref,
                    pl.program_id(1), pl.num_programs(1))


def _paged_tree_attn_kernel(tbl_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref):
    del tbl_ref  # consumed by the K/V index maps
    _tree_attn_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref)


def _ragged_tree_attn_kernel(owners_ref, tbl_ref, q_ref, k_ref, v_ref, mask_ref,
                             o_ref, m_ref, l_ref, acc_ref):
    del owners_ref, tbl_ref  # consumed by the K/V index maps
    _attn_tile_body(q_ref, k_ref, v_ref, mask_ref[0, 0], o_ref, m_ref, l_ref, acc_ref,
                    pl.program_id(2), pl.num_programs(2))


def _block_major(mask, block):
    """(R, T, nb*block) -> (R, nb, T, block): the logical block index moves
    to a leading axis, so a mask tile's last two dims are (T, block), the
    whole trailing extent.  The TPU lowering takes a tile whose minor dim
    is neither a multiple of 128 nor the full array only in this form: the
    flat (1, T, block) tile is refused for block < 128 (e.g. the serving
    default of 64).  Every tree-attention kernel takes its mask this way."""
    R, T, S = mask.shape
    return mask.reshape(R, T, S // block, block).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_tree_attention(q, k_arena, v_arena, tbl, mask, *, interpret: bool):
    """Block-table tree attention: KV streams straight from the paged arena.

    q (BH, T, D); k_arena, v_arena (NBLK, block, D) — the folded per-head
    arena; tbl (BH, max_blocks) int32 physical block ids (pre-clamped:
    unmapped logical blocks point at the trash block and must be masked
    False); mask (BH, T, S) bool over LOGICAL slots, S = max_blocks*block.
    Returns (BH, T, D).

    Identical online-softmax body as ``tree_attention``; the only change is
    the K/V BlockSpec index maps, which chase the scalar-prefetched block
    table instead of walking logical slots — the grid's minor axis j is the
    *logical* block index, so the mask (and any iota-derived validity)
    stays in logical coordinates while HBM reads hit exactly the mapped
    arena blocks.  Oracle: kernels/ref.py ``paged_gather_kv_ref`` composed
    with ``tree_attention_ref``."""
    BH, T, D = q.shape
    nblk, block = k_arena.shape[0], k_arena.shape[1]
    nb = tbl.shape[1]
    assert mask.shape == (BH, T, nb * block), (mask.shape, (BH, T, nb * block))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nb),
        in_specs=[
            pl.BlockSpec((1, T, D), lambda i, j, tbl: (i, 0, 0)),
            pl.BlockSpec((1, block, D), lambda i, j, tbl: (tbl[i, j], 0, 0)),
            pl.BlockSpec((1, block, D), lambda i, j, tbl: (tbl[i, j], 0, 0)),
            pl.BlockSpec((1, 1, T, block), lambda i, j, tbl: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, D), lambda i, j, tbl: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _paged_tree_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        interpret=interpret,
        name="paged_tree_attention",
    )(tbl, q, k_arena, v_arena, _block_major(mask, block))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_paged_tree_attention(q, k_arena, v_arena, tbl, owners, mask, *,
                                interpret: bool):
    """Ragged node-major tree attention over a paged arena.

    The Q axis is not a per-stream tree block but the FLAT ragged node
    buffer of every active stream's tree concatenated (docs/serving.md),
    tiled in 8-row Q tiles of UNIFORM owner (the engine 8-aligns segment
    offsets under the pallas impl, so no tile straddles two streams):

      q (H, Np, D) — head-major flat nodes, Np a multiple of 8;
      k_arena, v_arena (Hkv*NBLK, block, D) — the head-folded arena
        (ops._fold_paged_arena output);
      tbl (B*H, max_blocks) — the folded per-(row, head) block table;
      owners (Np//8,) int32 — pool row of each Q tile;
      mask (Np//8, 8, S) bool over the owner row's LOGICAL slots.

    The grid is (H, n_tiles, nb): a second scalar-prefetch operand
    (``owners``) steers the K/V index maps — tile t of head h reads the
    arena blocks of tbl[owners[t]*H + h, j], so each node attends over its
    OWN stream's block table while sharing one kernel launch with every
    co-resident tree.  Same online-softmax body as ``tree_attention``.
    Oracle: kernels/ref.py ``ragged_tree_attention_ref``."""
    H, Np, D = q.shape
    block = k_arena.shape[1]
    nb = tbl.shape[1]
    n_tiles = Np // 8
    assert Np % 8 == 0, Np
    assert mask.shape == (n_tiles, 8, nb * block), (mask.shape, (n_tiles, 8, nb * block))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H, n_tiles, nb),
        in_specs=[
            pl.BlockSpec((1, 8, D), lambda h, t, j, owners, tbl: (h, t, 0)),
            pl.BlockSpec((1, block, D),
                         lambda h, t, j, owners, tbl: (tbl[owners[t] * H + h, j], 0, 0)),
            pl.BlockSpec((1, block, D),
                         lambda h, t, j, owners, tbl: (tbl[owners[t] * H + h, j], 0, 0)),
            pl.BlockSpec((1, 1, 8, block), lambda h, t, j, owners, tbl: (t, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 8, D), lambda h, t, j, owners, tbl: (h, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((8, 1), jnp.float32),
            pltpu.VMEM((8, 1), jnp.float32),
            pltpu.VMEM((8, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _ragged_tree_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, Np, D), q.dtype),
        interpret=interpret,
        name="ragged_paged_tree_attention",
    )(owners, tbl, q, k_arena, v_arena, _block_major(mask, block))


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def tree_attention(q, k, v, mask, *, block_k: int = 512, interpret: bool):
    """q (BH, T, D); k, v (BH, S, D); mask (BH, T, S) -> (BH, T, D).

    S must be a multiple of block_k (caller pads; padded slots masked False).
    T and block_k should be multiples of 8 for TPU tiling; D is any head
    width (64 and 128 compile for a v5e).
    """
    BH, T, D = q.shape
    S = k.shape[1]
    assert S % block_k == 0, (S, block_k)
    nk = S // block_k
    grid = (BH, nk)
    return pl.pallas_call(
        _tree_attn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, T, block_k), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, T, D), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, 1), jnp.float32),
            pltpu.VMEM((T, D), jnp.float32),
        ],
        interpret=interpret,
        name="tree_attention",
    )(q, k, v, _block_major(mask, block_k))
