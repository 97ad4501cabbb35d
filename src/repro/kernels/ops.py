"""Jit'd public wrappers around the Pallas kernels.

Handle GQA head-group broadcasting and padding to TPU tile boundaries.
Every wrapper takes ``interpret`` as a required keyword: callers on the
serving path pass :func:`interpret_mode`, the one place the flag is
decided, so kernels compile to Mosaic on a TPU and run in the Pallas
interpreter only on the CPU backend (tests, rehearsals).
"""
from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention import decode_attention, paged_decode_attention
from repro.kernels.tree_attention import (
    paged_tree_attention,
    ragged_paged_tree_attention,
    tree_attention,
)


# (kernel, interpret) -> times its wrapper was traced into a program: the
# record chip_smoke.py reads to show that no kernel was built interpreted
# on the chip.  Tracing runs once per compiled shape, so this costs nothing
# per step.
KERNEL_TRACES: Counter = Counter()


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted: True only on the CPU backend.
    On a TPU every kernel compiles to Mosaic; no other backend is served."""
    return jax.default_backend() == "cpu"


def pool_commit_kv(k, v, src, dst, *, use_pallas: bool, interpret: bool):
    """Ring-compaction commit over the per-stream KV pool.

    k, v (L, B, Smax, Hkv, hd); src, dst (B, P) int32 slot indices (padding
    entries carry src == dst).  The Pallas path (kernels/commit_kv.py) moves
    only the touched (layer, row, slot) lanes in place; the ref path is the
    pure-jnp gather/scatter oracle.  Both honour the hazard-free index
    contract documented in serve_step.make_pool_commit_step.
    """
    if use_pallas:
        from repro.kernels.commit_kv import commit_kv

        KERNEL_TRACES["commit_kv", interpret] += 1
        return commit_kv(k, v, src, dst, interpret=interpret)
    from repro.kernels.ref import commit_kv_ref

    return commit_kv_ref(k, v, src, dst)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def gqa_tree_attention(q, k, v, mask, *, block_k: int = 512, interpret: bool):
    """Engine-layout tree attention.

    q (B, T, H, D); k, v (B, S, Hkv, D); mask (B, T, S) or (1, T, S) bool.
    Returns (B, T, H, D).
    """
    KERNEL_TRACES["tree_attention", interpret] += 1
    B, T, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    Tp = int(np.ceil(T / 8) * 8)
    bk = min(block_k, int(np.ceil(S / 128) * 128))
    qf = _pad_to(q.transpose(0, 2, 1, 3), 8, axis=2)  # (B, H, Tp, D)
    qf = qf.reshape(B * H, Tp, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    mb = jnp.broadcast_to(mask, (B, T, S))
    mb = _pad_to(mb, 8, axis=1)
    mb = jnp.broadcast_to(mb[:, None], (B, H, Tp, S)).reshape(B * H, Tp, S)
    # pad S to the block size (padded slots masked out)
    kf = _pad_to(kf, bk, axis=1)
    vf = _pad_to(vf, bk, axis=1)
    mb = _pad_to(mb, bk, axis=2)
    out = tree_attention(qf, kf, vf, mb, block_k=bk, interpret=interpret)
    return out.reshape(B, H, Tp, D)[:, :, :T].transpose(0, 2, 1, 3)


def _fold_paged_arena(k_arena, v_arena, tbl, H):
    """Fold KV heads into the arena's block axis so the paged kernels see
    (Hkv*NBLK, block, hd) arenas and a per-(batch, head) table.

    k_arena, v_arena (NBLK, block, Hkv, hd); tbl (B, max_blocks) with -1 for
    unmapped (clamped to the trash block here).  Returns (kf, vf, tbl_f)
    with tbl_f (B*H, max_blocks) — head h of batch b reads physical block
    kv_head(h)*NBLK + tbl[b, j].  The transpose touches arena bytes once
    (the arena is the pool's physical footprint, already far smaller than
    the dense per-stream view the non-paged wrappers materialize)."""
    NB, block, Hkv, hd = k_arena.shape
    G = H // Hkv
    kf = k_arena.transpose(2, 0, 1, 3).reshape(Hkv * NB, block, hd)
    vf = v_arena.transpose(2, 0, 1, 3).reshape(Hkv * NB, block, hd)
    kvh = jnp.arange(H, dtype=jnp.int32) // G
    tbl_f = (kvh[None, :, None] * NB + jnp.clip(tbl, 0)[:, None, :]).reshape(-1, tbl.shape[1])
    return kf, vf, tbl_f.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gqa_paged_tree_attention(q, k_arena, v_arena, tbl, mask, *, interpret: bool):
    """Engine-layout tree attention over a paged KV pool.

    q (B, T, H, D); k_arena, v_arena (NBLK, block, Hkv, D); tbl
    (B, max_blocks) int32 (-1 = unmapped); mask (B, T, S) or (1, T, S) bool
    over logical slots, S = max_blocks*block (unmapped slots carry pos = -1
    upstream, so the mask is False there).  Returns (B, T, H, D)."""
    KERNEL_TRACES["paged_tree_attention", interpret] += 1
    B, T, H, D = q.shape
    nb, block = tbl.shape[1], k_arena.shape[1]
    S = nb * block
    Tp = int(np.ceil(T / 8) * 8)
    qf = _pad_to(q.transpose(0, 2, 1, 3), 8, axis=2).reshape(B * H, Tp, D)
    kf, vf, tbl_f = _fold_paged_arena(k_arena, v_arena, tbl, H)
    mb = jnp.broadcast_to(mask, (B, T, S))
    mb = _pad_to(mb, 8, axis=1)
    mb = jnp.broadcast_to(mb[:, None], (B, H, Tp, S)).reshape(B * H, Tp, S)
    out = paged_tree_attention(qf, kf, vf, tbl_f, mb, interpret=interpret)
    return out.reshape(B, H, Tp, D)[:, :, :T].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gqa_ragged_tree_attention(q, k_arena, v_arena, tbl, owner, mask, *,
                              interpret: bool):
    """Engine-layout RAGGED tree attention over a paged KV pool.

    q (N, H, D) — the flat node-major buffer of every active stream's tree
    (models/transformer.py ``ragged``); k_arena, v_arena
    (NBLK, block, Hkv, D); tbl (B, max_blocks) int32 (-1 = unmapped);
    owner (N,) int32 pool row per node; mask (N, S) bool over the owner
    row's logical slots.  Returns (N, H, D).

    Pads N up to a multiple of 8 (pad nodes: owner 0, mask all-False —
    their rows are garbage and sliced off) and hands the kernel one owner
    per 8-row Q tile; the engine's 8-aligned segment offsets guarantee
    tiles are owner-uniform for real nodes."""
    KERNEL_TRACES["ragged_paged_tree_attention", interpret] += 1
    N, H, D = q.shape
    nb, block = tbl.shape[1], k_arena.shape[1]
    S = nb * block
    Np = int(np.ceil(N / 8) * 8)
    qp = _pad_to(q, 8, axis=0).transpose(1, 0, 2)  # (H, Np, D)
    op = _pad_to(owner.astype(jnp.int32), 8, axis=0)
    mp = _pad_to(mask, 8, axis=0).reshape(Np // 8, 8, S)
    owners_t = op.reshape(Np // 8, 8)[:, 0]
    kf, vf, tbl_f = _fold_paged_arena(k_arena, v_arena, tbl, H)
    out = ragged_paged_tree_attention(qp, kf, vf, tbl_f, owners_t, mp,
                                      interpret=interpret)
    return out.transpose(1, 0, 2)[:N]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def gqa_paged_decode_attention(q, k_arena, v_arena, tbl, lengths, *, window: int = 0,
                               interpret: bool):
    """Engine-layout flash-decode over a paged KV pool.

    q (B, 1, H, D); k_arena, v_arena (NBLK, block, Hkv, D); tbl
    (B, max_blocks) int32; lengths (B,) int32.  Returns (B, 1, H, D)."""
    KERNEL_TRACES["paged_decode_attention", interpret] += 1
    B, _, H, D = q.shape
    qf = jnp.broadcast_to(q.transpose(0, 2, 1, 3), (B, H, 8, D)).reshape(B * H, 8, D)
    kf, vf, tbl_f = _fold_paged_arena(k_arena, v_arena, tbl, H)
    lf = jnp.broadcast_to(lengths[:, None], (B, H)).reshape(B * H)
    out = paged_decode_attention(qf, kf, vf, tbl_f, lf, window=window, interpret=interpret)
    return out.reshape(B, H, 8, D)[:, :, :1].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("block_k", "window", "interpret"))
def gqa_decode_attention(q, k, v, lengths, *, block_k: int = 1024, window: int = 0, interpret: bool):
    """Engine-layout flash-decode.

    q (B, 1, H, D); k, v (B, S, Hkv, D); lengths (B,) int32.
    Returns (B, 1, H, D).
    """
    KERNEL_TRACES["decode_attention", interpret] += 1
    B, _, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    bk = min(block_k, int(np.ceil(S / 128) * 128))
    qf = jnp.broadcast_to(q.transpose(0, 2, 1, 3), (B, H, 8, D)).reshape(B * H, 8, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, S, D)
    kf = _pad_to(kf, bk, axis=1)
    vf = _pad_to(vf, bk, axis=1)
    lf = jnp.broadcast_to(lengths[:, None], (B, H)).reshape(B * H, 1)
    out = decode_attention(qf, kf, vf, lf, block_k=bk, window=window, interpret=interpret)
    return out.reshape(B, H, 8, D)[:, :, :1].transpose(0, 2, 1, 3)
