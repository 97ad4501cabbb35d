"""Batched ring-compaction KV commit — Pallas TPU kernel.

After a speculative tree pass, every stream's accepted path must be
compacted into contiguous ring slots: slot (C + n_j) % Smax moves to
(C + 1 + j) % Smax for the j-th accepted node n_j.  Doing this with eager
``.at[].set`` chains materializes a fresh copy of the whole
(L, B, Smax, Hkv, hd) pool per stream; this kernel instead touches only the
(layer, row, slot) lanes named by the index arrays:

  * ``src``/``dst`` are scalar-prefetched (SMEM) so the grid's block index
    maps can steer the HBM->VMEM pipeline directly at the named slots — the
    unit of data movement is one (Hkv * hd) lane, not the pool;
  * ``input_output_aliases`` pins the output to the input buffer, so slots
    outside the index arrays are never read or written (the XLA-level
    donation the serving step relies on);
  * the grid's minor axis walks the path positions j in order.  TPU grids
    execute sequentially, which makes the in-place copy exact under the
    hazard-free index contract (see ``serve_step.make_pool_commit_step``):
    accepted node indices are strictly increasing with n_j >= j + 1, so a
    source slot is never an EARLIER entry's destination (and destinations
    are pairwise distinct) — every entry reads its pre-commit value, and
    the sequential copy equals gather-then-scatter.

Padding convention: masked entries carry src == dst (an identity copy of a
slot no real entry writes), so ragged per-row path lengths need no masking
inside the kernel.

Layout: k, v (L, B, Smax, Hkv, hd); src, dst (B, P) int32.  One moved
lane is a (1, 1, 1, Hkv, hd) block: its last two dims are the array's own,
which is the form the TPU lowering accepts for any head count and head
width (a flattened (1, 1, 1, Hkv * hd) block puts a size-1 block on the
tiled slot axis and is refused).

Paged pools reuse this kernel unchanged: logical slots are translated
through the block table and the arena is committed as a single-row pool
(see docs/kernels.md "The paged scatter").
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _commit_kv_kernel(src_ref, dst_ref, k_in, v_in, k_out, v_out):
    del src_ref, dst_ref  # consumed by the index maps
    k_out[...] = k_in[...]
    v_out[...] = v_in[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def commit_kv(k, v, src, dst, *, interpret: bool):
    """k[l, b, dst[b, j]] <- k[l, b, src[b, j]] (and likewise v), in place.

    k, v: (L, B, Smax, Hkv, hd); src, dst: (B, P) int32.  Requires the
    hazard-free contract documented in the module docstring; entries with
    src == dst are no-ops (the padding convention).

    In-place-ness comes from ``input_output_aliases`` plus the caller's
    buffer donation (the serving commit step is jitted with
    ``donate_argnums=0`` over the whole pool); this wrapper itself does not
    donate, so eager callers keep their inputs valid.
    """
    L, B, S, H, hd = k.shape
    P = src.shape[1]
    lane = (1, 1, 1, H, hd)
    read = lambda l, b, j, src, dst: (l, b, src[b, j], 0, 0)
    write = lambda l, b, j, src, dst: (l, b, dst[b, j], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, B, P),
        in_specs=[pl.BlockSpec(lane, read), pl.BlockSpec(lane, read)],
        out_specs=[pl.BlockSpec(lane, write), pl.BlockSpec(lane, write)],
    )
    return pl.pallas_call(
        _commit_kv_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        input_output_aliases={2: 0, 3: 1},
        interpret=interpret,
        name="commit_kv",
    )(src, dst, k, v)
