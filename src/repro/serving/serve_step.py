"""Jittable batched serving steps — the units the dry-run lowers.

serve_step:      one new token per request against a KV/state cache of
                 ``seq_len`` (the decode_32k / long_500k shapes).
tree_serve_step: one speculation block per request — T tree tokens with a
                 shared topology (the production form of the paper's target
                 pass; used by the benchmarks to price tree passes).
pool steps:      the continuous-batching forms over a per-stream cache pool
                 (models/cache.py): per-row lengths, padded token counts
                 masked by ``lens``, per-row tree topologies, and the fused
                 post-verification commit — the units
                 BatchedSpeculativeEngine executes.  Per-step host->device
                 traffic for these is index arrays only: ancestor masks are
                 composed on device from parent pointers and the commit is
                 driven by (node_path, path_len, C) tables.

Every step here is verifier-agnostic by design: verification is host-side
per stream, resolved through the core/verify.py registry (engine.verify_tree),
and the device steps only ever see its *outcome* as (node_path, path_len)
commit tables.  That contract is what lets any registered verifier run under
batched, sharded and pipelined serving token-identically with zero changes
to the compiled step set.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import interpret_mode, pool_commit_kv
from repro.models.cache import merge_streams, paged_phys_slots
from repro.models.transformer import forward


def next_pow2(n: int) -> int:
    """Smallest power of two >= n — the shape-bucketing rule shared by both
    engines (bounds the jit cache under heterogeneous per-stream shapes)."""
    p = 1
    while p < n:
        p *= 2
    return p


class StagingBuffers:
    """Reusable host staging buffers for the per-step index arrays.

    Every pool step ships a handful of small int/bool arrays (tokens, parent
    pointers, commit tables); staging them in preallocated numpy buffers
    keeps the steady-state serving loop allocation-free on the host side.

    ``banks`` > 1 double-buffers the staging itself: ``flip()`` rotates to
    the next bank, so a pipelined engine refilling buffers for step i+1
    never touches the bank step i's arrays were built from.  ``jnp.asarray``
    copies host memory eagerly at dispatch today, so a single bank is safe
    for the synchronous engine — the bank flip makes the pipelined engine's
    no-overwrite contract explicit instead of resting on that copy timing.

    Staging is strictly per-engine: every shard of a sharded engine
    (ShardedBatchedSpeculativeEngine) owns its own instance, so its
    (per-shard-sized) tree/commit index arrays and bank rotation can never
    alias another shard's — shard isolation by construction, not by key.
    """

    def __init__(self, banks: int = 1):
        assert banks >= 1
        self._banks = banks
        self._bank = 0
        self._bufs: dict = {}

    def flip(self) -> None:
        """Rotate to the next bank (a pipelined ``begin_step`` boundary)."""
        self._bank = (self._bank + 1) % self._banks

    def get(self, name: str, shape: tuple, dtype, fill=0) -> np.ndarray:
        """A zeroed (or ``fill``-initialised) buffer of the given shape from
        the current bank, reused across steps with the same shape bucket."""
        key = (self._bank, name, shape)
        buf = self._bufs.get(key)
        if buf is None:
            buf = self._bufs[key] = np.empty(shape, dtype)
        buf.fill(fill)
        return buf


def make_serve_step(cfg):
    """(params, cache, tokens (B, 1)) -> (logits (B, 1, V), new_cache)."""

    def serve_step(params, cache, tokens):
        logits, new_cache, _ = forward(params, cfg, tokens, mode="decode", cache=cache)
        return logits, new_cache

    return serve_step


def make_tree_serve_step(cfg):
    """(params, cache, tokens (B, T), anc (T, T)) -> (logits, new_cache).

    The ancestor mask is shared across the batch (lockstep speculation with a
    common (K, L1, L2) action), matching the engine's batched deployment.
    """

    def tree_step(params, cache, tokens, anc):
        logits, new_cache, _ = forward(params, cfg, tokens, mode="tree", cache=cache, anc=anc)
        return logits, new_cache

    return tree_step


def make_prefill_step(cfg):
    def prefill(params, cache, tokens, enc_embeds=None, embeds=None):
        logits, new_cache, _ = forward(
            params, cfg, tokens, mode="full", cache=cache, enc_embeds=enc_embeds, embeds=embeds
        )
        return logits, new_cache

    return prefill


def make_pool_decode_step(cfg):
    """(params, pool_cache, tokens (B, Tpad), lens (B,)) ->
    (logits, cache, hidden).

    Padded decode over a per-stream pool: row b's tokens beyond lens[b] are
    written but invalidated (pos = -1), so heterogeneous per-stream deltas
    advance in one call.  Attention-family archs only (recurrent state
    cannot be length-masked — use make_pool_locked_step)."""

    def step(params, cache, tokens, lens):
        logits, new_cache, ex = forward(params, cfg, tokens, mode="decode", cache=cache, lens=lens)
        return logits, new_cache, ex["hidden"]

    return step


def make_pool_locked_step(cfg):
    """(params, pool_cache, tokens (B, 1), keep (B,)) -> (logits, cache).

    One lockstep token per stream; rows with keep=False are frozen at their
    exact prior state (merge_streams), which is the recurrent-safe padding
    primitive."""

    def step(params, cache, tokens, keep):
        logits, new_cache, _ = forward(params, cfg, tokens, mode="decode", cache=cache)
        return logits, merge_streams(new_cache, cache, keep)

    return step


def device_ancestor_mask(parents: jax.Array) -> jax.Array:
    """Compose per-row ancestor-or-self masks on device from parent pointers.

    parents: (B, T) int32, parent[b, i] = parent node of i, -1 for the root
    and for padding nodes (which become isolated roots, exactly the padding
    convention of the tree pass).  Returns (B, T, T) bool with
    mask[b, i, j] == True iff j is an ancestor of i or i == j — bit-identical
    to host-side ``core.trees.tree_ancestor_mask`` per row.

    This keeps the per-step H2D transfer at (B, T) index arrays instead of
    the dense (B, T, T) mask tensor the host used to rebuild every iteration.
    T chain-follow iterations bound any tree depth; each is a (B, T, T) OR.
    """
    B, T = parents.shape
    anc0 = jnp.broadcast_to(jnp.eye(T, dtype=bool)[None], (B, T, T))
    cur0 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(_, carry):
        anc, cur = carry
        nxt = jnp.where(
            cur >= 0, jnp.take_along_axis(parents, jnp.maximum(cur, 0), axis=1), -1
        )
        anc = anc | (jnp.arange(T, dtype=jnp.int32)[None, None, :] == nxt[:, :, None])
        return anc, nxt

    anc, _ = jax.lax.fori_loop(0, T, body, (anc0, cur0))
    return anc


def make_pool_tree_step(cfg):
    """(params, pool_cache, tokens (B, Tpad), parents (B, Tpad), keep (B,))
    -> (logits, cache, hidden).

    The continuous-batching target pass: per-row tree topologies over a
    per-stream cache pool.  The ancestor masks are composed on device from
    parent pointers (device_ancestor_mask) and rows with keep=False are
    frozen at their exact prior state inside the same jit call, so the host
    ships only (B, Tpad) index arrays per step.  Padding nodes carry
    parent = -1 (isolated roots) — never attended by real nodes and
    invalidated at commit."""

    def tree_step(params, cache, tokens, parents, keep):
        anc = device_ancestor_mask(parents)
        logits, new_cache, ex = forward(params, cfg, tokens, mode="tree", cache=cache, anc=anc)
        # idle slots must not advance; active rows keep the tree writes the
        # fused commit relies on
        return logits, merge_streams(new_cache, cache, keep), ex["hidden"]

    return tree_step


def make_pool_ragged_tree_step(cfg):
    """(params, pool_cache, toks (Npad,), owner, parent, depth, local,
    counts) -> (logits (Npad, V), cache, hidden (Npad, d)).

    The RAGGED continuous-batching target pass: every active stream's tree
    flattened into ONE node-major buffer instead of padding each row to the
    pool-wide Tpad (docs/serving.md "Ragged node-major tree batching").
    ``owner``/``parent``/``depth``/``local`` are per-node (Npad,) index
    arrays, ``counts`` the per-row (B,) appended-node counts; padding lanes
    carry local = -1/parent = -1 and write NOTHING (their ring slot is the
    out-of-range sentinel, so every drop-mode scatter vanishes) — which is
    also why no merge_streams is needed: idle rows advance by counts = 0
    and never see a stale write to undo.  Node j of stream s lands in the
    exact ring slot padded column j would, so the fused commit
    (make_pool_commit_step) is shared verbatim between both layouts."""

    def ragged_tree_step(params, cache, toks, owner, parent, depth, local, counts):
        logits, new_cache, ex = forward(
            params, cfg, toks[None], mode="tree", cache=cache,
            ragged={"owner": owner, "parent": parent, "depth": depth,
                    "local": local, "counts": counts},
        )
        return logits[0], new_cache, ex["hidden"][0]

    return ragged_tree_step


def make_pool_commit_step(cfg, Tpad: int):
    """Fused post-verification commit: ONE jitted call re-compacts every
    stream's accepted path in the KV ring, invalidates its speculative
    slots and advances its length — O(touched lanes) data movement instead
    of O(active_streams) full-pool copies.  Jit with ``donate_argnums=0``
    (both engines do) and XLA updates the pool buffers in place.

    Returned fn: (cache, node_path, path_len, C, active) -> cache
      node_path (B, P) int32 : accepted tree-node indices per row, padded
      path_len  (B,)   int32 : number of real entries per row (0 for rows
                               that accepted nothing, and for idle rows)
      C         (B,)   int32 : committed target length before the block
                               (the pending root sits at ring slot C % smax)
      active    (B,)   bool  : rows that ran a tree pass this iteration;
                               inactive rows are bit-identical no-ops

    The single-stream lockstep layout is also accepted (node_path (P,),
    scalar path_len/C, active ignored): the slot math is then shared across
    the batch axis, mirroring SpeculativeEngine's cache.

    Index contract (models/cache.py "Ring-compaction commit contract",
    documented in full in docs/kernels.md):
    padded/idle entries are identity copies of the root slot
    (src == dst == C % smax), which no real entry writes; accepted node
    indices are strictly increasing with n_j >= j + 1, so a src slot is
    never an EARLIER entry's dst slot and dst slots are pairwise distinct —
    the hazard-free property that lets the Pallas kernel's sequential
    in-place grid read every lane's pre-commit value.

    Paged pools (models/cache.py paged layout) run the same logical-slot
    arithmetic, then translate src/dst through the per-row block table
    into flat arena lanes and issue ONE pool_commit_kv over the arena
    viewed as a single-row pool: rows own disjoint physical blocks, so
    concatenating every row's entries row-major preserves the hazard-free
    property (idle/unmapped entries translate into the trash block with
    src == dst).  pos/len/block_tbl stay logical and untouched by the move.
    """
    use_pallas = cfg.attention_impl == "pallas"

    def commit(cache, node_path, path_len, C, active=None):
        a = cache["attn"]
        k, v, pos = a["k"], a["v"], a["pos"]
        paged = "block_tbl" in a
        smax = pos.shape[-1] if pos.ndim == 2 else pos.shape[0]
        P = node_path.shape[-1]
        j = jnp.arange(P, dtype=jnp.int32)
        t = jnp.arange(Tpad, dtype=jnp.int32)
        jj = jnp.arange(P + 1, dtype=jnp.int32)
        if pos.ndim == 2:  # per-stream pool (ring or paged)
            B = pos.shape[0]
            bidx = jnp.arange(B)[:, None]
            valid = j[None, :] < path_len[:, None]
            root = (C % smax)[:, None]
            src = jnp.where(valid, (C[:, None] + node_path) % smax, root)
            dst = jnp.where(valid, (C[:, None] + 1 + j[None, :]) % smax, root)
            if paged:
                tbl = a["block_tbl"]
                block = k.shape[2]
                nl = k.shape[0]
                srcf = paged_phys_slots(tbl, src, block).reshape(1, -1)
                dstf = paged_phys_slots(tbl, dst, block).reshape(1, -1)
                kf = k.reshape((nl, 1, k.shape[1] * block) + k.shape[3:])
                vf = v.reshape((nl, 1, v.shape[1] * block) + v.shape[3:])
                kf, vf = pool_commit_kv(
                    kf, vf, srcf.astype(jnp.int32), dstf.astype(jnp.int32),
                    use_pallas=use_pallas, interpret=interpret_mode(),
                )
                k, v = kf.reshape(k.shape), vf.reshape(v.shape)
            else:
                k, v = pool_commit_kv(
                    k, v, src.astype(jnp.int32), dst.astype(jnp.int32),
                    use_pallas=use_pallas, interpret=interpret_mode(),
                )
            new_pos = pos.at[bidx, (C[:, None] + t[None, :]) % smax].set(-1)
            keep_valid = jj[None, :] <= path_len[:, None]
            keep_slots = jnp.where(keep_valid, (C[:, None] + jj[None, :]) % smax, root)
            keep_vals = jnp.where(keep_valid, C[:, None] + jj[None, :], C[:, None])
            new_pos = new_pos.at[bidx, keep_slots].set(keep_vals)
            new_pos = jnp.where(active[:, None], new_pos, pos)
            new_len = jnp.where(active, C + 1 + path_len, a["len"])
        else:  # lockstep single-stream cache (shared pos/len tables)
            valid = j < path_len
            root = C % smax
            src = jnp.where(valid, (C + node_path) % smax, root)
            dst = jnp.where(valid, (C + 1 + j) % smax, root)
            k = k.at[:, :, dst].set(k[:, :, src])
            v = v.at[:, :, dst].set(v[:, :, src])
            new_pos = pos.at[(C + t) % smax].set(-1)
            keep_valid = jj <= path_len
            keep_slots = jnp.where(keep_valid, (C + jj) % smax, root)
            keep_vals = jnp.where(keep_valid, C + jj, C)
            new_pos = new_pos.at[keep_slots].set(keep_vals)
            new_len = (C + 1 + path_len).astype(jnp.int32)
        cache = dict(cache)
        new_attn = {"k": k, "v": v, "pos": new_pos, "len": new_len}
        if paged:
            new_attn["block_tbl"] = a["block_tbl"]
        cache["attn"] = new_attn
        return cache

    return commit


def make_group_commit_step(cfg, tpads: list[int]):
    """Grouped cross-shard commit: fuse N shard pools' post-verification
    commits into ONE jitted dispatch.

    The sharded engine's shards each own a private pool, so stepping them
    as a host loop pays one commit dispatch per shard per iteration — the
    9 -> 17 ``commit_calls`` regression the baselines recorded.  Shard pools are disjoint arrays, so
    their commits compose into a single program with no interference: this
    builds one ``make_pool_commit_step`` per shard (each with its own
    ``Tpad`` — shards bucket their speculation shapes independently) and
    applies them elementwise over tuples.

    Returned fn: (caches, node_paths, path_lens, Cs, actives) -> caches,
    every argument a length-N tuple in shard order, with per-shard index
    contracts exactly as in ``make_pool_commit_step``.  Jit with
    ``donate_argnums=0`` (the engine does) and XLA updates every shard's
    pool buffers in place in the one fused program.  Only valid when the
    shard pools are device-colocated (the engine checks); on multi-host
    topologies shards keep their per-shard commit calls."""
    fns = [make_pool_commit_step(cfg, T) for T in tpads]

    def group_commit(caches, node_paths, path_lens, Cs, actives):
        assert len(caches) == len(fns), (len(caches), len(fns))
        return tuple(
            fn(cache, npath, plen, C, act)
            for fn, cache, npath, plen, C, act
            in zip(fns, caches, node_paths, path_lens, Cs, actives)
        )

    return group_commit


def commit_row_reference(cache, slot: int, C: int, node_path, T: int):
    """PR-1 per-row sequential commit (eager ``.at[].set`` chains): the
    bit-exactness oracle the fused commit is property-tested and benchmarked
    against (tests/test_commit_fused.py, benchmarks/commit_bench.py).  Each
    call materializes a fresh copy of the whole pool — the O(active_streams)
    cost make_pool_commit_step removes."""
    a = cache["attn"]
    smax = a["k"].shape[2]
    tree_slots = (C + np.arange(T)) % smax
    src = [(C + n) % smax for n in node_path]
    dst = [(C + 1 + i) % smax for i in range(len(node_path))]
    k, v, pos = a["k"], a["v"], a["pos"]
    if src:
        src_i = jnp.asarray(src)
        dst_i = jnp.asarray(dst)
        k = k.at[:, slot, dst_i].set(k[:, slot, src_i])
        v = v.at[:, slot, dst_i].set(v[:, slot, src_i])
    pos = pos.at[slot, jnp.asarray(tree_slots)].set(-1)
    keep = np.asarray([(C + i) % smax for i in range(1 + len(node_path))])
    pos = pos.at[slot, jnp.asarray(keep)].set(
        jnp.asarray(C + np.arange(1 + len(node_path)), jnp.int32)
    )
    new_len = a["len"].at[slot].set(C + 1 + len(node_path))
    cache = dict(cache)
    cache["attn"] = {"k": k, "v": v, "pos": pos, "len": new_len}
    return cache
