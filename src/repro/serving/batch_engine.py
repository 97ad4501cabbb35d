"""Continuous-batching speculative engine: N concurrent streams per model call.

``SpeculativeEngine`` (serving/engine.py) advances one stream per target /
draft call, so multi-user throughput is bounded by single-stream latency.
This module packs every active stream into lockstep batched calls — per
iteration one padded draft-ingest pass, one padded draft step per tree level,
ONE padded tree-masked target pass, and ONE jitted pool-donating commit —
with per-stream host verification, so aggregate tokens/sec scales with the
number of streams while each stream's output remains exactly the warped
target process.  The commit path is device-resident: host->device traffic
per step is small index arrays (tokens, parent pointers, accepted-path
tables) staged in reusable buffers; ancestor masks are composed on device
and the ring compaction moves only touched (row, slot) KV lanes
(serve_step.make_pool_commit_step / kernels/commit_kv.py) instead of
copying the pool once per stream.

Substrate (models/cache.py): a slot-based per-stream KV pool.  Every model
call sees the same (n_slots, ...) shapes, so streams join (prefill a 1-row
cache, scatter it into a free slot) and leave (release the slot) without
recompiles.  Speculation shapes are BUCKETED: per-iteration (K, L1, L2) are
padded to the next power of two, so the jit cache stays bounded even under
heterogeneous per-stream NDE selector decisions.

By default the attention KV is PAGED (``paged=True``): instead of reserving
a full ``max_cache`` ring per slot, KV lives in a shared arena of
``block_size``-slot blocks indexed through per-stream block tables
(models/cache.py paged layout), so HBM holds only the blocks streams have
actually written — one long stream and many short ones co-reside in a pool
a ring design could not share.  Block pressure is handled in three stages
before any stream dies: admission is gated on the free list, dead tail
blocks past each stream's live frontier are recycled
(``counters["blocks_reclaimed"]``), and only then is the most recently
admitted stream evicted (LIFO — the oldest streams keep their residency).
With ``pool_blocks`` left at its default (n_slots * max_cache / block_size,
i.e. ring-equivalent capacity) scheduling decisions are identical to the
ring pool and the output is token-identical to it (property-tested in
tests/test_paged_pool.py).  See docs/serving.md for the full lifecycle.

Exactness contract (property-tested in tests/test_batch_engine.py): with the
same per-stream seed, the batched engine emits token-identical output to an
independent ``SpeculativeEngine`` run per stream.  This leans on three facts:

  * attention/MoE/MLP compute is per-row and per-query: padding extra rows
    (idle slots) or extra query tokens (masked via ``lens`` / the ancestor
    mask) contributes exact zeros to softmax sums, so logits are bit-equal
    to the unpadded single-stream call (verified: dense/ssm/hybrid logits
    are invariant to batch size on the XLA CPU path.  Not so on the TPU:
    granite-3-2b at full width in bf16 on a v5e gave single-stream tokens
    that differ from the batched pool's.  The two engines run programs of
    different shapes, and XLA may tile their bf16 reductions differently
    there; the cause is not isolated yet.  On the chip the contract is
    checked between pipelined and synchronous pool steps, and between the
    sharded and the unsharded pool, not against the single-stream engine);
  * MoE routing is dropless (models/moe.py), so expert outputs do not
    depend on batch co-tokens;
  * recurrent (ssm/rglru) state integrates *every* processed token and the
    chunked SSD scan is not bitwise-stable under length padding, so
    recurrent-arch multi-token calls are grouped by exact length (same T as
    the single engine) instead of padded, and T=1 lockstep steps are frozen
    per-row with ``merge_streams``.

Scheduling: admission is FIFO (``submit`` queues, free slots admit); a stream
is evicted (finished early) when its context can no longer fit a speculation
block in its cache ring.  ``launch/serve.py --streams N`` drives this engine.

Sharded streams (``ShardedBatchedSpeculativeEngine``, docs/serving.md
"Sharded streams"): the pool's stream axis is embarrassingly parallel, so
it shards across a mesh "data" axis — contiguous slot shards, each a full
engine over its own rows/arena/free-lists/admission-queue with its pool
arrays NamedSharding-committed to its mesh slice, under a shared
least-loaded scheduler.  No cross-shard state exists beyond the routing
decision, which is the property that scales the pool past one chip's HBM.

Pipelined stepping (``pipeline=True``, docs/serving.md "Pipelined stepping"):
``step()`` is built from phases — ``begin_step()`` runs the scheduling
boundary (admission, capacity eviction, paged block mapping) and dispatches
the draft + tree-pass device work, returning a ``PendingStep`` whose tree
outputs are still device futures; ``verify_step()`` blocks on those futures
and verifies per stream on host, ``commit_step()`` issues the fused commit,
and ``retire_step()`` advances token bookkeeping and dispatches the NEXT
step's draft/tree work before the host tail (the hidden-state readback and
stream retirement), so step i's tail overlaps step i+1's device work.
``finish_step()`` is the composition of the last three.  Scheduling — and
therefore tokens — stays identical to the synchronous engine because every
retiring stream's slot/block release lands BEFORE the begun-ahead boundary
(the boundary sees exactly the post-release pool a synchronous
``begin_step`` would), and a begun step can be drained (``drain_pipeline``)
or rewound (``abort_step``, ``abort_pipeline``) when out-of-band events —
a mid-run ``submit`` against a free row — would have changed it.  The rewind is LOGICAL for attention-family draft pools —
ingest writes are append-only and deterministic, so ``invalidate_from``
erases them and the re-begun step re-ingests identical lanes — and only
recurrent draft pools hold the double-buffered back frame (models/cache.py
``begin_frame``): keeping the pre-step arena alive was the pipelined mode's
single biggest overhead.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.traversal import delayed_structure
from repro.core.trees import DraftTree
from repro.core.verify import get_verifier
from repro.launch.mesh import shard_meshes
from repro.launch.sharding import pad_slots, pool_shardings
from repro.models.cache import (
    PagedCachePool,
    concat_streams,
    fork_streams,
    gather_streams,
    make_cache_pool,
    scatter_streams,
)
from repro.models.transformer import forward, init_cache
from repro.sampling import warp_logits
from repro.serving.engine import (
    EngineConfig,
    SamplingParams,
    SpeculativeEngine,
    draw_token,
    to_verifier_dtype,
    verify_tree,
)
from repro.serving.serve_step import (
    StagingBuffers,
    make_group_commit_step,
    make_pool_commit_step,
    make_pool_decode_step,
    make_pool_locked_step,
    make_pool_ragged_tree_step,
    make_pool_tree_step,
    next_pow2 as _next_pow2,
)
from repro.serving.tracing import jit_named, span, step_span, traced

RECURRENT = ("ssm", "hybrid")


@dataclass
class BatchRequest:
    rid: int
    prompt: list
    max_new: int
    seed: int
    t_submit: float  # perf_counter at submit(), for counters["queue_ms"]


@dataclass
class PendingStep:
    """A dispatched-but-unverified iteration: everything ``finish_step``
    needs to verify, commit and retire it.

    For the tree strategy ``p_dev``/``hid_dev`` are *device* arrays (the
    warped tree-pass logits and hidden states, with async host copies
    already kicked off) — the futures the pipeline overlaps host work
    against.  The replay strategy's target pass is host-interleaved, so it
    arrives already materialised as ``snapshot``/``p_host``.

    ``C0`` (committed length minus the pending root, per slot), ``D0``
    (the draft pool's pre-ingest length, per slot — attention-family draft
    pools rewind logically instead of holding a back frame) and
    ``rng_state`` (per-stream generator snapshots, pipelined mode only)
    are the rewind coordinates ``abort_step`` uses."""

    active: list[int]
    acts: dict[int, tuple]
    pads: tuple[int, int, int, int]
    trees: dict
    hq: dict
    C0: dict[int, int]
    p_dev: object = None
    hid_dev: object = None
    snapshot: dict | None = None
    p_host: dict | None = None
    rng_state: dict | None = None
    D0: dict[int, int] | None = None
    # tree strategy, ragged layout only: ({slot: (offset, n_nodes)}, Npad)
    # — how the flat node-major logits/hidden buffers slice back into
    # per-stream trees (None = padded (B, Tpad) layout)
    roffs: object = None
    # True when this step's scheduling boundary evicted a stream: its slot
    # and block releases stand, so replaying admission against the
    # post-eviction pool would not reproduce the synchronous
    # admit-before-evict order (submit()'s drain-vs-abort rule)
    boundary_evicted: bool = False


@dataclass
class VerifiedStep:
    """A verified-but-unretired iteration: ``verify_step``'s per-stream
    accept/correction decisions, ready for ``commit_step`` (which fills
    ``hid_last`` on the replay strategy) and ``retire_step``.

    The split exists so a driver holding several engines — the sharded
    engine's concurrent ``step()`` — can verify every shard against the
    others' in-flight device work, then batch the commits into one
    dispatch before any shard retires."""

    pending: PendingStep
    accepted: dict[int, list]
    corr: dict[int, int]
    node_paths: dict | None = None   # tree strategy: accepted node index paths
    hid_last: dict | None = None     # replay strategy: filled by commit_step


class BatchedSpeculativeEngine:
    """Multi-stream speculative decoding over a slot-based cache pool.

    API:  ``submit(prompt, max_new, seed) -> rid``; ``step()`` advances every
    active stream one speculative block (admitting queued requests first) and
    returns per-request progress; ``run()`` drains the queue and returns
    ``{rid: tokens}``.
    """

    def __init__(self, target_cfg, target_params, draft_cfg, draft_params,
                 ecfg: EngineConfig, sampling: SamplingParams | None = None,
                 selector=None, n_slots: int = 4, paged: bool = True,
                 block_size: int = 64, pool_blocks: int | None = None,
                 pipeline: bool = False, mesh=None, shard_id: int = 0,
                 ragged=True):
        assert target_cfg.vocab == draft_cfg.vocab
        assert n_slots >= 1, f"need at least one pool slot, got {n_slots}"
        assert target_cfg.arch_type not in ("encdec", "vlm"), \
            "batched serving covers decoder-only archs (encdec/vlm prefill kwargs are single-stream)"
        assert not ecfg.verify_on_device, \
            "batched serving verifies per-stream on host (verify_on_device consumes " \
            "randomness differently and would break batch-vs-single exactness)"
        get_verifier(ecfg.verifier)  # fail loudly on unknown names, at build time
        if mesh is not None:
            # weights live on this engine's mesh devices from construction
            # on, next to its pool: no step ever moves them across devices
            on_mesh = NamedSharding(mesh, PartitionSpec())
            target_params = jax.device_put(target_params, on_mesh)
            draft_params = jax.device_put(draft_params, on_mesh)
        self.tc, self.tp = target_cfg, target_params
        self.dc, self.dp = draft_cfg, draft_params
        self.ecfg = ecfg
        self.sampling = sampling or SamplingParams()
        self.selector = selector
        self.n_slots = n_slots
        # mesh: a jax mesh whose "data" axis carries this engine's pool
        # stream axis (launch/sharding.pool_shardings commits the pool
        # arrays to it; n_slots must divide the axis — pad_slots).  The
        # sharded engine hands every shard its own single-device mesh slice
        # (launch/mesh.shard_meshes); a multi-device data mesh on one
        # engine shards the one pool SPMD-style instead.
        self.mesh = mesh
        self.shard_id = shard_id
        self.strategy = "replay" if target_cfg.arch_type in RECURRENT else "tree"
        smax = ecfg.max_cache
        page = None
        if paged:
            bs = self.normalize_block_size(smax, block_size)
            self.block_size = bs
            self.max_blocks = smax // bs
            if pool_blocks is None:
                # ring-equivalent capacity: scheduling (admission/eviction)
                # is then identical to the ring pool, and so is the output
                pool_blocks = n_slots * self.max_blocks
            # an arena smaller than one logical ring is legal: streams that
            # outgrow it are pressure-evicted (submit() rejects prompts that
            # could never fit at all)
            assert pool_blocks >= 1, "the arena needs at least one usable block"
            self.pool_blocks = pool_blocks
            page = (pool_blocks, bs)
        tcache = init_cache(target_cfg, n_slots, smax, per_stream=True, page=page)
        dcache = init_cache(draft_cfg, n_slots, smax, per_stream=True, page=page)
        self.tpool = make_cache_pool(
            tcache, n_slots,
            sharding=pool_shardings(mesh, tcache) if mesh is not None else None)
        self.dpool = make_cache_pool(
            dcache, n_slots,
            sharding=pool_shardings(mesh, dcache) if mesh is not None else None)
        # pure-recurrent caches have no attn component to page
        self.paged = isinstance(self.tpool, PagedCachePool) or isinstance(self.dpool, PagedCachePool)
        # ragged node-major tree pass (docs/serving.md): False = always the
        # padded (B, Tpad) layout; True = auto (ragged whenever the flat
        # buffer is strictly smaller than the padded lane count — drain
        # tails, heterogeneous selector actions); "always" = every tree
        # step, regardless (the exactness tests force both layouts onto
        # identical workloads).  The pallas impl needs the block-table
        # kernel's Q-steering, so pallas + a non-paged (ring) target pool
        # keeps the padded path.
        self.ragged = ragged
        self._ragged_ok = (
            bool(ragged)
            and self.strategy == "tree"
            and target_cfg.arch_type in ("dense", "moe")
            and not (target_cfg.attention_impl == "pallas"
                     and not isinstance(self.tpool, PagedCachePool))
        )
        # pallas Q tiles are 8 rows of uniform owner, so segment offsets
        # 8-align there; the XLA gather path packs nodes back-to-back
        self._ragged_align = 8 if target_cfg.attention_impl == "pallas" else 1
        self.streams: dict[int, dict] = {}  # slot -> stream state
        self.queue: list[BatchRequest] = []
        self.finished: dict[int, dict] = {}
        self._next_rid = 0
        self._admit_seq = 0
        self._jit_cache: dict = {}
        # pipelined mode double-banks the staging so refilling step i+1's
        # index arrays never touches the bank step i was built from
        self.pipeline = pipeline
        self._staging = StagingBuffers(banks=2 if pipeline else 1)
        self._pending_next: PendingStep | None = None
        self._drained_events: list[dict] = []  # retired by submit(), not yet returned
        self._step_no = 0  # step() calls, the step number of the serve:step span
        # pipeline_iterations counts every pipeline-ahead decision point, and
        # each decision either runs ahead or stalls — so
        # pipeline_ahead + pipeline_stalls == pipeline_iterations holds by
        # construction (the race-harness invariant, tests/test_race.py)
        # pad_nodes_total / tree_lanes_total: padding-waste accounting for
        # the tree pass — lanes the dispatch shipped vs real tree nodes
        # (pad_fraction = pad_nodes_total / tree_lanes_total); the ragged
        # layout exists to shrink it (benchmarks/batch_throughput.py gates
        # it under the heterogeneous scenario).  tree_calls_padded /
        # tree_calls_ragged split target_calls by tree-pass layout.
        # readback_bytes: device->host bytes of every blocking read
        # (``_fetch``).  steps_begun / steps_rewound / steps_drained: begun
        # steps, and those a mid-run submit() rewound (abort_step) or
        # finished early; once nothing is pending, steps_begun == finished
        # steps + steps_rewound.  admitted / queue_ms: requests admitted and
        # their summed submit()-to-admission wait.
        self.counters = {"target_calls": 0, "target_tokens": 0, "draft_calls": 0,
                         "draft_tokens": 0, "accepted": 0, "blocks": 0, "evicted": 0,
                         "tree_calls_padded": 0, "tree_calls_ragged": 0,
                         "commit_calls": 0,
                         "blocks_reclaimed": 0, "admit_blocked": 0, "blocks_peak": 0,
                         "pad_nodes_total": 0, "tree_lanes_total": 0,
                         "pipeline_ahead": 0, "pipeline_stalls": 0,
                         "pipeline_iterations": 0, "readback_bytes": 0,
                         "steps_begun": 0, "steps_rewound": 0, "steps_drained": 0,
                         "admitted": 0, "queue_ms": 0.0}

    def reset_counters(self, keys) -> None:
        """Zero the named counters (shared surface with the sharded engine —
        benchmarks reset per-pass counters through one call either way)."""
        for key in keys:
            self.counters[key] = type(self.counters[key])()

    # ------------------------------------------------------------- helpers ---

    @staticmethod
    def normalize_block_size(smax: int, block_size: int) -> int:
        """The block size must tile the logical ring exactly: round the
        request down to a power of two first (48 -> 32), then halve until it
        divides ``smax`` — so a non-power-of-two request degrades to the
        nearest sensible block, never to 1-token blocks.  Shared with
        anything that sizes an arena before constructing the engine
        (benchmarks/batch_throughput.py)."""
        bs = max(1, min(block_size, smax))
        bs = 1 << (bs.bit_length() - 1)
        while smax % bs:
            bs //= 2
        return bs

    def _jit(self, name, fn, donate_argnums=None):
        """Per-engine jit cache; the program is named after its key
        (tracing.jit_named).  ``donate_argnums`` marks pool args whose
        buffers XLA may update in place (the commit path donates the pool so
        committing moves lanes instead of copying the pool)."""
        return jit_named(self._jit_cache, name, fn, donate_argnums)

    def _fetch(self, x, what: str) -> np.ndarray:
        """Block on a device array and copy it to the host, inside a
        ``serve:wait.<what>`` span, counting the bytes copied."""
        with span(f"serve:wait.{what}"):
            a = np.asarray(x)
        self.counters["readback_bytes"] += a.nbytes
        return a

    def jit_compile_count(self) -> int:
        """Compiled signatures across this engine's jit cache — the cold-start
        compile budget bench_smoke.sh gates."""
        return sum(fn._cache_size() for fn in self._jit_cache.values())

    def placement(self) -> dict[str, list[int]]:
        """Ids of the devices holding each param tree and each pool."""
        trees = {"target_params": self.tp, "draft_params": self.dp,
                 "target_pool": self.tpool.cache, "draft_pool": self.dpool.cache}
        return {name: sorted({d.id for leaf in jax.tree.leaves(tree)
                              for d in leaf.devices()})
                for name, tree in trees.items()}

    def _stage(self, name, shape, dtype, fill=0):
        """Reusable host staging buffer for per-step index arrays
        (serve_step.StagingBuffers) — keeps the per-step H2D traffic at a
        handful of small, allocation-free index arrays.  The synchronous
        engine runs one bank (every phase ends with a blocking host read, so
        a buffer is consumed before it is refilled); the pipelined engine
        flips between two banks at each ``begin_step``."""
        return self._staging.get(name, shape, dtype, fill)

    def _scatter_rows(self, pool_cache, trims, rows, *, donate: bool):
        """Write per-row sub-caches back into a pool with ONE scatter call.

        ``trims`` are row-sized caches (concatenated along the stream axis)
        — so the write-back moves touched rows only, once, instead of one
        full-pool ``scatter_streams`` copy per length group.  Rows are
        padded to n_slots with repeats of the first row (identical values
        re-written to the same slot) so the call compiles once."""
        combined = trims[0] if len(trims) == 1 else concat_streams(trims)
        rows = list(rows)
        pad = self.n_slots - len(rows)
        if pad:
            filler = gather_streams(combined, [0] * pad)
            combined = concat_streams([combined, filler])
            rows = rows + [rows[0]] * pad
        name = "commit_scatter" if donate else "stage_scatter"
        fn = self._jit(name, scatter_streams, donate_argnums=0 if donate else None)
        return fn(pool_cache, combined, jnp.asarray(np.asarray(rows, np.int32)))

    def _warp(self, logits):
        return warp_logits(logits, self.sampling.temperature, self.sampling.top_p)

    def _recurrent(self, cfg) -> bool:
        return cfg.arch_type in RECURRENT

    @staticmethod
    def _pad_group(rows: list[int], toks: np.ndarray, width: int):
        """Pad a row group to a fixed width by repeating its first row, so
        grouped recurrent calls compile once per token-length instead of
        once per (length, group-size).  Pad rows process row 0's tokens and
        scatter row 0's (identical) result again — bitwise harmless."""
        pad = width - len(rows)
        rows_p = rows + [rows[0]] * pad
        toks_p = np.concatenate([toks, np.repeat(toks[:1], pad, axis=0)]) if pad else toks
        return rows_p, toks_p

    # ------------------------------------------------------------ requests ---

    def submit(self, prompt: list[int], max_new: int = 64, seed: int | None = None,
               action_hint=None) -> int:
        """Queue a request; it is admitted when a pool slot frees up.
        ``seed`` drives this stream's drafting/verification randomness — a
        single-stream ``SpeculativeEngine`` with ``EngineConfig(seed=seed)``
        emits the identical token sequence.  ``action_hint`` — the expected
        (K, L1, L2) selector action — is a scheduler-only hint: the sharded
        engine bin-packs on it; a single engine has one pool, so it accepts
        and ignores it (API parity lets callers hint unconditionally)."""
        del action_hint
        if not 1 <= len(prompt) < self.ecfg.max_cache:
            raise ValueError(
                f"prompt of {len(prompt)} tokens cannot fit a {self.ecfg.max_cache}-slot cache ring"
            )
        if self.paged:
            # mirror _admit's gate exactly: a prompt accepted here must be
            # admittable into an otherwise-empty arena
            need = self._admit_need(len(prompt))
            cap = min(p.total_blocks for p in self._paged_pools())
            if need > cap:
                raise ValueError(
                    f"prompt of {len(prompt)} tokens needs {need} blocks "
                    f"(context + one speculation bucket); the arena has {cap}"
                )
        if self._pending_next is not None and self.tpool.free_slots:
            # A begun-ahead step locked in its admission decisions without
            # this request, and a free row means those decisions could have
            # included it (with zero free rows admission is provably
            # unchanged, so the dispatched step is kept).  Stall-and-drain:
            #   * boundary evicted a stream -> the release stands, so
            #     replaying admission would see post-eviction rows the
            #     synchronous admit-before-evict order would not; retire
            #     the step instead (its events surface at the next step())
            #     and the request joins at the following boundary — the
            #     same boundary at which the synchronous engine, whose
            #     admission ran before the eviction freed anything, admits;
            #   * otherwise -> rewind the step (abort_step) so the next
            #     begin_step re-runs the identical boundary with this
            #     request queued, exactly as the synchronous engine would.
            pending, self._pending_next = self._pending_next, None
            if pending.boundary_evicted:
                self.counters["steps_drained"] += 1
                with span("serve:drain"):
                    self._drained_events.extend(
                        self.finish_step(pending, pipeline_ahead=False))
            else:
                self.abort_step(pending)
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(BatchRequest(rid, list(prompt), max_new,
                                       self.ecfg.seed if seed is None else seed,
                                       time.perf_counter()))
        return rid

    def can_admit(self, prompt_len: int) -> bool:
        """Whether a fresh request of ``prompt_len`` tokens could be admitted
        at the NEXT scheduling boundary without queueing: a free pool row, an
        empty FIFO (admission is strictly in order), and — paged — enough
        free blocks for its context plus one speculation bucket.  Dead-tail
        reclamation is deliberately not counted: the scheduler routing on
        this probe (ShardedBatchedSpeculativeEngine) must not promise
        capacity that a resident stream's next step could take back."""
        if self.queue or not self.tpool.free_slots or not self.dpool.free_slots:
            return False
        if self.paged:
            need = self._admit_need(prompt_len)
            if any(p.free_blocks < need for p in self._paged_pools()):
                return False
        return True

    def _prefill_row(self, cfg, params, ctx, name: str):
        """Prefill a fresh 1-row per-stream cache with ``ctx`` tokens."""
        row = init_cache(cfg, 1, self.ecfg.max_cache, per_stream=True)
        if not ctx:
            return row, None
        T = len(ctx)
        if self._recurrent(cfg):
            fn = self._jit(f"{name}_prefill_{T}", partial(forward, cfg=cfg, mode="full"))
            _, row, ex = fn(params, tokens=jnp.asarray(np.asarray(ctx, np.int32)[None]), cache=row)
            return row, self._fetch(ex["hidden"][0, T - 1], "prefill")
        # bucket the pad, but never past the ring: a padded pass longer than
        # smax would wrap and overwrite the committed prefix it just wrote
        Tp = min(_next_pow2(T), self.ecfg.max_cache)
        toks = np.zeros((1, Tp), np.int32)
        toks[0, :T] = ctx
        fn = self._jit(f"{name}_prefill_p{Tp}", partial(forward, cfg=cfg, mode="full"))
        _, row, ex = fn(params, tokens=jnp.asarray(toks), cache=row,
                        lens=jnp.asarray([T], jnp.int32))
        return row, self._fetch(ex["hidden"][0, T - 1], "prefill")

    def _paged_pools(self) -> list[PagedCachePool]:
        return [p for p in (self.tpool, self.dpool) if isinstance(p, PagedCachePool)]

    def _admit_need(self, prompt_len: int) -> int:
        """Blocks a fresh stream must find free: its context plus one
        default-action speculation bucket (step-time pressure handles any
        selector-driven growth beyond that)."""
        _, _, _, tpad0 = self._bucket_actions(
            {0: (self.ecfg.K, self.ecfg.L1, self.ecfg.L2)})
        return min(-(-(prompt_len + tpad0) // self.block_size), self.max_blocks)

    @traced("serve:admit")
    def _admit(self):
        while self.queue and self.tpool.free_slots:
            req = self.queue[0]
            if self.paged:
                need = self._admit_need(len(req.prompt))
                short = [p for p in self._paged_pools() if p.free_blocks < need]
                if short:
                    # recycle resident streams' dead tails (blocks past the
                    # frontier a default-action step would write) before
                    # leaving the request queued
                    _, _, _, tpad0 = self._bucket_actions(
                        {0: (self.ecfg.K, self.ecfg.L1, self.ecfg.L2)})
                    keeps = {s: len(st["committed"]) - 1 + tpad0
                             for s, st in self.streams.items()}
                    for pool in short:
                        self.counters["blocks_reclaimed"] += pool.reclaim_tails(keeps)
                    short = [p for p in self._paged_pools() if p.free_blocks < need]
                if short:
                    if not self.streams:
                        raise RuntimeError(
                            f"request {req.rid} needs {need} free blocks but the "
                            f"empty pool only has {min(p.free_blocks for p in short)}"
                        )
                    self.counters["admit_blocked"] += 1
                    break  # FIFO: the head blocks the queue until blocks free up
            self.queue.pop(0)
            self.counters["admitted"] += 1
            self.counters["queue_ms"] += (time.perf_counter() - req.t_submit) * 1e3
            ctx = req.prompt[:-1]
            with span("serve:prefill", rid=req.rid):
                trow, h_p = self._prefill_row(self.tc, self.tp, ctx, "tgt")
            with span("serve:prefill", rid=req.rid):
                drow, h_q = self._prefill_row(self.dc, self.dp, ctx, "drf")
            slot = self.tpool.admit(trow, ctx_len=len(ctx))
            slot_d = self.dpool.admit(drow, ctx_len=len(ctx))
            assert slot == slot_d
            self._admit_seq += 1
            self.streams[slot] = {
                "rid": req.rid,
                "slot": slot,
                "seq": self._admit_seq,
                "rng": np.random.default_rng(req.seed),
                "max_new": req.max_new,
                "out": [],
                "committed": list(req.prompt),
                "pending": int(req.prompt[-1]),
                "draft_delta": [int(req.prompt[-1])],
                "h_prev_p": h_p if h_p is not None else np.zeros(self.tc.d_model, np.float32),
                "h_prev_q": h_q if h_q is not None else np.zeros(self.dc.d_model, np.float32),
                "p_prev": None,
                "q_prev": None,
                "done": False,
            }

    def _finish(self, slot: int, reason: str = "length"):
        st = self.streams.pop(slot)
        self.finished[st["rid"]] = {"tokens": st["out"][: st["max_new"]], "reason": reason}
        self.tpool.release(slot)
        self.dpool.release(slot)

    def choose_action(self, stream):
        if self.selector is None:
            return self.ecfg.K, self.ecfg.L1, self.ecfg.L2
        return self.selector(stream, self)

    # ------------------------------------------------------------ drafting ---

    @traced("serve:ingest")
    def _ingest_deltas(self, active):
        """Advance the draft pool over each stream's newly committed tokens.
        Returns per-slot (q0 dist, draft hidden at the new root)."""
        q0, hq = {}, {}
        if self._recurrent(self.dc):
            groups = defaultdict(list)
            for s in active:
                groups[len(self.streams[s]["draft_delta"])].append(s)
            trims, all_rows = [], []
            for L, rows in sorted(groups.items()):
                toks = np.asarray([self.streams[s]["draft_delta"] for s in rows], np.int32)
                rows_p, toks_p = self._pad_group(rows, toks, self.n_slots)
                sub = gather_streams(self.dpool.cache, rows_p)
                fn = self._jit(f"drf_ing_g{L}", partial(forward, cfg=self.dc, mode="decode"))
                logits, sub, ex = fn(self.dp, tokens=jnp.asarray(toks_p), cache=sub)
                trims.append(gather_streams(sub, list(range(len(rows)))))
                all_rows.extend(rows)
                w = self._fetch(self._warp(logits), "ingest")
                hid = self._fetch(ex["hidden"], "ingest")
                for i, s in enumerate(rows):
                    q0[s] = w[i, L - 1]
                    hq[s] = hid[i, L - 1]
                self.counters["draft_calls"] += 1
                self.counters["draft_tokens"] += L * len(rows)
            # one write-back for every length group's rows — donated unless
            # the pipelined back frame still aliases the pre-step buffer
            self.dpool.cache = self._scatter_rows(self.dpool.cache, trims, all_rows,
                                                  donate=not self.dpool.frame_held)
        else:
            Dp = _next_pow2(max(len(self.streams[s]["draft_delta"]) for s in active))
            toks = self._stage("ing_toks", (self.n_slots, Dp), np.int32)
            lens = self._stage("ing_lens", (self.n_slots,), np.int32)
            for s in active:
                d = self.streams[s]["draft_delta"]
                toks[s, : len(d)] = d
                lens[s] = len(d)
            fn = self._jit(f"drf_ing_p{Dp}", make_pool_decode_step(self.dc))
            logits, cache, hidden = fn(self.dp, self.dpool.cache, jnp.asarray(toks),
                                       jnp.asarray(lens))
            self.dpool.cache = cache
            w = self._fetch(self._warp(logits), "ingest")
            hid = self._fetch(hidden, "ingest")
            for s in active:
                q0[s] = w[s, lens[s] - 1]
                hq[s] = hid[s, lens[s] - 1]
            self.counters["draft_calls"] += 1
            self.counters["draft_tokens"] += int(lens.sum())
        return q0, hq

    @staticmethod
    def _bucket_actions(acts) -> tuple[int, int, int, int]:
        """Pad the batch's (K, L1, L2) actions to power-of-two buckets.

        The single source of truth for the iteration's static shapes: the
        drafting passes, the tree pass (Tpad) and step()'s eviction bound
        all use these same component-wise maxima."""
        Km = max(a[0] for a in acts.values())
        L1m = max(a[1] for a in acts.values())
        L2m = max(a[2] for a in acts.values())
        L1p = _next_pow2(L1m) if L1m else 0
        L2p = _next_pow2(L2m) if L2m else 0
        Kp = _next_pow2(Km) if (L2p and Km) else 0
        return Kp, L1p, L2p, 1 + L1p + Kp * L2p

    def _frontiers(self, active, Tpad, Dp) -> dict[int, int]:
        """Per-row live slot frontier for this iteration: the tree pass
        writes Tpad slots from C-1 and the padded ingest Dp slots from C-d
        (trunk drafting and replay commits stay within the tree extent) —
        mirror of step()'s logical-capacity eviction bound."""
        out = {}
        for s in active:
            C = len(self.streams[s]["committed"])
            d = len(self.streams[s]["draft_delta"])
            out[s] = max(C - 1 + Tpad, C - d + Dp)
        return out

    def _ensure_pool_blocks(self, active, acts, Tpad, Dp) -> bool:
        """Map the blocks this step's writes need, in three stages:
        free-list allocation, dead-tail reclamation (blocks wholly past a
        row's frontier — e.g. mapped for an earlier, bigger speculation
        bucket that committed short), then LIFO stream eviction.  Mutates
        ``active``/``acts`` when it evicts; returns True if it did.

        Tpad/Dp are RE-BUCKETED after every eviction: removing the stream
        that drove the batch maxima shrinks every survivor's frontier, so
        one victim's departure must not cascade into further evictions the
        smaller buckets would have avoided."""
        evicted = False
        fr = self._frontiers(active, Tpad, Dp)
        while active:
            short = False
            for pool in self._paged_pools():
                need = sum(pool.missing_blocks(s, fr[s]) for s in active)
                if need > pool.free_blocks:
                    self.counters["blocks_reclaimed"] += pool.reclaim_tails(fr)
                    need = sum(pool.missing_blocks(s, fr[s]) for s in active)
                    if need > pool.free_blocks:
                        short = True
            if not short:
                break
            victim = max(active, key=lambda s: self.streams[s]["seq"])
            self.counters["evicted"] += 1
            self._finish(victim, reason="evicted:pool_blocks")
            active.remove(victim)
            del acts[victim]
            evicted = True
            if active:
                _, _, _, Tpad = self._bucket_actions(acts)
                Dp = _next_pow2(max(len(self.streams[s]["draft_delta"]) for s in active))
                fr = self._frontiers(active, Tpad, Dp)
            else:
                fr = {}
        for pool in self._paged_pools():
            assert pool.ensure_rows(fr), "free list exhausted after the pressure loop"
        if isinstance(self.tpool, PagedCachePool):
            # peak is the TARGET arena's occupancy (the HBM that matters);
            # the draft arena is a proportionally smaller mirror
            self.counters["blocks_peak"] = max(self.counters["blocks_peak"],
                                               self.tpool.used_blocks)
        return evicted

    def pool_occupancy(self) -> dict:
        """Arena occupancy (blocks used/free, fragmentation) per pool —
        surfaced by benchmarks/batch_throughput.py next to the commit
        counters.  Empty for non-paged engines."""
        fr = {s: len(st["committed"]) for s, st in self.streams.items()}
        out = {}
        for name, pool in (("target", self.tpool), ("draft", self.dpool)):
            if isinstance(pool, PagedCachePool):
                out[name] = pool.occupancy(fr)
        return out

    @traced("serve:draft")
    def _draft_trees(self, active, acts, q0, pads):
        """Lockstep-draft every stream's (K, L1, L2) delayed tree on a local
        copy of the draft pool (discarded after, like the single engine)."""
        Kp, L1p, L2p, Tpad = pads
        # loop trip counts are host-side, not compiled shapes: iterate to the
        # raw batch maxima (the bucketed L1p/L2p only size the tree pass)
        L1m = max(a[1] for a in acts.values())
        L2m = max(a[2] for a in acts.values())
        dwork = self.dpool.cache
        cur = dict(q0)
        trunk_tok = {s: [] for s in active}
        trunk_q = {s: [] for s in active}
        step_fn = self._jit("drf_step", make_pool_locked_step(self.dc))
        for j in range(L1m):
            toks = np.zeros((self.n_slots, 1), np.int32)
            keep = np.zeros((self.n_slots,), bool)
            n_live = 0
            for s in active:
                if j < acts[s][1]:
                    t = draw_token(self.streams[s]["rng"], cur[s])
                    toks[s, 0] = t
                    keep[s] = True
                    trunk_tok[s].append(t)
                    n_live += 1
            logits, dwork = step_fn(self.dp, dwork, jnp.asarray(toks), jnp.asarray(keep))
            w = self._fetch(self._warp(logits[:, 0]), "draft")
            for s in active:
                if keep[s]:
                    cur[s] = w[s]
                    trunk_q[s].append(w[s])
            self.counters["draft_calls"] += 1
            self.counters["draft_tokens"] += n_live

        branch_tok = {s: [[] for _ in range(acts[s][0])] for s in active}
        branch_q = {s: [[] for _ in range(acts[s][0])] for s in active}
        if Kp and L2p:
            dfork = fork_streams(dwork, Kp)
            V = self.tc.vocab
            curb = np.zeros((self.n_slots * Kp, V), np.float32)
            for s in active:
                for k in range(acts[s][0]):
                    curb[s * Kp + k] = cur[s]
            bstep = self._jit(f"drf_bstep_k{Kp}", partial(forward, cfg=self.dc, mode="decode"))
            for j in range(L2m):
                toks = np.zeros((self.n_slots * Kp, 1), np.int32)
                n_live = 0
                for s in active:
                    K, _, L2 = acts[s]
                    if j < L2:
                        for k in range(K):
                            t = draw_token(self.streams[s]["rng"], curb[s * Kp + k])
                            toks[s * Kp + k, 0] = t
                            branch_tok[s][k].append(t)
                            n_live += 1
                logits, dfork, _ = bstep(self.dp, tokens=jnp.asarray(toks), cache=dfork)
                w = self._fetch(self._warp(logits[:, 0]), "draft")
                for s in active:
                    K, _, L2 = acts[s]
                    if j < L2:
                        for k in range(K):
                            curb[s * Kp + k] = w[s * Kp + k]
                            branch_q[s][k].append(w[s * Kp + k])
                self.counters["draft_calls"] += 1
                self.counters["draft_tokens"] += n_live

        trees = {}
        for s in active:
            K, L1, L2 = acts[s]
            tokens, parent, depth, pid, qs = [-1], [-1], [0], [0], [q0[s]]
            node = 0
            for j in range(L1):
                tokens.append(trunk_tok[s][j])
                parent.append(node)
                depth.append(depth[node] + 1)
                pid.append(0)
                qs.append(trunk_q[s][j])
                node = len(tokens) - 1
            branch_nodes = [node] * K
            for j in range(L2):
                for k in range(K):
                    tokens.append(branch_tok[s][k][j])
                    parent.append(branch_nodes[k])
                    depth.append(depth[branch_nodes[k]] + 1)
                    pid.append(k)
                    qs.append(branch_q[s][k][j])
                    branch_nodes[k] = len(tokens) - 1
            trees[s] = DraftTree(
                tokens=np.asarray(tokens, np.int64),
                parent=np.asarray(parent, np.int64),
                depth=np.asarray(depth, np.int64),
                q=np.stack(qs),
                path_id=np.asarray(pid, np.int64),
            )
        return trees

    # ----------------------------------------------------- target: tree -----

    @traced("serve:dispatch")
    def _target_tree_dispatch(self, active, trees, Tpad):
        """Dispatch ONE padded tree-masked target pass over every active row
        and return its warped logits / hidden states as DEVICE arrays (with
        async host copies kicked off) — the futures ``finish_step`` blocks
        on, so the host is free between dispatch and verification.

        The host ships (B, Tpad) token and parent-pointer index arrays only:
        ancestor masks are composed on device (device_ancestor_mask) and the
        idle-row freeze happens inside the same jit call — no per-iteration
        (B, Tpad, Tpad) mask tensor is rebuilt or transferred."""
        ttoks = self._stage("tree_toks", (self.n_slots, Tpad), np.int32)
        parents = self._stage("tree_parents", (self.n_slots, Tpad), np.int32, fill=-1)
        keep = self._stage("tree_keep", (self.n_slots,), np.bool_, fill=False)
        for s in active:
            tree = trees[s]
            n = tree.n_nodes
            ttoks[s, :n] = tree.tokens
            ttoks[s, 0] = self.streams[s]["pending"]
            parents[s, :n] = tree.parent
            keep[s] = True
        fn = self._jit(f"tgt_tree_p{Tpad}", make_pool_tree_step(self.tc),
                       donate_argnums=1)
        logits, cache, hidden = fn(self.tp, self.tpool.cache, jnp.asarray(ttoks),
                                   jnp.asarray(parents), jnp.asarray(keep))
        self.tpool.cache = cache
        real = sum(trees[s].n_nodes for s in active)
        self.counters["target_calls"] += 1
        self.counters["tree_calls_padded"] += 1
        self.counters["target_tokens"] += real
        self.counters["tree_lanes_total"] += self.n_slots * Tpad
        self.counters["pad_nodes_total"] += self.n_slots * Tpad - real
        p_dev = self._warp(logits)
        for arr in (p_dev, hidden):
            arr.copy_to_host_async()
        return p_dev, hidden

    def _ragged_layout(self, active, trees):
        """Per-stream (offset, n_nodes) segments in the flat node buffer,
        and its bucketed total Npad.  Offsets advance by the aligned segment
        size (pallas: 8, so Q tiles stay owner-uniform); Npad buckets to the
        next power of two so the jit cache stays bounded exactly like the
        padded path's Tpad buckets."""
        align = self._ragged_align
        offs, off = {}, 0
        for s in active:
            n = trees[s].n_nodes
            offs[s] = (off, n)
            off += -(-n // align) * align
        return offs, _next_pow2(max(off, align))

    @traced("serve:dispatch")
    def _target_tree_dispatch_ragged(self, active, trees, roffs):
        """Ragged counterpart of ``_target_tree_dispatch``: ONE flat
        node-major tree pass over every active stream's tree, no per-row
        padding to the pool-wide Tpad (serve_step.make_pool_ragged_tree_step;
        docs/serving.md "Ragged node-major tree batching").  The host ships
        (Npad,) token/owner/parent/depth/local arrays plus (B,) counts —
        the same small-index-arrays contract as the padded dispatch, with
        identical async-host-copy futures returned."""
        offs, Npad = roffs
        toks = self._stage("rtree_toks", (Npad,), np.int32)
        owner = self._stage("rtree_owner", (Npad,), np.int32)
        parent = self._stage("rtree_parent", (Npad,), np.int32, fill=-1)
        depth = self._stage("rtree_depth", (Npad,), np.int32)
        local = self._stage("rtree_local", (Npad,), np.int32, fill=-1)
        counts = self._stage("rtree_counts", (self.n_slots,), np.int32)
        align = self._ragged_align
        for s in active:
            o, n = offs[s]
            tree = trees[s]
            toks[o:o + n] = tree.tokens
            toks[o] = self.streams[s]["pending"]
            parent[o:o + n] = np.where(tree.parent >= 0, o + tree.parent, -1)
            depth[o:o + n] = tree.depth
            local[o:o + n] = np.arange(n)
            # owner covers the FULL aligned segment: alignment-gap lanes keep
            # local = -1 (they write nothing, attend to nothing) but carry
            # the segment's owner so pallas Q tiles stay owner-uniform
            owner[o:o + (-(-n // align) * align)] = s
            counts[s] = n
        fn = self._jit(f"tgt_rtree_n{Npad}", make_pool_ragged_tree_step(self.tc),
                       donate_argnums=1)
        logits, cache, hidden = fn(self.tp, self.tpool.cache, jnp.asarray(toks),
                                   jnp.asarray(owner), jnp.asarray(parent),
                                   jnp.asarray(depth), jnp.asarray(local),
                                   jnp.asarray(counts))
        self.tpool.cache = cache
        real = sum(trees[s].n_nodes for s in active)
        self.counters["target_calls"] += 1
        self.counters["tree_calls_ragged"] += 1
        self.counters["target_tokens"] += real
        self.counters["tree_lanes_total"] += Npad
        self.counters["pad_nodes_total"] += Npad - real
        p_dev = self._warp(logits)
        for arr in (p_dev, hidden):
            arr.copy_to_host_async()
        return p_dev, hidden

    def _commit_tables(self, active, node_paths):
        """Stage the fused commit's index tables (accepted node paths, path
        lengths, pre-block committed lengths, active mask) and return them
        with the padded path width P.  Shared between the single-engine
        commit and the sharded engine's grouped cross-shard commit."""
        B = self.n_slots
        P = _next_pow2(max([len(node_paths[s]) for s in active] + [1]))
        npath = self._stage("commit_path", (B, P), np.int32)
        plen = self._stage("commit_plen", (B,), np.int32)
        Cb = self._stage("commit_C", (B,), np.int32)
        act = self._stage("commit_act", (B,), np.bool_, fill=False)
        for s in active:
            path = node_paths[s]
            npath[s, : len(path)] = path
            plen[s] = len(path)
            Cb[s] = len(self.streams[s]["committed"]) - 1
            act[s] = True
        return npath, plen, Cb, act, P

    def _commit_tree_batch(self, active, node_paths, Tpad):
        """Fused commit: ONE jitted, pool-donating call re-compacts every
        active row's accepted path (serve_step.make_pool_commit_step) —
        the tentpole replacing the per-stream eager ``.at[].set`` chains
        (kept as serve_step.commit_row_reference, the test/bench oracle)."""
        npath, plen, Cb, act, P = self._commit_tables(active, node_paths)
        fn = self._jit(f"commit_T{Tpad}_P{P}",
                       make_pool_commit_step(self.tc, Tpad), donate_argnums=0)
        self.tpool.cache = fn(self.tpool.cache, jnp.asarray(npath), jnp.asarray(plen),
                              jnp.asarray(Cb), jnp.asarray(act))
        self.counters["commit_calls"] += 1

    # --------------------------------------------------- target: replay -----

    def _target_replay(self, active, trees, acts, Kp):
        """Recurrent targets: grouped trunk decode + forked branch replay.
        Returns (snapshot, per-slot p matrices) ready for verification.

        p matrices are float32 (the warped logits' native dtype) and cast to
        float64 only at the verifier boundary in step() — no dense float64
        (n_nodes, vocab) allocations per stream per step."""
        snapshot = self.tpool.cache
        structs = {s: delayed_structure(trees[s]) for s in active}
        p_host = {s: np.zeros((trees[s].n_nodes, trees[s].vocab), np.float32)
                  for s in active}
        groups = defaultdict(list)
        for s in active:
            trunk, _, _ = structs[s]
            groups[1 + len(trunk)].append(s)
        trims, trunk_rows = [], []
        for L, rows in sorted(groups.items()):
            toks = np.zeros((len(rows), L), np.int32)
            for i, s in enumerate(rows):
                trunk, _, _ = structs[s]
                toks[i, 0] = self.streams[s]["pending"]
                for j, v in enumerate(trunk):
                    toks[i, 1 + j] = int(trees[s].tokens[v])
            rows_p, toks_p = self._pad_group(rows, toks, self.n_slots)
            sub = gather_streams(snapshot, rows_p)
            fn = self._jit(f"tgt_trunk_g{L}", partial(forward, cfg=self.tc, mode="decode"))
            logits, sub, _ = fn(self.tp, tokens=jnp.asarray(toks_p), cache=sub)
            trims.append(gather_streams(sub, list(range(len(rows)))))
            trunk_rows.extend(rows)
            w = self._fetch(self._warp(logits), "tree")
            for i, s in enumerate(rows):
                trunk, _, _ = structs[s]
                p_host[s][0] = w[i, 0]
                for j, v in enumerate(trunk):
                    p_host[s][v] = w[i, 1 + j]
            self.counters["target_calls"] += 1
            self.counters["target_tokens"] += L * len(rows)
        # one write-back of all trunk-advanced rows (snapshot stays intact —
        # it is the commit checkpoint)
        work = self._scatter_rows(snapshot, trims, trunk_rows, donate=False)

        has_branches = [s for s in active if structs[s][2]]
        if has_branches and Kp:
            fork = fork_streams(work, Kp)
            bgroups = defaultdict(list)
            for s in has_branches:
                _, _, branches = structs[s]
                bgroups[len(branches[0])].append(s)
            for L2, rows in sorted(bgroups.items()):
                frows, meta = [], []
                for s in rows:
                    _, _, branches = structs[s]
                    for k, path in enumerate(branches):
                        frows.append(s * Kp + k)
                        meta.append((s, path))
                btoks = np.asarray(
                    [[int(trees[s].tokens[v]) for v in path] for s, path in meta], np.int32
                )
                frows_p, btoks_p = self._pad_group(frows, btoks, self.n_slots * Kp)
                sub = gather_streams(fork, frows_p)
                fn = self._jit(f"tgt_branch_g{L2}k{Kp}", partial(forward, cfg=self.tc, mode="decode"))
                logits, _, _ = fn(self.tp, tokens=jnp.asarray(btoks_p), cache=sub)
                pb = self._fetch(self._warp(logits), "tree")
                for i, (s, path) in enumerate(meta):
                    for j, v in enumerate(path):
                        p_host[s][v] = pb[i, j]
                self.counters["target_calls"] += 1
                self.counters["target_tokens"] += L2 * len(frows)
        return snapshot, p_host

    def _commit_replay(self, active, snapshot, accepted_by_slot):
        """Restore the checkpoint and re-advance each stream along
        [root] + accepted (grouped by commit length), then write every row
        back with ONE donated scatter — the replay strategy's single fused
        commit write per step."""
        hid_last = {}
        groups = defaultdict(list)
        for s in active:
            groups[1 + len(accepted_by_slot[s])].append(s)
        trims, all_rows = [], []
        for L, rows in sorted(groups.items()):
            toks = np.zeros((len(rows), L), np.int32)
            for i, s in enumerate(rows):
                toks[i, 0] = self.streams[s]["pending"]
                for j, t in enumerate(accepted_by_slot[s]):
                    toks[i, 1 + j] = int(t)
            rows_p, toks_p = self._pad_group(rows, toks, self.n_slots)
            sub = gather_streams(snapshot, rows_p)
            fn = self._jit(f"tgt_commit_g{L}", partial(forward, cfg=self.tc, mode="decode"))
            _, sub, ex = fn(self.tp, tokens=jnp.asarray(toks_p), cache=sub)
            trims.append(gather_streams(sub, list(range(len(rows)))))
            all_rows.extend(rows)
            hid = self._fetch(ex["hidden"], "hidden")
            for i, s in enumerate(rows):
                hid_last[s] = hid[i, L - 1]
        self.tpool.cache = self._scatter_rows(snapshot, trims, all_rows, donate=True)
        self.counters["commit_calls"] += 1
        return hid_last

    # ---------------------------------------------------------------- step ---

    @traced("serve:begin")
    def begin_step(self) -> PendingStep | None:
        """The DISPATCH half of a step: run the scheduling boundary (admit
        queued requests, capacity-evict, map paged blocks), then dispatch
        the draft ingest, the delayed-tree drafting and the tree-masked
        target pass.  Returns a ``PendingStep`` whose tree-pass outputs are
        device futures (tree strategy), or None when nothing is active.

        ALL admission/eviction/block-pressure decisions happen here, at the
        pipeline boundary — never between a dispatch and its verification —
        which is what lets the pipelined driver overlap ``finish_step``'s
        host tail with the next step's device work without perturbing
        scheduling (the exactness argument in docs/serving.md)."""
        self._staging.flip()
        self._admit()
        active = [s for s in sorted(self.streams) if not self.streams[s]["done"]]
        if not active:
            return None
        acts = {s: tuple(self.choose_action(self.streams[s])) for s in active}
        # eviction: a stream whose ring cannot hold another padded speculation
        # block (the tree pass writes Tpad slots from the batch-maxima
        # buckets) or the padded ingest width must finish instead of wrapping
        # the ring onto committed slots.
        _, _, _, Tpad = self._bucket_actions(acts)
        Dp = _next_pow2(max(len(self.streams[s]["draft_delta"]) for s in active))
        smax = self.ecfg.max_cache
        boundary_evicted = False
        for s in list(active):
            C = len(self.streams[s]["committed"])
            d = len(self.streams[s]["draft_delta"])
            # tree pass writes Tpad slots from C-1; padded ingest writes Dp
            # slots from the draft length C-d — either wrapping onto live
            # slots would corrupt the committed prefix
            if C - 1 + Tpad > smax or C - d + Dp > smax:
                self.counters["evicted"] += 1
                self._finish(s, reason="evicted:cache_full")
                active.remove(s)
                del acts[s]
                boundary_evicted = True
        if not active:
            return None
        # re-bucket: eviction can only shrink the maxima, never grow them
        pads = self._bucket_actions(acts)
        Kp, L1p, L2p, Tpad = pads
        if self.paged:
            # map every block this iteration's writes will touch; under
            # pressure reclaim dead tails first, evict (LIFO) only as a
            # last resort
            Dp = _next_pow2(max(len(self.streams[s]["draft_delta"]) for s in active))
            if self._ensure_pool_blocks(active, acts, Tpad, Dp):
                boundary_evicted = True
                if not active:
                    return None
                pads = self._bucket_actions(acts)
                Kp, L1p, L2p, Tpad = pads
        # rewind coordinates (pipelined mode): abort_step can restore
        # rng/draft state as if the step never began
        C0 = {s: len(self.streams[s]["committed"]) - 1 for s in active}
        rng_state, D0 = None, None
        if self.pipeline:
            # numpy's .state property builds a fresh dict per access, so the
            # snapshot needs no deepcopy
            rng_state = {s: self.streams[s]["rng"].bit_generator.state
                         for s in active}
            if self._recurrent(self.dc):
                # recurrent draft state integrates every token — it can only
                # be rewound from a saved copy, so hold the back frame
                self.dpool.begin_frame()
            else:
                # attention draft rewind is LOGICAL: this step's only pool
                # mutation is the append-only, deterministic delta ingest
                # (trunk drafting runs on a discarded local copy), so
                # abort_step erases pos >= D0 lanes and the re-begun step
                # re-ingests bit-identical values.  No back frame held:
                # keeping the pre-step arena alive serialized the allocator
                # and cost more than the pipeline overlap earned.
                D0 = {s: len(self.streams[s]["committed"])
                         - len(self.streams[s]["draft_delta"])
                      for s in active}
        self.counters["steps_begun"] += 1
        q0, hq = self._ingest_deltas(active)
        trees = self._draft_trees(active, acts, q0, pads)
        if self.strategy == "tree":
            roffs = None
            if self._ragged_ok:
                offs, Npad = self._ragged_layout(active, trees)
                # auto mode goes ragged only on a STRICT lane win (drain
                # tails, heterogeneous actions); a full homogeneous pool
                # where Npad == n_slots * Tpad keeps the padded layout
                if self.ragged == "always" or Npad < self.n_slots * Tpad:
                    roffs = (offs, Npad)
            if roffs is not None:
                p_dev, hid_dev = self._target_tree_dispatch_ragged(
                    active, trees, roffs)
            else:
                p_dev, hid_dev = self._target_tree_dispatch(active, trees, Tpad)
            return PendingStep(active=active, acts=acts, pads=pads, trees=trees,
                               hq=hq, C0=C0, p_dev=p_dev, hid_dev=hid_dev,
                               rng_state=rng_state, D0=D0, roffs=roffs,
                               boundary_evicted=boundary_evicted)
        snapshot, p_host = self._target_replay(active, trees, acts, Kp)
        return PendingStep(active=active, acts=acts, pads=pads, trees=trees,
                           hq=hq, C0=C0, snapshot=snapshot, p_host=p_host,
                           rng_state=rng_state, D0=D0,
                           boundary_evicted=boundary_evicted)

    @traced("serve:verify")
    def verify_step(self, pending: PendingStep) -> VerifiedStep:
        """The VERIFY phase: block on the tree-pass logits future and run
        every stream's host-side accept/reject walk.  Consumes per-stream
        rng, so it fixes this step's tokens — but touches no pool state and
        no scheduling state, which is what lets the sharded driver verify
        one shard while the other shards' dispatched device work is still
        in flight, then batch all commits into one call."""
        if self.dpool.frame_held:
            self.dpool.drop_frame()  # committing to this step: no rewind past here
        active, trees = pending.active, pending.trees
        accepted, corr = {}, {}
        if self.strategy == "tree":
            p_all = self._fetch(pending.p_dev, "tree")
            node_paths = {}
            for s in active:
                tree = trees[s]
                if pending.roffs is not None:
                    o, n = pending.roffs[0][s]
                    tree.p = to_verifier_dtype(p_all[o:o + n])
                else:
                    tree.p = to_verifier_dtype(p_all[s, : tree.n_nodes])
                acc, c = verify_tree(tree, self.ecfg.verifier, self.streams[s]["rng"])
                accepted[s], corr[s] = acc, int(c)
                node_paths[s] = SpeculativeEngine._accepted_nodes(tree, acc)
            return VerifiedStep(pending, accepted, corr, node_paths=node_paths)
        for s in active:
            tree = trees[s]
            tree.p = to_verifier_dtype(pending.p_host[s])
            acc, c = verify_tree(tree, self.ecfg.verifier, self.streams[s]["rng"])
            accepted[s], corr[s] = acc, int(c)
        return VerifiedStep(pending, accepted, corr)

    @traced("serve:commit")
    def commit_step(self, v: VerifiedStep) -> None:
        """The COMMIT phase: ONE fused, pool-donating call compacts every
        row's accepted path (tree strategy), or the grouped replay
        re-advance (replay strategy, which also yields the last hidden
        states).  Must run before ``retire_step`` extends ``committed`` —
        the commit indices are relative to the pre-block length."""
        pending = v.pending
        if self.strategy == "tree":
            self._commit_tree_batch(pending.active, v.node_paths, pending.pads[3])
        else:
            v.hid_last = self._commit_replay(pending.active, pending.snapshot,
                                             v.accepted)

    def _read_hidden(self, v: VerifiedStep) -> None:
        """Publish each stream's last accepted hidden state (``h_prev_p``).
        On the tree strategy this blocks on the hidden-state device future,
        so ``retire_step`` defers it behind the pipeline-ahead dispatch
        whenever nothing reads it at the next boundary — after which a
        stream may already be gone (the begun-ahead boundary can evict), so
        departed rows are skipped."""
        pending = v.pending
        if self.strategy == "tree":
            hid_all = self._fetch(pending.hid_dev, "hidden")
            for s in pending.active:
                if s not in self.streams:
                    continue
                path = v.node_paths[s]
                idx = path[-1] if path else 0
                if pending.roffs is not None:
                    self.streams[s]["h_prev_p"] = hid_all[pending.roffs[0][s][0] + idx]
                else:
                    self.streams[s]["h_prev_p"] = hid_all[s, idx]
        else:
            for s in pending.active:
                if s in self.streams:
                    self.streams[s]["h_prev_p"] = v.hid_last[s]

    @traced("serve:retire")
    def retire_step(self, v: VerifiedStep, pipeline_ahead: bool | None = None) -> list[dict]:
        """The RETIRE phase: token bookkeeping, the pipeline-ahead decision,
        then the host tail (hidden-state readback, releasing finished
        streams' rows/blocks).

        In pipelined mode (``pipeline_ahead`` defaults to ``self.pipeline``)
        the critical bookkeeping runs first — the stream fields the next
        boundary reads (``committed``, ``pending``, ``draft_delta``,
        ``done``) and the release of retiring streams' rows/blocks — then
        the next step is begun, then the host tail (the blocking
        hidden-state readback, deferred only when no selector consumes it
        at the next boundary) runs while the device already chews on step
        i+1.  Releasing BEFORE the begun-ahead boundary is what lets the
        pipeline run ahead across retiring iterations: the boundary sees
        exactly the post-release pool the synchronous engine's next
        ``begin_step`` would see, so admission and pressure decisions — and
        therefore tokens — stay identical.  The pipeline stalls only when
        the boundary itself comes up empty (nothing left to dispatch)."""
        pending = v.pending
        retire: list[tuple[int, dict]] = []
        for s in pending.active:
            node_path = None if v.node_paths is None else v.node_paths[s]
            retire.append(
                (s, self._advance_stream(s, pending.trees[s], v.accepted[s],
                                         v.corr[s], pending.hq[s], node_path))
            )
        if pipeline_ahead is None:
            pipeline_ahead = self.pipeline
        # defer the blocking hidden readback past the next dispatch only
        # when nothing at the next boundary consumes it (selectors read
        # h_prev_p); the replay strategy's hid_last is already host-side
        defer_hid = (pipeline_ahead and self.strategy == "tree"
                     and self.selector is None)
        if not defer_hid:
            self._read_hidden(v)
        # release finished streams' rows/blocks BEFORE the next boundary —
        # the freed capacity is scheduling-visible there (admission and
        # block pressure), exactly as after a synchronous step
        for s, ev in retire:
            if ev["done"]:
                self._finish(s)
        if pipeline_ahead:
            assert self._pending_next is None, "a begun-ahead step is already pending"
            self.counters["pipeline_iterations"] += 1
            self._pending_next = self.begin_step()
            if self._pending_next is not None:
                self.counters["pipeline_ahead"] += 1
            else:
                # an empty boundary (no live streams, nothing admissible)
                # is the only stall left: ahead + stalls == iterations
                self.counters["pipeline_stalls"] += 1
        # host tail: runs behind step i+1's dispatched device work
        if defer_hid:
            self._read_hidden(v)
        return [ev for _, ev in retire]

    def finish_step(self, pending: PendingStep, pipeline_ahead: bool | None = None) -> list[dict]:
        """Verify + commit + retire a dispatched step — the single-engine
        composition of the three phases (the sharded engine drives them
        separately to interleave its shards)."""
        v = self.verify_step(pending)
        self.commit_step(v)
        return self.retire_step(v, pipeline_ahead)

    def step(self) -> list[dict]:
        """Admit queued requests, advance every active stream one speculative
        block, and return per-request progress events.  Synchronous form of
        begin_step + finish_step; in pipelined mode it first consumes the
        step begun ahead by the previous ``finish_step`` (and surfaces any
        events a mid-run ``submit`` retired on its behalf)."""
        self._step_no += 1
        with step_span(self._step_no):
            events, self._drained_events = self._drained_events, []
            pending, self._pending_next = self._pending_next, None
            if pending is None:
                pending = self.begin_step()
            if pending is None:
                return events
            return events + self.finish_step(pending)

    def drain_pipeline(self) -> list[dict]:
        """Finish the begun-ahead step WITHOUT beginning another — the drain
        half of the stall-and-drain rule.  Call before out-of-band pool or
        scheduling mutations (or at shutdown) so no dispatched work is left
        in flight.  No-op (returns []) when nothing is pending."""
        pending, self._pending_next = self._pending_next, None
        if pending is None:
            return []
        return self.finish_step(pending, pipeline_ahead=False)

    @traced("serve:abort")
    def abort_step(self, pending: PendingStep) -> None:
        """Rewind a begun step as if it never dispatched (pipelined mode):
        restore every active stream's rng snapshot, rewind the draft pool —
        logically for attention-family drafts (the step's only draft-pool
        mutation is the append-only delta ingest: erase pos >= D0 lanes
        with ``invalidate_from`` and the re-begun step re-ingests identical
        values), from the double-buffered back frame for recurrent drafts —
        and invalidate the target rows' speculative tree writes (their pool
        buffer was donated, so the pre-pass buffer is gone — but every
        speculative lane carries pos >= C0 and is erased by
        ``CachePool.invalidate_from``; the replay strategy never touches the
        target pool before its commit).  Boundary decisions taken by
        ``begin_step`` (admissions, evictions, block mappings) are
        scheduling events that stand; dead mappings are recycled by the
        normal pressure path.  Work counters also stand — they count
        dispatched work — and ``steps_rewound`` counts the rewind."""
        assert pending.rng_state is not None, \
            "abort_step needs the rng snapshots only pipelined begin_step records"
        self.counters["steps_rewound"] += 1
        if pending is self._pending_next:
            self._pending_next = None
        for s, state in pending.rng_state.items():
            if s in self.streams:
                self.streams[s]["rng"].bit_generator.state = state
        if self.dpool.frame_held:
            self.dpool.rollback_frame()
        elif pending.D0 is not None:
            self.dpool.invalidate_from({s: pending.D0[s] for s in pending.active
                                        if s in self.streams})
        if self.strategy == "tree":
            self.tpool.invalidate_from({s: pending.C0[s] for s in pending.active
                                        if s in self.streams})

    def abort_pipeline(self) -> int:
        """Rewind the begun-ahead step, if any (``abort_step`` on
        ``_pending_next``).  Returns the number of steps rewound (0 or 1) —
        the sharded engine sums it across shards."""
        pending, self._pending_next = self._pending_next, None
        if pending is None:
            return 0
        self.abort_step(pending)
        return 1

    def _advance_stream(self, slot, tree, accepted, corr, h_q, node_path=None):
        """Token bookkeeping shared with SpeculativeEngine.step.  Marks the
        stream done when it reaches ``max_new`` but does NOT release its pool
        row — ``finish_step``'s retirement tail owns that, after the
        pipeline-ahead decision."""
        st = self.streams[slot]
        nodes = (
            node_path if node_path is not None
            else SpeculativeEngine._accepted_nodes(tree, accepted)
        )
        st["p_prev"] = tree.p[nodes[-1]] if accepted else tree.p[0]
        st["q_prev"] = tree.q[nodes[-1]] if accepted else tree.q[0]
        new_tokens = list(accepted) + [corr]
        st["committed"].extend(new_tokens)
        st["pending"] = corr
        st["draft_delta"] = new_tokens
        st["h_prev_q"] = h_q
        st["out"].extend(new_tokens)
        self.counters["accepted"] += len(accepted)
        self.counters["blocks"] += 1
        ev = {"rid": st["rid"], "new_tokens": new_tokens,
              "done": len(st["out"]) >= st["max_new"]}
        if ev["done"]:
            st["done"] = True
        return ev

    # ------------------------------------------------------ distribution peeks

    def _peek(self, cfg, params, pool, slot: int, toks: list[int], name: str):
        """Score ``toks`` against one pool row WITHOUT mutating the pool:
        gather the row to a dense 1-row cache (paged rows come back dense),
        decode, discard the advanced copy.  The pooled form of the
        single-stream peek oracles — compiled once per token-length bucket."""
        sub = gather_streams(pool.cache, [slot])
        T = len(toks)
        fn = self._jit(f"{name}_peek_{T}", partial(forward, cfg=cfg, mode="decode"))
        logits, _, _ = fn(params, tokens=jnp.asarray(np.asarray(toks, np.int32)[None]),
                          cache=sub)
        return self._fetch(self._warp(logits[0]), "draft" if name == "drf" else "tree")[-1]

    def peek_draft_dist(self, stream, ctx: list[int]) -> np.ndarray:
        """q(. | committed + ctx) for a pooled stream, functional.

        With the single-stream peeks this unblocks AnalyticSelector under
        continuous batching (the ROADMAP "Batched analytic selector" item).
        Note the selector itself draws from its OWN rng, shared across the
        streams it serves — its decisions are deterministic per arrival
        order, but not reproduced by independent single-stream runs."""
        toks = list(stream["draft_delta"]) + list(ctx)
        return self._peek(self.dc, self.dp, self.dpool, stream["slot"], toks, "drf")

    def peek_target_dist(self, stream, ctx: list[int]) -> np.ndarray:
        """p(. | committed + ctx) for a pooled stream, functional."""
        toks = [stream["pending"]] + list(ctx)
        return self._peek(self.tc, self.tp, self.tpool, stream["slot"], toks, "tgt")

    # ----------------------------------------------------------------- run ---

    def run(self) -> dict[int, dict]:
        """Drain the queue: step until every submitted request finished.

        Returns ``{rid: {"tokens", "reason"}}`` for the requests completed by
        this call, removing them from the engine — a long-lived serving loop
        does not accumulate finished payloads, and repeated calls never
        re-return stale results."""
        done: dict[int, dict] = {}

        def drain():
            while self.finished:
                rid, info = self.finished.popitem()
                done[rid] = info

        drain()
        while self.queue or self.streams:
            before = len(done)
            self.step()
            drain()
            if not self.streams and not self.queue:
                break
            assert self.streams or len(done) > before, "scheduler stalled"
        return done

    def generate_batch(self, prompts, max_new: int = 32, seeds=None) -> list[list[int]]:
        """Convenience: submit all prompts, drain, return outputs in order."""
        rids = [
            self.submit(p, max_new, None if seeds is None else seeds[i])
            for i, p in enumerate(prompts)
        ]
        out = self.run()
        return [out[r]["tokens"] for r in rids]


class ShardedBatchedSpeculativeEngine:
    """Stream axis sharded across a data mesh: the continuous-batching pool
    split into ``data_shards`` contiguous slot shards, each an independent
    ``BatchedSpeculativeEngine`` over its own rows and (paged) its own
    private block arena — shard-local free lists, host-mirrored
    pos/len/block tables, admission FIFO, pressure reclamation and
    eviction — with every shard's pool arrays NamedSharding-committed to
    its slice of the mesh data axis (launch/mesh.shard_meshes;
    launch/sharding.pool_shardings).  On a multi-device host the shards'
    pool steps dispatch onto distinct devices and overlap; on one device
    they serialize but stay token-identical (the host-local smoke path).

    The only cross-shard state is the scheduler: ``submit()`` routes each
    request to a shard that can admit it now (``can_admit`` — free row,
    empty FIFO, free blocks), bin-packing on the request's expected
    selector action first (``_pack_cost``: streams with similar (K, L1, L2)
    buckets land co-resident so shard-local Tpad buckets stay tight —
    docs/serving.md "Selector-aware bin-packing"), breaking cost ties
    least-loaded, falling back to least-loaded overall, deterministically
    in arrival order.  With homogeneous hints every pack cost is 0 and
    routing degrades exactly to the original least-loaded rule.  Requests
    never migrate; retirement, eviction and block recycling read and write
    nothing outside their shard — which is exactly what lets each shard
    live on its own host with no coherence traffic beyond routing.

    Exactness (property-tested in tests/test_sharding.py): a stream's
    tokens depend only on its own seed and its shard's model calls, and
    padded pool calls are bit-identical regardless of co-resident rows —
    so for the same arrival order the sharded engine emits exactly the
    unsharded engine's tokens, for both strategies, both verifiers,
    synchronous and pipelined stepping.  Scheduling-dependent *truncation*
    (eviction) also coincides whenever the eviction bound is per-stream
    (capacity eviction with homogeneous actions); block-pressure eviction
    is shard-local by design and compared against per-shard expectations
    instead (docs/serving.md "Sharded streams").

    ``n_slots`` that does not divide ``data_shards`` is padded UP
    (launch/sharding.pad_slots) — idle rows cost padding lanes, a
    replicated shard would cost HBM and the shard-local free-list
    invariant.  A given total ``pool_blocks`` is split evenly (ceil) so
    every shard's arena gates its own admissions.
    """

    def __init__(self, target_cfg, target_params, draft_cfg, draft_params,
                 ecfg: EngineConfig, sampling: SamplingParams | None = None,
                 selector=None, n_slots: int = 4, data_shards: int = 2,
                 paged: bool = True, block_size: int = 64,
                 pool_blocks: int | None = None, pipeline: bool = False,
                 meshes=None, ragged=True):
        assert data_shards >= 1, data_shards
        self.data_shards = data_shards
        self.n_slots = pad_slots(n_slots, data_shards)
        per_slots = self.n_slots // data_shards
        per_blocks = None
        if paged and pool_blocks is not None:
            per_blocks = -(-pool_blocks // data_shards)
        if meshes is None:
            meshes = shard_meshes(data_shards)
        assert len(meshes) == data_shards, (len(meshes), data_shards)
        self.shards = [
            BatchedSpeculativeEngine(
                target_cfg, target_params, draft_cfg, draft_params, ecfg,
                sampling, selector=selector, n_slots=per_slots, paged=paged,
                block_size=block_size, pool_blocks=per_blocks,
                pipeline=pipeline, mesh=meshes[i], shard_id=i, ragged=ragged)
            for i in range(data_shards)
        ]
        s0 = self.shards[0]
        self.paged, self.strategy, self.pipeline = s0.paged, s0.strategy, pipeline
        self.ecfg = ecfg
        if s0.paged:
            self.block_size = s0.block_size
            self.pool_blocks = s0.pool_blocks * data_shards
        self.finished: dict[int, dict] = {}
        self._next_rid = 0
        self._local: dict[int, tuple[int, int]] = {}   # global rid -> (shard, local rid)
        self._global: dict[tuple[int, int], int] = {}  # (shard, local rid) -> global rid
        # bin-packing state: global rid -> (shard, expected speculation
        # bucket Tpad) for every live routed request, pruned lazily against
        # _local at submit().  Scheduler-only — shapes no shard-local
        # decision and never migrates a stream (see _route)
        self._resident: dict[int, tuple[int, int]] = {}
        # grouped cross-shard commit (see _commit_shards): legal only when
        # every shard's pool lives on the same device set, which is exactly
        # the host-local smoke topology shard_meshes produces by cycling a
        # short device list
        devs = [tuple(sh.mesh.devices.flat) for sh in self.shards]
        self._colocated = all(d == devs[0] for d in devs)
        self._jit_cache: dict = {}
        self._step_no = 0  # step() calls, the step number of the serve:step span
        # engine-level commit counter: a grouped commit is ONE dispatch
        # that belongs to no single shard (the counters property merges
        # it into the summed per-shard view)
        self._counters = {"commit_calls": 0}

    # --------------------------------------------------------- scheduling ---

    @staticmethod
    def _action_tpad(action) -> int:
        """Speculation bucket (Tpad) a lone stream with this (K, L1, L2)
        action would occupy — the bin-packing coordinate.  Uses the engines'
        own shape-bucketing rule so 'similar action' means exactly 'same
        compiled tree-pass bucket'."""
        return BatchedSpeculativeEngine._bucket_actions({0: tuple(action)})[3]

    def _pack_cost(self, si: int, tpad: int) -> int:
        """Padding lanes (per iteration) that co-residency with shard
        ``si``'s routed streams would add: a shard steps at the max of its
        residents' buckets, so joining costs this stream (new_max - tpad)
        lanes and costs each resident any growth of that max.  0 for an
        empty shard and whenever every bucket matches — with homogeneous
        actions all costs are 0 and routing degrades EXACTLY to the
        original least-loaded rule."""
        res = [t for s, t in self._resident.values() if s == si]
        if not res:
            return 0
        cur = max(res)
        new = max(cur, tpad)
        return (new - tpad) + len(res) * (new - cur)

    def _route(self, prompt_len: int, tpad: int) -> int:
        """Shard that can admit now with the cheapest bin-packing cost for
        this request's expected speculation bucket; least-loaded breaks
        cost ties and least-loaded overall applies when none can admit (the
        request queues there).  Load = resident + queued, ties to the
        lowest shard id — a pure function of arrival order and hints, so
        the schedule (and therefore any eviction truncation) is
        deterministic and arrival-order-stable.  Routing is the ONLY
        cross-shard state: placement never migrates a running stream."""
        admitting = [i for i, sh in enumerate(self.shards)
                     if sh.can_admit(prompt_len)]
        pool = admitting or range(self.data_shards)
        return min(pool, key=lambda i: (self._pack_cost(i, tpad),
                                        len(self.shards[i].streams)
                                        + len(self.shards[i].queue), i))

    def shard_of(self, rid: int) -> int:
        """Which shard a live (unfinished) request was routed to."""
        return self._local[rid][0]

    def submit(self, prompt: list[int], max_new: int = 64, seed: int | None = None,
               action_hint=None) -> int:
        """Route to a shard (bin-packing on ``action_hint``, the request's
        expected (K, L1, L2) selector action — default: the engine-config
        action, under which routing is plain least-loaded) and queue it
        there.  Hints only steer placement; the resident selector still
        decides every stream's real per-iteration action."""
        self._resident = {r: v for r, v in self._resident.items()
                          if r in self._local}
        hint = tuple(action_hint) if action_hint is not None else (
            self.ecfg.K, self.ecfg.L1, self.ecfg.L2)
        tpad = self._action_tpad(hint)
        si = self._route(len(prompt), tpad)
        lrid = self.shards[si].submit(prompt, max_new=max_new, seed=seed)
        rid = self._next_rid
        self._next_rid += 1
        self._local[rid] = (si, lrid)
        self._global[(si, lrid)] = rid
        self._resident[rid] = (si, tpad)
        return rid

    def _collect(self, si: int, events: list[dict]) -> list[dict]:
        """Rewrite a shard's events/finished payloads to global rids."""
        out = []
        for ev in events:
            ev = dict(ev)
            ev["rid"] = self._global[(si, ev["rid"])]
            out.append(ev)
        sh = self.shards[si]
        while sh.finished:
            lrid, info = sh.finished.popitem()
            rid = self._global.pop((si, lrid))
            del self._local[rid]
            self.finished[rid] = info
        return out

    # --------------------------------------------------------------- steps ---

    def _jit(self, name, fn, donate_argnums=None):
        """Engine-level jit cache for the grouped cross-shard commit (the
        shards keep their own caches for everything shard-local)."""
        return jit_named(self._jit_cache, name, fn, donate_argnums)

    def jit_compile_count(self) -> int:
        """Compile budget of the whole sharded deployment: every shard's jit
        cache plus the engine-level grouped-commit cache."""
        return (sum(sh.jit_compile_count() for sh in self.shards)
                + sum(fn._cache_size() for fn in self._jit_cache.values()))

    def placement(self) -> list[dict[str, list[int]]]:
        """Per shard, the ids of the devices holding its params and pools."""
        return [sh.placement() for sh in self.shards]

    def _finish_order(self, sis: list[int]) -> list[int]:
        """The order shards' in-flight steps are VERIFIED in.  Shards are
        independent and verification touches only shard-local state, so any
        permutation yields identical tokens — the default is shard order;
        the race harness (tests/test_race.py) overrides this to shuffle
        host-side completion order under a seed."""
        return list(sis)

    def step(self) -> list[dict]:
        """Advance every shard one speculative block, CONCURRENTLY across
        shards: every shard's ``begin_step`` dispatches before any shard's
        verification blocks, so one shard's host-side verify loop hides
        behind the other shards' in-flight device work (on a multi-device
        host the shard passes themselves also overlap).  Then all verified
        shards commit in ONE grouped dispatch (``_commit_shards``) and
        retire in shard order — the retire phase runs each shard's
        pipeline-ahead dispatch when pipelining, so the next iteration's
        device work is already in flight when this call returns."""
        self._step_no += 1
        with step_span(self._step_no):
            events = []
            # phase 1 — begin: surface drained events, then dispatch every
            # shard's step (consuming a begun-ahead step where one is pending)
            # before any verification blocks on a device future
            pendings: list = []
            for si, sh in enumerate(self.shards):
                drained, sh._drained_events = sh._drained_events, []
                events.extend(self._collect(si, drained))
                pending, sh._pending_next = sh._pending_next, None
                if pending is None:
                    pending = sh.begin_step()
                pendings.append(pending)
            live = [si for si, p in enumerate(pendings) if p is not None]
            # phase 2 — verify: per-stream host walks, one shard at a time,
            # while the remaining shards' dispatched passes keep the device busy
            verified = {si: self.shards[si].verify_step(pendings[si])
                        for si in self._finish_order(live)}
            # phase 3 — commit: one grouped dispatch across shards
            self._commit_shards(verified)
            # phase 4 — retire (shard order, so event order is deterministic
            # regardless of the verify permutation)
            for si in sorted(verified):
                events.extend(self._collect(
                    si, self.shards[si].retire_step(verified[si])))
            # a shard whose boundary came up empty can still have retired a
            # stream there (capacity eviction) — surface its finished payloads
            for si in range(self.data_shards):
                if si not in verified:
                    events.extend(self._collect(si, []))
            return events

    def _commit_shards(self, verified: dict[int, VerifiedStep]) -> None:
        """Commit every verified shard's accepted paths.  Tree-strategy
        shards that share a device batch their staged index tables into ONE
        jitted, pool-donating dispatch (serve_step.make_group_commit_step)
        — restoring single-shard ``commit_calls`` — and fall
        back to per-shard commits when alone, un-colocated, or on the
        replay strategy (whose commit is a host-interleaved re-advance)."""
        group = sorted(verified) if self.strategy == "tree" and self._colocated \
            else []
        if len(group) <= 1:
            for si in sorted(verified):
                self.shards[si].commit_step(verified[si])
            return
        sigs, tables, caches = [], [], []
        for si in group:
            sh, v = self.shards[si], verified[si]
            npath, plen, Cb, act, P = sh._commit_tables(v.pending.active,
                                                        v.node_paths)
            sigs.append((v.pending.pads[3], P))
            tables.append((npath, plen, Cb, act))
            caches.append(sh.tpool.cache)
        key = "gcommit_" + "_".join(f"s{si}T{t}P{p}"
                                    for si, (t, p) in zip(group, sigs))
        fn = self._jit(key, make_group_commit_step(self.shards[0].tc,
                                                   [t for t, _ in sigs]),
                       donate_argnums=0)
        with span("serve:commit"):
            out = fn(tuple(caches),
                     tuple(jnp.asarray(t[0]) for t in tables),
                     tuple(jnp.asarray(t[1]) for t in tables),
                     tuple(jnp.asarray(t[2]) for t in tables),
                     tuple(jnp.asarray(t[3]) for t in tables))
        for si, cache in zip(group, out):
            self.shards[si].tpool.cache = cache
        self._counters["commit_calls"] += 1

    def drain_pipeline(self) -> list[dict]:
        """Drain every shard's begun-ahead step (see
        BatchedSpeculativeEngine.drain_pipeline)."""
        events = []
        for si, sh in enumerate(self.shards):
            events.extend(self._collect(si, sh.drain_pipeline()))
        return events

    def abort_pipeline(self) -> int:
        """Rewind EVERY shard's begun-ahead step (each shard restores its
        own rng snapshots and pool state — ``abort_step``).  Returns how
        many shards rewound a step; with several shards begun ahead all of
        them must land, or the next boundary would replay some shards'
        randomness against others' already-consumed state."""
        return sum(sh.abort_pipeline() for sh in self.shards)

    def run(self) -> dict[int, dict]:
        """Drain all shards; returns ``{rid: {"tokens", "reason"}}`` for the
        requests completed by this call (global rids)."""
        done: dict[int, dict] = {}

        def drain():
            while self.finished:
                rid, info = self.finished.popitem()
                done[rid] = info

        drain()
        while any(sh.queue or sh.streams for sh in self.shards):
            before = len(done)
            self.step()
            drain()
            if not any(sh.queue or sh.streams for sh in self.shards):
                break
            assert any(sh.streams for sh in self.shards) or len(done) > before, \
                "sharded scheduler stalled"
        return done

    def generate_batch(self, prompts, max_new: int = 32, seeds=None) -> list[list[int]]:
        """Convenience: submit all prompts, drain, return outputs in order."""
        rids = [
            self.submit(list(p), max_new, None if seeds is None else seeds[i])
            for i, p in enumerate(prompts)
        ]
        out = self.run()
        return [out[r]["tokens"] for r in rids]

    # ------------------------------------------------------------ counters ---

    @property
    def counters(self) -> dict:
        """Work/overlap counters summed across shards, plus the engine-level
        grouped-commit counters (a grouped commit is one dispatch belonging
        to no single shard).  Read-only view; use ``reset_counters`` or the
        per-shard dicts to mutate."""
        out: dict = {}
        for sh in self.shards:
            for key, val in sh.counters.items():
                out[key] = out.get(key, type(val)()) + val
        for key, val in self._counters.items():
            out[key] = out.get(key, type(val)()) + val
        return out

    def reset_counters(self, keys) -> None:
        for sh in self.shards:
            for key in keys:
                sh.counters[key] = type(sh.counters[key])()
        for key in keys:
            if key in self._counters:
                self._counters[key] = type(self._counters[key])()

    @property
    def queue(self) -> list:
        """All shards' queued requests (routing already fixed their shard)."""
        return [req for sh in self.shards for req in sh.queue]

    @property
    def streams(self) -> dict:
        """(shard, slot) -> stream state across shards, for observability."""
        return {(si, s): st for si, sh in enumerate(self.shards)
                for s, st in sh.streams.items()}

    def pool_occupancy(self) -> dict:
        """Aggregate arena occupancy in the unsharded schema, plus the
        per-shard breakdown benchmarks surface (the whole point of the
        shard counters: a balanced scheduler shows near-equal per-shard
        peaks)."""
        per = [sh.pool_occupancy() for sh in self.shards]
        out: dict = {}
        for name in ("target", "draft"):
            shards = [p[name] for p in per if name in p]
            if not shards:
                continue
            used = sum(s["blocks_used"] for s in shards)
            out[name] = {
                "blocks_total": sum(s["blocks_total"] for s in shards),
                "blocks_used": used,
                "blocks_free": sum(s["blocks_free"] for s in shards),
                "block_size": shards[0]["block_size"],
                "fragmentation": (sum(s["fragmentation"] * s["blocks_used"]
                                      for s in shards) / used) if used else 0.0,
            }
        if out:
            out["per_shard"] = per
        return out
