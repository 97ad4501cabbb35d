"""Speculative-decoding engine: delayed-tree drafting + tree-masked target
pass + lossless verification, with optional NDE action selection.

Two target-pass strategies (DESIGN.md §Arch-applicability):

  * "tree"   — attention-based targets: one batched pass over the speculation
               block with the ancestor mask; accepted KVs are committed
               in-place (slot copy) and stale tree slots invalidated.
  * "replay" — SSM / hybrid targets: a recurrent state has no tree analogue,
               so the trunk is scored in one chunked decode, branches are
               scored by replaying from a state checkpoint (cache fork), and
               commits restore the checkpoint and re-advance along the
               accepted path.  Delayed expansion is a natural fit here: the
               trunk scan is shared and only L2 steps are replayed per branch.

Each request is an independent stream; model calls inside a stream are
batched (branch drafting/replay runs all K branches at once).  The engine is
exact: emitted tokens follow the warped target distribution for every
verifier (property-tested against the core library).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trees import DraftTree, delayed_layout, tree_ancestor_mask
from repro.core.verify import VERIFIERS, get_verifier
from repro.models.cache import fork_streams
from repro.models.transformer import forward, init_cache
from repro.sampling import draw_keyed, stream_key, warp_logits
from repro.serving.serve_step import make_pool_commit_step, next_pow2
from repro.serving.tracing import jit_named

# top-down OT verifiers with a batched on-device solve (core/otlp_jax.py) —
# derived from registry metadata, not a hand-maintained name list
TOPDOWN = frozenset(n for n, s in VERIFIERS.items() if s.on_device)

VERIFIER_DTYPE = np.float64


def to_verifier_dtype(p: np.ndarray) -> np.ndarray:
    """Cast warped target scores to the dtype the host verifiers consume.

    The ONE verifier-boundary cast shared by both engines and both
    target-pass strategies: verification compares p/q ratios against
    uniform draws in float64, and the cast must live in exactly one place —
    the replay path once hand-rolled its own and drifted (PR-2 notes), which
    a future dtype change would silently repeat."""
    return np.asarray(p, VERIFIER_DTYPE)


def verify_tree(tree: DraftTree, verifier: str, rng: np.random.Generator):
    """Host-side verifier dispatch — the single mapping both engines share,
    resolved through the core/verify.py registry, so every registered
    verifier works identically under single-stream, batched, sharded and
    pipelined serving.  Returns (accepted_tokens, correction_token)."""
    return get_verifier(verifier).verify(tree, rng)


def fork_cache(cfg, cache: dict, K: int) -> dict:
    """Replicate a single-stream cache K ways along its batch axis.

    Thin wrapper over :func:`repro.models.cache.fork_streams`, which owns the
    per-family batch-axis map (lockstep pos/len stay shared)."""
    return fork_streams(cache, K)


@dataclass
class SamplingParams:
    temperature: float = 1.0
    top_p: float = 1.0


@dataclass
class EngineConfig:
    verifier: str = "specinfer"
    K: int = 2
    L1: int = 2
    L2: int = 2
    max_cache: int = 512
    seed: int = 0
    # run OT verification as a single jitted on-device program
    # (core/otlp_jax.py) instead of host numpy — the TPU deployment path
    verify_on_device: bool = False


class SpeculativeEngine:
    def __init__(self, target_cfg, target_params, draft_cfg, draft_params, ecfg: EngineConfig,
                 sampling: SamplingParams | None = None, selector=None):
        assert target_cfg.vocab == draft_cfg.vocab
        get_verifier(ecfg.verifier)  # fail loudly on unknown names, at build time
        self.tc, self.tp = target_cfg, target_params
        self.dc, self.dp = draft_cfg, draft_params
        self.ecfg = ecfg
        self.sampling = sampling or SamplingParams()
        self.selector = selector  # callable(features) -> (K, L1, L2) or None
        self.rng = np.random.default_rng(ecfg.seed)
        self.strategy = "replay" if target_cfg.arch_type in ("ssm", "hybrid") else "tree"
        self._jit_cache: dict = {}
        # latency accounting (model-call counting for the Eq. 11 throughput model)
        self.counters = {"target_calls": 0, "target_tokens": 0, "draft_calls": 0,
                         "draft_tokens": 0, "accepted": 0, "blocks": 0}

    # ------------------------------------------------------------- helpers ---

    def _jit(self, name, fn, donate_argnums=None):
        """Per-engine jit cache; the program is named after its key
        (tracing.jit_named).  ``donate_argnums`` marks pool/cache args
        whose buffers XLA may update in place (the commit path donates the
        cache so committing is a lane-move, not a pool copy)."""
        return jit_named(self._jit_cache, name, fn, donate_argnums)

    def jit_compile_count(self) -> int:
        """Compiled signatures across this engine's jit cache — the cold-start
        compile budget bench_smoke.sh gates (one cache entry can hold several
        compilations when a name is reused across shapes/dtypes)."""
        return sum(fn._cache_size() for fn in self._jit_cache.values())

    def _warp(self, logits):
        return warp_logits(logits, self.sampling.temperature, self.sampling.top_p)

    def _draft_fn(self, T: int):
        """The draft's decode program over T tokens (jit-cached per T)."""
        return self._jit(f"draft_dec_{T}", partial(forward, cfg=self.dc, mode="decode"))

    def _draft_decode(self, cache, tokens_np):
        """Run the draft model over T committed/drafted tokens. Returns
        (warped dists (T, V) np, new cache, hidden (T, D))."""
        T = len(tokens_np)
        toks = jnp.asarray(np.asarray(tokens_np, np.int32)[None])
        logits, cache, ex = self._draft_fn(T)(self.dp, tokens=toks, cache=cache)
        self.counters["draft_calls"] += 1
        self.counters["draft_tokens"] += T
        return np.asarray(self._warp(logits[0])), cache, np.asarray(ex["hidden"][0])

    def _target_pass_tree(self, cache, tree_tokens, anc):
        T = len(tree_tokens)
        fn = self._jit(f"tgt_tree_{T}", partial(forward, cfg=self.tc, mode="tree"))
        logits, cache, ex = fn(
            self.tp,
            tokens=jnp.asarray(np.asarray(tree_tokens, np.int32)[None]),
            cache=cache,
            anc=jnp.asarray(anc[None]),
        )
        self.counters["target_calls"] += 1
        self.counters["target_tokens"] += T
        return np.asarray(self._warp(logits[0])), cache, np.asarray(ex["hidden"][0])

    def _target_decode(self, cache, tokens_np, count=True):
        T = len(tokens_np)
        fn = self._jit(f"tgt_dec_{T}", partial(forward, cfg=self.tc, mode="decode"))
        logits, cache, ex = fn(
            self.tp, tokens=jnp.asarray(np.asarray(tokens_np, np.int32)[None]), cache=cache
        )
        if count:
            self.counters["target_calls"] += 1
            self.counters["target_tokens"] += T
        return np.asarray(self._warp(logits[0])), cache, np.asarray(ex["hidden"][0])

    # -------------------------------------------------------------- stream ---

    def new_stream(self, prompt: list[int], enc_embeds=None, embeds=None,
                   seed: int | None = None) -> dict:
        """Prefill prompt[:-1] into both caches; prompt[-1] is the pending root.

        ``seed`` (default: the engine's ``EngineConfig.seed``) keys the
        stream's draft draws, as a request's seed does in the batched
        engine; the engine's rng is spent by verification.  Streams of one
        engine that share a seed share their draft draws at equal committed
        lengths, so give each stream its own."""
        assert len(prompt) >= 1
        tcache = init_cache(self.tc, 1, self.ecfg.max_cache)
        dcache = init_cache(self.dc, 1, self.ecfg.max_cache)
        kwargs_t = {}
        if self.tc.arch_type == "encdec":
            kwargs_t["enc_embeds"] = enc_embeds
        if self.tc.arch_type == "vlm" and embeds is not None:
            kwargs_t["embeds"] = embeds
        ctx = prompt[:-1]
        h_p = h_q = None
        if ctx or kwargs_t:
            fn_t = self._jit("tgt_prefill_" + str(len(ctx)), partial(forward, cfg=self.tc, mode="full"))
            _, tcache, ex_t = fn_t(
                self.tp,
                tokens=jnp.asarray(np.asarray(ctx, np.int32)[None]) if ctx else None,
                cache=tcache,
                **{k: v for k, v in kwargs_t.items()},
            )
            h_p = np.asarray(ex_t["hidden"][0, -1])
        if ctx:
            fn_d = self._jit("drf_prefill_" + str(len(ctx)), partial(forward, cfg=self.dc, mode="full"))
            _, dcache, ex_d = fn_d(
                self.dp, tokens=jnp.asarray(np.asarray(ctx, np.int32)[None]), cache=dcache
            )
            h_q = np.asarray(ex_d["hidden"][0, -1])
        d = self.tc.d_model
        dd = self.dc.d_model
        return {
            "tcache": tcache,
            "dcache": dcache,
            "committed": list(prompt),
            "pending": int(prompt[-1]),
            "draft_delta": [int(prompt[-1])],  # tokens the draft hasn't seen
            "h_prev_p": h_p if h_p is not None else np.zeros(d, np.float32),
            "h_prev_q": h_q if h_q is not None else np.zeros(dd, np.float32),
            "p_prev": None,
            "q_prev": None,
            "done": False,
            "key": stream_key(self.ecfg.seed if seed is None else seed),
        }

    # ------------------------------------------------------------ drafting ---

    def _draft_tree(self, stream, K, L1, L2):
        """Draft a (K, L1, L2)-delayed tree.  Returns (tree, root_hidden).

        Every draft token is drawn on the device by the keyed draw both
        engines share (``sampling.draw_keyed``, keyed by the stream's seed,
        the committed length and the node index of ``delayed_layout``), from
        the warped float32 q the tree records, so the engine's rng is spent
        by verification alone.  Unlike the pool's fused draft programs, the
        trunk reuses the ingest's one-token decode program: this engine is
        the reference, not the serving path."""
        t, p = self.sampling.temperature, self.sampling.top_p
        warp = self._jit("draft_warp", partial(warp_logits, temperature=t, top_p=p))
        draw = self._jit("draft_draw", draw_keyed)
        T = len(stream["draft_delta"])
        toks = jnp.asarray(np.asarray(stream["draft_delta"], np.int32)[None])
        logits, dcache, ex = self._draft_fn(T)(self.dp, tokens=toks, cache=stream["dcache"])
        self.counters["draft_calls"] += 1
        self.counters["draft_tokens"] += T
        # dcache is now committed-consistent (delta tokens are committed) —
        # persist it immediately; trunk/branch drafting below works on local
        # functional values that are simply discarded (this also keeps
        # recurrent draft states exact, which a length rollback cannot).
        stream["dcache"] = dcache
        q = warp(logits[:, -1])
        key = stream["key"][None]
        C = len(stream["committed"])
        qs, tokens = [q], [np.asarray([-1], np.int32)]
        # trunk: sequential single-token drafting
        for i in range(L1):
            tok = draw(q, key, np.asarray([C], np.int32), np.asarray([i + 1], np.int32))
            logits, dcache, _ = self._draft_fn(1)(self.dp, tokens=tok[:, None], cache=dcache)
            q = warp(logits[:, -1])
            qs.append(q)
            tokens.append(tok)
            self.counters["draft_calls"] += 1
            self.counters["draft_tokens"] += 1
        # branches: fork the draft cache K ways and roll L2 batched steps,
        # branch k's next token drawn from its newest node's q
        if K > 0 and L2 > 0:
            fork = fork_cache(self.dc, dcache, K)
            q = jnp.repeat(q, K, axis=0)
            branch = self._jit("draft_branch", partial(forward, cfg=self.dc, mode="decode"))
            for j in range(L2):
                node = np.arange(1 + L1 + j * K, 1 + L1 + (j + 1) * K, dtype=np.int32)
                tok = draw(q, np.repeat(key, K, axis=0), np.full(K, C, np.int32), node)
                logits, fork, _ = branch(self.dp, tokens=tok[:, None], cache=fork)
                q = warp(logits[:, -1])
                qs.append(q)
                tokens.append(tok)
                self.counters["draft_calls"] += 1
                self.counters["draft_tokens"] += K
        parent, depth, pid = delayed_layout(K, L1, L2)
        tree = DraftTree(
            tokens=np.concatenate([np.asarray(t) for t in tokens]).astype(np.int64),
            parent=parent,
            depth=depth,
            q=np.concatenate([np.asarray(x) for x in qs]),
            path_id=pid,
        )
        return tree, np.asarray(ex["hidden"][0, -1])

    def _rollback_len(self, cache, new_len, cfg):
        cache = dict(cache)
        if "attn" in cache:
            a = dict(cache["attn"])
            a["len"] = jnp.asarray(new_len, jnp.int32)
            cache["attn"] = a
        if "len" in cache:
            cache["len"] = jnp.asarray(new_len, jnp.int32)
        return cache

    # -------------------------------------------------------------- verify ---

    def _verify(self, tree: DraftTree):
        if self.ecfg.verify_on_device and self.ecfg.verifier in TOPDOWN:
            return self._verify_jax(tree, self.ecfg.verifier)
        return verify_tree(tree, self.ecfg.verifier, self.rng)

    def _verify_jax(self, tree: DraftTree, solver: str):
        """On-device whole-tree verification (core/otlp_jax)."""
        from repro.core.otlp_jax import verify_topdown_jax

        N = tree.n_nodes
        max_depth = int(tree.max_depth()) + 1
        max_children = max(self.ecfg.K, 1)
        key = jax.random.PRNGKey(int(self.rng.integers(2**31)))
        out_tok, n_acc, corr = verify_topdown_jax(
            jnp.asarray(tree.tokens.astype(np.int32)),
            jnp.asarray(tree.parent.astype(np.int32)),
            jnp.asarray(tree.p.astype(np.float32)),
            jnp.asarray(tree.q.astype(np.float32)),
            key,
            solver=solver,
            max_depth=max_depth,
            max_children=max_children,
        )
        n = int(n_acc)
        return [int(t) for t in np.asarray(out_tok)[:n]], int(corr)

    @staticmethod
    def _accepted_nodes(tree: DraftTree, accepted: list[int]) -> list[int]:
        """Map the accepted token path -> node indices along the tree.

        Duplicate drafted nodes share a context (and hence KVs/positions), so
        the active *set* is tracked and the first representative is recorded.
        """
        nodes = []
        active = [0]
        for t in accepted:
            kids = [
                i
                for i in range(tree.n_nodes)
                if tree.parent[i] in active and int(tree.tokens[i]) == t
            ]
            nodes.append(kids[0])
            active = kids
        return nodes

    # ------------------------------------------------------------- commits ---

    def _commit_tree_cache(self, cache, C, node_path, T):
        """Copy accepted tree KVs into contiguous committed slots and
        invalidate the remaining tree slots — routed through the same fused
        primitive as the batched engine (serve_step.make_pool_commit_step):
        one jitted, cache-donating call per commit instead of eager
        ``.at[].set`` chains that each copy the whole cache."""
        P = next_pow2(max(1, len(node_path)))
        path = np.zeros((P,), np.int32)
        path[: len(node_path)] = node_path
        fn = self._jit(
            f"commit_T{T}_P{P}", make_pool_commit_step(self.tc, T), donate_argnums=0
        )
        return fn(cache, jnp.asarray(path), np.int32(len(node_path)), np.int32(C))

    # ---------------------------------------------------------------- step ---

    def choose_action(self, stream, q0=None, h_cur_q=None):
        if self.selector is None:
            return self.ecfg.K, self.ecfg.L1, self.ecfg.L2
        return self.selector(stream, self)

    def step(self, stream) -> list[int]:
        """One speculative decoding iteration; returns newly committed tokens."""
        K, L1, L2 = self.choose_action(stream)
        tree, h_cur_q = self._draft_tree(stream, K, L1, L2)
        C = len(stream["committed"]) - 1  # processed target tokens
        T = tree.n_nodes
        tree_tok = tree.tokens.copy()
        tree_tok[0] = stream["pending"]
        anc = tree_ancestor_mask(tree.parent)

        if self.strategy == "tree":
            p_dists, tcache, hid = self._target_pass_tree(stream["tcache"], tree_tok, anc)
            tree.p = to_verifier_dtype(p_dists)
            accepted, corr = self._verify(tree)
            node_path = self._accepted_nodes(tree, accepted)
            stream["tcache"] = self._commit_tree_cache(tcache, C, node_path, T)
            last_node = node_path[-1] if node_path else 0
            stream["h_prev_p"] = hid[last_node]
        else:
            accepted, corr, hid_last = self._verify_replay(stream, tree, tree_tok)
            stream["h_prev_p"] = hid_last

        stream["p_prev"] = tree.p[self._accepted_nodes(tree, accepted)[-1]] if accepted else tree.p[0]
        stream["q_prev"] = tree.q[self._accepted_nodes(tree, accepted)[-1]] if accepted else tree.q[0]
        new_tokens = list(accepted) + [int(corr)]
        stream["committed"].extend(new_tokens)
        stream["pending"] = int(corr)
        stream["draft_delta"] = new_tokens
        stream["h_prev_q"] = h_cur_q
        self.counters["accepted"] += len(accepted)
        self.counters["blocks"] += 1
        return new_tokens

    # -------------------------------------------------- replay (SSM/hybrid) --

    def _verify_replay(self, stream, tree: DraftTree, tree_tok):
        """Target pass for recurrent targets: trunk decode + branch replay."""
        from repro.core.traversal import delayed_structure

        trunk, broot, branches = delayed_structure(tree)
        snapshot = stream["tcache"]  # committed checkpoint (functional arrays)
        trunk_tokens = [int(tree_tok[0])] + [int(tree.tokens[v]) for v in trunk]
        p_seq, cache_after_trunk, hid = self._target_decode(snapshot, trunk_tokens)
        p = np.zeros((tree.n_nodes, tree.vocab), VERIFIER_DTYPE)
        p[0] = p_seq[0]
        for i, v in enumerate(trunk):
            p[v] = p_seq[i + 1]
        if branches:
            K = len(branches)
            L2 = len(branches[0])
            fork = fork_cache(self.tc, cache_after_trunk, K)
            btoks = np.asarray(
                [[int(tree.tokens[v]) for v in path] for path in branches], np.int32
            )
            fn = self._jit(f"tgt_branch_{L2}", partial(forward, cfg=self.tc, mode="decode"))
            logits, _, _ = fn(self.tp, tokens=jnp.asarray(btoks), cache=fork)
            self.counters["target_calls"] += 1
            self.counters["target_tokens"] += K * L2
            pb = np.asarray(self._warp(logits))
            for k, path in enumerate(branches):
                for j, v in enumerate(path):
                    p[v] = pb[k, j]
        tree.p = p
        accepted, corr = self._verify(tree)
        # commit: restore the checkpoint and advance along [root] + accepted
        node_path = self._accepted_nodes(tree, accepted)
        commit_toks = [int(tree_tok[0])] + [int(t) for t in accepted]
        _, new_cache, hid2 = self._target_decode(snapshot, commit_toks, count=False)
        stream["tcache"] = new_cache
        return accepted, int(corr), hid2[-1]

    # ------------------------------------------------------- distribution peeks

    def peek_draft_dist(self, stream, ctx: list[int]) -> np.ndarray:
        """q(. | committed + ctx) without mutating the stream (functional)."""
        toks = list(stream["draft_delta"]) + list(ctx)
        dists, _, _ = self._draft_decode(stream["dcache"], toks)
        return dists[-1]

    def peek_target_dist(self, stream, ctx: list[int]) -> np.ndarray:
        """p(. | committed + ctx) without mutating the stream."""
        toks = [stream["pending"]] + list(ctx)
        dists, _, _ = self._target_decode(stream["tcache"], toks)
        return dists[-1]

    # ------------------------------------------------------------ generate ---

    def generate(self, prompt: list[int], max_new: int = 64, **kw) -> list[int]:
        stream = self.new_stream(prompt, **kw)
        out: list[int] = []
        while len(out) < max_new:
            out.extend(self.step(stream))
        return out[:max_new]
