"""Speculative-decoding serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
        --verifier specinfer --K 2 --L1 2 --L2 2 --requests 4 --max-new 32

Builds a (reduced) target + a proportionally smaller draft of the same
family, serves a batch of synthetic requests through the speculative engine,
and reports block efficiency + the Eq. 11 modelled throughput.

``--streams N`` switches to the continuous-batching engine: an N-slot KV
pool with FIFO admission, so requests beyond N queue and are admitted as
slots free up — every model call advances all resident streams at once.
Batched serving steps pipelined by default (each step's host verify/retire
tail overlaps the next step's dispatched device work, token-identically);
``--no-pipeline`` restores strictly sequential steps.

``--data-shards N`` splits the pool's stream axis into N shard engines
(shard-local slots, block arenas, admission queues; pool arrays committed
to the mesh data axis) under a least-loaded scheduler — token-identical to
the unsharded pool for the same arrival order.

``--attention-impl pallas`` routes attention through the Pallas kernels
(compiled to Mosaic on a TPU, interpreted on the CPU backend).  The report
names the device it ran on; JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` at the root of
the checkout (``setup_compile_cache``).
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.core.verify import verifier_names
from repro.launch.mesh import shard_meshes
from repro.models.transformer import init_params
from repro.serving.batch_engine import (
    BatchedSpeculativeEngine,
    ShardedBatchedSpeculativeEngine,
)
from repro.serving.engine import EngineConfig, SamplingParams, SpeculativeEngine


def make_draft_cfg(cfg):
    """A ~10x smaller draft of the same family (paper: ~9:1 .. 100:1)."""
    if cfg.arch_type == "ssm":
        return cfg.replace(name=cfg.name + "-draft", n_layers=max(cfg.n_layers // 4, 1),
                           d_model=max(cfg.d_model // 2, 64))
    if cfg.arch_type == "hybrid":
        nl = max((cfg.n_layers // cfg.hybrid_attn_every) // 2 * cfg.hybrid_attn_every, cfg.hybrid_attn_every)
        return cfg.replace(name=cfg.name + "-draft", n_layers=nl,
                           d_model=max(cfg.d_model // 2, 64),
                           lru_width=max(cfg.lru_d // 2, 64),
                           d_ff=max(cfg.d_ff // 2, 64))
    kw = dict(
        name=cfg.name + "-draft",
        n_layers=max(cfg.n_layers // 4, 1),
        d_model=max(cfg.d_model // 2, 64),
        d_ff=max(cfg.d_ff // 2, 64),
        n_heads=max(cfg.n_heads // 2, 1),
        n_kv_heads=max(cfg.n_kv_heads // 2, 1),
    )
    if cfg.head_dim:
        kw["head_dim"] = cfg.head_dim
    if cfg.arch_type == "moe":
        kw["n_experts"] = max(cfg.n_experts // 2, 2)
        kw["top_k"] = min(cfg.top_k, max(cfg.n_experts // 2, 2))
    if cfg.arch_type == "encdec":
        kw["n_enc_layers"] = max(cfg.n_enc_layers // 4, 1)
    return cfg.replace(**kw)


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it: ``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache``
    at the root of the checkout (git-ignored).  A fixed path matters: the
    directory is part of every entry's key, so a path holding a temporary
    name, a process id or a time would never hit."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_label() -> str:
    """The device the run is on, as JAX reports it: platform, kind, count."""
    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind} x{len(devs)}"


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI surface, exposed for tests: every registry verifier
    must round-trip through ``--verifier`` (tests/test_verifiers.py)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--verifier", default="specinfer", choices=verifier_names(),
                    help="verification algorithm (core/verify.py registry; "
                         "single-path verifiers bv/naive_single need --K 1)")
    ap.add_argument("--K", type=int, default=2)
    ap.add_argument("--L1", type=int, default=2)
    ap.add_argument("--L2", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=0,
                    help="continuous batching: serve through an N-slot cache pool "
                         "(0 = sequential single-stream engine)")
    ap.add_argument("--data-shards", type=int, default=1,
                    help="shard the pool's stream axis across the mesh data "
                         "axis: N shard engines with shard-local slots, block "
                         "arenas and admission queues under a least-loaded "
                         "scheduler (1 = the unsharded pool)")
    ap.add_argument("--block-size", type=int, default=64,
                    help="paged KV pool block size in tokens (rounded down to "
                         "the nearest power of two dividing max_cache)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="total arena blocks shared by all streams (0 = "
                         "ring-equivalent capacity, streams * max_cache/block)")
    ap.add_argument("--ring", action="store_true",
                    help="disable the paged KV pool and reserve a full "
                         "max_cache ring per stream (the PR-1 layout)")
    ap.add_argument("--pipeline", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="pipelined stepping: overlap each step's host "
                         "verify/retire tail with the next step's dispatched "
                         "device work (token-identical; --no-pipeline "
                         "restores strictly sequential steps)")
    ap.add_argument("--attention-impl", default="xla", choices=["xla", "pallas"],
                    help="attention route of target and draft: XLA einsums, "
                         "or the Pallas kernels (kernels/)")
    ap.add_argument("--ragged", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="ragged node-major tree batching: dispatch the tree "
                         "pass as one flat node buffer with per-stream "
                         "offsets whenever that is smaller than the padded "
                         "(slots, Tpad) block (token-identical; --no-ragged "
                         "pins the padded row-major layout)")
    return ap


def build_models(args):
    """Target and draft configs plus seeded random params for the CLI args:
    the published config (or its ``--smoke`` preset) and the proportional
    draft of ``make_draft_cfg``, both on the ``--attention-impl`` route."""
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(attention_impl=args.attention_impl)
    dcfg = make_draft_cfg(cfg)
    tp = init_params(cfg, jax.random.PRNGKey(args.seed))
    dp = init_params(dcfg, jax.random.PRNGKey(args.seed + 1))
    return cfg, tp, dcfg, dp


def build_engine(args, cfg, tp, dcfg, dp, devices=None):
    """The engine the CLI args select: the continuous-batching pool
    (``--streams N``; split into shard engines by ``--data-shards``, placed
    round-robin on ``devices``, by default every local device) or the
    single-stream engine (``--streams 0``), seeded by ``--seed``."""
    ecfg = EngineConfig(verifier=args.verifier, K=args.K, L1=args.L1, L2=args.L2,
                        max_cache=1024, seed=args.seed)
    sampling = SamplingParams(args.temperature, args.top_p)
    if not args.streams:
        return SpeculativeEngine(cfg, tp, dcfg, dp, ecfg, sampling)
    kw = dict(n_slots=args.streams, paged=not args.ring,
              block_size=args.block_size, pool_blocks=args.pool_blocks or None,
              pipeline=args.pipeline, ragged=args.ragged)
    if args.data_shards > 1:
        return ShardedBatchedSpeculativeEngine(
            cfg, tp, dcfg, dp, ecfg, sampling, data_shards=args.data_shards,
            meshes=shard_meshes(args.data_shards, devices=devices), **kw)
    return BatchedSpeculativeEngine(cfg, tp, dcfg, dp, ecfg, sampling, **kw)


def main(argv=None):
    args = build_parser().parse_args(argv)
    setup_compile_cache()
    cfg, tp, dcfg, dp = build_models(args)
    eng = build_engine(args, cfg, tp, dcfg, dp)
    rng = np.random.default_rng(args.seed)

    if args.streams:
        t0 = time.time()
        rids = [
            eng.submit(rng.integers(0, cfg.vocab, size=8).tolist(),
                       max_new=args.max_new, seed=args.seed + r)
            for r in range(args.requests)
        ]
        outs = eng.run()
        for r, rid in enumerate(rids):
            out = outs[rid]["tokens"]
            print(f"req{r}: {out[:16]}{'...' if len(out) > 16 else ''}")
        dt = time.time() - t0
        c = eng.counters
        be = c["accepted"] / max(c["blocks"], 1) + 1
        pool = "ring" if not eng.paged else (
            f"paged(block={eng.block_size}, arena={eng.pool_blocks} blocks, "
            f"peak={c['blocks_peak']} used, reclaimed={c['blocks_reclaimed']})"
        )
        stepping = (
            f"pipelined(ahead={c['pipeline_ahead']}, stalls={c['pipeline_stalls']}"
            f"/{c['pipeline_iterations']} iters)"
            if args.pipeline else "sync"
        )
        if args.data_shards > 1:
            per = [sh.counters["blocks_peak"] for sh in eng.shards]
            # grouped commits are engine-level dispatches (no single shard
            # owns them); surfacing them shows the cross-shard batching
            grouped = eng._counters["commit_calls"]
            stepping += (f" shards={args.data_shards}"
                         f"(x{eng.n_slots // args.data_shards} slots, "
                         f"peaks={per}, commits={c['commit_calls']} "
                         f"of which {grouped} grouped)")
        print(
            f"\n[batched x{args.streams}] verifier={args.verifier} "
            f"({args.K},{args.L1},{args.L2}) block_efficiency={be:.3f} "
            f"target_calls={c['target_calls']} draft_tokens={c['draft_tokens']} "
            f"evicted={c['evicted']} pool={pool} stepping={stepping} "
            f"wall={dt:.1f}s "
            f"tokens/s={sum(len(o['tokens']) for o in outs.values()) / dt:.2f} "
            f"device={device_label()}"
        )
        return

    t0 = time.time()
    kw = {}
    if cfg.arch_type == "encdec":
        import jax.numpy as jnp
        kw["enc_embeds"] = jnp.asarray(rng.standard_normal((1, cfg.enc_len, cfg.d_model)), cfg.jdtype)
    for r in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, size=8).tolist()
        out = eng.generate(prompt, max_new=args.max_new, **kw)
        print(f"req{r}: {out[:16]}{'...' if len(out) > 16 else ''}")
    dt = time.time() - t0
    c = eng.counters
    be = c["accepted"] / max(c["blocks"], 1) + 1
    print(
        f"\nverifier={args.verifier} ({args.K},{args.L1},{args.L2}) "
        f"block_efficiency={be:.3f} target_calls={c['target_calls']} "
        f"draft_tokens={c['draft_tokens']} wall={dt:.1f}s "
        f"tokens/s={args.requests * args.max_new / dt:.2f} device={device_label()}"
    )


if __name__ == "__main__":
    main()
