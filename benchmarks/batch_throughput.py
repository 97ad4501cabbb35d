"""Continuous-batching throughput: batched pool engine vs. sequential loop,
with the pipelined stepping mode measured against both.

    PYTHONPATH=src python benchmarks/batch_throughput.py [--arch granite-8b]
        [--batch-sizes 1,4,8] [--max-new 24] [--verifier specinfer]
        [--ring] [--block-size 64] [--coresidency] [--heterogeneous]
        [--no-pipeline] [--no-ragged] [--data-shards 2]
        [--json BENCH_batch_throughput.json]

For each batch size N, serves N synthetic requests three ways:

  * sequential — one ``SpeculativeEngine``, requests one after another (the
    pre-batching serving path: throughput == single-stream latency);
  * batched    — ``BatchedSpeculativeEngine`` with an N-slot pool: every
    draft/target call advances all N streams;
  * pipelined  — the same engine with ``pipeline=True``: each step's host
    verify/retire tail overlaps the next step's dispatched device work
    (skipped with ``--no-pipeline``).

Reported tokens/sec is aggregate (all requests' emitted tokens / wall).
Wall-clock excludes compilation: each engine first runs the whole workload
untimed (populating its jit cache for every shape bucket the workload
hits), then the timed pass re-runs it — so the comparison prices the
steady-state serving loop.  The warmup pass doubles as the occupancy
probe; both passes run unblocked, so commit dispatches overlap host work
exactly as they do in production for BOTH stepping modes.  The batched and pipelined timed reps are interleaved in
alternating order (``_interleaved_timed``) so machine drift cannot
masquerade as a stepping-mode difference.  Outputs are seeded
identically, so the batched and pipelined columns also re-check the
exactness contract while they measure.

``--json`` writes the machine-readable ``BENCH_batch_throughput.json``
document (benchmarks/common.py ``write_bench_json``) that
scripts/bench_smoke.sh gates CI on and benchmarks/baselines/ archives.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

try:
    from benchmarks.common import write_bench_json
except ImportError:  # executed as a script: benchmarks/ itself is sys.path[0]
    from common import write_bench_json

from repro.configs import get_smoke
from repro.launch.serve import make_draft_cfg
from repro.models.transformer import init_params
from repro.serving.batch_engine import (
    BatchedSpeculativeEngine,
    ShardedBatchedSpeculativeEngine,
)
from repro.serving.engine import EngineConfig, SamplingParams, SpeculativeEngine


def _prompts(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=6).tolist() for _ in range(n)]


def _best_timed(workload, reps):
    """Minimum wall-clock over ``reps`` repeats of a deterministic workload.
    The tiny smoke configs finish in fractions of a second, where scheduler
    noise swamps single-shot timings; the minimum is the standard low-noise
    estimator (cf. ``timeit``) because interruptions — GC, page faults,
    noisy CI neighbours — only ever ADD time to a deterministic run."""
    times, outs = [], None
    for _ in range(reps):
        t0 = time.time()
        outs = workload()
        times.append(time.time() - t0)
    return outs, min(times)


def _interleaved_timed(workloads, reps):
    """Time several workloads rep-by-rep in alternating order (the order
    flips every round).  Sequential per-mode timing lets slow machine drift
    (thermal throttling, noisy neighbours) land entirely on whichever mode
    runs last — exactly the bias that made the pipelined column look slower
    than batched.  Interleaving spreads drift across all modes and the
    per-mode minimum (see ``_best_timed``) discards what noise remains.
    Returns ``{name: (outs, best_secs)}``."""
    times = {name: [] for name in workloads}
    outs = {}
    for rnd in range(reps):
        order = list(workloads)
        if rnd % 2:
            order.reverse()
        for name in order:
            t0 = time.time()
            outs[name] = workloads[name]()
            times[name].append(time.time() - t0)
    return {name: (outs[name], min(times[name])) for name in workloads}


def run_sequential(cfg, tp, dcfg, dp, ecfg, sampling, prompts, max_new, seeds, reps=1):
    eng = SpeculativeEngine(cfg, tp, dcfg, dp, ecfg, sampling)

    def workload():
        outs = []
        for p, sd in zip(prompts, seeds):
            eng.rng = np.random.default_rng(sd)
            outs.append(eng.generate(list(p), max_new=max_new))
        return outs

    t0 = time.time()
    workload()  # warm every shape the workload compiles
    warm = {"warmup_secs": time.time() - t0,
            "compile_count": eng.jit_compile_count()}
    return (*_best_timed(workload, reps), warm)


_OVERLAP_KEYS = ("pipeline_ahead", "pipeline_stalls", "pipeline_iterations")
_WARM_KEYS = ("commit_calls", "blocks_reclaimed", "blocks_peak") + _OVERLAP_KEYS


def prepare_batched(cfg, tp, dcfg, dp, ecfg, sampling, prompts, max_new, seeds,
                    paged=True, block_size=64, pipeline=False, data_shards=1,
                    ragged=True, selector=None):
    """Build a batched (or sharded) engine, run the warmup pass and return
    ``(eng, workload, commit_stats, peak_occ)`` ready for timing.

    The warmup pass compiles every shape bucket, counts commits and probes
    pool occupancy whenever the used-block peak advances.  The workload
    repeats deterministically, so the warmup's commit count and peak
    occupancy are the timed pass's too."""
    if data_shards > 1:
        eng = ShardedBatchedSpeculativeEngine(
            cfg, tp, dcfg, dp, ecfg, sampling, selector=selector,
            n_slots=len(prompts), data_shards=data_shards, paged=paged,
            block_size=block_size, pipeline=pipeline, ragged=ragged)
    else:
        eng = BatchedSpeculativeEngine(cfg, tp, dcfg, dp, ecfg, sampling,
                                       selector=selector, n_slots=len(prompts),
                                       paged=paged, block_size=block_size,
                                       pipeline=pipeline, ragged=ragged)
    engines = eng.shards if data_shards > 1 else [eng]

    def workload():
        # per-pass units: the reported overlap counters describe ONE
        # workload pass, like the commit/occupancy numbers they sit next to
        eng.reset_counters(_OVERLAP_KEYS)
        rids = [eng.submit(list(p), max_new=max_new, seed=sd) for p, sd in zip(prompts, seeds)]
        outs = eng.run()
        return [outs[r]["tokens"] for r in rids]

    t0 = time.time()
    for p, sd in zip(prompts, seeds):
        eng.submit(list(p), max_new=max_new, seed=sd)
    peak = {"blocks": -1, "occ": {}}
    while eng.queue or eng.streams:
        eng.step()
        occ = eng.pool_occupancy()
        if occ and occ["target"]["blocks_used"] >= peak["blocks"]:
            peak = {"blocks": occ["target"]["blocks_used"], "occ": occ}
    eng.finished.clear()
    # cold-start compile budget: the warmup pass IS the compile phase (the
    # timed pass recompiles nothing), so its wall and the jit-cache census
    # after it are the numbers the bench_smoke compile-hygiene gate tracks
    warm = {"warmup_secs": time.time() - t0,
            "compile_count": eng.jit_compile_count()}
    commit_stats = {k: eng.counters[k] for k in
                    ("commit_calls", "blocks_peak", "blocks_reclaimed")}
    # the per-shard peaks tell the scheduler-balance story the aggregate hides
    commit_stats["shard_blocks_peak"] = (
        [e.counters["blocks_peak"] for e in engines] if data_shards > 1 else None)
    # zero the warmup's tallies so the timed pass reports its own
    eng.reset_counters(_WARM_KEYS)
    return eng, workload, commit_stats, peak["occ"], warm


def run_batched(cfg, tp, dcfg, dp, ecfg, sampling, prompts, max_new, seeds,
                paged=True, block_size=64, pipeline=False, reps=1, data_shards=1,
                ragged=True):
    eng, workload, commit_stats, occ, _ = prepare_batched(
        cfg, tp, dcfg, dp, ecfg, sampling, prompts, max_new, seeds,
        paged=paged, block_size=block_size, pipeline=pipeline,
        data_shards=data_shards, ragged=ragged)
    outs, dt = _best_timed(workload, reps)
    counters = dict(eng.counters)
    counters.update(commit_stats)  # the warmup pass's commit and block numbers
    return outs, dt, counters, occ


def run_coresidency(cfg, tp, dcfg, dp, ecfg, sampling, seed, block_size=16):
    """The paged pool's headline scenario: 1 long + 7 short streams share an
    arena strictly smaller than TWO per-stream rings — HBM in which the ring
    layout could hold at most the long stream alone."""
    smax = ecfg.max_cache
    # size the arena from the block size the engine will actually use
    bs = BatchedSpeculativeEngine.normalize_block_size(smax, block_size)
    pool_blocks = (2 * smax) // bs - 1  # < 2 rings of HBM
    eng = BatchedSpeculativeEngine(cfg, tp, dcfg, dp, ecfg, sampling, n_slots=8,
                                   paged=True, block_size=bs, pool_blocks=pool_blocks)
    rng = np.random.default_rng(seed)
    long_max = max(16, smax // 2 - 12)  # the long stream spans many blocks
    eng.submit(rng.integers(0, cfg.vocab, size=12).tolist(), max_new=long_max, seed=seed)
    for i in range(7):
        eng.submit(rng.integers(0, cfg.vocab, size=4).tolist(), max_new=4, seed=seed + 1 + i)
    peak_resident, peak_occ = 0, {}
    while eng.queue or eng.streams:
        eng.step()
        if len(eng.streams) >= peak_resident:
            peak_resident = len(eng.streams)
            occ = eng.pool_occupancy()
            if occ:
                peak_occ = occ["target"]
    ring_fit = (pool_blocks * eng.block_size) // smax
    print(f"\n[coresidency] arena={pool_blocks} blocks x {eng.block_size} tokens "
          f"(= {pool_blocks * eng.block_size} slots, ring layout fits {ring_fit} "
          f"stream{'s' if ring_fit != 1 else ''} of Smax={smax})")
    print(f"  co-resident streams (peak): {peak_resident}  "
          f"blocks used at peak: {peak_occ.get('blocks_used', '?')}/{pool_blocks}  "
          f"fragmentation: {peak_occ.get('fragmentation', 0.0):.2f}  "
          f"reclaimed: {eng.counters['blocks_reclaimed']}  "
          f"evicted: {eng.counters['evicted']}")
    assert peak_resident >= 8, "expected the paged pool to co-host all 8 streams"
    return peak_resident, ring_fit


def run_heterogeneous(cfg, tp, dcfg, dp, ecfg, sampling, seed, max_new=16,
                      block_size=64, reps=5, json_path=None):
    """The ragged layout's headline scenario: ONE stream on an aggressive
    NDE action co-resident with 7 thin trees.

    A selector keyed on stream CONTENT (the first committed token — stable
    across engines and shard assignments) gives stream 0 a (4, 2, 4) action
    (19-node trees) and everyone else (1, 1, 0) (2-node trees).  Under the
    padded layout the pool-wide power-of-two bucket follows the single
    aggressive stream, so every thin tree ships Tpad = 19 lanes; the ragged
    layout ships the flat node total instead.  Both layouts run the same
    prompts/seeds and must agree token-for-token (the exactness contract);
    timing is interleaved like the batched/pipelined comparison.  The
    ``pad_fraction`` gap and the throughput ratio here are what
    scripts/bench_smoke.sh gates (``BENCH_batch_throughput_hetero.json``)."""
    n = 8
    aggressive, thin = (4, 2, 8), (1, 1, 0)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab, size=6).tolist() for _ in range(n)]
    for i, p in enumerate(prompts):
        p[0] = 1 if i == 0 else 0  # the selector's content key
    seeds = [seed + 100 + i for i in range(n)]

    def selector(stream, eng):
        return aggressive if stream["committed"][0] == 1 else thin

    def build(ragged):
        eng = BatchedSpeculativeEngine(cfg, tp, dcfg, dp, ecfg, sampling,
                                       selector=selector, n_slots=n, paged=True,
                                       block_size=block_size, ragged=ragged)

        def workload():
            rids = [eng.submit(list(p), max_new=max_new, seed=sd)
                    for p, sd in zip(prompts, seeds)]
            outs = eng.run()
            return [outs[r]["tokens"] for r in rids]

        workload()  # warm every shape bucket the selector mix hits
        eng.reset_counters(("pad_nodes_total", "tree_lanes_total"))
        return eng, workload

    eng_pad, wl_pad = build(False)
    eng_rag, wl_rag = build("always")
    timed = _interleaved_timed({"padded": wl_pad, "ragged": wl_rag}, reps)
    outs_pad, dt_pad = timed["padded"]
    outs_rag, dt_rag = timed["ragged"]
    exact = outs_pad == outs_rag
    tok = sum(len(o) for o in outs_pad)

    def pad_frac(eng):
        c = eng.counters
        return c["pad_nodes_total"] / max(c["tree_lanes_total"], 1)

    pf_pad, pf_rag = pad_frac(eng_pad), pad_frac(eng_rag)
    print(f"\n[heterogeneous] 1 stream @ {aggressive} + {n - 1} @ {thin}, "
          f"max_new={max_new}")
    print(f"  {'layout':>8} {'tok/s':>10} {'pad_fraction':>13} "
          f"{'pad_nodes':>10} {'tree_lanes':>11}")
    for name, dt, eng in (("padded", dt_pad, eng_pad), ("ragged", dt_rag, eng_rag)):
        c = eng.counters
        print(f"  {name:>8} {tok / dt:>10.2f} {pad_frac(eng):>13.3f} "
              f"{c['pad_nodes_total']:>10} {c['tree_lanes_total']:>11}")
    print(f"  exact={'yes' if exact else 'NO'}  "
          f"ragged/padded throughput: {dt_pad / dt_rag:.2f}x  "
          f"pad_fraction {pf_pad:.3f} -> {pf_rag:.3f}")
    assert exact, "ragged layout diverged from padded on the heterogeneous mix"
    row = {
        "scenario": "heterogeneous",
        "streams": n,
        "aggressive_action": list(aggressive),
        "thin_action": list(thin),
        "max_new": max_new,
        "tokens": tok,
        "exact": bool(exact),
        "tokens_per_sec": {"padded": tok / dt_pad, "ragged": tok / dt_rag},
        "throughput_ratio_ragged_vs_padded": dt_pad / dt_rag,
        "pad_fraction": {"padded": pf_pad, "ragged": pf_rag},
        "pad_nodes_total": {"padded": eng_pad.counters["pad_nodes_total"],
                            "ragged": eng_rag.counters["pad_nodes_total"]},
        "tree_lanes_total": {"padded": eng_pad.counters["tree_lanes_total"],
                             "ragged": eng_rag.counters["tree_lanes_total"]},
    }
    if json_path:
        write_bench_json(json_path, "batch_throughput_hetero",
                         {"arch": cfg.name, "verifier": ecfg.verifier,
                          "streams": n, "aggressive_action": list(aggressive),
                          "thin_action": list(thin), "max_new": max_new,
                          "block_size": block_size, "max_cache": ecfg.max_cache,
                          "seed": seed}, [row])
        print(f"wrote {json_path}")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--batch-sizes", default="1,4,8")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--verifier", default="specinfer")
    ap.add_argument("--K", type=int, default=2)
    ap.add_argument("--L1", type=int, default=1)
    ap.add_argument("--L2", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ring", action="store_true",
                    help="benchmark the PR-1 per-stream ring pool instead of paged")
    ap.add_argument("--block-size", type=int, default=64)
    ap.add_argument("--data-shards", type=int, default=1,
                    help="run the batched/pipelined columns through the "
                         "sharded engine (N shard-local pools on the mesh "
                         "data axis); per-shard occupancy is reported and "
                         "the exactness column still pins outputs to the "
                         "sequential engine")
    ap.add_argument("--coresidency", action="store_true",
                    help="run the long+short co-residency scenario instead of "
                         "the throughput sweep")
    ap.add_argument("--heterogeneous", action="store_true",
                    help="run the adversarial padding-waste scenario (one "
                         "aggressive-action stream + 7 thin trees, padded vs "
                         "ragged layout) instead of the throughput sweep")
    ap.add_argument("--ragged", default=True, action=argparse.BooleanOptionalAction,
                    help="ragged node-major tree dispatch for the batched/"
                         "pipelined columns (auto: ragged whenever the flat "
                         "node buffer beats the padded lane count; "
                         "--no-ragged pins the padded layout)")
    ap.add_argument("--pipeline", default=True, action=argparse.BooleanOptionalAction,
                    help="also measure the pipelined stepping mode "
                         "(--no-pipeline skips that column)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_batch_throughput.json document here")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed repetitions per mode; the reported wall is "
                         "the per-mode minimum (smoke configs are sub-second, "
                         "where single-shot timings are scheduler noise and "
                         "interruptions only ever add time)")
    args = ap.parse_args(argv)

    cfg = get_smoke(args.arch)
    dcfg = make_draft_cfg(cfg)
    tp = init_params(cfg, jax.random.PRNGKey(args.seed))
    dp = init_params(dcfg, jax.random.PRNGKey(args.seed + 1))
    ecfg = EngineConfig(verifier=args.verifier, K=args.K, L1=args.L1, L2=args.L2,
                        max_cache=256, seed=args.seed)
    sampling = SamplingParams()

    if args.coresidency:
        run_coresidency(cfg, tp, dcfg, dp, ecfg, sampling, args.seed,
                        block_size=min(args.block_size, 16))
        return []

    if args.heterogeneous:
        print(f"arch={args.arch}(smoke) verifier={args.verifier} "
              f"scenario=heterogeneous")
        run_heterogeneous(cfg, tp, dcfg, dp, ecfg, sampling, args.seed,
                          max_new=args.max_new, block_size=args.block_size,
                          reps=args.reps, json_path=args.json)
        return []

    sizes = [int(s) for s in args.batch_sizes.split(",")]
    pool = "ring" if args.ring else f"paged(block={args.block_size})"
    if args.data_shards > 1:
        pool += f" x {args.data_shards} shards"
    print(f"arch={args.arch}(smoke) verifier={args.verifier} "
          f"action=({args.K},{args.L1},{args.L2}) max_new={args.max_new} pool={pool}")
    header = f"{'batch':>5} {'seq tok/s':>10} {'batched tok/s':>14}"
    if args.pipeline:
        header += f" {'pipelined tok/s':>16} {'pipe/sync':>9}"
    print(header + f" {'exact':>6}")
    rows, json_rows = [], []
    for n in sizes:
        prompts = _prompts(n, cfg.vocab, args.seed)
        seeds = [args.seed + 100 + i for i in range(n)]
        outs_s, dt_s, warm_s = run_sequential(cfg, tp, dcfg, dp, ecfg, sampling,
                                              prompts, args.max_new, seeds, reps=args.reps)
        # build + warm both stepping modes first, then time them with reps
        # interleaved — the batched-vs-pipelined comparison is the headline
        # number, so it must not absorb machine drift as a mode difference
        eng_b, wl_b, counters, occ, warm_b = prepare_batched(
            cfg, tp, dcfg, dp, ecfg, sampling, prompts, args.max_new, seeds,
            paged=not args.ring, block_size=args.block_size,
            data_shards=args.data_shards, ragged=args.ragged)
        workloads = {"batched": wl_b}
        eng_p, warm_p = None, {}
        if args.pipeline:
            eng_p, wl_p, pcommit, _, warm_p = prepare_batched(
                cfg, tp, dcfg, dp, ecfg, sampling, prompts, args.max_new, seeds,
                paged=not args.ring, block_size=args.block_size, pipeline=True,
                data_shards=args.data_shards, ragged=args.ragged)
            workloads["pipelined"] = wl_p
        timed = _interleaved_timed(workloads, args.reps)
        outs_b, dt_b = timed["batched"]
        counters.update({k: eng_b.counters[k] for k in _OVERLAP_KEYS})
        # padding-waste accounting for the tree pass (warmup + timed passes
        # of the same deterministic workload, so the FRACTION is per-pass)
        pad_nodes = eng_b.counters["pad_nodes_total"]
        tree_lanes = eng_b.counters["tree_lanes_total"]
        pad_fraction = pad_nodes / max(tree_lanes, 1)
        shard_pad_fraction = (
            [sh.counters["pad_nodes_total"] / max(sh.counters["tree_lanes_total"], 1)
             for sh in eng_b.shards] if args.data_shards > 1 else None)
        # actual emitted tokens (an evicted request returns fewer than
        # max_new); the exactness checks below pin all modes to this count
        tok = sum(len(o) for o in outs_s)
        exact = all(a == b for a, b in zip(outs_s, outs_b))
        dt_p, pipe_exact, pcounters = None, True, {}
        if args.pipeline:
            outs_p, dt_p = timed["pipelined"]
            pcounters = dict(eng_p.counters)
            pcounters.update(pcommit)
            pipe_exact = all(a == b for a, b in zip(outs_s, outs_p))
        rows.append((n, tok / dt_s, tok / dt_b,
                     tok / dt_p if dt_p else None, exact and pipe_exact))
        pool_note = ""
        if occ:
            # blocks_peak and blocks_total both describe the TARGET arena
            # (the engine scopes the peak counter to it)
            t = occ["target"]
            pool_note = (f"   pool: {counters['blocks_peak']}/{t['blocks_total']} blocks peak"
                         f" (frag {t['fragmentation']:.2f}, "
                         f"reclaimed {counters['blocks_reclaimed']})")
        if counters.get("shard_blocks_peak"):
            pool_note += "   shard peaks: " + "/".join(
                str(p) for p in counters["shard_blocks_peak"])
        line = f"{n:>5} {tok / dt_s:>10.2f} {tok / dt_b:>14.2f}"
        if dt_p:
            line += f" {tok / dt_p:>16.2f} {dt_b / dt_p:>8.2f}x"
        line += (f" {'yes' if exact and pipe_exact else 'NO':>6}"
                 f"   pad: {pad_fraction:.2f}"
                 + ("(" + "/".join(f"{f:.2f}" for f in shard_pad_fraction) + ")"
                    if shard_pad_fraction else "")
                 + f"   commit: {counters['commit_calls']} calls")
        if pcounters:
            line += (f"   overlap: {pcounters['pipeline_ahead']} ahead, "
                     f"{pcounters['pipeline_stalls']} stalls / "
                     f"{pcounters['pipeline_iterations']} iters")
        line += (f"   compiles: {warm_s['compile_count']}s/"
                 f"{warm_b['compile_count']}b"
                 + (f"/{warm_p['compile_count']}p" if warm_p else "")
                 + f" (warmup {warm_b['warmup_secs']:.1f}s)")
        print(line + pool_note)
        json_rows.append({
            "batch": n,
            "tokens": tok,
            "tokens_per_sec": {
                "sequential": tok / dt_s,
                "batched": tok / dt_b,
                "pipelined": tok / dt_p if dt_p else None,
            },
            "speedup_batched_vs_sequential": dt_s / dt_b,
            "speedup_pipelined_vs_batched": dt_b / dt_p if dt_p else None,
            "exact": bool(exact),
            "pipeline_exact": bool(pipe_exact),
            "commit_calls": counters["commit_calls"],
            "blocks_peak": counters["blocks_peak"],
            "blocks_reclaimed": counters["blocks_reclaimed"],
            "shard_blocks_peak": counters.get("shard_blocks_peak"),
            "pad_nodes_total": pad_nodes,
            "tree_lanes_total": tree_lanes,
            "pad_fraction": pad_fraction,
            "shard_pad_fraction": shard_pad_fraction,
            "pipeline_ahead": pcounters.get("pipeline_ahead"),
            "pipeline_stalls": pcounters.get("pipeline_stalls"),
            "pipeline_iterations": pcounters.get("pipeline_iterations"),
            "compile_count": {
                "sequential": warm_s["compile_count"],
                "batched": warm_b["compile_count"],
                "pipelined": warm_p.get("compile_count"),
            },
            "warmup_secs": {
                "sequential": warm_s["warmup_secs"],
                "batched": warm_b["warmup_secs"],
                "pipelined": warm_p.get("warmup_secs"),
            },
        })
    if len(rows) > 1:
        first, last = rows[0], rows[-1]
        scale = last[2] / first[2]
        print(f"\nbatched tokens/sec scaling {first[0]}->{last[0]} streams: {scale:.2f}x "
              f"(sequential stays ~flat by construction)")
    if args.json:
        write_bench_json(args.json, "batch_throughput",
                         {"arch": args.arch, "verifier": args.verifier,
                          "K": args.K, "L1": args.L1, "L2": args.L2,
                          "max_new": args.max_new, "batch_sizes": sizes,
                          "pool": pool, "block_size": args.block_size,
                          "data_shards": args.data_shards, "ragged": args.ragged,
                          "max_cache": ecfg.max_cache, "seed": args.seed},
                         json_rows)
        print(f"wrote {args.json}")
    return rows


if __name__ == "__main__":
    main()
