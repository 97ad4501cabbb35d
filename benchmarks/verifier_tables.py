"""Tables 2 & 3 — systematic comparison of verification algorithms under
matched i.i.d. root-rollout drafts (L1 = 0), plus the delayed-expansion rows
(the "X, delayed expansion" rows of Tables 8-15).

For each (family x domain x sampling) cell, every verifier picks its best
configuration from the (K, L) grid by the requested metric, exactly as the
paper does ("we select the branching factor K in [1,4] and block length L in
[0,8] that maximizes the block-efficiency or throughput").

Estimation:  OT-based methods use the exact Eq. 3 conditional estimator over
s sampled trees; Traversal/BV/Naive use their exact conditional block-length
laws over the same tree samples.  Verification variance is therefore zero;
only drafting variance remains.

``--matrix`` instead runs the Table-1-style cross-verifier matrix over the
WHOLE core/verify.py registry — losslessness gap x block efficiency x
engine-level batched==sequential exactness, for every registered verifier,
both target-pass strategies (tree and replay archs from the configs/ zoo)
and the paper's sampling grid — and emits the machine-readable
``BENCH_verifier_matrix.json`` document (benchmarks/common.py
``write_bench_json``) that scripts/verifier_matrix.sh gates CI on.  Quick
mode (the default) is the per-PR gate; ``--full`` is the weekly matrix.
"""
from __future__ import annotations

import argparse

import numpy as np

try:
    from benchmarks.common import (
        DOMAINS,
        FAMILIES,
        SAMPLING,
        SAMPLING_QUICK,
        family_latency,
        make_process,
        write_bench_json,
    )
except ImportError:  # executed as a script: benchmarks/ itself is sys.path[0]
    from common import (
        DOMAINS,
        FAMILIES,
        SAMPLING,
        SAMPLING_QUICK,
        family_latency,
        make_process,
        write_bench_json,
    )
from repro.core.delayed import expected_block_efficiency, expected_block_efficiency_traversal
from repro.core.enumerate import mean_block_len
from repro.core.trees import attach_target, build_delayed_tree
from repro.core.verify import verify_topdown_output_dist

OT_METHODS = ["nss", "naivetree", "spectr", "specinfer", "khisti"]
SINGLE_PATH = ["naive", "bv"]  # K = 1 only
ALL_METHODS = OT_METHODS + SINGLE_PATH + ["traversal"]


def block_efficiency(proc, method: str, K: int, L1: int, L2: int, s: int, seed: int) -> float:
    rng = np.random.default_rng(seed)
    vals = []
    for _ in range(s):
        tree = build_delayed_tree(rng, proc.q, K, L1, L2)
        attach_target(tree, proc.p)
        if method == "traversal" or method == "bv":
            vals.append(expected_block_efficiency_traversal(tree))
        elif method == "naive":
            d = verify_topdown_output_dist(tree, "naive")
            vals.append(mean_block_len(d))
        else:
            vals.append(expected_block_efficiency(tree, method))
    return float(np.mean(vals))


def grid_for(method: str, quick: bool, delayed: bool):
    Ks = [1] if method in SINGLE_PATH else ([2, 4] if quick else [1, 2, 3, 4])
    Ls = [2, 4, 6] if quick else [1, 2, 3, 4, 6, 8]
    out = []
    for K in Ks:
        for L in Ls:
            if delayed and K > 1:
                # split the same node budget into trunk + branches
                for L1 in ([1, 2] if quick else [1, 2, 3]):
                    if L - L1 >= 1:
                        out.append((K, L1, L - L1))
            else:
                out.append((K, 0, L))
    return out


def run(quick: bool = True, delayed: bool = False, metric: str = "block_efficiency",
        s: int = 4, seed: int = 0):
    """Returns {family: {method: avg}}, detail rows."""
    sampling = SAMPLING_QUICK if quick else SAMPLING
    domains = DOMAINS[:3] if quick else DOMAINS
    rows = []
    agg: dict = {f: {m: [] for m in ALL_METHODS} for f in FAMILIES}
    for family in FAMILIES:
        lat = family_latency(family)
        for domain in domains:
            for (temp, top_p) in sampling:
                proc = make_process(family, domain, temp, top_p)
                for method in ALL_METHODS:
                    best = -1.0
                    best_a = None
                    for (K, L1, L2) in grid_for(method, quick, delayed):
                        be = block_efficiency(proc, method, K, L1, L2, s, seed)
                        score = be if metric == "block_efficiency" else be / lat.action_time(256, K, L1, L2)
                        if score > best:
                            best, best_a = score, (K, L1, L2)
                    agg[family][method].append(best)
                    rows.append(dict(family=family, domain=domain, temp=temp, top_p=top_p,
                                     method=method, score=best, action=best_a))
    table = {f: {m: float(np.mean(v)) for m, v in d.items()} for f, d in agg.items()}
    return table, rows


def print_table(table: dict, title: str):
    methods = sorted(next(iter(table.values())), key=lambda m: np.mean([table[f][m] for f in table]))
    print(f"\n== {title} ==")
    fams = list(table)
    print(f"{'method':12s} " + " ".join(f"{f:>14s}" for f in fams) + f" {'average':>10s}")
    for m in methods:
        vals = [table[f][m] for f in fams]
        print(f"{m:12s} " + " ".join(f"{v:14.3f}" for v in vals) + f" {np.mean(vals):10.3f}")


def main(quick=True):
    t2, _ = run(quick=quick, metric="block_efficiency")
    print_table(t2, "Table 2 analogue: block efficiency (iid root rollouts, best (K,L))")
    t3, _ = run(quick=quick, metric="throughput")
    # recompute printable TPS values: rows store score = TPS directly
    print_table(t3, "Table 3 analogue: modelled throughput score (Eq. 11 latency)")
    return {"table2": t2, "table3": t3}


# ------------------------------------------- Table-1 cross-verifier matrix ---
#
# Three cell kinds, every one computed for EVERY registered verifier:
#
#   lossless — exact enumeration over draft-tree AND verifier randomness
#              (core/enumerate.py): the composed block law must equal the
#              target process.  Gap is reported; the gate is < 1e-9.
#   block_efficiency — E[tau+1] over s sampled delayed trees per sampling
#              temperature (core/delayed.py registry dispatch), at a matched
#              5-node budget so the columns are comparable.
#   exactness — the serving contract: one batched+pipelined pool engine must
#              emit token-identical outputs to per-request single-stream
#              engines, per verifier, per target-pass strategy (a tree arch
#              and a replay arch from the configs/ zoo); --full adds the
#              2-shard engine.

# matched 5-node tree budgets: multipath (K=2: 1 trunk + 2x2 branches) vs
# single-path (K=1: one path of 5) — and the engine smoke action per kind
MATRIX_BE_ACTION = {True: (2, 1, 2), False: (1, 2, 3)}
MATRIX_ENGINE_ACTION = {True: (2, 1, 1), False: (1, 1, 1)}
MATRIX_ARCHES_QUICK = ["granite-8b", "mamba2-2.7b"]  # one arch per strategy
MATRIX_ARCHES = ["granite-8b", "minitron-8b", "mamba2-2.7b", "recurrentgemma-2b"]
LOSSLESS_GATE = 1e-9


def _registry():
    from repro.core.verify import VERIFIERS

    return sorted(VERIFIERS.items())


def lossless_cases(multipath: bool, quick: bool):
    """(K, L1, L2) enumeration cases; single-path verifiers only see K=1."""
    if not multipath:
        return [(1, 0, 2), (1, 1, 1)] if quick else [(1, 0, 1), (1, 0, 2), (1, 1, 1), (1, 2, 1)]
    return [(2, 1, 1), (2, 0, 2)] if quick else [(2, 0, 1), (2, 1, 1), (2, 1, 2), (3, 0, 2), (1, 0, 2)]


def losslessness_rows(quick: bool, seed: int = 11) -> list[dict]:
    from repro.core.enumerate import RandomModel, expected_block_dist, lossless_gap

    rows = []
    for name, spec in _registry():
        for (K, L1, L2) in lossless_cases(spec.multipath, quick):
            model = RandomModel(3, seed=seed, divergence=0.7)
            bd = expected_block_dist(spec.output_dist, model, K, L1, L2)
            gap = float(lossless_gap(bd, model, L1 + L2 + 1))
            rows.append(dict(cell="lossless", verifier=name, K=K, L1=L1, L2=L2,
                             gap=gap, lossless=bool(gap < LOSSLESS_GATE)))
    return rows


def block_efficiency_rows(quick: bool, s: int = 3, seed: int = 0) -> list[dict]:
    from repro.core.delayed import estimate_block_efficiency

    sampling = SAMPLING_QUICK if quick else SAMPLING
    families = ["llama-9to1"] if quick else list(FAMILIES)
    rows = []
    for family in families:
        for (temp, top_p) in sampling:
            proc = make_process(family, 0, temp, top_p)
            for name, spec in _registry():
                K, L1, L2 = MATRIX_BE_ACTION[spec.multipath]
                rng = np.random.default_rng(seed)  # shared trees per K-class
                be = estimate_block_efficiency(rng, proc.q, proc.p, name, K, L1, L2, s=s)
                rows.append(dict(cell="block_efficiency", verifier=name, family=family,
                                 temp=temp, top_p=top_p, K=K, L1=L1, L2=L2,
                                 block_efficiency=float(be)))
    return rows


def exactness_rows(quick: bool, seed: int = 0, max_new: int = 8) -> list[dict]:
    from dataclasses import replace

    import jax

    from repro.configs import get_smoke
    from repro.launch.serve import make_draft_cfg
    from repro.models.transformer import init_params
    from repro.serving.batch_engine import (
        BatchedSpeculativeEngine,
        ShardedBatchedSpeculativeEngine,
    )
    from repro.serving.engine import EngineConfig, SamplingParams, SpeculativeEngine

    rows = []
    for arch in (MATRIX_ARCHES_QUICK if quick else MATRIX_ARCHES):
        cfg = get_smoke(arch)
        dcfg = make_draft_cfg(cfg)
        tp = init_params(cfg, jax.random.PRNGKey(seed))
        dp = init_params(dcfg, jax.random.PRNGKey(seed + 1))
        prng = np.random.default_rng(seed)
        prompts = [prng.integers(0, cfg.vocab, size=5).tolist() for _ in range(2)]
        seeds = [seed + 100 + i for i in range(len(prompts))]
        sampling = SamplingParams()
        base = EngineConfig(K=2, L1=1, L2=1, max_cache=128, seed=seed)
        # ONE engine pair per arch, re-aimed per verifier: the jit cache is
        # per-engine, and the verifier is host-side state the compiled steps
        # never see — rebuilding per verifier would recompile 11x for nothing
        seq = SpeculativeEngine(cfg, tp, dcfg, dp, base, sampling)
        beng = BatchedSpeculativeEngine(cfg, tp, dcfg, dp, base, sampling,
                                        n_slots=len(prompts), pipeline=True)
        sheng = None
        if not quick and beng.strategy == "tree":
            sheng = ShardedBatchedSpeculativeEngine(
                cfg, tp, dcfg, dp, base, sampling, n_slots=len(prompts),
                data_shards=2)
        for name, spec in _registry():
            K, L1, L2 = MATRIX_ENGINE_ACTION[spec.multipath]
            ecfg = replace(base, verifier=name, K=K, L1=L1, L2=L2)
            seq.ecfg = beng.ecfg = ecfg
            singles = []
            for p, sd in zip(prompts, seeds):
                seq.rng = np.random.default_rng(sd)
                singles.append(seq.generate(list(p), max_new=max_new, seed=sd))
            outs = beng.generate_batch([list(p) for p in prompts], max_new, seeds=seeds)
            exact = singles == outs
            c = beng.counters
            be = c["accepted"] / max(c["blocks"], 1) + 1
            row = dict(cell="exactness", verifier=name, arch=arch,
                       strategy=beng.strategy, K=K, L1=L1, L2=L2,
                       exact=bool(exact), pipelined=True,
                       block_efficiency=float(be))
            if sheng is not None:
                sheng.ecfg = ecfg
                for sh in sheng.shards:
                    sh.ecfg = ecfg
                shouts = sheng.generate_batch([list(p) for p in prompts], max_new, seeds=seeds)
                row["sharded_exact"] = bool(singles == shouts)
            rows.append(row)
            beng.reset_counters(("accepted", "blocks"))
    return rows


def run_matrix(quick: bool = True, json_path: str | None = None, seed: int = 0):
    names = [n for n, _ in _registry()]
    rows = losslessness_rows(quick, seed=seed + 11)
    rows += block_efficiency_rows(quick, seed=seed)
    rows += exactness_rows(quick, seed=seed)

    by_v = {n: {} for n in names}
    for r in rows:
        v = by_v[r["verifier"]]
        if r["cell"] == "lossless":
            v["gap"] = max(v.get("gap", 0.0), r["gap"])
        elif r["cell"] == "block_efficiency":
            v.setdefault("be", []).append(r["block_efficiency"])
        else:
            v["exact"] = v.get("exact", True) and r["exact"] and r.get("sharded_exact", True)
    print(f"\n== Table 1 analogue: verifier matrix ({'quick' if quick else 'full'}) ==")
    print(f"{'verifier':14s} {'worst gap':>12s} {'mean E[tau+1]':>14s} {'engine exact':>13s}")
    for n in names:
        v = by_v[n]
        print(f"{n:14s} {v['gap']:12.2e} {np.mean(v['be']):14.3f} "
              f"{'yes' if v['exact'] else 'NO':>13s}")

    if json_path:
        write_bench_json(
            json_path, "verifier_matrix",
            {"mode": "quick" if quick else "full", "seed": seed,
             "verifiers": names,
             "arches": MATRIX_ARCHES_QUICK if quick else MATRIX_ARCHES,
             "sampling": SAMPLING_QUICK if quick else SAMPLING,
             "be_actions": {str(k): list(v) for k, v in MATRIX_BE_ACTION.items()},
             "engine_actions": {str(k): list(v) for k, v in MATRIX_ENGINE_ACTION.items()},
             "lossless_gate": LOSSLESS_GATE},
            rows)
        print(f"wrote {json_path}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--matrix", action="store_true",
                    help="run the Table-1 cross-verifier matrix over the "
                         "whole registry instead of the Table-2/3 sweeps")
    ap.add_argument("--full", action="store_true",
                    help="full grid (weekly tier); default is the quick "
                         "per-PR slice")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_verifier_matrix.json document here")
    args = ap.parse_args()
    if args.matrix:
        run_matrix(quick=not args.full, json_path=args.json)
    else:
        main(quick=not args.full)
