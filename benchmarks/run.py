"""Benchmark driver.  One function per paper table/figure, plus core-op
microbenchmarks.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src:. python -m benchmarks.run [--full]

The roofline sweep (needs the 512-device dry-run env) runs separately:
    PYTHONPATH=src:. python -m benchmarks.roofline --out results/roofline.json
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _time(fn, n=20, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def micro_rows():
    """Core-op microbenchmarks (CPU walltime; TPU numbers come from the
    roofline terms, not from this container)."""
    import jax
    import jax.numpy as jnp

    from repro.core.otlp import OTLP_SOLVERS
    from repro.core.traversal import verify_traversal
    from repro.core.trees import attach_target, build_delayed_tree
    from repro.kernels.ops import gqa_decode_attention, gqa_tree_attention, interpret_mode
    from benchmarks.common import make_process

    rows = []
    rng = np.random.default_rng(0)
    proc = make_process("llama-9to1", 0, 1.0, 1.0)
    p = proc.p(())
    q = proc.q(())
    xs = [1, 3]
    for name in ["naive", "nss", "spectr", "specinfer", "khisti"]:
        solve, output_dist, _ = OTLP_SOLVERS[name]
        us = _time(lambda: output_dist(p, q, xs), n=200)
        rows.append((f"otlp_output_dist_{name}", us, f"V={len(p)},k=2"))
    tree = attach_target(build_delayed_tree(rng, proc.q, 2, 2, 2), proc.p)
    us = _time(lambda: verify_traversal(tree, rng), n=100)
    rows.append(("verify_traversal", us, "K2,L1=2,L2=2"))

    interp = interpret_mode()
    route = "interpret" if interp else "mosaic"
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    qq = jax.random.normal(ks[0], (1, 8, 4, 128), jnp.float32)
    kk = jax.random.normal(ks[1], (1, 256, 2, 128), jnp.float32)
    vv = jax.random.normal(ks[2], (1, 256, 2, 128), jnp.float32)
    mm = jax.random.bernoulli(ks[3], 0.7, (1, 8, 256))
    out = gqa_tree_attention(qq, kk, vv, mm, block_k=128, interpret=interp)
    jax.block_until_ready(out)
    us = _time(lambda: jax.block_until_ready(
        gqa_tree_attention(qq, kk, vv, mm, block_k=128, interpret=interp)), n=5)
    rows.append((f"pallas_tree_attention_{route}", us, "T8,S256,H4"))
    q1 = jax.random.normal(ks[0], (1, 1, 4, 128), jnp.float32)
    ln = jnp.asarray([250], jnp.int32)
    out = gqa_decode_attention(q1, kk, vv, ln, block_k=128, interpret=interp)
    jax.block_until_ready(out)
    us = _time(lambda: jax.block_until_ready(
        gqa_decode_attention(q1, kk, vv, ln, block_k=128, interpret=interp)), n=5)
    rows.append((f"pallas_decode_attention_{route}", us, "S256,H4"))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale settings (slow)")
    ap.add_argument("--out", default="results/bench.json")
    args = ap.parse_args(argv)
    quick = not args.full

    results = {}
    print("name,us_per_call,derived")

    t0 = time.time()
    from benchmarks.verifier_tables import run as run_tables

    t2, _ = run_tables(quick=quick, metric="block_efficiency", s=2 if quick else 4)
    results["table2"] = t2
    avg = {m: float(np.mean([t2[f][m] for f in t2])) for m in next(iter(t2.values()))}
    print(f"table2_block_efficiency,{(time.time()-t0)*1e6:.0f},"
          f"traversal={avg['traversal']:.3f};specinfer={avg['specinfer']:.3f};nss={avg['nss']:.3f}")

    t0 = time.time()
    t3, _ = run_tables(quick=quick, metric="throughput", s=2 if quick else 4)
    results["table3"] = t3
    avg3 = {m: float(np.mean([t3[f][m] for f in t3])) for m in next(iter(t3.values()))}
    best3 = max(avg3, key=avg3.get)
    print(f"table3_throughput,{(time.time()-t0)*1e6:.0f},best={best3}:{avg3[best3]:.2f}")

    t0 = time.time()
    from benchmarks.fig1_acceptance_depth import run as run_fig1

    acc, l1 = run_fig1(quick=quick)
    results["fig1"] = {"l1": list(map(float, l1))}
    print(f"fig1_acceptance_depth,{(time.time()-t0)*1e6:.0f},"
          f"l1_d0={l1[0]:.3f};l1_d6={l1[-1]:.3f};spectr_drop={acc['spectr'][0]-acc['spectr'][-1]:.3f}")

    t0 = time.time()
    from benchmarks.nde_tables import run as run_nde

    nde = run_nde(quick=quick)
    results.update({k: v for k, v in nde.items()})
    t5avg = {m: float(np.mean(list(d.values()))) for m, d in nde["t5"].items()}
    t7avg = {m: float(np.mean(list(d.values()))) for m, d in nde["t7"].items()}
    si = t7avg.get("specinfer-nde", 0.0)
    tv = t7avg.get("traversal", 1.0)
    print(f"table45_nde_ratio,{(time.time()-t0)*1e6:.0f},tps_ratio_avg={np.mean(list(t5avg.values())):.3f}")
    print(f"table67_nde_vs_traversal,0,specinfer_nde/traversal={si/tv:.3f}")

    for name, us, derived in micro_rows():
        print(f"{name},{us:.1f},{derived}")

    # attach roofline summary if present
    try:
        with open("results/roofline.json") as f:
            rl = json.load(f)
        ok = [r for r in rl if "dominant" in r]
        doms: dict = {}
        for r in ok:
            doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
        print(f"roofline_summary,0,pairs={len(ok)};" + ";".join(f"{k}={v}" for k, v in doms.items()))
        results["roofline_dominants"] = doms
    except FileNotFoundError:
        pass

    import os

    os.makedirs("results", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=float)
    return results


if __name__ == "__main__":
    main()
