"""Per-step commit cost: sequential per-row commit (PR-1) vs the fused
device-resident commit, at 1/4/8 streams.

    PYTHONPATH=src python benchmarks/commit_bench.py [--streams 1,4,8]
        [--layers 4] [--smax 256] [--kv-heads 4] [--head-dim 64]
        [--tpad 8] [--iters 20] [--impl xla|pallas]

Builds a synthetic per-stream KV pool and a random accepted path per row
(the post-verification state of ``BatchedSpeculativeEngine.step``), then
commits it two ways:

  * sequential — ``serve_step.commit_row_reference`` per active row: each
    call's eager ``.at[].set`` chain materializes a fresh copy of the whole
    (L, B, Smax, Hkv, hd) pool, so device traffic is O(streams) pool copies;
  * fused      — ONE jitted ``serve_step.make_pool_commit_step`` call with
    the pool donated, so XLA moves only the touched (row, slot) lanes.

Reports wall-time per step (median over --iters, post-warmup) and the
analytic device-copy bytes each strategy moves per step.  The fused column
must win at 8 streams (ISSUE 2 acceptance criterion).  ``--json PATH``
writes the machine-readable ``BENCH_commit_bench.json`` document
(benchmarks/common.py ``write_bench_json``) the CI bench-smoke gate and the
checked-in baselines consume.
"""
from __future__ import annotations

import argparse
import statistics
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.common import write_bench_json
except ImportError:  # executed as a script: benchmarks/ itself is sys.path[0]
    from common import write_bench_json

from repro.serving.serve_step import commit_row_reference, make_pool_commit_step, next_pow2


def _pool(rng, L, B, S, H, hd):
    return {
        "attn": {
            "k": jnp.asarray(rng.normal(size=(L, B, S, H, hd)).astype(np.float32)),
            "v": jnp.asarray(rng.normal(size=(L, B, S, H, hd)).astype(np.float32)),
            "pos": jnp.asarray(rng.integers(-1, S, size=(B, S)).astype(np.int32)),
            "len": jnp.asarray(rng.integers(1, S // 2, size=(B,)).astype(np.int32)),
        }
    }


def _case(rng, n_active, B, S, Tpad):
    """One step's commit inputs: per-row C and a random accepted path."""
    paths, Cs = {}, {}
    for b in range(n_active):
        Cs[b] = int(rng.integers(1, S - Tpad))
        tau = int(rng.integers(0, Tpad))
        paths[b] = (
            sorted(rng.choice(np.arange(1, Tpad), size=tau, replace=False).tolist())
            if tau else []
        )
    return paths, Cs


def _bytes_sequential(L, B, S, H, hd, n_active, Tpad):
    """Each per-row commit rewrites full k and v (the eager copy) plus the
    row's pos table; len is negligible."""
    kv = 2 * L * B * S * H * hd * 4
    pos = B * S * 4
    return n_active * (kv + 2 * pos)


def _bytes_fused(L, B, S, H, hd, n_active, Tpad, P):
    """Donated fused commit: per active row, P KV lane moves per layer
    (read+write) plus the pos scatter rows."""
    lanes = 2 * L * P * H * hd * 4 * 2  # k and v, read + write
    pos = 2 * B * S * 4  # pos invalidate + rewrite over the donated table
    return n_active * lanes + pos


def run(args):
    sizes = [int(s) for s in args.streams.split(",")]
    B = max(sizes)
    L, S, H, hd, Tpad = args.layers, args.smax, args.kv_heads, args.head_dim, args.tpad
    cfg = types.SimpleNamespace(attention_impl=args.impl)
    rng = np.random.default_rng(args.seed)
    print(f"pool: L={L} B={B} Smax={S} Hkv={H} hd={hd}  Tpad={Tpad}  impl={args.impl}")
    print(f"{'streams':>8} {'seq ms/step':>12} {'fused ms/step':>14} {'speedup':>8} "
          f"{'seq MB/step':>12} {'fused MB/step':>14}")
    rows = []
    for n in sizes:
        paths, Cs = _case(rng, n, B, S, Tpad)
        P = next_pow2(max([len(p) for p in paths.values()] + [1]))
        npath = np.zeros((B, P), np.int32)
        plen = np.zeros((B,), np.int32)
        C = np.zeros((B,), np.int32)
        act = np.zeros((B,), np.bool_)
        for b in range(n):
            npath[b, : len(paths[b])] = paths[b]
            plen[b] = len(paths[b])
            C[b] = Cs[b]
            act[b] = True
        args_dev = tuple(jnp.asarray(a) for a in (npath, plen, C, act))
        fused_fn = jax.jit(make_pool_commit_step(cfg, Tpad), donate_argnums=0)

        def seq_step(pool):
            for b in range(n):
                pool = commit_row_reference(pool, b, Cs[b], paths[b], Tpad)
            return jax.block_until_ready(pool)

        def fused_step(pool):
            return jax.block_until_ready(fused_fn(pool, *args_dev))

        def bench(step):
            step(_pool(rng, L, B, S, H, hd))  # warm (compile)
            ts = []
            for _ in range(args.iters):
                pool = _pool(rng, L, B, S, H, hd)
                jax.block_until_ready(pool)
                t0 = time.perf_counter()
                step(pool)
                ts.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(ts)

        seq_ms = bench(seq_step)
        fused_ms = bench(fused_step)
        sb = _bytes_sequential(L, B, S, H, hd, n, Tpad) / 1e6
        fb = _bytes_fused(L, B, S, H, hd, n, Tpad, P) / 1e6
        rows.append((n, seq_ms, fused_ms))
        print(f"{n:>8} {seq_ms:>12.3f} {fused_ms:>14.3f} {seq_ms / fused_ms:>7.2f}x "
              f"{sb:>12.2f} {fb:>14.3f}")
    if args.json:
        write_bench_json(
            args.json, "commit_bench",
            {"streams": sizes, "layers": L, "smax": S, "kv_heads": H,
             "head_dim": hd, "tpad": Tpad, "iters": args.iters,
             "impl": args.impl, "seed": args.seed},
            [{"streams": n, "median_ms": {"sequential": s, "fused": f},
              "speedup_fused_vs_sequential": s / f} for n, s, f in rows],
        )
        print(f"wrote {args.json}")
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", default="1,4,8")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--smax", type=int, default=256)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--tpad", type=int, default=8)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--impl", default="xla", choices=["xla", "pallas"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the BENCH_commit_bench.json document here")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    main()
