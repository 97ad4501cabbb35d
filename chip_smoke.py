#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: granite-3-2b at its published widths.

    python3 chip_smoke.py                 # one chip: phases A, B and C
    python3 chip_smoke.py --four-chips    # four chips: the sharded phase only
    JAX_PLATFORMS=cpu python3 chip_smoke.py --smoke   # CPU rehearsal, smoke preset

The engines are built by the serving CLI's own constructors
(``repro.launch.serve.build_models`` / ``build_engine``) with random bf16
weights made from ``--seed``: the 40-layer target (d_model 2048, 32/8 heads,
d_ff 8192, vocab 49155) and its ``make_draft_cfg`` draft.  Requests are 8
prompts of 128 random tokens, 32 new tokens each, verifier specinfer,
(K, L1, L2) = (2, 2, 2), temperature 1.0, a 4-stream paged pool at block
size 64.

  A  default route (XLA attention), pipelined, then the same requests
     synchronous (``--no-pipeline``): the tokens must be identical.
  B  the same engine on the Pallas route: the padded tree pass, the ragged
     tree pass and the fused commit each dispatch, no kernel is built
     interpreted, and the first tree pass's log-probabilities agree with
     phase A's within ``LOGP_ATOL``.
  C  requests 0 and 1 through the single-stream engine: prints whether its
     tokens equal phase A's.  This reports what XLA does on the device; it
     is not a gate.
  --four-chips: the pool split into 4 shard engines, one per chip.  The
     four shards must sit on four distinct devices, and their tokens must
     equal those of the same 4 shards placed together on chip 0: the shard
     programs are the same, only their devices differ.  Whether the tokens
     also equal the unsharded 4-stream engine's is printed, not gated: a
     shard steps 1 stream where the unsharded pool steps 4, and on the TPU
     programs of different row counts need not round bf16 alike (phase C
     reports the same fact for the single-stream engine).

Each phase prints its wall time (cold = first pass with compilation, and the
compile share of it; steady = the same requests again, compiled), tokens,
block efficiency, ``jit_compile_count()``, ``peak_bytes_in_use`` and the
device of each param tree and pool.  Everything runs in this one process.
Any failure, a platform other than ``tpu`` without ``--smoke`` included,
exits non-zero before a result is printed; on success the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.ops import KERNEL_TRACES  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    build_models,
    build_parser,
    setup_compile_cache,
)

N_REQUESTS, PROMPT_LEN, MAX_NEW, STREAMS = 8, 128, 32, 4
# Phase B compares log-probabilities of the first tree pass (logits up to a
# per-row shift) between the XLA and the Pallas route.  They are not bitwise
# equal: the kernels accumulate QK and PV in float32 over 64-slot KV blocks,
# where XLA rounds QK logits and softmax weights to bfloat16 (8 significant
# bits, relative step 2**-8), and every layer's output is rounded to
# bfloat16 before the next of 40 layers.  That compounds to a few hundredths
# of a nat; a kernel reading a wrong block, slot or mask lane instead moves
# log-probabilities by the spread of the logits, whole nats.
LOGP_ATOL = 0.25
PHASE_KERNELS = ("paged_tree_attention", "ragged_paged_tree_attention", "commit_kv")


def check(ok: bool, msg: str) -> None:
    """Stop the run with a non-zero exit, before any result line."""
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


_compile_secs = [0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event.startswith("/jax/core/compile/"):
        _compile_secs[0] += secs


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def serve_args(opts, *extra):
    """Parsed serving-CLI args for this run, plus per-phase ``extra`` flags."""
    argv = ["--arch", "granite-3-2b", "--verifier", "specinfer", "--K", "2",
            "--L1", "2", "--L2", "2", "--temperature", "1.0",
            "--max-new", str(MAX_NEW), "--streams", str(STREAMS),
            "--block-size", "64", "--seed", str(opts.seed)]
    return build_parser().parse_args(argv + (["--smoke"] if opts.smoke else []) + list(extra))


def peak_bytes(devices) -> str:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return "not reported by this backend"
    return ", ".join(f"dev{d.id}={s['peak_bytes_in_use']}" for d, s in zip(devices, stats))


def timed_passes(eng, prompts, seeds, on_first_step=None):
    """Serve the requests twice: a cold pass that compiles, then a steady
    pass of the same requests.  Returns (tokens, stats)."""
    counters0 = dict(eng.counters)
    c0 = _compile_secs[0]
    t0 = time.perf_counter()
    rids = [eng.submit(list(p), max_new=MAX_NEW, seed=s) for p, s in zip(prompts, seeds)]
    if on_first_step is not None:
        pending = eng.begin_step()
        v = eng.verify_step(pending)  # reads the drafted trees back
        on_first_step(pending)
        eng.commit_step(v)
        eng.retire_step(v)
    outs = eng.run()
    cold = time.perf_counter() - t0
    compile_s = _compile_secs[0] - c0
    tokens = [outs[r]["tokens"] for r in rids]
    c = {k: v - counters0[k] for k, v in eng.counters.items()}
    t1 = time.perf_counter()
    again = eng.generate_batch([list(p) for p in prompts], MAX_NEW, list(seeds))
    steady = time.perf_counter() - t1
    n = sum(len(t) for t in tokens)
    return tokens, {
        "wall_cold_s": cold, "compile_s": compile_s, "wall_steady_s": steady,
        "tokens": n, "tokens_per_s_steady": n / steady,
        "block_efficiency": c["accepted"] / max(c["blocks"], 1) + 1,
        "steady_pass_same_tokens": again == tokens, "counters": c,
    }


def report(phase: str, eng, stats: dict, devices) -> None:
    c = stats.pop("counters")
    line = " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in stats.items())
    print(f"[{phase}] {line} target_calls={c['target_calls']} "
          f"tree_calls_padded={c['tree_calls_padded']} "
          f"tree_calls_ragged={c['tree_calls_ragged']} commit_calls={c['commit_calls']} "
          f"jit_compile_count={eng.jit_compile_count()} "
          f"peak_bytes_in_use=[{peak_bytes(devices)}] placement={eng.placement()}",
          flush=True)


def first_pass_rows(pending) -> dict:
    """Per active slot: (drafted tree tokens, log-probabilities per node) of a
    begun step's tree pass."""
    p = np.asarray(pending.p_dev)
    rows = {}
    for s in pending.active:
        tree = pending.trees[s]
        n = tree.n_nodes
        ps = p[s, :n] if pending.roffs is None else p[pending.roffs[0][s][0]:][:n]
        rows[s] = (np.asarray(tree.tokens), np.log(np.maximum(ps, 1e-30)))
    return rows


def compare_first_pass(rows_a: dict, rows_b: dict) -> tuple[float, float, int]:
    """Max and mean |log p_A - log p_B| over the nodes whose whole token
    prefix agrees (the root always does), and how many nodes that was."""
    check(rows_a.keys() == rows_b.keys(), "first steps ran different streams")
    diffs = []
    for s in rows_a:
        (ta, la), (tb, lb) = rows_a[s], rows_b[s]
        same = 1
        while same < len(ta) and ta[same] == tb[same]:
            same += 1
        diffs.append(np.abs(la[:same] - lb[:same]))
    d = np.concatenate(diffs)
    return float(d.max()), float(d.mean()), int(d.shape[0])


def one_chip(opts, devices) -> None:
    rng = np.random.default_rng(opts.seed)
    args_a = serve_args(opts)
    cfg, tp, dcfg, dp = build_models(args_a)
    prompts = rng.integers(0, cfg.vocab, size=(N_REQUESTS, PROMPT_LEN))
    seeds = [opts.seed + r for r in range(N_REQUESTS)]
    print(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={cfg.dtype} params={cfg.param_count()} "
          f"draft(layers={dcfg.n_layers} d_model={dcfg.d_model} "
          f"params={dcfg.param_count()})", flush=True)

    # ---- A: default route, pipelined then synchronous
    first = {}
    eng = build_engine(args_a, cfg, tp, dcfg, dp)
    tokens_a, stats = timed_passes(eng, prompts, seeds,
                                   on_first_step=lambda p: first.update(a=first_pass_rows(p)))
    report("A pipelined", eng, stats, devices)
    del eng
    eng = build_engine(serve_args(opts, "--no-pipeline"), cfg, tp, dcfg, dp)
    tokens_sync, stats = timed_passes(eng, prompts, seeds)
    report("A synchronous", eng, stats, devices)
    del eng
    same = tokens_sync == tokens_a
    print(f"[A] pipelined tokens identical to synchronous: {same}", flush=True)
    check(same, "pipelined and synchronous tokens differ")

    # ---- B: the Pallas route
    KERNEL_TRACES.clear()
    args_b = serve_args(opts, "--attention-impl", "pallas")
    cfg_b, dcfg_b = (c.replace(attention_impl="pallas") for c in (cfg, dcfg))
    eng = build_engine(args_b, cfg_b, tp, dcfg_b, dp)
    _, stats = timed_passes(eng, prompts, seeds,
                            on_first_step=lambda p: first.update(b=first_pass_rows(p)))
    counters = dict(stats["counters"])
    report("B pallas", eng, stats, devices)
    # a lone stream: its tree is smaller than the padded block, so the
    # ragged pass dispatches whatever the drain tail above did
    before = eng.counters["tree_calls_ragged"]
    eng.generate_batch([list(prompts[0])], MAX_NEW, [seeds[0]])
    counters["tree_calls_ragged"] += eng.counters["tree_calls_ragged"] - before
    del eng
    want_interp = devices[0].platform == "cpu"
    built = {(k, i): n for (k, i), n in KERNEL_TRACES.items()}
    print(f"[B] dispatches: padded tree passes={counters['tree_calls_padded']} "
          f"ragged tree passes={counters['tree_calls_ragged']} "
          f"fused commits={counters['commit_calls']}; kernel builds "
          f"(kernel, interpreted)->count: {built}", flush=True)
    check(counters["tree_calls_padded"] > 0, "no padded tree pass dispatched")
    check(counters["tree_calls_ragged"] > 0, "no ragged tree pass dispatched")
    check(counters["commit_calls"] > 0, "no fused commit dispatched")
    for k in PHASE_KERNELS:
        check(KERNEL_TRACES[k, want_interp] > 0, f"kernel {k} was never built")
    wrong = {k: n for (k, i), n in KERNEL_TRACES.items() if i != want_interp}
    check(not wrong, f"kernels built with interpret={not want_interp}: {wrong}")
    dmax, dmean, nodes = compare_first_pass(first["a"], first["b"])
    print(f"[B] first tree pass, pallas vs xla: max|dlogp|={dmax:.6g} "
          f"mean|dlogp|={dmean:.6g} over {nodes} nodes x {cfg.vocab} tokens "
          f"(tolerance {LOGP_ATOL})", flush=True)
    check(dmax <= LOGP_ATOL, f"pallas and xla first-pass log-probs differ by {dmax}")

    # ---- C: single-stream engine vs the batched pool (reported, not gated)
    c0 = _compile_secs[0]
    t0 = time.perf_counter()
    same, first_diff = [], []
    for r in range(2):
        eng = build_engine(serve_args(opts, "--streams", "0", "--seed", str(seeds[r])),
                           cfg, tp, dcfg, dp)
        single = eng.generate(list(prompts[r]), max_new=MAX_NEW)
        same.append(single == tokens_a[r])
        first_diff.append(next((i for i, (a, b) in enumerate(zip(single, tokens_a[r]))
                                if a != b), None))
    print(f"[C single-stream] wall_cold_s={time.perf_counter() - t0:.6g} "
          f"compile_s={_compile_secs[0] - c0:.6g} jit_compile_count={eng.jit_compile_count()} "
          f"peak_bytes_in_use=[{peak_bytes(devices)}]", flush=True)
    print(f"[C] single-stream tokens identical to batched for requests 0, 1: {same} "
          f"(first differing token index: {first_diff})", flush=True)


def four_chips(opts, devices) -> None:
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    rng = np.random.default_rng(opts.seed)
    args = serve_args(opts)
    cfg, tp, dcfg, dp = build_models(args)
    prompts = rng.integers(0, cfg.vocab, size=(N_REQUESTS, PROMPT_LEN))
    seeds = [opts.seed + r for r in range(N_REQUESTS)]
    eng = build_engine(args, cfg, tp, dcfg, dp)
    tokens_one, stats = timed_passes(eng, prompts, seeds)
    report("unsharded", eng, stats, devices)
    del eng
    args4 = serve_args(opts, "--data-shards", "4")
    eng = build_engine(args4, cfg, tp, dcfg, dp, devices=devices[:1])
    tokens_colocated, stats = timed_passes(eng, prompts, seeds)
    report("4 shards on chip 0", eng, stats, devices)
    del eng
    eng = build_engine(args4, cfg, tp, dcfg, dp)
    tokens_four, stats = timed_passes(eng, prompts, seeds)
    report("4 shards", eng, stats, devices)
    shard_devs = [sorted({d for ids in p.values() for d in ids}) for p in eng.placement()]
    print(f"[4 shards] devices per shard: {shard_devs}", flush=True)
    check(all(len(d) == 1 for d in shard_devs), "a shard spans several devices")
    check(len({d[0] for d in shard_devs}) == 4, "two shards share a device")
    same = tokens_four == tokens_colocated
    print(f"[4 shards] tokens identical to the same shards on chip 0: {same}", flush=True)
    print(f"[4 shards] tokens identical to the unsharded engine: "
          f"{tokens_four == tokens_one} (reported, not gated)", flush=True)
    check(same, "shards on four chips and the same shards on one chip differ")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard phase (needs 4 chips)")
    ap.add_argument("--smoke", action="store_true",
                    help="the smoke preset; allowed on the CPU backend (rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu" or opts.smoke,
          f"no TPU found (JAX reports {dev.platform}); --smoke rehearses on the CPU")
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
          f"compile_cache={setup_compile_cache()}", flush=True)
    (four_chips if opts.four_chips else one_chip)(opts, devices)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
