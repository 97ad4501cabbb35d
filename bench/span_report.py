#!/usr/bin/env python3
"""A traced run of one cell, read through the program's own spans.

    python3 bench/span_report.py --workload g3-2b.chat --seed 7 --seconds 51

Runs the cell as ``bench/run.py --trace 1`` does and prints its result line
with one more key, ``program``: what the engine's ``serve:`` spans, its
counters and its programs' stable names (``bench/program_trace.py``) show of
the same window, before the harness deletes the trace:

- ``wait_ms``: host time a step blocked on device-to-host reads
  (``serve:wait.*``), and ``wait_ms_by_read`` split by what was read;
- ``draft_host_ms``, ``verify_host_ms``: self time a step of
  ``serve:ingest`` + ``serve:draft``, and of ``serve:verify``;
- ``host_self_ms``: self time a step of every ``serve:`` span;
- ``inside_vs_outside``: summed ``serve:begin`` / ``serve:verify`` against
  the harness's ``bench:begin`` / ``bench:verify`` (ratio near 1);
- ``idle_gaps_by_span``: the ten longest device idle gaps, each labelled
  with the innermost ``serve:`` span open over it, or ``outside``;
- ``device_ms_by_program``: device time a step per program name;
- ``steps``: served steps, and the engine's ``steps_begun``,
  ``steps_rewound``, ``steps_drained`` and finished (``commit_calls``)
  deltas over the window;
- ``spans_per_step``: ``serve:`` spans the engine opened a step;
- ``end_to_end``: the window's end-to-end metrics, which a traced run of
  ``bench/run.py`` does not print, to price the trace against an untraced
  run of the same seed.

A diagnostic beside the benchmark, not part of it: the benchmark's
command is ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys

import program_trace as pt
import reduce_trace as rt
import run


def program_readings(tr: dict, rec: dict) -> dict:
    """The readings above from ``program_trace.load``'s output and the
    harness's record of the same window."""
    lo, hi = tr["window"]
    spans = [s for s in tr["program_spans"] if s[2] > lo and s[1] < hi]
    steps = rec["steps"]

    def per_step(t):
        return t / 1e6 / steps if steps else None

    waits: dict[str, float] = {}
    for name, s, e in spans:
        if name.startswith(pt.WAIT):
            waits[name[len(pt.WAIT):]] = waits.get(name[len(pt.WAIT):], 0.0) + e - s
    inside, outside = rt.totals(spans), rt.totals(rec["spans"])
    dev = sorted({d for d, *_ in rec["ops"]}) or [0]
    by_prog = pt.device_time_by_program(rec["modules"], dev[0], lo, hi)
    c = rec["counters"]
    return {
        "wait_ms": pt.wait_ms(spans, steps),
        "wait_ms_by_read": {k: per_step(v) for k, v in sorted(waits.items())},
        "draft_host_ms": pt.self_ms(spans, ("serve:ingest", "serve:draft"), steps),
        "verify_host_ms": pt.self_ms(spans, ("serve:verify",), steps),
        "host_self_ms": {k: per_step(v) for k, v in sorted(rt.self_times(spans).items())},
        "inside_vs_outside": {
            phase: {"serve_ms": per_step(inside.get(f"serve:{phase}", 0.0)),
                    "bench_ms": per_step(outside.get(f"bench:{phase}", 0.0)),
                    "ratio": (inside.get(f"serve:{phase}", 0.0) / outside[f"bench:{phase}"]
                              if outside.get(f"bench:{phase}") else None)}
            for phase in ("begin", "verify")},
        "idle_gaps_by_span": pt.idle_gaps_by_span(rec["ops"], spans, dev[0], lo, hi),
        "device_ms_by_program": dict(sorted(
            ((k, 1e3 * v / steps) for k, v in by_prog.items()), key=lambda x: -x[1])[:16])
        if steps else {},
        "steps": {"served": steps, "begun": c.get("steps_begun"),
                  "rewound": c.get("steps_rewound"), "drained": c.get("steps_drained"),
                  "finished": c.get("commit_calls")},
        "spans_per_step": len(spans) / steps if steps else None,
    }


def report(spec: dict, seed: int, seconds: float, *, allow_cpu: bool = False,
           log=print) -> dict:
    """One traced run of the cell; its result with ``program`` added."""
    reduce0, metrics0 = run.reduce_trace, run.Loop.metrics
    found: dict = {}

    def reduce_and_read(trace_dir, recorder, loop_rec, peak, chips):
        rec, device, breakdown = reduce0(trace_dir, recorder, loop_rec, peak, chips)
        found.update(program_readings(pt.load(str(trace_dir)), rec))
        return rec, device, breakdown

    def metrics(loop, chips):
        e2e = metrics0(loop, chips)
        found["end_to_end"] = e2e
        return e2e

    run.reduce_trace, run.Loop.metrics = reduce_and_read, metrics
    try:
        result = run.run_cell(spec, seed, seconds, True, allow_cpu=allow_cpu, log=log)
    finally:
        run.reduce_trace, run.Loop.metrics = reduce0, metrics0
    result["program"] = found
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        spec = run.load_cell(args.workload)
        result = report(spec, args.seed, args.seconds,
                        log=lambda s: print(s, file=sys.stderr, flush=True))
    except run.Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
