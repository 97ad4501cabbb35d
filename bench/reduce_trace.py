"""Reduction of a profiler trace to the run's shared record.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps three
lists, all on the trace's one clock (nanoseconds):

* ``ops``: device operations, ``(device, name, start, end)``, from each TPU
  plane's "XLA Ops" line;
* ``modules``: compiled programs run on the device, same form, from the
  "XLA Modules" line;
* ``spans``: the harness's host spans, ``(name, start, end)``, whose names
  start with ``bench:``.

The functions below compute what the per-layer readers need from those
lists; they are plain Python so that tests can feed them synthetic events.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise RuntimeError(f"no trace written under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in ("XLA Ops", "XLA Modules"):
                out = ops if line.name == "XLA Ops" else modules
                for e in line.events:
                    out.append((int(dev.group(1)), e.name, e.start_ns, e.end_ns))
            elif dev is None:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    return {"ops": ops, "modules": modules, "spans": spans}


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def idle_gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    gaps, cur = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def innermost(spans, t: float):
    """Name of the shortest span that contains time ``t``, or None."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return None if best is None else best[0]


def self_times(spans) -> dict[str, float]:
    """Per span name, the summed time its spans spent with no other span
    nested inside them (spans nest, as host call stacks do)."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [name, start, end, child time]

    def close(frame):
        out[frame[0]] += (frame[2] - frame[1]) - frame[3]
        if stack:
            stack[-1][3] += frame[2] - frame[1]

    for name, s, e in spans:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def totals(spans) -> dict[str, float]:
    """Per span name, the summed length of its spans."""
    out: dict[str, float] = defaultdict(float)
    for name, s, e in spans:
        out[name] += e - s
    return dict(out)


OPCODE = re.compile(r"\b([a-z][a-z0-9-]*)\(")


def op_label(text: str) -> str:
    """A short name for an HLO op event: ``while.22 (while)`` from
    ``%while.22 = (s32[], ...) while(...)``."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    return f"{name.lstrip('%')} ({m.group(1)})" if m else name.lstrip("%")


def program_label(text: str) -> str:
    """``jit_tree_step`` from ``jit_tree_step(17511529183589481640)``."""
    return text.split("(")[0]


def device_time_by_op(ops, modules, device: int, lo: float, hi: float) -> dict[str, float]:
    """Seconds per ``program/op`` on one device inside [lo, hi]; an op is
    named by the program whose run contains it."""
    progs = sorted((s, e, program_label(n)) for d, n, s, e in modules if d == device)
    out: dict[str, float] = defaultdict(float)
    i = 0
    for d, name, s, e in sorted(ops, key=lambda x: x[2]):
        if d != device or s < lo or e > hi:
            continue
        while i < len(progs) and progs[i][1] < s:
            i += 1
        prog = progs[i][2] if i < len(progs) and progs[i][0] <= s else "?"
        out[f"{prog}/{op_label(name)}"] += (e - s) / 1e9
    return dict(out)
