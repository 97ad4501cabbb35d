"""The program's own instrumentation in a profiler trace: the engine's
``serve:`` spans (``src/repro/serving/tracing.py``) and its programs' stable
names (``jit_<cache key>``).

``reduce_trace.load`` keeps only the harness's ``bench:`` spans, so this
module reads the ``serve:`` spans itself (``load``); the functions below are
plain Python over ``(name, start, end)`` spans and ``(device, name, start,
end)`` module events, as in ``reduce_trace``, so tests feed them synthetic
events.  A program that has no ``serve:`` spans or stable names (an older
commit) leaves every reading here empty: ``None``, never an error.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

from reduce_trace import DEVICE_PLANE, clip, idle_gaps, innermost, program_label, self_times

PREFIX = "serve:"
WAIT = PREFIX + "wait."
TREE_PROGRAMS = re.compile(r"jit_tgt_r?tree_")       # the target tree pass, padded or ragged
DRAFT_PROGRAMS = re.compile(r"jit_drf_(ing|step|bstep)")  # draft ingest and drafting


def load(trace_dir: str) -> dict:
    """The trace's ``serve:`` spans, ``(name, start, end)``, and the
    harness's window ``(start, end)`` (None when the trace has none)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise RuntimeError(f"no trace written under {trace_dir}")
    spans, window = [], None
    for plane in ProfileData.from_file(files[-1]).planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.name, e.start_ns, e.end_ns))
                elif e.name == "bench:window":
                    window = (e.start_ns, e.end_ns)
    return {"program_spans": spans, "window": window}


def device_time_by_program(modules, device: int, lo: float, hi: float) -> dict[str, float]:
    """Seconds per program (``jit_tgt_tree_p8`` from
    ``jit_tgt_tree_p8(1234)``) on one device, clipped to [lo, hi]."""
    out: dict[str, float] = defaultdict(float)
    for d, name, s, e in modules:
        if d == device:
            for cs, ce in clip([(s, e)], lo, hi):
                out[program_label(name)] += (ce - cs) / 1e9
    return dict(out)


def program_ms_per_step(rec: dict, pattern: re.Pattern) -> float | None:
    """Device milliseconds a served step of the programs whose names match
    ``pattern``, averaged over the devices in the trace.  The harness's
    trace holds the window alone (it starts as the window opens and stops
    as it closes, after a wait for the device), so every module counts."""
    devices = sorted({d for d, *_ in rec["modules"]})
    if not devices or not rec["steps"]:
        return None
    inf = float("inf")
    total = sum(t for dev in devices
                for name, t in device_time_by_program(rec["modules"], dev, -inf, inf).items()
                if pattern.match(name))
    return 1e3 * total / len(devices) / rec["steps"] if total else None


def wait_ms(spans, steps: int) -> float | None:
    """Host milliseconds a step blocked on device-to-host reads."""
    t = sum(e - s for name, s, e in spans if name.startswith(WAIT))
    return t / 1e6 / steps if t and steps else None


def self_ms(spans, names, steps: int) -> float | None:
    """Host milliseconds a step spent in the named spans with no other
    ``serve:`` span (a read, a dispatch, a nested phase) open inside."""
    st = self_times(spans)
    t = sum(st.get(n, 0.0) for n in names)
    return t / 1e6 / steps if t and steps else None


def idle_gaps_by_span(ops, spans, device: int, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` longest stretches of [lo, hi] in which the device ran no
    operation, longest first, as ``[label, seconds]``: the innermost
    ``serve:`` span open at the gap's middle, or ``outside``."""
    busy = [(s, e) for d, _, s, e in ops if d == device]
    gaps = sorted(idle_gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[innermost(spans, (s + e) / 2) or "outside", (e - s) / 1e9] for s, e in gaps]
