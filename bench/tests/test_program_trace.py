"""The readings of the program's own instrumentation: ``program_trace`` and
the per-layer readers built on it, on synthetic events; the "nothing to
read" case of a program without the counters or program names (every
reader says ``None``); and one traced run of the smoke cell on the CPU
through ``span_report``."""
from __future__ import annotations

import importlib.util

import pytest

from smoke import BENCH, spec

import program_trace as pt
import span_report

MS = 1_000_000
NEW = ("rewind_frac", "queue_ms", "readback_mb", "tree_dev_ms", "draft_dev_ms")


def reader(name):
    s = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.read


def record():
    """Two served steps on one chip: tree passes of 30 and 20 ms (padded,
    then ragged), draft programs of 5 + 3 ms, a draft prefill of 4 ms and a
    commit of 2 ms; counters of the window."""
    modules = [(0, "jit_drf_ing_p4(11)", 0, 5 * MS), (0, "jit_drf_step(12)", 5 * MS, 8 * MS),
               (0, "jit_tgt_tree_p8(13)", 10 * MS, 40 * MS),
               (0, "jit_commit_T8_P4(14)", 40 * MS, 42 * MS),
               (0, "jit_drf_prefill_p64(15)", 50 * MS, 54 * MS),
               (0, "jit_tgt_rtree_n32(16)", 60 * MS, 80 * MS)]
    return {"modules": modules, "ops": [], "spans": [], "steps": 2,
            "counters": {"steps_begun": 8, "steps_rewound": 2, "admitted": 4,
                         "queue_ms": 10.0, "readback_bytes": 3_000_000}}


def test_device_time_by_program_clips_and_names():
    mods = record()["modules"] + [(1, "jit_tgt_tree_p8(13)", 0, 10 * MS)]
    got = pt.device_time_by_program(mods, 0, 20 * MS, 70 * MS)
    assert got == pytest.approx({"jit_tgt_tree_p8": 0.020, "jit_commit_T8_P4": 0.002,
                                 "jit_drf_prefill_p64": 0.004, "jit_tgt_rtree_n32": 0.010})
    assert pt.device_time_by_program(mods, 1, 0, 100 * MS) == {"jit_tgt_tree_p8": 0.010}


def test_new_readers_on_a_synthetic_record():
    rec = record()
    assert reader("rewind_frac")(rec) == pytest.approx(25.0)
    assert reader("queue_ms")(rec) == pytest.approx(2.5)
    assert reader("readback_mb")(rec) == pytest.approx(1.5)
    assert reader("tree_dev_ms")(rec) == pytest.approx(25.0)   # (30 + 20) / 2
    assert reader("draft_dev_ms")(rec) == pytest.approx(4.0)   # (5 + 3) / 2, no prefill


def test_new_readers_find_nothing_and_say_so():
    """A program without the counters and the stable names, as an older
    commit: every new reader returns None."""
    rec = record()
    rec["counters"] = {"blocks": 4, "accepted": 3}
    rec["modules"] = [(0, "jit_tree_step(1)", 0, MS), (0, "jit_step(2)", MS, 2 * MS),
                      (0, "jit__unknown(3)", 2 * MS, 3 * MS)]
    for name in NEW:
        assert reader(name)(rec) is None, name
    rec = record()
    rec["counters"] = dict.fromkeys(rec["counters"], 0)
    rec["modules"] = []
    for name in NEW:
        assert reader(name)(rec) is None, name


def test_span_readings_and_gap_labels():
    spans = [("serve:step", 0, 100 * MS), ("serve:begin", 0, 40 * MS),
             ("serve:draft", 5 * MS, 35 * MS), ("serve:wait.draft", 10 * MS, 20 * MS),
             ("serve:verify", 50 * MS, 90 * MS), ("serve:wait.tree", 50 * MS, 70 * MS)]
    assert pt.wait_ms(spans, 2) == pytest.approx(15.0)
    assert pt.self_ms(spans, ("serve:ingest", "serve:draft"), 2) == pytest.approx(10.0)
    assert pt.self_ms(spans, ("serve:verify",), 2) == pytest.approx(10.0)
    assert pt.wait_ms([], 2) is None and pt.self_ms([], ("serve:verify",), 2) is None
    ops = [(0, "a", 0, 12 * MS), (0, "b", 18 * MS, 60 * MS), (0, "c", 60 * MS, 95 * MS),
           (1, "d", 0, 120 * MS)]
    assert pt.idle_gaps_by_span(ops, spans, 0, 0, 120 * MS) == [
        ["outside", pytest.approx(0.025)], ["serve:wait.draft", pytest.approx(0.006)]]


def test_traced_smoke_run_reads_the_program():
    s = spec()
    s["limits"] = {"target_gap": 1.0, "served_z": 100.0, "served_z_draft": 100.0,
                   "served_tokens_min": 1}
    s["per_layer"] += [{"name": n, "unit": "u"} for n in NEW]
    r = span_report.report(s, 12345678901, 6.0, allow_cpu=True, log=lambda _: None)
    m, p = r["metrics"], r["program"]
    for name in ("rewind_frac", "queue_ms", "readback_mb"):
        assert m[name]["value"] is not None, name
    # the CPU trace has no device plane: device readers find nothing
    assert "tree_dev_ms" not in m and "draft_dev_ms" not in m
    assert p["wait_ms"] > 0 and p["verify_host_ms"] > 0 and p["draft_host_ms"] > 0
    for phase in ("begin", "verify"):
        assert p["inside_vs_outside"][phase]["ratio"] == pytest.approx(1.0, abs=0.03)
    st = p["steps"]
    # every begun step finished or was rewound, but for one pending at an end
    assert abs(st["begun"] - st["finished"] - st["rewound"]) <= 1
