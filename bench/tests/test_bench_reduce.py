"""The benchmark's yardstick on the CPU: the trace reduction and the
per-layer readers on a small synthetic trace."""
from __future__ import annotations

import importlib.util
import json

import pytest

from smoke import BENCH, family

import reduce_trace as rt

G32B = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())


@pytest.fixture
def m():
    return family(BENCH / "families" / "dense.py").dims(G32B)


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_busy_union_and_gaps():
    ivs = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert rt.merge(ivs) == [(0, 20), (30, 40)]
    assert rt.busy(ivs, 0, 50) == 30
    assert rt.busy(ivs, 15, 35) == 10
    assert rt.idle_gaps(ivs, 0, 50) == [(20, 30), (40, 50)]
    assert rt.idle_gaps(ivs, -5, 20) == [(-5, 0)]


def test_span_self_time_innermost_and_totals():
    spans = [("bench:step", 0, 100), ("bench:verify", 10, 40), ("bench:retire", 50, 90),
             ("bench:begin", 60, 80)]
    st = rt.self_times(spans)
    assert st == {"bench:step": 30, "bench:verify": 30, "bench:retire": 20, "bench:begin": 20}
    assert rt.totals(spans)["bench:retire"] == 40
    assert rt.innermost(spans, 70) == "bench:begin"
    assert rt.innermost(spans, 45) == "bench:step"
    assert rt.innermost(spans, 120) is None


def synthetic_record(m):
    """A 100 ms window: the device runs a tree program from 10 to 60 ms with
    two ops inside it (40 ms busy), and a draft program with one op of 5 ms."""
    ms = 1_000_000
    ops = [(0, "fusion.1", 10 * ms, 30 * ms),
           (0, "fusion.2", 30 * ms, 50 * ms),
           (0, "while.3", 70 * ms, 75 * ms)]
    modules = [(0, "jit_tree_step(3)", 10 * ms, 60 * ms), (0, "jit_step(4)", 65 * ms, 80 * ms)]
    spans = [("bench:begin", 0, 10 * ms), ("bench:verify", 10 * ms, 60 * ms),
             ("bench:begin", 60 * ms, 65 * ms)]
    return {"ops": ops, "modules": modules, "spans": spans, "steps": 2,
            "window_ns": 100 * ms, "busy_ns": rt.busy([(s, e) for _, _, s, e in ops], 0, 100 * ms),
            "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}, "chips": 1,
            "model_ops": 197e12 * 0.001,
            "counters": {"pad_nodes_total": 4, "tree_lanes_total": 16, "accepted": 3,
                         "blocks": 2},
            "occupancy": [1.0, 0.5]}


def test_readers_on_a_synthetic_record(m):
    rec = synthetic_record(m)
    assert reader("idle_frac")(rec) == pytest.approx(100 * (1 - 45 / 100))
    assert reader("begin_ms")(rec) == pytest.approx(7.5)
    assert reader("verify_ms")(rec) == pytest.approx(25.0)
    assert reader("occupancy")(rec) == pytest.approx(75.0)
    assert reader("pad_frac")(rec) == pytest.approx(25.0)
    assert reader("block_eff")(rec) == pytest.approx(2.5)
    assert reader("step_mfu")(rec) == pytest.approx(1.0)


def test_readers_find_nothing_and_say_so(m):
    rec = synthetic_record(m)
    rec.update(modules=[], spans=[], busy_ns=0, model_ops=0, occupancy=[],
               counters={"pad_nodes_total": 0, "tree_lanes_total": 0, "accepted": 0,
                         "blocks": 0})
    for name in ("begin_ms", "verify_ms", "idle_frac", "step_mfu",
                 "occupancy", "pad_frac", "block_eff"):
        assert reader(name)(rec) is None, name


def test_device_time_by_op_names_program_and_op():
    ops = [(0, "%while.22 = (s32[], bf16[16,7]{1,0:T(8,128)}) while(%tuple.1), body=%b", 10, 30),
           (0, "%copy.1 = bf16[4]{0} copy(%x)", 40, 45),
           (1, "%copy.1 = bf16[4]{0} copy(%x)", 40, 45)]
    mods = [(0, "jit_tree_step(123)", 5, 35), (0, "jit_commit(9)", 38, 50)]
    got = rt.device_time_by_op(ops, mods, 0, 0, 100)
    assert got == {"jit_tree_step/while.22 (while)": 20e-9, "jit_commit/copy.1 (copy)": 5e-9}
