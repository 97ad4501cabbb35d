#!/usr/bin/env python3
"""Run one benchmark cell: the batched speculative engine under closed-loop
traffic on the chips of this machine.

    python3 bench/run.py --workload g3-2b.chat --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the configuration names its family
(``bench/families/<family>.py``: its sizes, weights, work and, where the
harness's own does not cover its programs, warm-up); per-layer metrics are
readers in ``bench/metrics/<metric>.py``.  A run:

1. set-up (``setup_s``, from process start to the first timed step): random
   bf16 weights made on the device in one compiled call from the
   configuration's ``weight_seed`` (every seed serves the same model), the
   engine built as ``launch/serve.build_engine`` builds it but with the
   traffic file's settings (a cell on more than one chip: the sharded
   engine, one pool and one chip a shard, behind its router), then a
   warm-up of every program shape the cell's traffic can reach, then a
   pre-window of the traffic itself;
2. the window: ``--seconds`` of closed-loop serving, one client per pool
   row, each submitting its next request as soon as its last one ends;
   with ``--trace 1`` under the profiler, with the harness's spans;
3. the check: once the window has closed and the program's state is freed,
   the plain reference (``bench/configs/<reference>.py``) runs over a
   sample of finished requests drawn from ``--seed``.  Each number compared
   has its limit in ``bench/limits/<cell>.json``:

   - ``target_gap``: at every served position, how far below the
     reference's best logit lies the token the target tree pass put first
     (the tree pass, its paged KV reads and every fused commit before it);
   - ``served_z``, ``served_z_draft``: whether the served tokens are draws
     from the reference target's distribution, as lossless speculative
     sampling at T = 1 makes them (the verifier): the served tokens' summed
     ``-log p``, and summed ``log p/q`` against the reference draft, each
     less its mean under ``p`` and over its standard deviation, so that
     a sound run reads a standard normal;
   - ``draft_gap`` where the limits file has it: the target_gap of the
     draft's first choice.

   With ``--control 1`` the int8 control's first choices stand in the
   program's place in the gaps, and ``correct`` has to come out false.

The last line of standard output is the result, JSON; the numbers compared
and their limits end it and standard error.  Without a TPU, with fewer chips
than the cell asks for, or on a device missing from ``bench/peaks.json``,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

from faults import FAULTS  # noqa: E402
from traffic import Clients  # noqa: E402
from weights import make_weights  # noqa: E402

WORK_DIR = ROOT / ".bench"


class Refused(Exception):
    """The run cannot measure here: no result is printed."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell's entry, configuration, family, traffic and metric list,
    found by name from ``BENCHMARK.json``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    per_layer = [m for m in bench["per_layer"] if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    config = json.loads((ROOT / conf["file"]).read_text())
    return {"cell": cell, "config": config,
            "family": load_module(BENCH / "families" / f"{config['family']}.py"),
            "traffic": json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
            "limits": json.loads((BENCH / "limits" / f"{name}.json").read_text()),
            "per_layer": per_layer, "end_to_end": end_to_end}


# ------------------------------------------------------------------ device --

def check_device(chips: int, allow_cpu: bool):
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if allow_cpu:
        return devs, {"flops_bf16": 1.0, "hbm_bytes_per_s": 1.0}
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} ({kind})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} has no peaks in bench/peaks.json")
    return devs, peaks[kind]


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache, at ``.jax_cache`` in the root of
    the checkout whatever the environment says, so that two checkouts on one
    machine share nothing and a checkout's second run finds every program;
    every program is cached, however fast it compiled."""
    import jax

    path = ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    path = str(path)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts compilations through ``jax.monitoring`` (the listener of
    ``chip_smoke.py``): backend compiles and the seconds they took."""

    def __init__(self):
        import jax

        self.compiles, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.secs += secs


# ------------------------------------------------------------------ engine --

def engines(eng) -> list:
    """The pool engines a (possibly sharded) engine drives, in shard order."""
    return getattr(eng, "shards", [eng])


def build(spec: dict, seed: int):
    """Program configs checked against the configuration file, seeded
    weights, and the engine with the traffic file's settings: one pool, or
    on a cell of more than one chip a pool and a chip a shard."""
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config, get_smoke
    from repro.launch.serve import make_draft_cfg
    from repro.models.transformer import init_params
    from repro.serving.batch_engine import (
        BatchedSpeculativeEngine, ShardedBatchedSpeculativeEngine)
    from repro.serving.engine import EngineConfig, SamplingParams

    conf, eng_s, fam = spec["config"], spec["traffic"]["engine"], spec["family"]
    base = get_smoke(conf["arch"]) if conf.get("smoke") else get_config(conf["arch"])
    cfg = base.replace(attention_impl=eng_s["attention_impl"], **conf.get("program_replace", {}))
    dcfg = make_draft_cfg(cfg)
    mt, md = fam.dims(conf), fam.dims(conf["draft"])
    for c, m, what in ((cfg, mt, "target"), (dcfg, md, "draft")):
        have = fam.program_dims(c)
        if have != m:
            raise Refused(f"{what} of the program {have} differs from the configuration file {m}")
    tp, dp = make_weights(fam, conf["weight_seed"], mt, md)
    for p, c in ((tp, cfg), (dp, dcfg)):
        want = jax.eval_shape(lambda k, c=c: init_params(c, k), jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), p)
        if jax.tree.structure(want) != jax.tree.structure(p) or got != jax.tree.map(
                lambda a: (a.shape, a.dtype), want):
            raise Refused(f"weights of {c.name} do not have the program's layout")
    ecfg = EngineConfig(verifier=eng_s["verifier"], K=eng_s["K"], L1=eng_s["L1"],
                        L2=eng_s["L2"], max_cache=eng_s["max_cache"], seed=seed)
    args = (cfg, tp, dcfg, dp, ecfg, SamplingParams(eng_s["temperature"], eng_s["top_p"]))
    kw = dict(n_slots=eng_s["streams"], paged=True, block_size=eng_s["block_size"],
              pipeline=eng_s["pipeline"], ragged=eng_s["ragged"])
    if spec["cell"]["chips"] > 1:
        eng = ShardedBatchedSpeculativeEngine(*args, data_shards=spec["cell"]["chips"], **kw)
    else:
        eng = BatchedSpeculativeEngine(*args, **kw)
    return eng, mt, md


class Recorder:
    """What the harness reads from the engine while it runs: per request the
    token the target tree pass and the draft put first at every served
    position, and, in traced runs, the phases as ``bench:`` spans plus the
    real work each step did (for ``step_mfu``, counted by the family).  A
    sharded engine is read shard by shard, its requests keyed by the id the
    router's ``submit`` returns, and a fault is planted in every shard."""

    SPANS = {"begin_step": "begin", "_admit": "admit", "_ingest_deltas": "draft",
             "_draft_trees": "draft", "_target_tree_dispatch": "dispatch",
             "_target_tree_dispatch_ragged": "dispatch", "verify_step": "verify",
             "commit_step": "commit", "retire_step": "retire"}

    def __init__(self, eng, family, mt: dict, md: dict, spans: bool, fault=None):
        self.family, self.mt, self.md = family, mt, md
        self.first: dict[int, list] = {}
        self.ids: dict[tuple, int] = {}  # (shard, the shard's id) -> the router's id
        self.work_on = False
        self.model_ops = 0
        shards = engines(eng)
        for si, sh in enumerate(shards):
            self._read(sh, si, spans)
            if fault is not None:
                fault(sh)
        if len(shards) > 1:
            self._key_by_router(eng)

    def _key_by_router(self, eng) -> None:
        """Map each shard's request id to the router's: the router's
        ``submit`` calls one shard's ``submit`` and returns its own id."""
        last = []
        for si, sh in enumerate(eng.shards):
            def submit(*a, _submit=sh.submit, si=si, **k):
                last[:] = [(si, _submit(*a, **k))]
                return last[0][1]

            sh.submit = submit
        route = eng.submit

        def routed(*a, **k):
            rid = route(*a, **k)
            self.ids[last[0]] = rid
            return rid

        eng.submit = routed

    def _read(self, eng, si: int, spans: bool) -> None:
        from repro.serving.engine import SpeculativeEngine

        accepted_nodes = SpeculativeEngine._accepted_nodes
        adv = eng._advance_stream

        def advance(slot, tree, accepted, corr, h_q, node_path=None):
            st = eng.streams[slot]
            path = node_path if node_path is not None else accepted_nodes(tree, accepted)
            rid = self.ids.get((si, st["rid"]), st["rid"])
            self.first.setdefault(rid, []).extend(
                (int(np.argmax(tree.p[n])), int(np.argmax(tree.q[n]))) for n in [0, *path])
            if self.work_on:
                self._count_step(st, tree)
            return adv(slot, tree, accepted, corr, h_q, node_path)

        eng._advance_stream = advance
        if spans:
            import jax

            for meth, label in self.SPANS.items():
                setattr(eng, meth, self._span(getattr(eng, meth), "bench:" + label, jax))
            pre = eng._prefill_row

            def prefill(cfg, params, ctx, name):
                if self.work_on:
                    m = self.mt if name == "tgt" else self.md
                    self.model_ops += self.family.prefill_flops(m, len(ctx))
                return pre(cfg, params, ctx, name)

            eng._prefill_row = prefill

    @staticmethod
    def _span(fn, name, jax):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    def _count_step(self, st, tree):
        """Real work of one stream's verified step: its tree pass (every
        node against the committed prefix and its ancestors), its draft
        ingest, and the draft tokens that built the tree."""
        flops = self.family.forward_flops
        prefix = len(st["committed"]) - 1
        depths = [int(d) for d in tree.depth]
        ctx = sum(prefix + d + 1 for d in depths)
        self.model_ops += flops(self.mt, len(depths), ctx)
        n_ing = len(st["draft_delta"])
        n_draft = len(depths) - 1
        self.model_ops += flops(self.md, n_ing + n_draft, (n_ing + n_draft) * (prefix + 1))


def warm_up(eng, traffic: dict) -> None:
    """The harness's warm-up of one pool engine on the tree strategy, for a
    family that brings none.  Compile every program shape the cell's
    traffic can reach before the window: each prefill bucket of target and
    draft, the draft ingest widths, the padded and ragged tree passes, the
    fused commits, the probability warps on each of their shapes, and the
    pipeline's rewind for every row count.  Every call leaves the (still
    empty) pool as it found it."""
    import jax.numpy as jnp

    from repro.serving.serve_step import (
        make_pool_commit_step, make_pool_decode_step, make_pool_ragged_tree_step,
        make_pool_tree_step, next_pow2)

    e = traffic["engine"]
    n, smax = eng.n_slots, e["max_cache"]
    *_, Tpad = eng._bucket_actions({0: (e["K"], e["L1"], e["L2"])})
    lo, hi = traffic["prompt"]["min"] - 1, traffic["prompt"]["max"] - 1
    Tp = next_pow2(lo)
    while Tp <= min(next_pow2(hi), smax):
        for cfg, params, name in ((eng.tc, eng.tp, "tgt"), (eng.dc, eng.dp, "drf")):
            eng._prefill_row(cfg, params, [0] * Tp, name)
        Tp *= 2
    Dp = 1
    while Dp <= next_pow2(1 + e["L1"] + e["L2"]):
        fn = eng._jit(f"drf_ing_p{Dp}", make_pool_decode_step(eng.dc))
        logits, _, _ = fn(eng.dp, eng.dpool.cache, jnp.zeros((n, Dp), jnp.int32),
                          jnp.zeros((n,), jnp.int32))
        np.asarray(eng._warp(logits))
        Dp *= 2
    fn = eng._jit(f"tgt_tree_p{Tpad}", make_pool_tree_step(eng.tc), donate_argnums=1)
    logits, eng.tpool.cache, hid = fn(eng.tp, eng.tpool.cache, jnp.zeros((n, Tpad), jnp.int32),
                                      jnp.full((n, Tpad), -1, jnp.int32),
                                      jnp.zeros((n,), bool))
    np.asarray(eng._warp(logits)), np.asarray(hid)
    if eng._ragged_ok:
        N = next_pow2(max(Tpad, eng._ragged_align))
        while N < n * Tpad:
            fn = eng._jit(f"tgt_rtree_n{N}", make_pool_ragged_tree_step(eng.tc),
                          donate_argnums=1)
            z, m1 = jnp.zeros((N,), jnp.int32), jnp.full((N,), -1, jnp.int32)
            logits, eng.tpool.cache, hid = fn(eng.tp, eng.tpool.cache, z, z, m1, z, m1,
                                              jnp.zeros((n,), jnp.int32))
            np.asarray(eng._warp(logits)), np.asarray(hid)
            N *= 2
    P = 1
    while P <= next_pow2(e["L1"] + e["L2"]):
        fn = eng._jit(f"commit_T{Tpad}_P{P}", make_pool_commit_step(eng.tc, Tpad),
                      donate_argnums=0)
        eng.tpool.cache = fn(eng.tpool.cache, jnp.zeros((n, P), jnp.int32),
                             jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.int32),
                             jnp.zeros((n,), bool))
        P *= 2
    for rows in range(1, n + 1):
        for pool in (eng.tpool, eng.dpool):
            pool.invalidate_from({r: 0 for r in range(rows)})
    import jax

    jax.block_until_ready((eng.tpool.cache, eng.dpool.cache))


def warm_all(eng, spec: dict) -> None:
    """The family's warm-up, or the harness's, on every shard's engine in
    turn."""
    warm = getattr(spec["family"], "warm_up", warm_up)
    for sh in engines(eng):
        warm(sh, spec["traffic"])


def pools(eng) -> list:
    """Every shard's target pool, for a wait on the device."""
    return [sh.tpool.cache for sh in engines(eng)]


# ------------------------------------------------------------------ window --

class Loop:
    """The closed loop: one client per pool row; token and time bookkeeping
    for the end-to-end metrics."""

    def __init__(self, eng, clients: Clients):
        self.eng, self.clients = eng, clients
        self.meta: dict[int, dict] = {}
        self.window = None  # (t0, t1) once the window has closed
        self.t_open = None
        self.on_step = None  # traced runs: read the pool at each step boundary

    def submit(self, c: int):
        req = self.clients.next(c)
        t = time.perf_counter()
        rid = self.eng.submit(req.prompt, max_new=req.max_new, seed=req.seed)
        self.meta[rid] = {"client": c, "prompt": req.prompt, "max_new": req.max_new,
                          "t_submit": t, "in_window": self.t_open is not None,
                          "emitted": 0, "times": [], "counted": []}
        return rid

    def step(self):
        if self.on_step is None:
            events = self.eng.step()
        else:
            import jax

            self.on_step()
            with jax.profiler.TraceAnnotation("bench:step"):
                events = self.eng.step()
        t = time.perf_counter()
        for ev in events:
            m = self.meta[ev["rid"]]
            k = min(len(ev["new_tokens"]), m["max_new"] - m["emitted"])
            if k > 0:
                m["emitted"] += k
                m["times"].append(t)
                m["counted"].append(k)
            if ev["done"] and self.window is None:
                self.submit(m["client"])
        return t

    def run_window(self, seconds: float) -> None:
        self.t_open = time.perf_counter()
        end = self.t_open + seconds
        t = self.t_open
        while t < end:
            t = self.step()
        self.window = (self.t_open, t)
        # every request submitted inside the window gets its first token
        waiting = [m for m in self.meta.values() if m["in_window"] and not m["times"]]
        while any(not m["times"] for m in waiting):
            self.step()

    def metrics(self, chips: int) -> dict:
        """The end-to-end metrics; output tokens per second per chip."""
        t0, t1 = self.window
        tokens = sum(k for m in self.meta.values()
                     for t, k in zip(m["times"], m["counted"]) if t0 < t <= t1)
        gaps = [b - a for m in self.meta.values()
                for a, b in zip(m["times"], m["times"][1:]) if t0 <= a and b <= t1]
        ttft = [m["times"][0] - m["t_submit"] for m in self.meta.values() if m["in_window"]]
        return {"out_tok_s": tokens / (t1 - t0) / chips,
                "itl_p95_ms": 1e3 * statistics.quantiles(gaps, n=20)[-1],
                "ttft_p50_ms": 1e3 * statistics.median(ttft),
                "_tokens": tokens, "_gaps": len(gaps), "_ttft_n": len(ttft)}


# ------------------------------------------------------------------- check --

def sample_requests(loop: Loop, eng, want_tokens: int, seed: int) -> list[int]:
    """Finished requests whose every token the timed engine made: the one
    with the longest sequence, then others drawn from the seed until the
    sample holds ``want_tokens`` served tokens."""
    done = [r for r in eng.finished if eng.finished[r]["reason"] == "length"]
    done.sort(key=lambda r: -(len(loop.meta[r]["prompt"]) + len(eng.finished[r]["tokens"])))
    if not done:
        return []
    pick, rest = [done[0]], done[1:]
    rng = np.random.default_rng([seed, 1])
    for r in rng.permutation(len(rest)):
        if sum(len(eng.finished[p]["tokens"]) for p in pick) >= want_tokens:
            break
        pick.append(rest[r])
    return pick


def reference_readings(spec: dict, seqs: list, control: bool) -> dict:
    """The reference's readings over the sample: the widest and the mean
    gap of the target's and the draft's first choices (with ``control``,
    also of the int8 control's), and the two served-token z-scores."""
    import jax
    import jax.numpy as jnp

    conf, fam = spec["config"], spec["family"]
    ref = load_module(BENCH / "configs" / f"{conf['reference']}.py")
    mt, md = fam.dims(conf), fam.dims(conf["draft"])
    tp, dp = make_weights(fam, conf["weight_seed"], mt, md)
    T = spec["traffic"]["check_len"]
    gaps = ["gap_target", "gap_draft"] + (["control_target", "control_draft"] if control else [])
    sums = {k: 0.0 for k in ("nll", "nll_mean", "nll_var", "llr", "llr_mean", "llr_var")}
    widest = {k: 0.0 for k in gaps}
    gap_sum = {k: 0.0 for k in gaps}
    n_all = 0
    with jax.default_matmul_precision("highest"):
        for prompt, served, first in seqs:
            n = len(served)
            toks = np.zeros(T, np.int32)
            toks[:len(prompt) + n - 1] = list(prompt) + list(served[:-1])
            at = np.full(T, -1, np.int32)
            at[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            cols = np.zeros((3, T), np.int32)
            cols[0, :n] = served
            cols[1:, :n] = np.asarray(first[:n], np.int32).T
            r = ref.readings(tp, dp, jnp.asarray(toks), jnp.asarray(at), *map(jnp.asarray, cols),
                             mt=tuple(sorted(mt.items())), md=tuple(sorted(md.items())),
                             control=control)
            r = {k: np.asarray(v, np.float64)[:n] for k, v in r.items()}
            for k in sums:
                sums[k] += r[k].sum()
            for k in gaps:
                widest[k] = max(widest[k], float(r[k].max()))
                gap_sum[k] += r[k].sum()
            n_all += n
    del tp, dp
    names = {"gap_target": "target_gap", "gap_draft": "draft_gap",
             "control_target": "control_target_gap", "control_draft": "control_draft_gap"}
    out = {}
    for k in gaps:
        out[names[k]] = widest[k]
        out[names[k] + "_mean"] = float(gap_sum[k]) / max(n_all, 1)
    for key, name in (("nll", "served_z"), ("llr", "served_z_draft")):
        var = sums[key + "_var"]
        out[name] = float((sums[key] - sums[key + "_mean"]) / np.sqrt(var)) if var > 0 else None
    out["served_nll_excess"] = float(sums["nll"] - sums["nll_mean"]) / max(n_all, 1)
    return out


# ------------------------------------------------------------------- trace --

def reduce_trace(trace_dir: Path, recorder: Recorder, loop_rec: dict, peak: dict,
                 chips: int) -> tuple[dict, dict, dict]:
    """The shared record of a traced window, the device fields and the
    breakdown."""
    import reduce_trace as rt

    tr = rt.load(str(trace_dir))
    win = [(s, e) for name, s, e in tr["spans"] if name == "bench:window"]
    if not win:
        raise RuntimeError("the traced window span is missing from the trace")
    lo, hi = win[0]
    devices = sorted({d for d, *_ in tr["ops"]}) or [0]
    busy_ns = sum(rt.busy([(s, e) for d, _, s, e in tr["ops"] if d == dev], lo, hi)
                  for dev in devices) / len(devices)
    spans = [s for s in tr["spans"] if s[0] != "bench:window" and s[2] > lo and s[1] < hi]
    rec = dict(loop_rec, ops=tr["ops"], modules=tr["modules"], spans=spans,
               window_ns=hi - lo, busy_ns=busy_ns, peak=peak, chips=chips,
               model_ops=recorder.model_ops)
    per_op = rt.device_time_by_op(tr["ops"], tr["modules"], devices[0], lo, hi)
    d0 = [(s, e) for d, _, s, e in tr["ops"] if d == devices[0]]
    gaps = sorted(rt.idle_gaps(d0, lo, hi), key=lambda g: g[0] - g[1])[:10]
    label = {f"bench:{v}": v for v in Recorder.SPANS.values()}
    label["bench:admit"] = "admit/prefill"
    label["bench:step"] = "step"
    breakdown = {
        "device_ops": sorted(([k, v] for k, v in per_op.items()), key=lambda x: -x[1])[:10],
        "idle_gaps": [[label.get(rt.innermost(spans, (s + e) / 2), "harness"), (e - s) / 1e9]
                      for s, e in gaps],
    }
    device = {"busy_s": busy_ns / 1e9, "window_s": (hi - lo) / 1e9}
    return rec, device, breakdown


# --------------------------------------------------------------------- run --

def serve(spec: dict, seed: int, seconds: float, trace: bool, devs, peak: dict,
          fault, log) -> tuple[dict, list]:
    """Set-up, the window and its metrics.  Returns the result so far and
    the sampled requests for the check; every reference to the program's
    state dies with this function's frame."""
    import jax

    cache_dir = setup_compile_cache()
    compiles = CompileCounter()
    traffic = spec["traffic"]
    eng, mt, md = build(spec, seed)
    rec = Recorder(eng, spec["family"], mt, md, spans=trace, fault=fault)
    warm_all(eng, spec)
    clients = Clients(traffic, mt["V"], seed)
    loop = Loop(eng, clients)
    for c in range(clients.n):
        loop.submit(c)
    for _ in range(traffic["prewindow_steps"]):
        loop.step()
    jax.block_until_ready(pools(eng))
    # Exempt what set-up made (modules, compiled programs, the engine) from
    # the collector's full passes, as a long-running server does once it is
    # warm: such a pass rescans all of it, about 0.1-0.4 s each, a few times
    # a window.  The window's own garbage is collected as before.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROC0
    c_setup = compiles.compiles
    log(f"set-up: {setup_s:.3f} s, {c_setup} compiles ({compiles.secs:.3f} s), "
        f"compile cache {cache_dir}")

    counters0 = dict(eng.counters)
    occupancy: list[float] = []
    trace_dir = WORK_DIR / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        rec.work_on = True
        loop.on_step = lambda: occupancy.append(len(eng.streams) / eng.n_slots)
        with jax.profiler.TraceAnnotation("bench:window"):
            loop.run_window(seconds)
            jax.block_until_ready(pools(eng))
        rec.work_on = False
        jax.profiler.stop_trace()
    else:
        loop.run_window(seconds)
    c_window = compiles.compiles - c_setup
    e2e = loop.metrics(spec["cell"]["chips"])
    counters = {k: v - counters0[k] for k, v in eng.counters.items()}
    stats = [d.memory_stats() or {} for d in devs[:spec["cell"]["chips"]]]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"peak bytes in use by chip: {[s.get('peak_bytes_in_use') for s in stats]}")
    log(f"window: {loop.window[1] - loop.window[0]:.3f} s, {e2e['_tokens']} tokens, "
        f"{e2e['_gaps']} token gaps, {e2e['_ttft_n']} requests submitted, "
        f"{counters['blocks']} blocks, {c_window} compiles inside the window")

    window_reqs = [r for r, m in loop.meta.items() if m["in_window"]]
    result: dict = {"correct": False, "attempted": len(window_reqs),
                    "failed": sum(1 for r in window_reqs if r in eng.finished
                                  and eng.finished[r]["reason"] != "length"),
                    "metrics": {}, "compiles": {"setup": c_setup, "window": c_window}}
    if trace:
        loop_rec = {"counters": counters, "occupancy": occupancy, "steps": len(occupancy)}
        lrec, device_extra, breakdown = reduce_trace(trace_dir, rec, loop_rec, peak,
                                                     spec["cell"]["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in spec["per_layer"]:
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(lrec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    d0 = devs[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": spec["cell"]["chips"], "memory_peak_bytes": mem_peak}
    if trace:
        result["device"].update(device_extra)
        result["breakdown"] = breakdown
    pick = sample_requests(loop, eng, spec["limits"]["served_tokens_min"], seed)
    seqs = [(loop.meta[r]["prompt"], eng.finished[r]["tokens"], rec.first[r]) for r in pick]
    return result, seqs


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *, allow_cpu: bool = False,
             control: bool = False, fault=None, log=print) -> dict:
    """One run of one cell; returns the result object (``main`` prints it).
    With ``control`` the int8 control's first choices take the program's
    place in the gaps compared; ``fault`` is planted in the engine."""
    devs, peak = check_device(spec["cell"]["chips"], allow_cpu)
    import jax

    result, seqs = serve(spec, seed, seconds, trace, devs, peak, fault, log)
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays())
    log(f"check: {len(seqs)} requests, {sum(len(s[1]) for s in seqs)} served tokens; "
        f"{live} bytes of device arrays still live")
    rd = reference_readings(spec, seqs, control)
    limits = spec["limits"]
    served = sum(len(s[1]) for s in seqs)
    values = {"target_gap": rd["control_target_gap" if control else "target_gap"],
              "served_z": rd["served_z"], "served_z_draft": rd["served_z_draft"],
              "draft_gap": rd["control_draft_gap" if control else "draft_gap"]}
    checks = {k: {"value": None if values[k] is None else abs(values[k]), "limit": limits[k]}
              for k in values if k in limits}
    result["correct"] = bool(served >= limits["served_tokens_min"] and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values()))
    checks["served_tokens_min"] = {"value": served, "limit": limits["served_tokens_min"]}
    result["readings"] = rd
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the int8 control in the program's place (limit-setting runs)")
    ap.add_argument("--fault", choices=sorted(FAULTS),
                    help="plant a fault under the timed path (limit-setting runs)")
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          control=bool(args.control), fault=FAULTS.get(args.fault),
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
