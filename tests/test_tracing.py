"""The serving engine's own spans, counters and program names
(serving/tracing.py, docs/serving.md "Spans and counters").

  * a traced run writes the ``serve:`` phase spans and ``serve:wait.*``
    read spans, none inside drafting, with the pipelined engine's
    begun-ahead ``serve:begin`` nested inside ``serve:retire``;
  * span metadata (the request's ``rid``) leaves the event name clean;
  * a closed loop (a new request on every ``done``) makes the pipelined
    engine rewind begun steps, and ``steps_begun == finished steps +
    steps_rewound`` once nothing is pending;
  * ``readback_bytes`` equals the bytes every device-to-host read copied;
  * ``admitted`` / ``queue_ms`` count every admission;
  * every jitted program lowers under a module named after its cache key.
"""
import glob
import re

import jax
import numpy
import pytest
from jax.profiler import ProfileData

import repro.serving.batch_engine as batch_engine
from repro.models.config import ModelConfig
from repro.models.transformer import init_params
from repro.serving.batch_engine import (
    BatchedSpeculativeEngine,
    ShardedBatchedSpeculativeEngine,
)
from repro.serving.engine import EngineConfig, SpeculativeEngine

V = 32

DENSE_T = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
DENSE_D = ModelConfig(name="d", arch_type="dense", n_layers=1, d_model=32, n_heads=4,
                      n_kv_heads=2, d_ff=96, vocab=V, dtype="float32")
SSM_CFG = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=48, vocab=V,
                      ssm_state=16, ssm_headdim=16, ssm_chunk=8, dtype="float32")

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [3, 1]]
SEEDS = [20, 21, 22, 23]
ECFG = EngineConfig(verifier="specinfer", K=2, L1=1, L2=1, max_cache=128)


@pytest.fixture(scope="module")
def dense_models():
    return (DENSE_T, init_params(DENSE_T, jax.random.PRNGKey(0)),
            DENSE_D, init_params(DENSE_D, jax.random.PRNGKey(1)))


def _traced_events(tmp_path, run):
    """Run ``run()`` under the profiler; return the host's ``serve:`` events
    as (name, start, end, stats) and the run's result."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for plane in ProfileData.from_file(pb).planes
              if not plane.name.startswith("/device:")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve:")]
    return events, out


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
def test_traced_run_writes_phase_and_wait_spans(dense_models, tmp_path, pipeline):
    tc, tp, dc, dp = dense_models
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=4, pipeline=pipeline)
    events, _ = _traced_events(
        tmp_path, lambda: eng.generate_batch(PROMPTS, max_new=10, seeds=SEEDS))
    names = {e[0] for e in events}
    for phase in ("step", "begin", "admit", "prefill", "ingest", "draft", "dispatch",
                  "verify", "commit", "retire"):
        assert f"serve:{phase}" in names, phase
    for what in ("prefill", "draft", "tree", "hidden"):
        assert f"serve:wait.{what}" in names, what
    # every wait sits inside a served step; phases nest as the call stack;
    # drafting (ingest and tree levels, on the device) waits on nothing:
    # its outputs are read once, in verify
    steps = [e for e in events if e[0] == "serve:step"]
    assert len(steps) == eng._step_no
    drafting = [e for e in events if e[0] in ("serve:ingest", "serve:draft")]
    verifies = [e for e in events if e[0] == "serve:verify"]
    for e in events:
        if e[0].startswith("serve:wait."):
            assert any(_within(e, s) for s in steps), e
            assert not any(_within(e, d) for d in drafting), e
            if e[0] == "serve:wait.draft":
                assert any(_within(e, v) for v in verifies), e
    begins = [e for e in events if e[0] == "serve:begin"]
    retires = [e for e in events if e[0] == "serve:retire"]
    ahead = [b for b in begins if any(_within(b, r) for r in retires)]
    if pipeline:
        assert ahead and eng.counters["pipeline_iterations"] == len(ahead)
    else:
        assert not ahead


def test_span_metadata_leaves_the_name_clean(dense_models, tmp_path):
    tc, tp, dc, dp = dense_models
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=4)

    def run():
        for p, s in zip(PROMPTS, SEEDS):
            eng.submit(p, max_new=4, seed=s)
        return eng.step()

    events, _ = _traced_events(tmp_path, run)
    prefills = [e for e in events if "prefill" in e[0] and "wait" not in e[0]]
    # one span per prefilled model (target and draft) per request
    assert {e[0] for e in prefills} == {"serve:prefill"}
    rids = sorted(e[3]["rid"] for e in prefills)
    assert rids == sorted(2 * list(range(len(PROMPTS))))
    steps = [e for e in events if e[0].startswith("serve:step")]
    assert [e[0] for e in steps] == ["serve:step"]
    assert steps[0][3]["step_num"] == 1


def _closed_loop(eng, total: int, max_new: int = 6) -> int:
    """Serve ``total`` requests, submitting the next one as each finishes
    (the benchmark's closed-loop client); returns the requests submitted."""
    rng = numpy.random.default_rng(0)
    submitted = 0

    def submit():
        nonlocal submitted
        prompt = rng.integers(0, V, size=int(rng.integers(2, 6))).tolist()
        eng.submit(prompt, max_new=max_new, seed=submitted)
        submitted += 1

    for _ in range(eng.n_slots):
        submit()
    while eng.queue or eng.streams:
        for ev in eng.step():
            if ev["done"] and submitted < total:
                submit()
    eng.drain_pipeline()
    return submitted


@pytest.mark.parametrize("pipeline", [False, True], ids=["sync", "pipelined"])
def test_closed_loop_rewinds_and_step_invariant(dense_models, pipeline):
    tc, tp, dc, dp = dense_models
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=4, pipeline=pipeline)
    _closed_loop(eng, total=12)
    c = eng.counters
    # one commit per finished step on a single tree-strategy engine
    assert c["steps_begun"] == c["commit_calls"] + c["steps_rewound"]
    if pipeline:
        # the finished stream's row is free under the begun-ahead step
        assert c["steps_rewound"] > 0
    else:
        assert c["steps_rewound"] == c["steps_drained"] == 0


class _CountingNumpy:
    """numpy, with the bytes of every ``asarray`` of a device array summed."""

    def __init__(self):
        self.device_bytes = 0

    def __getattr__(self, name):
        return getattr(numpy, name)

    def asarray(self, x, *a, **k):
        out = numpy.asarray(x, *a, **k)
        if isinstance(x, jax.Array):
            self.device_bytes += out.nbytes
        return out


@pytest.mark.parametrize("arch", ["dense", "ssm"])
def test_readback_bytes_match_every_device_read(dense_models, monkeypatch, arch):
    if arch == "dense":
        tc, tp, dc, dp = dense_models
    else:
        tc = dc = SSM_CFG
        tp = dp = init_params(SSM_CFG, jax.random.PRNGKey(0))
    counting = _CountingNumpy()
    monkeypatch.setattr(batch_engine, "np", counting)
    eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=4, pipeline=True)
    eng.generate_batch(PROMPTS, max_new=6, seeds=SEEDS)
    assert eng.counters["readback_bytes"] == counting.device_bytes > 0


@pytest.mark.parametrize("shards", [1, 2], ids=["single", "sharded"])
def test_admission_counters(dense_models, shards):
    tc, tp, dc, dp = dense_models
    if shards == 1:
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=2, pipeline=True)
    else:
        eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=2,
                                              data_shards=2, pipeline=True)
    n = _closed_loop(eng, total=6)
    c = eng.counters
    assert c["admitted"] == n == 6
    assert c["queue_ms"] >= 0.0


def _recording_jit(obj, seen: dict):
    """Wrap ``obj._jit`` to keep, per program, the abstract arguments of its
    first call (donated buffers are gone after the call)."""
    orig = obj._jit

    def recording(name, fn, donate_argnums=None):
        f = orig(name, fn, donate_argnums)

        def call(*a, **k):
            if name not in seen:
                spec = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x,
                    (a, k))
                seen[name] = (f, spec)
            return f(*a, **k)
        return call

    obj._jit = recording


@pytest.mark.parametrize("engine", ["single_stream", "pool", "sharded"])
def test_every_program_is_named_after_its_key(dense_models, engine):
    tc, tp, dc, dp = dense_models
    seen: dict = {}
    if engine == "single_stream":
        eng = SpeculativeEngine(tc, tp, dc, dp, ECFG)
        _recording_jit(eng, seen)
        eng.generate(PROMPTS[0], max_new=6)
    elif engine == "pool":
        eng = BatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=4, pipeline=True,
                                       ragged="always")
        _recording_jit(eng, seen)
        eng.generate_batch(PROMPTS, max_new=6, seeds=SEEDS)
    else:
        eng = ShardedBatchedSpeculativeEngine(tc, tp, dc, dp, ECFG, n_slots=4,
                                              data_shards=2)
        for obj in (eng, *eng.shards):
            _recording_jit(obj, seen)
        eng.generate_batch(PROMPTS, max_new=6, seeds=SEEDS)
    assert seen
    if engine == "sharded":
        assert any(k.startswith("gcommit_") for k in seen)
    for name, (f, (a, k)) in seen.items():
        module = re.match(r"module @(\S+)", f.lower(*a, **k).as_text()).group(1)
        assert module == f"jit_{name}", (name, module)
