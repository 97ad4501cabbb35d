"""Families: what the harness knows of a model's architecture lives in a
module of its own (``bench/families/<family>.py``).  The dense family's
weights and work counts are those the benchmark had before families; a
family the harness has never seen (``ssm_family.py``, beside this file)
runs through it with no harness file edited."""
from __future__ import annotations

import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from smoke import BENCH, family, spec

import run
from weights import make_weights

DENSE = family(BENCH / "families" / "dense.py")
G32B = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())

# sha256 over every leaf (shape, dtype, bytes) of the smoke cell's target and
# draft weights, as bench/weights.py made them before the families existed
DENSE_SMOKE_SHA256 = "9f01a6adfc464248e222b5c5f227799147d59d4c9c6201b6a3b6f1731b1cc91d"


@pytest.fixture
def m():
    return DENSE.dims(G32B)


def test_dense_weights_are_bit_identical():
    conf = spec()["config"]
    tp, dp = make_weights(DENSE, conf["weight_seed"], DENSE.dims(conf), DENSE.dims(conf["draft"]))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves((tp, dp)):
        a = np.asarray(leaf)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == DENSE_SMOKE_SHA256


def test_matmul_params_by_hand(m):
    # per layer: q 2048x2048, k and v 2048x512 each, o 2048x2048, MLP 3 x 2048x8192
    per_layer = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 3 * 2048 * 8192
    assert per_layer == 60817408
    assert DENSE.matmul_params(m, head=False) == 40 * per_layer
    assert DENSE.matmul_params(m, head=True) == 40 * per_layer + 2048 * 49155


def test_forward_and_prefill_flops_by_hand(m):
    # one token at context 100: 2 x params + 4 x L x H x hd x 100
    one = 2 * (40 * 60817408 + 2048 * 49155) + 4 * 40 * 32 * 64 * 100
    assert DENSE.forward_flops(m, 1, 100) == one
    # prefill of 3 tokens: contexts 1, 2, 3, no head
    assert DENSE.prefill_flops(m, 3) == 2 * 40 * 60817408 * 3 + 4 * 40 * 32 * 64 * 6


def ssm_spec(fam) -> dict:
    """mamba2-2.7b's smoke preset and its draft under the smoke cell's
    closed loop, its requests sampled whole for the look below."""
    dims = {"hidden_size": 256, "num_hidden_layers": 2, "state_size": 32, "head_dim": 32,
            "expand": 2, "n_groups": 1, "conv_kernel": 4, "chunk_size": 16,
            "vocab_size": 512, "tie_word_embeddings": True, "layer_norm_epsilon": 1e-6}
    s = spec()
    s["family"] = fam
    s["config"] = dict(dims, family="ssm_family", arch="mamba2-2.7b", smoke=True,
                       weight_seed=20260102, program_replace={},
                       draft=dict(dims, hidden_size=128, num_hidden_layers=1))
    s["limits"] = {"served_tokens_min": 10**9}
    return s


def test_another_family_runs_through_the_harness(monkeypatch):
    fam = family(Path(__file__).with_name("ssm_family.py"))
    counted = []

    def counting(fn):
        def wrapped(*a, **k):
            counted.append(fn(*a, **k))
            return counted[-1]
        return wrapped

    for name in ("forward_flops", "prefill_flops"):
        monkeypatch.setattr(fam, name, counting(getattr(fam, name)))
    recorders = []

    class Counting(run.Recorder):
        """The harness's recorder, counting work from set-up on."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.work_on = True
            recorders.append(self)

    monkeypatch.setattr(run, "Recorder", Counting)
    devs, peak = run.check_device(1, allow_cpu=True)
    result, seqs = run.serve(ssm_spec(fam), 4100000001, 6.0, False, devs, peak, None,
                             log=lambda _: None)
    assert result["compiles"]["window"] == 0, result["compiles"]
    assert result["failed"] == 0 and seqs
    (rec,) = recorders
    assert rec.model_ops > 0 and rec.model_ops == sum(counted)
    for prompt, served, first in seqs:
        assert len(first) >= len(served)
