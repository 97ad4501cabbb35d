"""The check that decides ``correct`` must fail what it exists to catch.

Each test drives a whole run of the smoke cell on the CPU, past the
harness's look for a chip: a sound run, which has to come out correct; the
int8 control in the program's place; and the timed path broken underneath
in each way a one-chip serving cell can break (``bench/faults.py``: a step
that leaves its state unchanged, an answer altered where it is produced, a
served token altered, a lossy verifier).  The smoke limits sit above the
program's readings and below the control's and the faults' on the test's
seed; PERF.md gives the cells' own readings on the chip and the limits set
from them."""
from __future__ import annotations

import pytest

from smoke import spec

import run
from faults import FAULTS

SEED = 12345678901
LIMITS = {"target_gap": 0.025, "served_z": 3.0, "served_z_draft": 3.0,
          "served_tokens_min": 200}


def one_run(fault=None, control=False, seed=SEED):
    s = spec()
    s["limits"] = dict(LIMITS)
    return run.run_cell(s, seed, 8.0, False, allow_cpu=True, control=control, fault=fault,
                        log=lambda _: None)


def test_program_is_correct():
    r = one_run()
    assert r["correct"], r["checks"]


def test_int8_control_in_the_programs_place_is_not_correct():
    r = one_run(control=True)
    assert not r["correct"], r["checks"]
    assert r["checks"]["target_gap"]["value"] == r["readings"]["control_target_gap"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault):
    r = one_run(fault=FAULTS[fault])
    assert not r["correct"], r["checks"]
