"""The sharded build rehearsed on four virtual CPU devices: the smoke cell
on four chips (one client a row, one row a shard, each shard's pool and
weights on a device of its own, behind the router) runs as a four-chip cell
does and comes out correct, and comes out not correct with the timed path
broken in every shard: a served token altered where it is produced, and a
commit that leaves the KV pool unchanged.  Both read a ``target_gap`` of
1.6-2.4 against the smoke limit of 0.025.

The int8 control is not held here: at this size its widest gap (0.026-0.035
over four seeds on four shards) stands too close to the sound runs' (up to
0.018) and to the limit for a test to hold it; ``test_control.py`` holds it
on one chip.  Nor is the lossy verifier, whose ``served_z`` reads 4.6-5.8
against a limit of 3 here, or a row swap, which a one-row shard cannot show.

XLA fixes the number of CPU devices when JAX first touches the backend, so
the runs happen in a child process started with
``--xla_force_host_platform_device_count=4``:

    python bench/tests/test_shards.py    # the child: one JSON line a run
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from test_control import LIMITS, SEED

FAULTS = ("served_token_altered", "commit_leaves_state_unchanged")


def test_four_shards_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, __file__], env=env, capture_output=True, text=True,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    sound, *broken = (json.loads(line) for line in out.stdout.splitlines()[-1 - len(FAULTS):])
    assert sound["shard_devices"] == [[0], [1], [2], [3]]
    assert sound["compiles"]["window"] == 0, sound["compiles"]
    assert sound["correct"], sound["checks"]
    for fault, r in zip(FAULTS, broken):
        assert not r["correct"], (fault, r["checks"])


def main() -> None:
    from smoke import spec

    import run
    from faults import FAULTS as PLANTED

    devices = []
    build = run.build

    def placed(s, seed):
        eng, mt, md = build(s, seed)
        devices.append([sorted({d for ids in sh.placement().values() for d in ids})
                        for sh in eng.shards])
        return eng, mt, md

    run.build = placed
    for fault in (None, *FAULTS):
        s = spec(chips=4)
        s["limits"] = dict(LIMITS)
        r = run.run_cell(s, SEED, 8.0, False, allow_cpu=True,
                         fault=fault and PLANTED[fault], log=lambda _: None)
        print(json.dumps(dict(r, shard_devices=devices[-1])), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main()
