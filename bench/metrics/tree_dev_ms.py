"""Device: milliseconds a step of the target tree pass on the chip, the
programs ``jit_tgt_tree_*`` (padded) and ``jit_tgt_rtree_*`` (ragged)."""

from program_trace import TREE_PROGRAMS, program_ms_per_step


def read(rec):
    return program_ms_per_step(rec, TREE_PROGRAMS)
