"""Device: milliseconds a step of the draft model's step programs on the
chip: the ingest of committed tokens (``jit_drf_ing_*``) and the lockstep
trunk and branch drafting (``jit_drf_step``, ``jit_drf_bstep_*``); the
draft's prefill belongs to admission and is left out."""

from program_trace import DRAFT_PROGRAMS, program_ms_per_step


def read(rec):
    return program_ms_per_step(rec, DRAFT_PROGRAMS)
