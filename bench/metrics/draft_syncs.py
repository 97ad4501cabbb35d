"""Step phases on the host: blocking device-to-host reads a step made while
it drafted, from the draft ingest to the tree dispatch, from the engine's
``draft_syncs`` counter (a program without it reads nothing)."""


def read(rec):
    n = rec["counters"].get("draft_syncs")
    return n / rec["steps"] if n is not None and rec["steps"] else None
