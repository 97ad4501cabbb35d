"""Step phases on the host: time per step inside ``verify_step`` (waiting on
the tree pass's output, then host verification of every stream), from the
harness's ``bench:verify`` spans in the trace."""

from reduce_trace import totals


def read(rec):
    t = totals(rec["spans"]).get("bench:verify")
    return t / 1e6 / rec["steps"] if t and rec["steps"] else None
