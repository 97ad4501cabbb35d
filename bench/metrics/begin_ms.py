"""Step phases on the host: time per step inside ``begin_step`` (admission
and prefill, draft ingest, drafting, tree-pass dispatch), from the harness's
``bench:begin`` spans in the trace."""

from reduce_trace import totals


def read(rec):
    t = totals(rec["spans"]).get("bench:begin")
    return t / 1e6 / rec["steps"] if t and rec["steps"] else None
