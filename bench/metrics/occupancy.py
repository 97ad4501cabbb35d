"""Scheduler: mean share of the pool's rows that held a stream, read at each
step boundary of the traced window (program counter: active streams)."""


def read(rec):
    occ = rec["occupancy"]
    return 100.0 * sum(occ) / len(occ) if occ else None
