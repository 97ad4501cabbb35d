"""Step phases on the host: megabytes a step copied from the device to the
host (warped logits of the draft calls and the tree pass, hidden states),
from the engine's ``readback_bytes`` counter."""


def read(rec):
    b = rec["counters"].get("readback_bytes")
    return b / 1e6 / rec["steps"] if b and rec["steps"] else None
