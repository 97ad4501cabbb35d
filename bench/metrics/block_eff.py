"""Verification: tokens emitted per target pass over the window, from the
engine's ``accepted`` / ``blocks`` counters (+1 for the correction token)."""


def read(rec):
    c = rec["counters"]
    return c["accepted"] / c["blocks"] + 1.0 if c.get("blocks") else None
