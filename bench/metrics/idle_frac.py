"""Device: share of the traced window in which no operation ran on the
chip, averaged over the chips used (1 - busy / window)."""


def read(rec):
    if rec["window_ns"] <= 0 or rec["busy_ns"] <= 0:
        return None
    return 100.0 * (1.0 - rec["busy_ns"] / rec["window_ns"])
