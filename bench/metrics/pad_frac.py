"""Scheduler: share of the tree pass's lanes that carried padding over the
window, from the engine's ``pad_nodes_total`` / ``tree_lanes_total``."""


def read(rec):
    c = rec["counters"]
    lanes = c.get("tree_lanes_total", 0)
    return 100.0 * c["pad_nodes_total"] / lanes if lanes else None
