"""Scheduler: share of the steps begun in the window that the pipelined
engine rewound: a request submitted against a free row while a step was
begun ahead discards that step's draft calls, reads and tree pass
(``abort_step``); from the engine's ``steps_rewound`` / ``steps_begun``."""


def read(rec):
    c = rec["counters"]
    begun = c.get("steps_begun")
    return 100.0 * c["steps_rewound"] / begun if begun else None
