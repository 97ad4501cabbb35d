"""Scheduler: mean wait of a request admitted in the window from
``submit()`` to its admission, from the engine's ``queue_ms`` /
``admitted`` counters."""


def read(rec):
    c = rec["counters"]
    n = c.get("admitted")
    return c["queue_ms"] / n if n else None
