"""Seconds per save that the staging arena's back-pressure blocked it."""


def read(rec):
    n = rec.counters.get("saves")
    return rec.counters["arena_blocked_s"] / n if n else None
