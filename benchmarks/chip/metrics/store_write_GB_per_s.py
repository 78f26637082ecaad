"""Bytes the store wrote over the seconds its writes took (``IOStats``),
10^9 bytes per second."""


def read(rec):
    s = rec.counters.get("write_seconds")
    return rec.counters["bytes_written"] / s / 1e9 if s else None
