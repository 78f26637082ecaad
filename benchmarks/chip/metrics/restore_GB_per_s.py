"""Bytes of leaves placed on the target devices over the window (10^9
bytes per second)."""

from benchmarks.chip.harness import rate


def read(rec):
    r = rate(rec, "bytes") if rec.ops and "bytes" in rec.ops[0] else None
    return None if r is None else r / 1e9
