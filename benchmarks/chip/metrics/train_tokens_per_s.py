"""Tokens of the completed steps over the window, save stalls included."""

from benchmarks.chip.harness import rate


def read(rec):
    return rate(rec, "tokens") if rec.ops and "tokens" in rec.ops[0] else None
