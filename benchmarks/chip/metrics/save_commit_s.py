"""Mean time from a save's call to the end of its step's commit job, over
every save made in the window."""

from benchmarks.chip.harness import mean


def read(rec):
    return mean(rec.counters.get("commit_s", []))
