"""Mean seconds the writer thread spent on one save's state job."""

from benchmarks.chip.harness import mean


def read(rec):
    return mean(rec.counters.get("writer_s", []))
