"""Mean seconds the training loop is blocked in each save call."""

from benchmarks.chip.harness import mean


def read(rec):
    return mean(rec.span_seconds("save"))
