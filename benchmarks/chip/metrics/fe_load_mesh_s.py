"""Mean seconds of ``FEMCheckpoint.load_mesh`` per restart."""

from benchmarks.chip.harness import mean


def read(rec):
    return mean(rec.span_seconds("fe.load_mesh"))
