"""Bytes per second of the host copies the training loop waits for in a
save: the per-chunk copies of the snapshot (``ckpt.snapshot.copy``) and
the copy into the staging arena (``ckpt.stage.pack``), 10^9 bytes per
second."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.rate_GB_per_s(rec, "ckpt.snapshot.copy", "ckpt.stage.pack")
