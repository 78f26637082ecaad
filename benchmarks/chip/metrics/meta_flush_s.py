"""Writer seconds per save spent rewriting the store's ``store.json``
(``ckpt.store.flush_meta``): one flush per dataset created and the
commit's."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.per_save_s(rec, "ckpt.store.flush_meta")
