"""Writer seconds per save in checksums: crc32 of each chunk
(``ckpt.write.crc``) and the store's blake2b content hash
(``ckpt.store.hash``)."""

from benchmarks.chip import program_spans as P


def read(rec):
    return P.per_save_s(rec, "ckpt.write.crc", "ckpt.store.hash")
