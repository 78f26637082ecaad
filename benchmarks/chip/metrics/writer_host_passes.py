"""Host passes over each saved byte on the writer thread: the bytes that
its copying or scanning spans (``pass_``) touch, over the bytes the
window's saves snapshot."""

from benchmarks.chip import program_spans as P


def read(rec):
    saves = P.of_saves(rec)
    if saves is None:
        return None
    spans, _ = saves
    writer = {s.thread for s in spans if s.name == "ckpt.writer.job"}
    saved = P.nbytes(spans, "ckpt.snapshot")
    if not writer or not saved:
        return None
    return sum(s.attrs.get("bytes", 0) for s in spans
               if s.attrs.get("pass_") and s.thread in writer) / saved
