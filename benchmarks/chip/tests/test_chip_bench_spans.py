"""The per-layer metrics that read the program's own spans, on the CPU at a
test's size: each reads a number in the cells it lists, and the program's
spans agree with the benchmark's wrappers around the same operations."""

from __future__ import annotations

import statistics

import pytest

import tiny_cells
from benchmarks.chip import harness as H
from benchmarks.chip import program_spans as P

TRAIN = "smollm-135m.train_save"
RESTORE = "smollm-135m.restore"
FE = "fe-p4-tri.restart_4to2"
PROGRAM_METRICS = {
    "d2h_GB_per_s", "stall_copy_GB_per_s", "writer_checksum_s",
    "meta_flush_s", "writer_host_passes", "load_plan_share",
    "load_exchange_share", "fe_closure_s", "fe_owners_s", "fe_directory_s",
    "fe_locals_s", "setup_compile_s", "window_compiles.train",
    "window_compiles.restore", "window_compiles.fe"}


def _run_traced(name, monkeypatch):
    """A traced tiny run of ``name``: its result line and its records.
    Compiles are recorded as ``run.py``'s start-up has them recorded
    (``init_compile_cache``)."""
    from repro.core.spans import record_compiles

    record_compiles()
    seen = {}
    metric_values = H.metric_values

    def keep(metrics, rec, base=H.HERE):
        seen["rec"] = rec
        return metric_values(metrics, rec, base)

    monkeypatch.setattr(H, "metric_values", keep)
    out = tiny_cells.run_tiny(name, trace=True)
    assert out["correct"] is True, out["checks"]
    return out, seen["rec"]


def _agree(pairs: list[tuple[float, float]]) -> bool:
    """(wrapper, program) durations of the same ops: no program span is
    longer than its wrapper, and the typical op's two lengths are within
    2 % or 1 ms of each other.  The typical op is the median one: on a
    loaded CPU the scheduler can stall a thread for milliseconds between
    any two clock reads, the wrapper's included."""
    assert pairs
    assert all(inner <= outer for outer, inner in pairs), pairs
    gap = statistics.median(outer - inner for outer, inner in pairs)
    outer = statistics.median(outer for outer, _ in pairs)
    return gap <= max(0.02 * outer, 1e-3)


def _pairs(rec, wrapper: str, program: str) -> list[tuple[float, float]]:
    """Each wrapper span of the window holds exactly one program span of
    the same operation: their durations."""
    spans = P.recorded()
    pairs = []
    for name, t0, t1 in rec.spans:
        if name != wrapper:
            continue
        inner = [s for s in spans if s.name == program
                 and t0 <= s.t0 and s.t1 <= t1]
        assert len(inner) == 1, (wrapper, len(inner))
        pairs.append((t1 - t0, inner[0].t1 - inner[0].t0))
    return pairs


@pytest.mark.parametrize("name", [TRAIN, RESTORE, FE])
def test_every_program_span_metric_reads_a_number(name, monkeypatch):
    out, _ = _run_traced(name, monkeypatch)
    listed = {m["name"] for m in tiny_cells.tiny_cell(name).per_layer
              if m["name"] in PROGRAM_METRICS}
    assert listed, name
    missing = listed - set(out["metrics"])
    assert not missing, missing
    for m in listed:
        assert out["metrics"][m]["value"] >= 0, (m, out["metrics"][m])
    assert {m["name"] for m in tiny_cells.bench()["per_layer"]} \
        >= PROGRAM_METRICS


def test_train_spans_agree_with_the_wrappers(monkeypatch):
    out, rec = _run_traced(TRAIN, monkeypatch)
    assert _agree(_pairs(rec, "save", "ckpt.save"))
    assert _agree(_pairs(rec, "snapshot", "ckpt.snapshot"))
    m = out["metrics"]
    assert m["window_compiles.train"]["value"] == 0
    # four host passes over each saved byte (concatenation, crc32's copy
    # and scan, blake2b) and a little more for the crc rows it hashes
    assert 4.0 <= m["writer_host_passes"]["value"] < 5.0


def test_restore_spans_agree_with_the_timed_engine(monkeypatch):
    _, rec = _run_traced(RESTORE, monkeypatch)
    engine = [s.t1 - s.t0 for s in P.in_window(rec)
              if s.name == "ckpt.load.state"]
    timed = rec.counters["load_state_s"]
    assert len(engine) == len(timed) >= 1
    assert _agree(list(zip(timed, engine)))


def test_fe_spans_agree_with_the_wrappers(monkeypatch):
    _, rec = _run_traced(FE, monkeypatch)
    assert _agree(_pairs(rec, "fe.load_mesh", "fe.load_mesh"))
