"""The chip benchmark's own arithmetic, on the CPU: the trace reduction on
a small trace recorded on a TPU v5e, the window's rates, the model FLOPs,
discovery by name, and the refusals of a wrong device."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import tiny_cells
from benchmarks.chip import harness as H
from benchmarks.chip import lm
from benchmarks.chip import trace as T

DATA = Path(__file__).resolve().parent / "data"


# --------------------------------------------------------------- the trace
def test_reduce_recorded_trace_busy_window_and_names():
    trace = json.loads((DATA / "trace_v5e_small.json").read_text())
    got = T.reduce(trace, {"op"}, chips=1)
    ops = [(s, s + d) for _, s, d in T.device_events(trace)["/device:TPU:0"]]
    anchors = [(s, s + d) for pl in trace["planes"] for ln in pl["lines"]
               for n, s, d in ln["events"] if n == "op"]
    lo, hi = min(a for a, _ in anchors), max(b for _, b in anchors)
    # the union counted on a nanosecond grid, independently of _union
    grid = np.zeros(int(hi - lo), bool)
    for a, b in ops:
        grid[max(int(a - lo), 0):max(int(b - lo), 0)] = True
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert got["busy_s"] == pytest.approx(grid.sum() * 1e-9, abs=2e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    assert [n for n, _ in got["device_ops"]] == [
        "convolution_reduce_fusion", "copy-done", "copy-start"]
    assert all(name == "op" for name, _ in got["idle_gaps"])
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)


def test_self_times_take_children_off_their_parent():
    events = [["%while.3 = (..) while(..)", 0, 100],
              ["%fusion.1 = f32[] fusion(..)", 10, 30],
              ["%fusion.2 = f32[] fusion(..)", 50, 20],
              ["%copy.7 = f32[] copy(..)", 200, 5]]
    assert dict(T.self_times(events)) == {"while": 50, "fusion": 50,
                                          "copy": 5}


def test_reduce_without_device_or_anchor_reads_nothing():
    host = {"name": "/host:CPU",
            "lines": [{"name": "python3", "events": [["op", 0, 10]]}]}
    assert T.reduce({"planes": [host]}, {"op"}, chips=1) is None
    dev = {"name": "/device:TPU:0",
           "lines": [{"name": "XLA Ops", "events": [["%a.1 = x", 0, 5]]}]}
    assert T.reduce({"planes": [dev]}, {"op"}, chips=1) is None


def test_idle_share_counts_a_missing_chip_as_idle():
    dev = {"name": "/device:TPU:0",
           "lines": [{"name": "XLA Ops", "events": [["%a.1 = x", 0, 50]]}]}
    host = {"name": "/host:CPU",
            "lines": [{"name": "python3", "events": [["op", 0, 100]]}]}
    one = T.reduce({"planes": [dev, host]}, {"op"}, chips=1)
    two = T.reduce({"planes": [dev, host]}, {"op"}, chips=2)
    rec = H.Records(trace=one)
    assert T.idle_share(rec) == pytest.approx(50.0)
    assert two["busy_s"] == pytest.approx(one["busy_s"] / 2)


# -------------------------------------------------------------- the window
def test_closed_loop_counts_the_op_in_flight_whole():
    now = [0.0]
    clock = lambda: now[0]                                    # noqa: E731

    def op():
        now[0] += 4.0
        return {"bytes": 100, "restarts": 1}

    rec = H.Records(window_t0=0.0)
    H.closed_loop(rec, 10.0, op, clock)
    # ops start at 0, 4 and 8; the one started at 8 ends at 12, past 10
    assert [o["t0"] for o in rec.ops] == [0.0, 4.0, 8.0]
    assert rec.window_end == 12.0
    assert H.rate(rec, "bytes") == pytest.approx(300 / 12)
    assert H.reader("restore_GB_per_s")(rec) == pytest.approx(300 / 12 / 1e9)
    assert H.reader("fe_restart_s")(rec) == pytest.approx(4.0)


def test_rates_are_all_work_over_all_time():
    rec = H.Records(window_t0=0.0)
    rec.ops = [{"t0": 0.0, "t1": 1.0, "tokens": 10},
               {"t0": 1.0, "t1": 9.0, "tokens": 10}]
    # a slow op weighs by its time, not as one sample of a mean of rates
    assert H.reader("train_tokens_per_s")(rec) == pytest.approx(20 / 9)


def test_train_mfu_reads_steps_without_a_save():
    rec = H.Records(peaks={"bf16_flops_per_s": 100.0})
    rec.counters = {"step_s_nosave": [1.0, 3.0], "flops_per_step": 50.0,
                    "chips": 1}
    assert H.reader("train_mfu")(rec) == pytest.approx(100 * 100 / 4 / 100)


# ---------------------------------------------------------------- the FLOPs
def test_model_flops_of_smollm_135m():
    cfg = H.load_json(H.HERE / "configs" / "smollm-135m.json")
    # matrices: 30 x (576*576*2 + 576*192*2 + 3*576*1536) + 49152*576
    # = 134,479,872; causal attention: 30 * 2 * 1024 * 9 * 64 = 35,389,440
    assert lm.model_flops_per_token(cfg, 2048) == 6.0 * 169_869_312


# ---------------------------------------------------------------- discovery
def test_new_config_mix_and_metric_are_found_from_new_files(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as files
    and entries only, without editing a file that is there."""
    base = tmp_path / "benchmarks" / "chip"
    shutil.copytree(H.HERE, base, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    bench = tiny_cells.bench()
    cfg = json.loads((base / "configs" / "fe-p4-tri.json").read_text())
    cfg.update(name="fe-p4-small", mesh={"generator": "unit_square_triangles",
                                         "nx": 4, "ny": 5})
    (base / "configs" / "fe-p4-small.json").write_text(json.dumps(cfg))
    (base / "traffic" / "restart_3to1.json").write_text(json.dumps(
        {"generator": "fe_restart", "save_ranks": 3, "load_ranks": 1,
         "save_partition": "contiguous", "load_partition": "contiguous"}))
    (base / "metrics" / "fe_h2d_s.py").write_text(
        "from benchmarks.chip.harness import mean\n\n\n"
        "def read(rec):\n    return mean(rec.span_seconds('fe.h2d'))\n")
    bench["configs"].append({"name": "fe-p4-small",
                             "file": "benchmarks/chip/configs/fe-p4-small.json"})
    bench["workloads"].append({"name": "fe-p4-small.restart_3to1",
                               "config": "fe-p4-small",
                               "traffic": "restart_3to1", "chips": 1})
    bench["per_layer"].append({"name": "fe_h2d_s", "unit": "s",
                               "workloads": ["fe-p4-small.restart_3to1"]})
    cell = H.find_cell(bench, "fe-p4-small.restart_3to1", base)
    assert cell.config["mesh"]["ny"] == 5 and cell.traffic["save_ranks"] == 3
    assert "fe_h2d_s" in [m["name"] for m in cell.per_layer]
    out = tiny_cells.run_tiny(cell.name, cell=cell, trace=True, base=base)
    assert out["correct"] is True
    assert out["metrics"]["fe_h2d_s"]["value"] > 0


def test_unknown_workload_is_refused():
    with pytest.raises(H.BenchError, match="no workload"):
        H.find_cell(tiny_cells.bench(), "no-such.cell")


# ----------------------------------------------------------------- refusals
def test_non_tpu_platform_and_too_few_chips_are_refused():
    class Dev:
        def __init__(self, platform):
            self.platform = platform

    with pytest.raises(H.BenchError, match="needs a TPU.*'cpu'"):
        H.require_accelerator([Dev("cpu")], 1)
    with pytest.raises(H.BenchError, match="asks for 4 chips"):
        H.require_accelerator([Dev("tpu")], 4)
    H.require_accelerator([Dev("tpu")] * 4, 4)


def test_unknown_device_kind_is_refused():
    with pytest.raises(H.BenchError, match="no peaks for device kind"):
        H.peaks_for("TPU v99")
    assert H.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("tree", ["checkout", "benchmark_only"])
def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path,
                                                              tree):
    """On the CPU, and in a directory holding only ``BENCHMARK.json`` and
    the benchmark's own files, the command refuses to run."""
    root = tiny_cells.ROOT
    if tree == "benchmark_only":
        shutil.copy(root / "BENCHMARK.json", tmp_path)
        shutil.copytree(H.HERE, tmp_path / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        root = tmp_path
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "fe-p4-tri.restart_4to2", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
