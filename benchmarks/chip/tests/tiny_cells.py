"""Small copies of the benchmark's cells that a CPU test run can hold:
the same generators and checks at a few thousandths of the size."""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness as H  # noqa: E402

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TINY_LM = {"hidden_size": 64, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 256}
TINY_JOB = {"seq_len": 32, "global_batch": 4}


def bench() -> dict:
    return H.load_json(ROOT / "BENCHMARK.json")


def tiny_cell(name: str, one_device: bool = True,
              workload: dict | None = None) -> H.Cell:
    """The cell ``name`` (committed, or the ``workload`` entry given) with
    its sizes cut for the CPU (and its meshes cut to one device, unless
    ``one_device`` is false)."""
    b = bench()
    if workload is not None:
        b["workloads"].append(workload)
    cell = copy.deepcopy(H.find_cell(b, name))
    if "hidden_size" in cell.config:
        cell.config.update(TINY_LM)
        cell.traffic.update(TINY_JOB)
        if one_device:
            for key in ("mesh", "save_mesh", "load_mesh"):
                if key in cell.traffic:
                    cell.traffic[key] = [1, 1]
            cell.chips = 1
    else:
        cell.config["mesh"].update(nx=6, ny=6)
    return cell


def run_tiny(name: str, seconds: float = 0.3, seed: int = 2**31 + 7,
             trace: bool = False, cell: H.Cell | None = None,
             base: Path | None = None) -> dict:
    """One run of a tiny cell on the CPU, past the harness's chip check."""
    import jax

    from benchmarks.chip.run import run_cell

    return run_cell(cell or tiny_cell(name), seed, seconds, trace,
                    jax.devices(), PEAKS, time.perf_counter(), base)
