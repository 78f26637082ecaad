"""The chip benchmark's general machinery, driven by the names in
``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  Each is a data file found
by that name (``configs/<config>.json``, ``traffic/<traffic>.json``); the
traffic file names the generator (``generators/<name>.py``) that makes its
load from the parameters it holds.  Each metric, end-to-end or per-layer,
is a reader of its own (``metrics/<metric>.py``) that reduces the run's
records to one number, or to ``None`` when it finds nothing to read.
Adding a configuration, a mix or a metric is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import statistics
import sys
from pathlib import Path
from typing import Any, Callable

HERE = Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """A benchmark input that cannot be run: unknown cell, device or file."""


# ----------------------------------------------------------------- discovery
def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import the Python file ``path`` under a private module name (metric
    files carry dots in their names, so they are not importable by name)."""
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, mix and metrics."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str, base: Path = HERE) -> Cell:
    """The cell ``name`` of ``bench`` with its files read from ``base``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(base.parents[1] / configs[w["config"]]["file"])
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if applies(m, name)],
                [m for m in bench["per_layer"] if applies(m, name)])


def generator(traffic: dict, base: Path = HERE):
    return load_module(base / "generators" / f"{traffic['generator']}.py",
                       f"chipbench_generator_{traffic['generator']}")


def reader(metric: str, base: Path = HERE) -> Callable:
    mod = load_module(base / "metrics" / f"{metric}.py",
                      "chipbench_metric_" + metric.replace(".", "_"))
    return mod.read


def peaks_for(device_kind: str, base: Path = HERE) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown is an error."""
    table = load_json(base / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def require_accelerator(devices: list, chips: int) -> None:
    """Refuse anything but ``chips`` or more TPU devices."""
    platform = devices[0].platform if devices else "none"
    if platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX's first device is on "
                         f"platform {platform!r}")
    if len(devices) < chips:
        raise BenchError(f"cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")


# ------------------------------------------------------------------- records
@dataclasses.dataclass
class Records:
    """What a run leaves for the metric readers.

    ``ops`` are the closed loop's operations as dicts with at least ``t0``
    and ``t1`` (host ``perf_counter`` seconds); ``spans`` are named host
    intervals; ``counters`` are numbers and lists a generator reads from the
    program.  ``trace`` is the reduced profiler trace of a traced run."""

    window_t0: float = 0.0
    setup_s: float = 0.0
    ops: list[dict] = dataclasses.field(default_factory=list)
    spans: list[tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    trace: dict | None = None
    peaks: dict = dataclasses.field(default_factory=dict)

    def span_seconds(self, name: str) -> list[float]:
        return [t1 - t0 for n, t0, t1 in self.spans if n == name]

    @property
    def window_end(self) -> float:
        """End of the last operation: the op in flight at the deadline is
        counted whole, so the window runs to its end."""
        return max((op["t1"] for op in self.ops), default=self.window_t0)


def rate(rec: Records, key: str) -> float | None:
    """All of ``key`` over all the time from the window's start to the end
    of its last operation."""
    if not rec.ops:
        return None
    return sum(op[key] for op in rec.ops) / (rec.window_end - rec.window_t0)


def mean(values) -> float | None:
    values = list(values)
    return statistics.fmean(values) if values else None


def closed_loop(rec: Records, deadline: float, op: Callable[[], dict],
                clock: Callable[[], float]) -> None:
    """Run ``op`` back to back while the window is open.  An op starts only
    before ``deadline``; the one in flight then runs to its end and counts
    whole.  ``op`` returns the op's own fields (bytes, tokens, ...)."""
    while True:
        t0 = clock()
        if t0 >= deadline:
            return
        fields = op()
        rec.ops.append({"t0": t0, "t1": clock(), **fields})


class Span:
    """A named host interval recorded into ``rec.spans``; in a traced run
    it is also a ``TraceAnnotation`` in the profiler's trace."""

    def __init__(self, rec: Records, name: str, clock: Callable[[], float],
                 annotate: bool = False):
        self.rec, self.name, self.clock = rec, name, clock
        self.annotation = None
        if annotate:
            import jax
            self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = self.clock()
        return self

    def __exit__(self, *exc):
        self.t1 = self.clock()
        self.rec.spans.append((self.name, self.t0, self.t1))
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False


@dataclasses.dataclass
class Ctx:
    """What a generator is given: the cell, the run's arguments, the devices
    it may use, a scratch directory and the records to fill."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    workdir: Path
    rec: Records
    clock: Callable[[], float]

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def span(self, name: str) -> Span:
        return Span(self.rec, name, self.clock, annotate=self.trace)


# ------------------------------------------------------------------- results
@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def metric_values(metrics: list[dict], rec: Records,
                  base: Path = HERE) -> dict:
    out = {}
    for m in metrics:
        value = reader(m["name"], base)(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(checks: list[Check], attempted: int, failed: int,
                metrics: dict, device: dict,
                breakdown: dict | None = None) -> dict:
    out = {"correct": bool(checks) and all(c.ok for c in checks)
           and failed == 0,
           "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def print_checks(checks: list[Check]) -> None:
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
