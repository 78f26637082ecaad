#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, its traffic
and its metrics are found by name from ``BENCHMARK.json``.  The run sets up
(builds, compiles or loads from the persistent cache, warms up), then
drives the cell's operations back to back for ``--seconds``, waits for
what the window left in flight, reads the device's peak memory, checks the
window's results against the plain reference, and prints one JSON line:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
beside its limit, which also end standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices: list,
             peaks: dict, t_start: float, base=None) -> dict:
    """Set up, measure and check ``cell``; the result line as a dict."""
    import jax

    from benchmarks.chip import harness as H
    from benchmarks.chip import trace as T

    base = base or H.HERE
    clock = time.perf_counter
    rec = H.Records(peaks=peaks)
    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp:
        ctx = H.Ctx(cell, seed, seconds, trace, devices[:cell.chips],
                    Path(tmp), rec, clock)
        gen = H.generator(cell.traffic, base).Generator(ctx)
        gen.setup()
        rec.spans.clear()
        trace_dir = Path(tmp) / "trace"
        if trace:
            # host annotations and device operations; no Python call tracing
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        rec.window_t0 = clock()
        rec.setup_s = rec.window_t0 - t_start

        def op():
            with ctx.span(T.ANCHOR):
                return gen.op()

        H.closed_loop(rec, rec.window_t0 + seconds, op, clock)
        if trace:
            jax.profiler.stop_trace()
        gen.after_window()
        stats = devices[0].memory_stats() or {}
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell.chips])
        breakdown = None
        if trace:
            files = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                              recursive=True)
            if files:
                rec.trace = T.reduce(T.load_xplane(files[0]),
                                     {n for n, _, _ in rec.spans},
                                     cell.chips)
        t_checks = clock()
        checks = gen.checks()
        t_checks = clock() - t_checks
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": int(peak)}
    if "bytes_limit" in stats:
        device["memory_bytes_limit"] = int(stats["bytes_limit"])
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        breakdown = {"device_ops": rec.trace["device_ops"],
                     "idle_gaps": rec.trace["idle_gaps"]}
    metrics = H.metric_values(cell.per_layer if trace else cell.end_to_end,
                              rec, base)
    print(f"chip benchmark: {len(rec.ops)} ops, window "
          f"{rec.window_end - rec.window_t0:.3f} s, checks took "
          f"{t_checks:.3f} s", file=sys.stderr)
    H.print_checks(checks)
    return H.result_line(checks, len(rec.ops), 0, metrics, device, breakdown)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        from benchmarks.chip import harness as H
    except ImportError as e:
        print(f"chip benchmark: cannot import its harness: {e}",
              file=sys.stderr)
        return 2
    try:
        bench = H.load_json(ROOT / "BENCHMARK.json")
        cell = H.find_cell(bench, args.workload)
        import jax

        devices = jax.devices()
        H.require_accelerator(devices, cell.chips)
        peaks = H.peaks_for(devices[0].device_kind)
        from repro.launch.compile_cache import init_compile_cache
    except (H.BenchError, OSError, ImportError, KeyError) as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
