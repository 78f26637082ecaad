#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, on the chip, at the cells'
own sizes; the benchmark's own runs never run this.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,... --control-seeds 1,2,3 [--out FILE]

For each seed, in one process: the program's numbers against the plain
reference (the lower readings); for each control seed, the control's
numbers, the reference itself in the next precision below the
configuration's, put in the program's place (the upper readings); and, for
a training cell, the numbers of the program with half of each batch left
out (the mean taken over the rest).  One JSON line per reading is printed
and, with ``--out``, written to FILE.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import harness as H  # noqa: E402

NO_LIMITS = {"loss_gap": float("inf"), "grad_norm_gap": float("inf"),
             "change_norm_gap": float("inf")}


def _ctx(cell, seed, devices, tmp):
    return H.Ctx(cell, seed, 0.0, False, devices[:cell.chips], Path(tmp),
                 H.Records(), time.perf_counter)


def _numbers(checks) -> dict:
    return {c.name: c.value for c in checks}


def train_program(cell, seed, devices, tmp):
    """The program's first three steps through the loop's own call and
    feed (no saves: the numbers are the step program's), and the generator
    that holds their readings."""
    import copy

    cell = copy.deepcopy(cell)
    cell.traffic["ckpt_every"] = 0
    gen = H.generator(cell.traffic).Generator(_ctx(cell, seed, devices, tmp))
    gen.setup()
    gen._restore()
    del gen.state, gen.tr
    return gen


def half_batch():
    """Plant the fault: the loss leaves out the second half of each batch
    and takes its mean over the rest."""
    import repro.models.transformer as tf

    loss = tf.loss_fn

    def half(params, cfg, batch):
        b = batch["mask"].shape[0]
        mask = batch["mask"].at[b // 2:].set(0.0)
        return loss(params, cfg, {**batch, "mask": mask})

    tf.loss_fn = half
    return lambda: setattr(tf, "loss_fn", loss)


def calibrate_train(cell, seeds, control_seeds, devices, emit):
    """Program and control readings; the half-batch fault on the control
    seeds.  (The other fault, a step that returns its state unchanged,
    reads 1 on ``change_norm_gap`` by definition.)"""
    import jax.numpy as jnp

    train = H.generator(cell.traffic)
    for seed in sorted(set(seeds) | set(control_seeds)):
        with tempfile.TemporaryDirectory() as tmp:
            gen = train_program(cell, seed, devices, tmp)
            ref, p0 = gen.reference()
            if seed in seeds:
                emit({"kind": "program", "seed": seed, **_numbers(
                    train.compare(gen.reading(p0), ref, NO_LIMITS))})
            if seed not in control_seeds:
                continue
            ctl, _ = gen.reference(quant=jnp.float8_e4m3fn)
            emit({"kind": "control_fp8", "seed": seed, **_numbers(
                train.compare(ctl, ref, NO_LIMITS))})
            undo = half_batch()
            try:
                bad = train_program(cell, seed, devices, tmp)
            finally:
                undo()
            emit({"kind": "fault_half_batch", "seed": seed, **_numbers(
                train.compare(bad.reading(p0), ref, NO_LIMITS))})


def calibrate_restore(cell, control_seeds, devices, emit):
    """Control: the plain reader's leaves, rounded to the next precision
    below each leaf's (float32 -> bfloat16, bfloat16 -> float8), placed on
    the target devices in the program's place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip.reference import store_reader

    lower = {"float32": jnp.bfloat16, "bfloat16": jnp.float8_e4m3fn}
    for seed in control_seeds:
        with tempfile.TemporaryDirectory() as tmp:
            gen = H.generator(cell.traffic).Generator(
                _ctx(cell, seed, devices, tmp))
            gen.setup()
            root = str(Path(tmp) / "ckpt")
            for name in gen.order:
                host = store_reader.read_step(root, gen.step_idx, names={name})[name]
                low = lower.get(str(host.dtype))
                if low is not None:
                    host = np.asarray(host.astype(low).astype(host.dtype))
                gen.loaded[name] = jax.device_put(
                    host, gen.targets[name].sharding)
                gen.i += 1
            emit({"kind": "control_lower_precision", "seed": seed,
                  **_numbers(gen.checks())})


def calibrate_fe(cell, control_seeds, devices, emit):
    """Control: the loaded ranks' DoFs from the plain reference, rounded
    to float32, placed on the chip in the program's place."""
    import jax
    import numpy as np

    from benchmarks.chip.reference import fe_field

    for seed in control_seeds:
        with tempfile.TemporaryDirectory() as tmp:
            gen = H.generator(cell.traffic).Generator(
                _ctx(cell, seed, devices, tmp))
            gen.setup()
            loaded = gen.ck.load_mesh("m", gen.comm,
                                      partition=gen.job["load_partition"])
            held = []
            for lp in loaded.plexes:
                vals = fe_field.rank_dofs(gen.field, gen.offsets, lp.loc_g,
                                          lp.dims, gen.degree)
                vals = vals.astype(np.float32).astype(np.float64)
                held.append((lp.loc_g, lp.dims, lp.owner == lp.rank,
                             jax.device_put(vals.view(np.uint32)
                                            .reshape(-1, 2), gen.device)))
            gen.kept = [held]
            emit({"kind": "control_float32", "seed": seed,
                  **_numbers(gen.checks())})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    import jax

    from repro.launch.compile_cache import init_compile_cache

    cell = H.find_cell(H.load_json(ROOT / "BENCHMARK.json"), args.workload)
    devices = jax.devices()
    H.require_accelerator(devices, cell.chips)
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = open(args.out, "a") if args.out else None

    def emit(row):
        row = {"workload": cell.name, **row}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()

    kind = cell.traffic["generator"]
    if kind == "train":
        calibrate_train(cell, seeds, control, devices, emit)
    elif kind == "leaf_restore":
        calibrate_restore(cell, control, devices, emit)
    elif kind == "fe_restart":
        calibrate_fe(cell, control, devices, emit)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
