#!/usr/bin/env python3
"""Chip smoke test: train -> async save -> kill -> resume on a TPU.

Default (one chip, one process): full-width smollm-135m (seq 2048, global
batch 8, AdamW) on a (1, 1) mesh through ``Trainer``, saving
asynchronously every 3 steps.

* Run A (straight): ``run(3)``, host copy H3 of the state, then on to
  step 6 -> state S6 and losses.
* Run B (kill and resume): ``run(6, fail_at=5)`` is preempted with step 3
  committed; a fresh ``Trainer`` restores step 3, which must equal H3 bit
  for bit and pass ``verify_step``, and runs on to step 6, whose state and
  losses must equal run A's bit for bit.

``--chips 4``: trains 3 steps on a (4, 1) mesh over four chips, saves
step 3, and restores it onto a (2, 2) mesh and onto a (2, 1) mesh of the
first two chips.  Every restored leaf must equal the saved state bit for
bit and carry its target sharding, and one step on each new mesh must
give a finite loss.

Timings printed before the last line are smoke readings, not benchmark
numbers.  The last line is one JSON object naming the device.  Any
failure raises and exits non-zero; without a TPU it exits non-zero
before doing anything.

Run:  python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "smollm_135m"
SEQ_LEN = 2048
GLOBAL_BATCH = 8
CKPT_EVERY = 3
SEED = 0
TIMED_STEPS = 5


def require_tpu() -> jax.Device:
    """The first device, which must be a TPU.  JAX falls back to the CPU
    quietly when the TPU backend fails to start, and the Pallas wrappers
    then switch to interpret mode: nothing may go on from there."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX's first device is on "
                 f"platform {dev.platform!r} ({dev.device_kind})")
    return dev


def reading(name: str, value) -> None:
    print(f"smoke reading (not a benchmark): {name} = {value}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"ok: {what}", flush=True)


def assert_bit_equal(got: dict, want: dict, what: str) -> None:
    check(sorted(got) == sorted(want), f"{what}: same {len(want)} leaves")
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if (a.dtype != b.dtype or a.shape != b.shape
                or a.tobytes() != b.tobytes()):
            raise AssertionError(f"{what}: leaf {name!r} differs "
                                 f"({a.dtype}{a.shape} vs {b.dtype}{b.shape})")
    print(f"ok: {what}: every leaf bit-identical", flush=True)


def assert_shardings(state: dict, step, what: str) -> None:
    for name, arr in state.items():
        want = step.state_shardings[name]
        if not arr.sharding.is_equivalent_to(want, arr.ndim):
            raise AssertionError(f"{what}: leaf {name!r} has sharding "
                                 f"{arr.sharding}, want {want}")
    print(f"ok: {what}: every leaf on its target sharding", flush=True)


class Model:
    """The full-width config, its train step on ``mesh`` and its data."""

    def __init__(self, mesh):
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        from repro.distrib.rules import rules_for
        from repro.models.api import build_model
        from repro.train.data import SyntheticLM
        from repro.train.optim import make_optimizer
        from repro.train.schedule import warmup_cosine
        from repro.train.step import init_train_state, make_train_step

        cfg = get_config(ARCH)
        api = build_model(cfg)
        opt = make_optimizer(cfg.optimizer)
        sched = functools.partial(warmup_cosine, base_lr=1e-3, warmup=2,
                                  total=100)
        self.step = make_train_step(
            api, opt, sched, mesh, rules_for(cfg.arch),
            ShapeConfig("chip_smoke", SEQ_LEN, GLOBAL_BATCH, "train"))
        self.data = SyntheticLM(cfg.vocab, SEQ_LEN, GLOBAL_BATCH, seed=SEED)
        self.init_state = lambda: init_train_state(api, opt,
                                                   jax.random.key(SEED))

    def batch(self, i: int) -> dict:
        """Global batch ``i`` on the step's input shardings."""
        return {k: jax.device_put(v, self.step.batch_shardings[k])
                for k, v in self.data.batch(i).items()}

    def trainer(self, ckpt_dir: str):
        from repro.train.loop import Trainer, TrainerConfig

        return Trainer(self.step, self.data,
                       TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=CKPT_EVERY,
                                     async_ckpt=True, log_every=1),
                       init_state_fn=self.init_state)


def saves_since(t0: float) -> list[tuple[int, float]]:
    """(step, seconds) of each save that blocked the loop since ``t0``:
    the program's ``ckpt.save`` spans."""
    from repro.core import spans

    return [(s.attrs["step"], s.seconds) for s in spans.spans()
            if s.name == "ckpt.save" and s.t0 >= t0]


def losses(history: list[dict], first: int, last: int) -> list[float]:
    return [h["loss"] for h in history if first <= h["step"] <= last]


def time_steps(model: Model, ckpt_dir: str) -> None:
    """First step from a cold start (compile included) and the median of
    the next steps, each ending in ``block_until_ready``."""
    state, _ = model.trainer(ckpt_dir).restore_latest()
    times = []
    for i in range(TIMED_STEPS + 1):
        batch = jax.block_until_ready(model.batch(i))
        t0 = time.perf_counter()
        state, metrics = model.step(state, batch)
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t0)
    median = statistics.median(times[1:])
    reading("first_step_seconds_incl_compile", times[0])
    reading("compile_seconds_estimate", times[0] - median)
    reading("median_step_seconds", median)
    reading("step_seconds", times[1:])


def one_chip(workdir: Path) -> None:
    from repro.core.comm import Comm
    from repro.core.store import DatasetStore
    from repro.core.tensor_ckpt import TensorCheckpoint
    from repro.launch.mesh import make_debug_mesh
    from repro.train.loop import SimulatedPreemption

    model = Model(make_debug_mesh(1, 1))
    time_steps(model, str(workdir / "cold"))
    t_saves = time.perf_counter()

    # ---- run A: straight to step 6
    ta = model.trainer(str(workdir / "a"))
    ra = ta.run(3)
    check(ra["saved_steps"] == [3], "run A saved step 3")
    h3 = jax.device_get(ra["state"])
    ra = ta.run(6, start_state=ra.pop("state"), start_step=3)
    check(ra["saved_steps"] == [6], "run A saved step 6")
    s6 = jax.device_get(ra.pop("state"))
    losses_a = losses(ta.history, 1, 6)
    check(len(losses_a) == 6 and all(map(math.isfinite, losses_a)),
          f"run A: 6 finite losses {losses_a}")

    # ---- run B: killed at step 5, resumed from committed step 3
    tb_dir = str(workdir / "b")
    tb = model.trainer(tb_dir)
    try:
        tb.run(6, fail_at=5)
    except SimulatedPreemption:
        print("ok: run B preempted at step 5", flush=True)
    else:
        raise AssertionError("run B was not preempted")
    ck = TensorCheckpoint(DatasetStore(tb_dir, "r"))
    check(ck.steps() == [3], f"run B committed steps {ck.steps()} == [3]")

    tr = model.trainer(tb_dir)
    t0 = time.perf_counter()
    state, start = tr.restore_latest()
    jax.block_until_ready(state)
    restore_s = time.perf_counter() - t0
    check(start == 3, f"restore_latest resumes at step {start} == 3")
    assert_shardings(state, model.step, "restored step 3")
    assert_bit_equal(jax.device_get(state), h3, "restored step 3 == H3")
    check(ck.verify_step(Comm(jax.process_count()), 3), "verify_step(3)")

    rb = tr.run(6, start_state=state, start_step=3)
    assert_bit_equal(jax.device_get(rb["state"]), s6, "resumed step 6 == S6")
    losses_b = losses(tr.history, 4, 6)
    check(losses_b == losses_a[3:],
          f"resumed losses of steps 4-6 {losses_b} == run A's")

    for step_idx, seconds in saves_since(t_saves):
        reading(f"save_blocked_loop_seconds[step {step_idx}]", seconds)
    reading("restore_seconds[step 3]", restore_s)


def four_chips(workdir: Path) -> None:
    from repro.launch.mesh import make_debug_mesh

    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} devices == 4")
    ckpt_dir = str(workdir / "four")

    src = Model(make_debug_mesh(4, 1))
    tr = src.trainer(ckpt_dir)
    t_saves = time.perf_counter()
    res = tr.run(3)
    check(res["saved_steps"] == [3], "(4, 1) mesh saved step 3")
    assert_shardings(res["state"], src.step, "(4, 1) state after 3 steps")
    h3 = jax.device_get(res["state"])
    reading("save_blocked_loop_seconds[step 3, (4, 1) mesh]",
            saves_since(t_saves)[0][1])

    for shape, devs in (((2, 2), None), ((2, 1), devices[:2])):
        dst = Model(make_debug_mesh(*shape, devices=devs))
        tr = dst.trainer(ckpt_dir)
        t0 = time.perf_counter()
        state, start = tr.restore_latest()
        jax.block_until_ready(state)
        reading(f"restore_seconds[step 3 onto {shape} mesh]",
                time.perf_counter() - t0)
        check(start == 3, f"{shape} mesh resumes at step {start} == 3")
        assert_shardings(state, dst.step, f"{shape} mesh restore")
        assert_bit_equal(jax.device_get(state), h3,
                         f"{shape} mesh restore == (4, 1) step 3")
        res = tr.run(4, start_state=state, start_step=3)
        assert_shardings(res["state"], dst.step, f"{shape} mesh after 1 step")
        loss = tr.history[-1]["loss"]
        check(math.isfinite(loss), f"{shape} mesh step 4 loss {loss} finite")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the save-on-4 / restore-on-4-and-2 phase")
    args = ap.parse_args(argv)
    dev = require_tpu()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import init_compile_cache

    reading("compile_cache_dir", init_compile_cache())
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        (four_chips if args.chips == 4 else one_chip)(Path(d))
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
