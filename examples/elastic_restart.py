"""Elastic restart: train on one mesh, crash mid-checkpoint, restart on a
DIFFERENT mesh.

The N-to-M headline applied to live training state, now with the failure
actually injected: a run sharded over mesh (4, 2) ("data", "model")
checkpoints steps 10 and 20; a second run on the same mesh dies
mid-checkpoint of step 30 (a fault-injected store kills the async writer
after a handful of write ops — before the commit marker lands); a third
run re-loads onto mesh (2, 4) — different device count per axis, different
parameter partitions — and restarts from committed series step 20 by
explicit ``restore_from(20)``: the torn step-30 write never entered the
step manifest, exactly the recovery contract documented in
``core/async_io.py``.

Run:  PYTHONPATH=src python examples/elastic_restart.py
(relaunches itself with XLA_FLAGS for 8 simulated host devices)
"""

import functools
import os
import shutil
import subprocess
import sys

CKPT = "/tmp/ex_elastic_ckpt"


def phase(mesh_shape, steps, expect_start, store_factory=None,
          expect_crash=False, from_step=None):
    import jax

    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.distrib.rules import rules_for
    from repro.launch.mesh import make_debug_mesh
    from repro.models.api import build_model
    from repro.train.data import SyntheticLM
    from repro.train.loop import Trainer, TrainerConfig
    from repro.train.optim import make_optimizer
    from repro.train.schedule import warmup_cosine
    from repro.train.step import init_train_state, make_train_step

    cfg = get_smoke_config("qwen3_1_7b")
    api = build_model(cfg)
    mesh = make_debug_mesh(*mesh_shape)
    rules = rules_for(cfg.arch)
    shape = ShapeConfig("ex", 32, 8, "train")
    opt = make_optimizer(cfg.optimizer)
    sched = functools.partial(warmup_cosine, base_lr=3e-3, warmup=10,
                              total=100)
    step = make_train_step(api, opt, sched, mesh, rules, shape)
    data = SyntheticLM(cfg.vocab, 32, 8, seed=0)
    tcfg = TrainerConfig(ckpt_dir=CKPT, ckpt_every=10, log_every=10,
                         store_factory=store_factory)
    tr = Trainer(step, data, tcfg,
                 init_state_fn=lambda: init_train_state(
                     api, opt, jax.random.key(0)))
    if from_step is None:
        state, start = tr.restore_latest()
    else:
        # restart-from-step-k: name the committed series step explicitly
        # (a torn or unknown step raises ValueError with the committed
        # prefix — the stream's manifest is the source of truth)
        state, start = tr.restore_from(from_step)
    assert start == expect_start, (start, expect_start)
    print(f"mesh {mesh_shape}: restored step {start}; param sharding "
          f"example: "
          f"{step.state_shardings['params/wq'].spec}")
    if expect_crash:
        try:
            tr.run(steps, start_state=state, start_step=start)
        except RuntimeError as e:
            print(f"mesh {mesh_shape}: died mid-checkpoint as injected "
                  f"({e.__cause__ or e})")
            return
        raise SystemExit("FAIL: the injected crash never fired")
    res = tr.run(steps, start_state=state, start_step=start)
    print(f"mesh {mesh_shape}: ran to step {steps}; "
          f"last loss {tr.history[-1]['loss']:.4f}")


def main():
    if os.environ.get("_ELASTIC_CHILD") != "1":
        shutil.rmtree(CKPT, ignore_errors=True)
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["_ELASTIC_CHILD"] = "1"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # tests dir on the path for helpers.faultstore (the fault injector)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(repo, "src"), os.path.join(repo, "tests")])
        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env)
        sys.exit(r.returncode)

    from helpers.faultstore import FaultStore

    print("== phase 1: mesh (4, 2) — N side ==")
    phase((4, 2), steps=20, expect_start=0)
    print("== phase 2: crash mid-checkpoint of step 30 (fault injection) ==")
    # the async writer dies after 4 write ops of the step-30 save — well
    # before its commit marker — leaving step 20 the last committed step
    phase((4, 2), steps=30, expect_start=20,
          store_factory=lambda root, mode: FaultStore(
              root, mode, kill_after_ops=4),
          expect_crash=True)
    print("== phase 3: mesh (2, 4) — M side (restart from step 20) ==")
    phase((2, 4), steps=40, expect_start=20, from_step=20)
    print("elastic N-to-M restart after an injected crash OK")


if __name__ == "__main__":
    main()
