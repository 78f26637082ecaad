"""The paper's headline use case: save big, post-process small.

A training run on an 8-device (4, 2) mesh checkpoints a step SERIES; a
"workstation" (M = 1 device, different process) later sweeps every
committed step, loading ONLY the arrays it needs — the embedding table
and the final norm — without touching the rest of the multi-GiB state
and without any knowledge of the save-time distribution (paper §1:
"post-process the result on a local workstation using a much smaller
number of processes").  The sweep is ``core/resharder.sweep_steps``:
one region plan built once, per-step I/O only the step's own
(non-deduped) extents.

Run:  PYTHONPATH=src python examples/postprocess_small_m.py
"""

import functools
import os
import shutil
import subprocess
import sys

CKPT = "/tmp/ex_postprocess_ckpt"


def train_phase():
    """Runs in a subprocess with 8 simulated devices."""
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

    cfg = get_smoke_config("smollm_135m")
    api = build_model(cfg)
    mesh = make_debug_mesh(4, 2)
    rules = rules_for(cfg.arch)
    shape = ShapeConfig("pp", 32, 8, "train")
    opt = make_optimizer(cfg.optimizer)
    sched = functools.partial(warmup_cosine, base_lr=3e-3, warmup=5,
                              total=30)
    step = make_train_step(api, opt, sched, mesh, rules, shape)
    data = SyntheticLM(cfg.vocab, 32, 8, seed=0)
    tr = Trainer(step, data,
                 TrainerConfig(ckpt_dir=CKPT, ckpt_every=10, log_every=10),
                 init_state_fn=lambda: init_train_state(
                     api, opt, jax.random.key(0)))
    tr.run(20)
    print(f"[N side] trained 20 steps on mesh (4,2); checkpointed to {CKPT}")


def postprocess_phase():
    """The M = 1 'workstation': a selective sweep over every committed
    step of the stream — no mesh, no model."""
    import numpy as np

    from repro.core.comm import Comm
    from repro.core.resharder import sweep_steps
    from repro.core.store import DatasetStore
    from repro.core.tensor_ckpt import TensorCheckpoint

    ck = TensorCheckpoint(DatasetStore(CKPT, "r"))
    layout = ck.layout()
    wanted = ["params/embed", "params/final_norm"]
    plan = [{name: [layout.spec(name).full_box] for name in wanted}]
    total_arrays = len(layout.names)
    print(f"[M side] sweeping committed steps {ck.steps()} on 1 process, "
          f"{len(wanted)}/{total_arrays} arrays each:")
    embed = None
    for step, out in sweep_steps(ck, plan, Comm(1), arrays=wanted):
        embed = out[0]["params/embed"][0]
        norm = out[0]["params/final_norm"][0]
        print(f"  step {step:>3}: "
              f"|embed| = {float(np.abs(embed.astype(np.float32)).mean()):.4f}, "
              f"final_norm mean = {float(norm.astype(np.float32).mean()):.4f}")
    # nearest-neighbour demo over the last step's embeddings
    e = embed.astype(np.float32)
    e = e / (np.linalg.norm(e, axis=1, keepdims=True) + 1e-6)
    sims = e[:8] @ e.T
    np.fill_diagonal(sims[:, :8], -1)
    print(f"  nearest neighbours of tokens 0..7 (step {ck.steps()[-1]}): "
          f"{sims.argmax(1).tolist()}")


def main():
    if os.environ.get("_PP_CHILD") == "1":
        train_phase()
        return
    shutil.rmtree(CKPT, ignore_errors=True)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["_PP_CHILD"] = "1"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env)
    assert r.returncode == 0
    postprocess_phase()


if __name__ == "__main__":
    main()
