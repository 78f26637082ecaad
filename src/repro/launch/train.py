"""Training launcher.

On real hardware this runs under one process per host with
``jax.distributed.initialize()``; in this container it drives the same
code on the 1-device CPU view (reduced configs) — the multi-pod story is
proven by ``dryrun.py``.

Example (CPU):
    PYTHONPATH=src python -m repro.launch.train \
        --arch smollm-135m --smoke --steps 60 --batch 4 --seq 64 \
        --ckpt-dir /tmp/ck --ckpt-every 20
"""

from __future__ import annotations

import argparse
import functools
import json

import jax

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.distrib.rules import rules_for
from repro.launch.compile_cache import init_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.api import build_model
from repro.train.data import SyntheticLM
from repro.train.loop import Trainer, TrainerConfig
from repro.train.optim import make_optimizer
from repro.train.schedule import warmup_cosine
from repro.train.step import init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_debug_mesh(args.data_mesh, args.model_mesh))
    rules = rules_for(cfg.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    opt = make_optimizer(cfg.optimizer)
    sched = functools.partial(warmup_cosine, base_lr=args.lr,
                              warmup=max(2, args.steps // 20),
                              total=args.steps)
    step = make_train_step(api, opt, sched, mesh, rules, shape)
    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=args.seed)
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir or "/tmp/repro_ckpt",
                         ckpt_every=args.ckpt_every, log_every=10)
    trainer = Trainer(step, data, tcfg,
                      init_state_fn=lambda: init_train_state(
                          api, opt, jax.random.key(args.seed)))
    result = trainer.run(args.steps, fail_at=args.fail_at)
    for h in result["history"]:
        print(json.dumps(h))
    print(json.dumps({"final_loss": result["history"][-1]["loss"]
                      if result["history"] else None,
                      "saved_steps": result["saved_steps"],
                      "seconds": round(result["seconds"], 2)}))


if __name__ == "__main__":
    main()
