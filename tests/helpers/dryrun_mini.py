"""Subprocess helper: miniature end-to-end dry-run on 8 simulated devices.

Exercises the exact production path (rules -> step builders -> lower ->
compile -> hlo_analysis) with a reduced config and a (2, 4) mesh, and
checks the analysis invariants the roofline depends on.
"""

import sys

import jax

from repro.configs import get_smoke_config
from repro.configs.base import ShapeConfig
from repro.distrib.rules import rules_for
from repro.launch.mesh import make_debug_mesh
from repro.launch.hlo_analysis import analyze_compiled
from repro.models.api import build_model
from repro.train.optim import make_optimizer
from repro.train.schedule import warmup_cosine
from repro.train.step import make_decode_step, make_train_step
import functools


def main():
    assert len(jax.devices()) == 8
    mesh = make_debug_mesh(2, 4)

    # train step: gemma2 family (local/global windows, softcaps)
    cfg = get_smoke_config("gemma2_2b")
    api = build_model(cfg)
    rules = rules_for(cfg.arch)
    shape = ShapeConfig("mini", 64, 8, "train")
    sched = functools.partial(warmup_cosine, base_lr=1e-3, warmup=2,
                              total=10)
    step = make_train_step(api, make_optimizer(cfg.optimizer), sched,
                           mesh, rules, shape)
    lowered = step.lower()
    compiled = lowered.compile()
    rec = analyze_compiled(compiled)
    assert rec["flops"] > 0
    assert rec["bytes"] > 0
    assert rec["coll_bytes"] > 0, "sharded train step must communicate"
    assert rec["unknown_trips"] == 0, "scan trip counts must be known"
    assert rec["memory"]["temp_bytes"] > 0
    print("train cell:", {k: round(v) for k, v in rec.items()
                          if isinstance(v, (int, float))})

    # decode step: MoE family with EP + padded experts
    cfg2 = get_smoke_config("granite_moe_3b_a800m")
    import dataclasses

    from repro.configs.base import MoEConfig

    cfg2 = dataclasses.replace(
        cfg2, moe=MoEConfig(num_experts=6, top_k=2, d_ff_expert=32,
                            capacity_factor=2.0, impl="ep"))
    api2 = build_model(cfg2)
    rules2 = rules_for(cfg2.arch)
    dshape = ShapeConfig("mini_dec", 64, 8, "decode")
    dec = make_decode_step(api2, mesh, rules2, dshape)
    rec2 = analyze_compiled(dec.lower().compile())
    assert rec2["coll_bytes"] > 0      # EP combine psum at minimum
    print("decode cell:", {k: round(v) for k, v in rec2.items()
                           if isinstance(v, (int, float))})
    print("dryrun_mini OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
