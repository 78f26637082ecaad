"""Generator ``leaf_restore``: a real train state saved through ``Trainer``,
then loaded back one leaf at a time through the program's ``load_jax``.

Traffic parameters: ``save_mesh`` and ``load_mesh`` (data, model),
``train_steps`` (steps before the save, at least 2 so that both AdamW
moments are non-zero and no two leaves are equal), ``seq_len``,
``global_batch``, ``schedule``.

Set-up trains ``train_steps`` steps on the save mesh, saves the last one
and waits for its commit, keeps a host copy of the saved state and frees
the device state.  One op loads one leaf onto its sharding on the load
mesh, leaves in layout order and cycling, and ends when the leaf is on
every target device; a one-number fingerprint of the leaf is then computed
on the device (the jitted fingerprints compile in set-up).

Check: every leaf loaded in the window equals the saved state bit for bit
and carries its target sharding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import lm
from benchmarks.chip.harness import Check


def _fingerprint(x):
    return jnp.sum(x.astype(jnp.float32))


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.job = ctx.config, ctx.traffic

    def _mesh(self, key: str):
        from repro.launch.mesh import make_debug_mesh

        data, model = self.job[key]
        return make_debug_mesh(data, model,
                               devices=self.ctx.devices[:data * model])

    def setup(self) -> None:
        from repro.core.store import DatasetStore
        from repro.core.tensor_ckpt import TensorCheckpoint
        from repro.train.loop import Trainer, TrainerConfig

        ctx, cfg, job = self.ctx, self.cfg, self.job
        steps = int(job["train_steps"])
        step = lm.train_step(cfg, job, self._mesh("save_mesh"))
        init = jax.jit(lambda k: lm.make_state(cfg, k),
                       out_shardings=step.state_shardings)
        ckpt_dir = str(ctx.workdir / "ckpt")
        tr = Trainer(step, lm.Feed(ctx.seed, cfg["vocab_size"],
                                   job["seq_len"], job["global_batch"]),
                     TrainerConfig(ckpt_dir=ckpt_dir, ckpt_every=steps,
                                   async_ckpt=True, log_every=0),
                     init_state_fn=lambda: init(lm.seed_key(ctx.seed)))
        res = tr.run(steps, start_state=init(lm.seed_key(ctx.seed)),
                     start_step=0)
        self.saved = {n: np.asarray(a)
                      for n, a in jax.device_get(res["state"]).items()}
        del res, tr, step
        self.step_idx = steps

        target_step = lm.train_step(cfg, job, self._mesh("load_mesh"))
        self.targets = {n: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=target_step.state_shardings[n])
            for n, s in target_step.abstract_state.items()}
        rec = ctx.rec
        clock = ctx.clock

        class Timed(TensorCheckpoint):
            """The program's checkpoint with the engine's time recorded."""

            def load_state(self, *args, **kwargs):
                t0 = clock()
                try:
                    return super().load_state(*args, **kwargs)
                finally:
                    rec.counters["load_state_s"].append(clock() - t0)

        rec.counters.update(load_state_s=[])
        self.ck = Timed(DatasetStore(ckpt_dir, "r"))
        self.order = [s["name"] for s in self.ck.store.get_attrs("layout")]
        self.fingerprint = {n: jax.jit(_fingerprint).lower(
            self.targets[n]).compile() for n in self.order}
        self.loaded: dict[str, jax.Array] = {}
        self.i = 0

    def op(self) -> dict:
        from repro.core.jax_io import load_jax

        name = self.order[self.i % len(self.order)]
        self.i += 1
        n0 = len(self.ctx.rec.counters["load_state_s"])
        with self.ctx.span("load_leaf") as span:
            arr = load_jax(self.ck, {name: self.targets[name]},
                           self.step_idx)[name]
            arr.block_until_ready()
        self.fingerprint[name](arr).block_until_ready()
        # the latest load of each leaf is kept for the check: at most one
        # copy of the state stays on the device however fast loads get
        self.loaded[name] = arr
        engine = sum(self.ctx.rec.counters["load_state_s"][n0:])
        return {"bytes": int(arr.nbytes), "load_state_s": engine,
                "load_s": span.t1 - span.t0}

    def after_window(self) -> None:
        pass

    def checks(self) -> list[Check]:
        differ = wrong_sharding = 0
        for name, arr in self.loaded.items():
            want = self.saved[name]
            got = np.asarray(jax.device_get(arr))
            if (got.dtype != want.dtype or got.shape != want.shape
                    or got.tobytes() != want.tobytes()):
                differ += 1
            if not arr.sharding.is_equivalent_to(
                    self.targets[name].sharding, arr.ndim):
                wrong_sharding += 1
        self.loaded.clear()
        return [Check("leaves_differ", float(differ), 0),
                Check("leaves_off_sharding", float(wrong_sharding), 0),
                Check("no_leaf_loaded", float(self.i == 0), 0)]
