"""Generator ``train``: a training job through the program's ``Trainer``, with
asynchronous saves every ``ckpt_every`` steps as series steps.

Traffic parameters: ``mesh`` (data, model), ``seq_len``, ``global_batch``,
``ckpt_every`` (0: no saves), ``schedule`` (warm-up cosine).

Set-up builds the step and the seed's state once, runs the first three
steps through the loop's own call and feed (the step compiles there, and
the third step's save and its commit are made), and hands the same
``Trainer`` and state to the window.  One op is one save cycle:
``ckpt_every`` steps and the save that ends them.  After the window every
save made is waited for.

Checks: the last committed step, read back by the plain store reader,
equals bit for bit the state the loop held; every save made is
committed; and the first three steps agree with the plain float32
reference (each step's loss, the first gradient's norm per leaf as AdamW's
first moment holds it, the parameters' change per leaf after three steps).
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import lm
from benchmarks.chip.harness import Check
from benchmarks.chip.reference import decoder_lm, store_reader

REFERENCE_STEPS = 3


class _TimedStep:
    """The program's ``TrainStep`` with each call's start recorded."""

    def __init__(self, inner, calls: list, clock):
        self._inner, self._calls, self._clock = inner, calls, clock

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __call__(self, state, batch):
        self._calls.append(self._clock())
        return self._inner(state, batch)


def _leaf_gap(prog: dict, ref: dict, counted: list[str]) -> float:
    """Worst leaf's gap between the program's norm and the reference's, as
    a share of the larger of that leaf's reference norm and the median
    leaf's."""
    median = statistics.median(ref[n] for n in counted)
    return max(abs(prog[n] - ref[n]) / max(ref[n], median) for n in counted)


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.job = ctx.config, ctx.traffic
        self.every = int(self.job["ckpt_every"])
        self.cycle = self.every or REFERENCE_STEPS
        self.tokens_per_step = self.job["seq_len"] * self.job["global_batch"]

    # --------------------------------------------------------------- set-up
    def setup(self) -> None:
        import repro.train.loop as loop_mod
        from repro.launch.mesh import make_debug_mesh
        from repro.train.loop import Trainer, TrainerConfig

        ctx, cfg, job = self.ctx, self.cfg, self.job
        data, model = job["mesh"]
        mesh = make_debug_mesh(data, model,
                               devices=ctx.devices[:data * model])
        step = lm.train_step(cfg, job, mesh)
        init = jax.jit(lambda k: lm.make_state(cfg, k),
                       out_shardings=step.state_shardings)
        state = init(lm.seed_key(ctx.seed))
        rec = ctx.rec
        rec.counters.update(step_calls=[], save_t0={}, chips=data * model)
        self.ckpt_dir = str(ctx.workdir / "ckpt")
        feed = lm.Feed(ctx.seed, cfg["vocab_size"], job["seq_len"],
                       job["global_batch"])
        tr = Trainer(_TimedStep(step, rec.counters["step_calls"], ctx.clock),
                     feed,
                     TrainerConfig(ckpt_dir=self.ckpt_dir,
                                   ckpt_every=self.every, async_ckpt=True,
                                   log_every=1),
                     init_state_fn=lambda: init(lm.seed_key(ctx.seed)))
        save = tr._save

        def timed_save(state, step_idx):
            rec.counters["save_t0"][int(step_idx)] = ctx.clock()
            with ctx.span("save"):
                save(state, step_idx)

        tr._save = timed_save
        snapshot = loop_mod.snapshot_jax

        def timed_snapshot(layout, tree):
            with ctx.span("snapshot"):
                return snapshot(layout, tree)

        loop_mod.snapshot_jax = timed_snapshot
        self._restore = lambda: setattr(loop_mod, "snapshot_jax", snapshot)
        self.tr = tr
        rec.counters["state_bytes"] = lm.tree_nbytes(step.abstract_state)
        rec.counters["flops_per_step"] = (
            lm.model_flops_per_token(cfg, job["seq_len"])
            * self.tokens_per_step)

        res = tr.run(1, start_state=state, start_step=0)
        m_first = jax.jit(decoder_lm.norms)(
            {n[len("opt/m/"):]: a for n, a in res["state"].items()
             if n.startswith("opt/m/")})
        b1 = cfg["optimizer"]["b1"]
        self.grad_norms = {n: float(x) / (1.0 - b1)
                           for n, x in m_first.items()}
        res = tr.run(REFERENCE_STEPS, start_state=res["state"], start_step=1)
        self.params_after = {
            n[len("params/"):]: np.asarray(a) for n, a in jax.device_get(
                {n: a for n, a in res["state"].items()
                 if n.startswith("params/")}).items()}
        self.k = REFERENCE_STEPS
        if self.every and self.k % self.every:
            self.k += self.every - self.k % self.every
            res = tr.run(self.k, start_state=res["state"],
                         start_step=REFERENCE_STEPS)
        self.state = res["state"]
        self.losses = [h["loss"] for h in tr.history[:REFERENCE_STEPS]]
        jax.block_until_ready(self.state)
        # from here on the loop leaves its writes in flight, as a job does
        self.tr.wait_for_writes = lambda: None
        rec.counters["step_calls"].clear()
        self.first_window_step = self.k
        if tr._async is not None:
            self.io0 = tr._async.store.stats.as_dict()
            self.blocked0 = tr._async.arena.stats.blocked_seconds

    # --------------------------------------------------------------- window
    def op(self) -> dict:
        with self.ctx.span("cycle"):
            res = self.tr.run(self.k + self.cycle, start_state=self.state,
                              start_step=self.k)
        self.state = res["state"]
        self.k += self.cycle
        return {"tokens": self.cycle * self.tokens_per_step,
                "steps": self.cycle}

    def after_window(self) -> None:
        """Wait for every save made in the window; read the writer's log,
        the arena's and the store's counters."""
        del self.tr.wait_for_writes
        self.tr.wait_for_writes()
        self._restore()
        jax.block_until_ready(self.state)
        c = self.ctx.rec.counters
        saved = [s for s in sorted(c["save_t0"]) if s > self.first_window_step]
        self.saved = saved
        calls = c["step_calls"]
        c["step_s_nosave"] = [b - a for i, (a, b) in
                              enumerate(zip(calls, calls[1:]))
                              if (i + 1) % self.cycle]
        ac = self.tr._async
        if ac is None or not saved:
            return
        log = {j["label"]: j for j in ac.job_log}
        c["commit_s"] = [log[f"commit/s{s}"]["t1"] - c["save_t0"][s]
                         for s in saved]
        c["writer_s"] = [log[f"state/s{s}"]["seconds"] for s in saved]
        c["saves"] = len(saved)
        c["arena_blocked_s"] = ac.arena.stats.blocked_seconds - self.blocked0
        io = ac.store.stats.as_dict()
        c["bytes_written"] = io["bytes_written"] - self.io0["bytes_written"]
        c["write_seconds"] = io["write_seconds"] - self.io0["write_seconds"]

    # --------------------------------------------------------------- checks
    def checks(self) -> list[Check]:
        limits = self.cfg["limits"]["train"]
        held = {n: np.asarray(a) for n, a in
                jax.device_get(self.state).items()}
        del self.state, self.tr
        out = []
        if self.every:
            committed = store_reader.committed_steps(self.ckpt_dir)
            out.append(Check("saves_not_committed",
                             float(len(set(self.saved) - set(committed))), 0))
            last = self.k
            stored = (store_reader.read_step(self.ckpt_dir, last)
                      if last in committed else {})
            differ = sum(
                1 for n, a in held.items()
                if n not in stored or stored[n].dtype != a.dtype
                or stored[n].shape != a.shape
                or stored[n].tobytes() != a.tobytes())
            out.append(Check("saved_leaves_differ", float(differ), 0))
        out += self.reference_checks(limits)
        return out

    def reference_checks(self, limits: dict) -> list[Check]:
        ref, params0 = self.reference()
        return compare(self.reading(params0), ref, limits)

    def reference(self, quant=None) -> tuple[dict, dict]:
        """The plain reference's readings over the first steps (in
        ``quant`` precision for the control), and the seed's weights."""
        cfg, job, ctx = self.cfg, self.job, self.ctx
        feed = lm.Feed(ctx.seed, cfg["vocab_size"], job["seq_len"],
                       job["global_batch"])
        batches = [jax.tree.map(jnp.asarray, feed.batch(i))
                   for i in range(REFERENCE_STEPS)]
        params0 = jax.jit(lambda k: lm.make_params(cfg, k))(
            lm.seed_key(ctx.seed))
        ref = decoder_lm.follow(params0, batches, cfg, job["schedule"],
                                quant)
        return ref, jax.device_get(params0)

    def reading(self, params0: dict) -> dict:
        """The program's readings in the reference's terms."""
        change = {n: float(np.linalg.norm(
            self.params_after[n].astype(np.float32)
            - np.asarray(params0[n]).astype(np.float32)))
            for n in self.params_after}
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "change_norms": change}


def compare(prog: dict, ref: dict, limits: dict) -> list[Check]:
    """The three training numbers.  Leaves whose reference gradient is
    under a thousandth of the median leaf's move under AdamW by rounding
    alone, and are left out of the change."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    names = sorted(ref["grad_norms"])
    median = statistics.median(ref["grad_norms"][n] for n in names)
    moved = [n for n in names if ref["grad_norms"][n] >= 1e-3 * median]
    return [
        Check("loss_gap", loss_gap, limits["loss_gap"]),
        Check("grad_norm_gap", _leaf_gap(prog["grad_norms"],
                                         ref["grad_norms"], names),
              limits["grad_norm_gap"]),
        Check("change_norm_gap", _leaf_gap(prog["change_norms"],
                                           ref["change_norms"], moved),
              limits["change_norm_gap"]),
    ]
