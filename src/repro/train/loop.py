"""Fault-tolerant training loop — the paper's technique as the recovery
path, not a side feature.

Every ``ckpt_every`` steps the loop snapshots the (sharded) train state
to host memory and writes it through the N-to-M TensorCheckpoint on a
background thread (double-buffered; the commit marker lands last, so a
crash mid-write falls back to the previous committed step).  A restart —
same process count or different, same mesh or different — goes through
``restore_latest``, which is the paper's load path: the saved layout is
re-partitioned onto whatever sharding the new mesh dictates.

The data pipeline state (next step index) and the RNG seed ride in the
checkpoint attrs, so a restart resumes the exact token stream.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import numpy as np

from repro.core.async_io import AsyncCheckpointer
from repro.core.comm import Comm
from repro.core.jax_io import (
    layout_from_jax,
    load_jax,
    save_jax,
    snapshot_jax,
    tree_names,
)
from repro.core.spans import span
from repro.core.store import DatasetStore
from repro.core.tensor_ckpt import TensorCheckpoint
from repro.train.data import SyntheticLM
from repro.train.step import TrainStep


class SimulatedPreemption(RuntimeError):
    """Raised mid-run to emulate a node failure / wall-time kill."""


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str
    ckpt_every: int = 20
    async_ckpt: bool = True
    log_every: int = 10
    # store constructor (root, mode) -> DatasetStore; lets harnesses swap in
    # an instrumented store (e.g. tests/helpers/faultstore.FaultStore)
    store_factory: Callable[[str, str], DatasetStore] | None = None


class Trainer:
    def __init__(self, step: TrainStep, data: SyntheticLM,
                 cfg: TrainerConfig, init_state_fn: Callable[[], dict]):
        self.step = step
        self.data = data
        self.cfg = cfg
        self.init_state_fn = init_state_fn
        self.comm = Comm(jax.process_count())
        self.history: list[dict] = []
        self._ckpt: TensorCheckpoint | None = None
        self._async: AsyncCheckpointer | None = None

    # ------------------------------------------------------------ ckpt io
    def _open_ckpt(self, mode: str) -> TensorCheckpoint:
        make = self.cfg.store_factory or DatasetStore
        return TensorCheckpoint(make(self.cfg.ckpt_dir, mode))

    def restore_latest(self) -> tuple[dict, int]:
        """(state on the CURRENT mesh/sharding, start_step).  Fresh init
        if no committed checkpoint exists — the cold-start path."""
        try:
            ck = self._open_ckpt("r")
            steps = ck.steps()
        except FileNotFoundError:
            steps = []
        if not steps:
            # built under jit straight onto the step's shardings: not all on
            # the first device, and in the same layout as every later step
            # input (another layout would compile the step a second time)
            init = jax.jit(self.init_state_fn,
                           out_shardings=self.step.state_shardings)
            return init(), 0
        return self.restore_from(steps[-1])

    def restore_from(self, step: int) -> tuple[dict, int]:
        """Restart-from-step-k: load committed step ``step`` of the
        checkpoint stream onto the CURRENT mesh/sharding.  A torn or unknown
        step raises ``ValueError`` naming the committed prefix.  The stream
        is append-only, so a run resumed from an earlier step can only save
        steps beyond the last committed one."""
        step = int(step)
        ck = self._open_ckpt("a")
        if step not in ck.steps():
            raise ValueError(
                f"restore_from({step}): step is not committed "
                f"(committed steps: {ck.steps()})")
        target = {k: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=self.step.state_shardings[k])
                  for k, s in self.step.abstract_state.items()}
        state = load_jax(ck, target, step)
        return state, step

    def _save(self, state: dict, step_idx: int) -> None:
        """Synchronous host snapshot; the store write is double-buffered
        on a daemon thread when cfg.async_ckpt.  Each save is one series
        step bracketed by ``begin_step``/``commit_step``: the manifest
        entry is the commit marker, so a crash mid-write falls back to the
        previous committed step, and unchanged arrays dedup against the
        stream (stored once, aliased in the manifest)."""
        with span("ckpt.save", step=int(step_idx)):
            ck = self._open_ckpt("a" if self._ckpt_exists() else "w")
            if not ck.store.has_attrs("layout"):
                ck.save_layout(layout_from_jax(state),
                               extra={"pipeline": self.data.state(step_idx)})
            if not self.cfg.async_ckpt:
                ck.store.begin_step(step_idx)
                save_jax(ck, state, step_idx)
                ck.store.commit_step()
                return
            if (self._async is None
                    or self._async.ckpt.store.root != ck.store.root):
                self._async = AsyncCheckpointer(ck, self.comm)
            per_rank = snapshot_jax(ck.layout(), state)
            self._async.begin_step(step_idx)
            self._async.submit(per_rank, step_idx)
            self._async.commit_step()
            # the arena holds the copy now: the loop also waits while the
            # snapshot's chunk copies are freed, so that is inside the span
            del per_rank

    def wait_for_writes(self) -> None:
        if self._async is not None:
            self._async.wait()

    def _ckpt_exists(self) -> bool:
        import os
        return os.path.exists(os.path.join(self.cfg.ckpt_dir, "store.json"))

    # -------------------------------------------------------------- batches
    def _device_batch(self, step_idx: int) -> dict:
        batch = self.data.batch(step_idx)
        out = {}
        for k, sh in self.step.batch_shardings.items():
            if k in batch:
                out[k] = jax.device_put(batch[k], sh)
        # extra inputs (e.g. whisper enc_frames) default to zeros
        for k, sds in self.step.abstract_batch.items():
            if k not in out:
                out[k] = jax.device_put(
                    np.zeros(sds.shape, dtype=np.dtype(str(sds.dtype))),
                    self.step.batch_shardings[k])
        return out

    # ----------------------------------------------------------------- run
    def run(self, num_steps: int, *, fail_at: int | None = None,
            start_state=None, start_step: int | None = None) -> dict:
        if start_state is None:
            state, start = self.restore_latest()
        else:
            state, start = start_state, int(start_step or 0)
        t0 = time.time()
        saved_steps = []
        for i in range(start, num_steps):
            if fail_at is not None and i == fail_at:
                # SIGTERM grace period: flush the in-flight async write
                # (the commit marker either lands whole or not at all)
                self.wait_for_writes()
                raise SimulatedPreemption(f"preempted at step {i}")
            batch = self._device_batch(i)
            state, metrics = self.step(state, batch)
            if self.cfg.log_every and (i + 1) % self.cfg.log_every == 0:
                self.history.append(
                    {"step": i + 1,
                     "loss": float(metrics["loss"]),
                     "lr": float(metrics["lr"])})
            if self.cfg.ckpt_every and (i + 1) % self.cfg.ckpt_every == 0:
                self._save(state, i + 1)
                saved_steps.append(i + 1)
        self.wait_for_writes()
        return {"state": state, "steps_run": num_steps - start,
                "saved_steps": saved_steps,
                "seconds": time.time() - t0,
                "history": self.history}
