"""Generator ``fe_restart``: an FE mesh and field saved on N ranks and
restarted on M through the program's ``FEMCheckpoint``.

Traffic parameters: ``save_ranks`` (N), ``load_ranks`` (M),
``save_partition`` and ``load_partition`` (cell partition methods).

Set-up builds the configuration's mesh, distributes it over N ranks, makes
the field's DoFs from the seed on the device (64-bit words as ``uint32``
pairs: the chip holds no float64), saves mesh and field on N ranks, and
makes one restart to warm up.  One op is one restart: ``load_mesh`` on M
ranks, ``load_function``, and each loaded rank's DoFs placed on the device
bit for bit, with a fingerprint computed there.

Check: the DoFs of every sampled restart equal, bit for bit, the saved
field at the same global entities (``reference/fe_field.py``), and the
loaded ranks own every entity once.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.harness import Check
from benchmarks.chip.reference import fe_field

KEPT_RESTARTS = 16       # restarts held for the check, sampled by the seed


def _field_words(key, n: int):
    """``n`` doubles of either sign in [1, 2), random 52-bit mantissas,
    as (low, high) ``uint32`` word pairs: every value finite."""
    w = jax.random.bits(key, (n, 2), jnp.uint32)
    hi = (w[:, 1] & jnp.uint32(0x800FFFFF)) | jnp.uint32(0x3FF00000)
    return w.at[:, 1].set(hi)


def _fingerprint(words):
    return jnp.sum(words, dtype=jnp.uint32)


class Generator:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.job = ctx.config, ctx.traffic

    def setup(self) -> None:
        from benchmarks.chip import lm
        from benchmarks.chip.fe_mesh import unit_square_triangles
        from repro.core.comm import Comm
        from repro.core.store import DatasetStore
        from repro.fem import (Element, FEMCheckpoint, Function,
                               FunctionSpace, distribute)

        ctx, cfg, job = self.ctx, self.cfg, self.job
        m, el = cfg["mesh"], cfg["element"]
        mesh = unit_square_triangles(m["nx"], m["ny"])
        self.degree = el["degree"]
        self.num_entities = mesh.num_entities
        plexes, _, _ = distribute(mesh, job["save_ranks"],
                                  method=job["save_partition"])
        self.offsets = fe_field.global_offsets(mesh.dims, self.degree)
        ndof = int(self.offsets[-1])
        self.device = ctx.devices[0]
        self.words = jax.jit(_field_words, static_argnums=1)(
            lm.seed_key(ctx.seed), ndof)
        self.field = np.asarray(jax.device_get(self.words)).reshape(-1) \
            .view(np.float64)
        element = Element(el["family"], el["degree"], el["cell"])
        funcs = [Function(FunctionSpace(lp, element), fe_field.rank_dofs(
            self.field, self.offsets, lp.loc_g, lp.dims, self.degree))
            for lp in plexes]
        self.ck = FEMCheckpoint(DatasetStore(str(ctx.workdir / "fe"), "w"))
        comm = Comm(job["save_ranks"])
        self.ck.save_mesh("m", plexes, comm)
        self.ck.save_function("m", "f", funcs, comm)
        self.comm = Comm(job["load_ranks"])
        self.fingerprint = jax.jit(_fingerprint)
        self.kept: list = []
        self.n = 0
        self.rng = random.Random(ctx.seed)
        self.restart()                  # warm-up: compiles the fingerprints
        self.kept.clear()
        self.n = 0

    def restart(self) -> list:
        ctx = self.ctx
        with ctx.span("fe.load_mesh"):
            loaded = self.ck.load_mesh("m", self.comm,
                                       partition=self.job["load_partition"])
        with ctx.span("fe.load_function"):
            _, funcs = self.ck.load_function(loaded, "f", self.comm)
        with ctx.span("fe.h2d"):
            arrs = [jax.device_put(f.values.view(np.uint32).reshape(-1, 2),
                                   self.device) for f in funcs]
            prints = [self.fingerprint(a) for a in arrs]
            jax.block_until_ready((arrs, prints))
        held = [(lp.loc_g, lp.dims, lp.owner == lp.rank, a)
                for lp, a in zip(loaded.plexes, arrs)]
        # reservoir sample of the restarts, drawn from the seed
        self.n += 1
        if len(self.kept) < KEPT_RESTARTS:
            self.kept.append(held)
        else:
            j = self.rng.randrange(self.n)
            if j < KEPT_RESTARTS:
                self.kept[j] = held
        return held

    def op(self) -> dict:
        held = self.restart()
        return {"restarts": 1, "dofs": sum(int(a.shape[0]) for *_, a in held)}

    def after_window(self) -> None:
        pass

    def checks(self) -> list[Check]:
        differ = unowned = 0
        for held in self.kept:
            owned = []
            for loc_g, dims, own, arr in held:
                want = fe_field.rank_dofs(self.field, self.offsets, loc_g,
                                          dims, self.degree)
                got = np.asarray(jax.device_get(arr)).reshape(-1) \
                    .view(np.float64)
                if got.shape != want.shape:
                    differ += max(got.size, want.size)
                else:
                    differ += int(np.count_nonzero(
                        got.view(np.uint64) != want.view(np.uint64)))
                owned.append(loc_g[own])
            ids = np.concatenate(owned)
            unowned += (self.num_entities - np.unique(ids).size
                        + ids.size - np.unique(ids).size)
        return [Check("dofs_differ", float(differ), 0),
                Check("entities_not_owned_once", float(unowned), 0),
                Check("no_restart_checked", float(not self.kept), 0)]
