"""JAX bridge: checkpoint live ``jax.Array`` pytrees through the N-to-M core.

Production shape: one checkpoint *rank* per JAX process.  Each process owns
the chunks that its addressable, replica-0 shards cover (replica_id != 0 are
ghosts and save nothing — §2.1.1's ownership rule); the chunk grid is aligned
to the shard grid so every shard is a whole number of chunks and every write
is contiguous.  Loading builds the region plan from the *target* sharding —
which may live on a different process/device count — and assembles arrays with
``jax.make_array_from_callback``.

In this container there is one process, so the multi-rank paths are exercised
by the numpy-level tests; this module keeps the JAX-facing contract honest.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from repro.core.chunk_layout import ArraySpec, Box, StateLayout
from repro.core.comm import Comm
from repro.core.spans import span
from repro.core.store import np_dtype
from repro.core.tensor_ckpt import ArrayShard, PerRankState, TensorCheckpoint

_INT = np.int64


def tree_names(tree: Any) -> tuple[list[str], list[Any], Any]:
    """Stable path-derived names for every leaf + leaves + treedef."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, leaves = [], []
    for path, leaf in flat:
        names.append(jax.tree_util.keystr(path, simple=True, separator="/"))
        leaves.append(leaf)
    assert len(set(names)) == len(names)
    return names, leaves, treedef


def _box_from_index(index: tuple[slice, ...], shape: tuple[int, ...]) -> Box:
    start, stop = [], []
    for sl, n in zip(index, shape):
        a = 0 if sl.start is None else int(sl.start)
        b = n if sl.stop is None else int(sl.stop)
        start.append(a)
        stop.append(b)
    return Box(tuple(start), tuple(stop))


def _shard_grid(arr: jax.Array) -> tuple[int, ...]:
    """Per-dim shard counts of a jax array's sharding."""
    shape = arr.shape
    if not shape:
        return ()
    sshape = arr.sharding.shard_shape(shape)
    return tuple(n // max(s, 1) if s else 1 for n, s in zip(shape, sshape))


def _grid_factor(n: int, shard_g: int, subdiv: int = 16) -> int:
    """Per-dim chunk count: a multiple of the current shard grid AND of
    the largest power-of-two divisor of n (capped at ``subdiv``), so that
    any later power-of-two re-sharding still tiles the chunk grid — the
    elastic-restart re-save case (paper §7's 'the loaded mesh is a new
    mesh' limitation, solved here by a mesh-agnostic chunk grid)."""
    if n == 0:
        return 1
    pow2 = 1
    while pow2 < subdiv and n % (pow2 * 2) == 0:
        pow2 *= 2
    g = max(shard_g, 1)
    # lcm(g, pow2) for g a divisor of n; fall back to g if not dividing
    import math
    cand = g * pow2 // math.gcd(g, pow2)
    return cand if n % cand == 0 else g


def layout_from_jax(tree: Any, subdiv: int = 16) -> StateLayout:
    """Mesh-agnostic chunk grid: refines the current shard grid to the
    largest power-of-two split (<= subdiv) per dim, so the same layout
    accepts re-saves from any power-of-two mesh."""
    names, leaves, _ = tree_names(tree)
    specs = []
    for name, leaf in zip(names, leaves):
        shape = tuple(int(s) for s in leaf.shape)
        grid = tuple(_grid_factor(n, g, subdiv)
                     for n, g in zip(shape, _shard_grid(leaf)))
        chunk = tuple(max(1, n // g) for n, g in zip(shape, grid))
        specs.append(ArraySpec(name, shape, str(leaf.dtype), chunk))
    return StateLayout(tuple(specs))


def snapshot_jax(layout, tree: Any) -> PerRankState:
    """Device -> host snapshot of this process's owned chunks.

    The returned numpy blocks are COPIES (safe against buffer donation
    by the next step while an async write is in flight).  Per leaf, the
    blocking device -> host fetch of its shards (``ckpt.snapshot.d2h``)
    and the per-chunk host copies (``ckpt.snapshot.copy``) are spans."""
    names, leaves, _ = tree_names(tree)
    rank_state: dict[str, ArrayShard] = {}
    with span("ckpt.snapshot") as snap:
        for name, leaf in zip(names, leaves):
            spec = layout.spec(name)
            grid = spec.grid
            with span("ckpt.snapshot.d2h") as d2h:
                owned = [(_box_from_index(shard.index, spec.shape),
                          np.asarray(shard.data))
                         for shard in leaf.addressable_shards
                         if shard.replica_id == 0]   # ghosts: paper §2.1.1
                d2h.attrs["bytes"] = sum(b.nbytes for _, b in owned)
            data: dict[int, np.ndarray] = {}
            with span("ckpt.snapshot.copy", pass_=True) as copy:
                for box, block in owned:
                    for o in grid.chunks_intersecting(box):
                        cbox = grid.chunk_box(o)
                        assert box.contains(cbox), (
                            f"{name}: shard box {box} does not tile chunk "
                            f"{cbox}")
                        data[o] = np.array(block[cbox.slices(origin=box)],
                                           copy=True, order="C")
                copy.attrs["bytes"] = sum(b.nbytes for b in data.values())
                owned = block = None     # the fetched shards are freed here
            if data:
                ords = np.array(sorted(data), dtype=_INT)
                rank_state[name] = ArrayShard(ords, data)
        snap.attrs["bytes"] = sum(b.nbytes for sh in rank_state.values()
                                  for b in sh.data.values())
    return [rank_state]                         # one rank per process


def save_jax(ck: TensorCheckpoint, tree: Any, step: int) -> None:
    """Save a pytree of jax Arrays; must follow a prior ``save_layout``
    (``ck.save_layout(layout_from_jax(tree))``) or any layout whose chunk
    grids the shard boxes tile exactly."""
    per_rank = snapshot_jax(ck.layout(), tree)
    ck.save_state(per_rank, Comm(jax.process_count()), step)


def load_jax(ck: TensorCheckpoint, target: Any, step: int) -> Any:
    """Load into a pytree of ``jax.ShapeDtypeStruct`` (with ``.sharding``) or
    arrays; returns a pytree of committed jax Arrays on the target sharding.
    The arrays are built on the devices under ``ckpt.load.h2d``, which
    times the transfers' issue: nothing here waits for them to land."""
    names, leaves, treedef = tree_names(target)
    with span("ckpt.load") as load:
        plan_rank: dict[str, list[Box]] = {}
        for name, leaf in zip(names, leaves):
            shape = tuple(int(s) for s in leaf.shape)
            boxes: list[Box] = []
            idx_map = leaf.sharding.addressable_devices_indices_map(shape)
            for index in idx_map.values():
                b = _box_from_index(index, shape)
                if b not in boxes:
                    boxes.append(b)
            plan_rank[name] = boxes
        out = ck.load_state([plan_rank], Comm(jax.process_count()), step)[0]

        results = []
        with span("ckpt.load.h2d") as h2d:
            for name, leaf in zip(names, leaves):
                shape = tuple(int(s) for s in leaf.shape)
                lut = {(b.start, b.stop): arr
                       for b, arr in zip(plan_rank[name], out[name])}

                def cb(index, _name=name, _shape=shape, _lut=lut, _leaf=leaf):
                    b = _box_from_index(index, _shape)
                    return np.asarray(_lut[(b.start, b.stop)],
                                      dtype=np_dtype(str(_leaf.dtype)))

                results.append(jax.make_array_from_callback(
                    shape, leaf.sharding, cb))
            h2d.attrs["bytes"] = sum(int(a.nbytes) for a in results)
        load.attrs["bytes"] = h2d.attrs["bytes"]
    return jax.tree_util.tree_unflatten(treedef, results)
