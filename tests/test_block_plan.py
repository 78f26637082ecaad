"""The block plan of the N-to-M engine against a naive per-element reference.

``TensorCheckpoint.load_state`` and ``resharder.reshard`` fill target boxes by
copying whole (target box, saved chunk) intersections.  The reference here
moves one element at a time: each element of an intersection is found in its
chunk's row-major run and placed in its box by the ``row_major_ids``
numbering, with no block arithmetic.  Every case must agree bit for bit.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

from repro.core import spans as S
from repro.core.chunk_layout import (
    ArraySpec, Box, StateLayout, plan_regions, row_major_ids,
)
from repro.core.comm import Comm
from repro.core.resharder import reshard
from repro.core.store import DatasetStore, np_dtype
from repro.core.tensor_ckpt import (
    TensorCheckpoint, balanced_chunk_partition, shards_from_arrays,
)


@dataclasses.dataclass(frozen=True)
class Case:
    shape: tuple[int, ...]
    chunk: tuple[int, ...]
    dtype: str
    N: int
    plan: tuple[tuple[Box, ...], ...]     # plan[rank] = target boxes


def _grid_boxes(shape, grid):
    """The boxes of an even ``grid`` split of ``shape``, row-major."""
    step = [s // g for s, g in zip(shape, grid)]
    return tuple(Box(tuple(i * c for i, c in zip(idx, step)),
                     tuple((i + 1) * c for i, c in zip(idx, step)))
                 for idx in np.ndindex(*grid))


CASES = {
    # the restore cell's geometry, small: every chunk whole, one full box
    "whole_chunks_one_box": Case((48, 36), (6, 9), "float32", 1,
                                 ((Box((0, 0), (48, 36)),),)),
    # chunks of a (4, 1) save, loaded onto a (2, 2) layout: each box cuts
    # two chunks in half along the columns
    "partial_4x1_onto_2x2": Case((16, 12), (4, 12), "float32", 4,
                                 tuple((b,) for b in
                                       _grid_boxes((16, 12), (2, 2)))),
    # a ragged edge chunk in both dims, boxes cutting across chunks
    "ragged_edges": Case((10, 7), (4, 3), "float64", 2, (
        (Box((1, 1), (9, 6)),),
        (Box((0, 0), (10, 1)), Box((8, 5), (10, 7))),
        (Box((3, 2), (4, 7)),))),
    # replicated targets: one chunk needed by several boxes and ranks
    "replicated_chunk": Case((8, 8), (4, 4), "float32", 2, (
        (Box((0, 0), (8, 8)),),
        (Box((0, 0), (8, 8)), Box((1, 1), (3, 3)), Box((2, 0), (4, 4))),
        (Box((0, 0), (8, 8)),))),
    # M greater than the box count: empty ranks, and a zero-volume box
    "empty_ranks": Case((9, 6), (3, 2), "int64", 3, (
        (), (Box((0, 0), (9, 6)),), (),
        (Box((2, 3), (2, 6)), Box((4, 1), (7, 5))), ())),
    # N != M: three savers, two loaders on a layout of other cuts
    "n_ne_m": Case((20, 10), (5, 5), "float32", 3, (
        (Box((0, 0), (7, 10)),), (Box((7, 0), (20, 10)),))),
    "zero_d": Case((), (), "float32", 1, ((Box((), ()),), (Box((), ()),))),
    "one_d": Case((37,), (5,), "int32", 3, (
        (Box((0,), (12,)),), (Box((12,), (13,)), Box((20,), (37,))),
        (Box((13,), (20,)),), (Box((3,), (31,)),))),
    "three_d": Case((6, 5, 4), (4, 2, 3), "float64", 2, (
        (Box((0, 0, 0), (3, 5, 4)),), (Box((3, 0, 0), (6, 5, 4)),),
        (Box((1, 1, 1), (5, 4, 3)),))),
    "bf16": Case((12, 10), (5, 4), "bfloat16", 2, (
        (Box((0, 0), (6, 10)),), (Box((6, 3), (12, 10)), Box((6, 0), (12, 3))))),
}


def _setup(case: Case, seed: int = 0):
    spec = ArraySpec("a", case.shape, case.dtype, case.chunk)
    layout = StateLayout((spec,))
    rng = np.random.default_rng(seed)
    dt = np_dtype(case.dtype)
    if np.issubdtype(dt, np.integer):
        arr = rng.integers(-1000, 1000, case.shape).astype(dt)
    else:
        arr = np.asarray(rng.normal(size=case.shape)).astype(dt)
    own = balanced_chunk_partition(layout, case.N)
    per_rank = shards_from_arrays(layout, {"a": arr}, own)
    plan = [{"a": list(boxes)} if boxes else {} for boxes in case.plan]
    return spec, layout, arr, per_rank, plan


def _naive(spec: ArraySpec, arr: np.ndarray, box: Box) -> tuple[np.ndarray, int]:
    """``box`` filled one element at a time from the saved chunk runs, and
    the number of (box, chunk) intersections met on the way."""
    out = np.zeros(box.size, dtype=arr.dtype)
    filled = np.zeros(box.size, dtype=bool)
    met = 0
    for _, cbox in spec.grid.iter_boxes():
        inter = cbox.intersect(box) if box.size else None
        if inter is None:
            continue
        met += 1
        run = arr[cbox.slices()].reshape(-1)     # as the saver writes it
        src = row_major_ids(inter, cbox)
        dst = row_major_ids(inter, box)
        for s, d in zip(src.tolist(), dst.tolist()):
            out[d] = run[s]
            filled[d] = True
    assert filled.all()
    return out.reshape(box.shape), met


def _check(spec, arr, plan, out):
    met = 0
    for rank_plan, rank_out in zip(plan, out):
        boxes = rank_plan.get("a", [])
        assert len(rank_out.get("a", [])) == len(boxes)
        for box, got in zip(boxes, rank_out.get("a", [])):
            ref, n = _naive(spec, arr, box)
            met += n
            assert got.shape == box.shape and got.dtype == arr.dtype
            assert got.flags["C_CONTIGUOUS"]
            assert got.tobytes() == ref.tobytes()
            assert got.tobytes() == np.ascontiguousarray(
                arr[box.slices()]).tobytes()
    return met


@pytest.mark.parametrize("name", sorted(CASES))
def test_load_state_block_plan_matches_per_element(tmp_path, name):
    case = CASES[name]
    spec, layout, arr, per_rank, plan = _setup(case, seed=len(name))
    ck = TensorCheckpoint(DatasetStore(str(tmp_path / "ck"), "w"))
    ck.save_layout(layout)
    ck.save_state(per_rank, Comm(case.N), step=3)
    t0 = time.perf_counter()
    out = ck.load_state(plan, Comm(len(plan)), step=3)
    met = _check(spec, arr, plan, out)
    # one scatter span, with the block path's attributes (the same-count
    # fast path records none)
    scatter = [s.attrs for s in S.spans()
               if s.t0 >= t0 and s.name == "ckpt.load.scatter"]
    assert scatter == [{"blocks": met, "bytes": sum(
        b.size for p in plan for b in p.get("a", [])) * arr.itemsize}]


@pytest.mark.parametrize("name", sorted(CASES))
def test_reshard_block_plan_matches_per_element(name):
    case = CASES[name]
    spec, layout, arr, per_rank, plan = _setup(case, seed=len(name) + 7)
    comm_dst = Comm(len(plan))
    out = reshard(layout, per_rank, plan, Comm(case.N), comm_dst)
    _check(spec, arr, plan, out)
    # the directory query round, then the data round: each intersection's
    # bytes once
    moved = sum(b.size for p in plan for b in p.get("a", [])) * arr.itemsize
    needed = plan_regions(spec.grid, [p.get("a", []) for p in plan]
                          ).needed_ord.size
    assert comm_dst.stats.rounds == 2
    assert comm_dst.stats.bytes_moved == needed * 8 * 2 + moved


def test_block_plan_holds_no_per_element_array():
    """The plan of the restore geometry is O(intersections): no field has
    as many rows as the leaf has elements."""
    spec = ArraySpec("a", (4096, 64), "float32", (256, 4))
    rp = plan_regions(spec.grid, [[spec.full_box]])
    assert len(rp.inter_box) == spec.grid.num_chunks == 256
    assert int(rp.inter_sizes.sum()) == spec.size
    for f in dataclasses.fields(rp):
        v = getattr(rp, f.name)
        if isinstance(v, np.ndarray):
            assert len(v) <= spec.grid.num_chunks, f.name


def test_region_box_outside_the_array_is_refused():
    spec = ArraySpec("a", (8, 6), "float32", (4, 3))
    with pytest.raises(ValueError, match="outside the array"):
        plan_regions(spec.grid, [[Box((0, 0), (8, 7))]])


def test_saved_chunk_offset_outside_the_vec_is_refused(tmp_path):
    case = CASES["ragged_edges"]
    spec, layout, arr, per_rank, plan = _setup(case)
    store = DatasetStore(str(tmp_path / "ck"), "w")
    ck = TensorCheckpoint(store)
    ck.save_layout(layout)
    ck.save_state(per_rank, Comm(case.N), step=0)
    # the last chunk's offset pushed one element past the end of the vec
    off = store.read_rows("a/e0/OFF", 0, spec.grid.num_chunks)
    with open(store._path("a/e0/OFF"), "r+b") as f:
        f.seek(8 * (len(off) - 1))
        f.write(np.int64(spec.size - 1).tobytes())
    with pytest.raises(ValueError, match="outside the"):
        ck.load_state(plan, Comm(len(plan)), step=0)


def test_reshard_of_a_chunk_no_source_holds_is_refused():
    case = CASES["n_ne_m"]
    spec, layout, arr, per_rank, plan = _setup(case)
    del per_rank[1]["a"]
    with pytest.raises(ValueError, match="held by no source rank"):
        reshard(layout, per_rank, plan, Comm(case.N), Comm(len(plan)))
