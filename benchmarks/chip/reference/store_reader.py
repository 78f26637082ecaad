"""Plain single-process reader of a tensor checkpoint store, written from
the on-disk format alone; it imports nothing of the program.

The format: ``store.json`` holds the datasets and the attrs.  Attr
``layout`` lists each array's name, shape, dtype and chunk shape; chunks
are numbered row-major over the chunk grid, and an edge chunk may be
smaller.  Attr ``meta`` maps each committed step to each array's section
epoch, and ``section/<name>/e<epoch>`` lists the chunk ordinals each saving
rank owned.  The series manifest (attr ``series/manifest``) maps a step's
logical dataset names to physical ones; dataset ``<name>/e<epoch>/s<step>/
vec`` holds the owned chunks of every rank, rank after rank, each chunk's
elements in row-major order within its box.  A physical dataset lives in
``<root>/<name with "/" replaced by "__">.bin``.
"""

from __future__ import annotations

import itertools
import json
import math
import os

import ml_dtypes
import numpy as np


def _dtype(name: str) -> np.dtype:
    return np.dtype(getattr(ml_dtypes, name, None) or name)


def committed_steps(root: str, series: str = "series") -> list[int]:
    """Steps whose manifest entry is on disk: a torn step has none."""
    with open(os.path.join(root, "store.json")) as f:
        attrs = json.load(f)["attrs"]
    man = attrs.get("series/manifest", {}).get(series, {"steps": {}})
    return sorted(set(int(s) for s in man["steps"])
                  & set(int(s) for s in attrs["meta"]["steps"]))


def read_step(root: str, step: int, series: str = "series",
              names=None) -> dict[str, np.ndarray]:
    """Every array (or those in ``names``) of committed ``step``."""
    with open(os.path.join(root, "store.json")) as f:
        attrs = json.load(f)["attrs"]
    physical = attrs["series/manifest"][series]["steps"][str(step)]
    epochs = attrs["meta"]["steps"][str(step)]
    out = {}
    for spec in attrs["layout"]:
        name = spec["name"]
        if names is not None and name not in names:
            continue
        shape, chunk = tuple(spec["shape"]), tuple(spec["chunk_shape"])
        dtype = _dtype(spec["dtype"])
        epoch = epochs[name]
        sec = attrs["meta"][f"section/{name}/e{epoch}"]
        phys = physical[f"{name}/e{epoch}/s{step}/vec"]
        flat = np.fromfile(os.path.join(root, phys.replace("/", "__")
                                        + ".bin"), dtype=dtype)
        arr = np.empty(shape, dtype)
        counts = [-(-n // c) for n, c in zip(shape, chunk)]
        grid = list(itertools.product(*[range(c) for c in counts]))
        pos = 0
        for ordinal in itertools.chain.from_iterable(
                sec["ordinals_per_rank"]):
            idx = grid[ordinal]
            box = tuple(slice(i * c, min((i + 1) * c, n))
                        for i, c, n in zip(idx, chunk, shape))
            size = math.prod(b.stop - b.start for b in box)
            arr[box] = flat[pos:pos + size].reshape(arr[box].shape)
            pos += size
        if pos != flat.size:
            raise ValueError(f"{name}: {flat.size} stored elements, chunks "
                             f"cover {pos}")
        out[name] = arr
    return out
