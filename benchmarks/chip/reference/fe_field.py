"""Plain reference of an FE field's DoFs, from the mesh's entity dimensions
alone; it imports nothing of the program.

A Lagrange element of degree p on triangles puts 1 node on a vertex, p - 1
on an edge and (p - 1)(p - 2) / 2 inside a cell.  The global DoF vector is
numbered entity by entity in global entity order, each entity's nodes
contiguous.  A rank holding entities ``loc_g`` holds, in the same order,
the DoFs of each of them.
"""

from __future__ import annotations

import numpy as np


def nodes_per_dim(degree: int) -> np.ndarray:
    p = degree
    return np.array([1, p - 1, (p - 1) * (p - 2) // 2], dtype=np.int64)


def global_offsets(dims: np.ndarray, degree: int) -> np.ndarray:
    """First DoF of every global entity, plus the total at the end."""
    counts = nodes_per_dim(degree)[dims]
    return np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)


def rank_dofs(field: np.ndarray, offsets: np.ndarray, loc_g: np.ndarray,
              loc_dims: np.ndarray, degree: int) -> np.ndarray:
    """The values a rank holding entities ``loc_g`` (of dimensions
    ``loc_dims``) must hold, in its own entity order."""
    counts = nodes_per_dim(degree)[loc_dims]
    starts = offsets[loc_g]
    first = np.repeat(np.cumsum(counts) - counts, counts)
    idx = np.arange(int(counts.sum()), dtype=np.int64) - first \
        + np.repeat(starts, counts)
    return field[idx]

