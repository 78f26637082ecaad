"""The FE cells' input mesh: a structured triangulation of the unit square,
made here so that a change to the program's own generators cannot change
what the benchmark feeds it.  (Copied from the repository's
``repro.fem.plex.tri_mesh_fast`` as the cells were defined.)

Numbering: the 2 nx ny cells first (quad (i, j) split into (v00, v10, v11)
and (v00, v11, v01)), then the edges as sorted vertex pairs in ascending
order, then the (nx + 1)(ny + 1) vertices row by row; cones in that order.
"""

from __future__ import annotations

import numpy as np

_INT = np.int64


def unit_square_triangles(nx: int, ny: int):
    """The mesh as the program's ``Plex``."""
    from repro.fem.plex import Plex

    nvy = ny + 1
    ncells = 2 * nx * ny
    ii, jj = np.meshgrid(np.arange(nx, dtype=_INT),
                         np.arange(ny, dtype=_INT), indexing="ij")
    ii, jj = ii.reshape(-1), jj.reshape(-1)
    v00, v01 = ii * nvy + jj, ii * nvy + jj + 1
    v10, v11 = (ii + 1) * nvy + jj, (ii + 1) * nvy + jj + 1
    tri_v = np.empty((ncells, 3), dtype=_INT)
    tri_v[0::2] = np.stack([v00, v10, v11], axis=1)
    tri_v[1::2] = np.stack([v00, v11, v01], axis=1)
    raw = np.stack([tri_v, np.roll(tri_v, -1, axis=1)], axis=2)
    raw = np.sort(raw.reshape(-1, 2), axis=1)
    edges, tri_e = np.unique(raw, axis=0, return_inverse=True)
    nedges, nverts = len(edges), (nx + 1) * nvy
    dims = np.concatenate([np.full(ncells, 2, _INT), np.full(nedges, 1, _INT),
                           np.zeros(nverts, _INT)])
    sizes = np.concatenate([np.full(ncells, 3, _INT), np.full(nedges, 2, _INT),
                            np.zeros(nverts, _INT)])
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(_INT)
    indices = np.concatenate([ncells + tri_e.reshape(-1),
                              ncells + nedges + edges.reshape(-1)]).astype(_INT)
    gx, gy = np.meshgrid(np.arange(nx + 1) / nx, np.arange(nvy) / ny,
                         indexing="ij")
    coords = np.stack([gx.reshape(-1), gy.reshape(-1)], axis=1)
    return Plex(2, dims, offsets, indices, vertex_start=ncells + nedges,
                coords=coords)
