"""Bytes the gather-PIP step needs, from the index and the points.

This counts the work the algorithm needs, whatever implements it.  A
point whose covering cell is a boundary cell needs point-in-polygon tests
against the candidate blocks of that cell, in slot order, up to and
including the first block that holds it (all of them when none does).
Each test reads the block's edges, 16 B each (x1, y1, x2, y2 in float32).
Each such point also reads 8 B of coordinates and writes a 4 B id.

No operation count is used for a bound: the TPU v5e publishes no float32
vector-unit peak, so the roofline share is bounded by bytes alone.
"""
from __future__ import annotations

import numpy as np

from benchlib.cells import cell_rows

EDGE_BYTES = 16
POINT_BYTES = 8
ID_BYTES = 4


def needed(cov, n_edges: np.ndarray, xy: np.ndarray,
           true_block: np.ndarray) -> dict:
    """{"bytes", "pip_points", "tests", "edges"} for one batch of points.

    ``cov`` is the covering (``lo``, ``hi``, ``val``, ``cand``, ``extent``,
    ``max_level``), ``n_edges`` the edge count of each block, and
    ``true_block`` each point's block."""
    rows = cell_rows(cov, xy)
    pip = rows >= 0
    cand = np.asarray(cov.cand)[rows[pip]]                 # [m, K]
    k = cand.shape[1] if cand.ndim == 2 else 0
    match = cand == np.asarray(true_block)[pip][:, None]
    last = np.where(match.any(axis=1), np.argmax(match, axis=1), k - 1)
    tested = (cand >= 0) & (np.arange(k)[None, :] <= last[:, None])
    edges = int(np.where(tested, np.asarray(n_edges)[np.clip(cand, 0, None)],
                         0).sum())
    m = int(pip.sum())
    return {"bytes": EDGE_BYTES * edges + (POINT_BYTES + ID_BYTES) * m,
            "pip_points": m, "tests": int(tested.sum()), "edges": edges}
