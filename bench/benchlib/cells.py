"""Host lookup of a point's covering cell (numpy), kept with the benchmark.

The traffic generator uses it to draw points inside boundary cells, and
``bench/counts`` to find the candidate blocks a point needs tested.  It
follows the quantization the covering is defined on: the 2^L x 2^L grid
over the map's extent, fixed point in float32 (subtract, multiply,
truncate), Morton-interleaved to a leaf code.
"""
from __future__ import annotations

import numpy as np


def quant(extent, max_level: int) -> np.ndarray:
    """(x0, y0, sx, sy) float32, with s = 2^L / span."""
    x0, x1, y0, y1 = extent
    n = 1 << max_level
    return np.array([x0, y0, n / (x1 - x0), n / (y1 - y0)], np.float32)


def _spread(v: np.ndarray) -> np.ndarray:
    v = v.astype(np.int64) & 0xFFFF
    v = (v | (v << 8)) & 0x00FF00FF
    v = (v | (v << 4)) & 0x0F0F0F0F
    v = (v | (v << 2)) & 0x33333333
    return (v | (v << 1)) & 0x55555555


def leaf_codes(q: np.ndarray, max_level: int, xy: np.ndarray):
    """(codes [n] i64, in_extent [n] bool) of float32 points ``xy``."""
    n = 1 << max_level
    xy = np.asarray(xy, np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        fx = (xy[:, 0] - q[0]) * q[2]
        fy = (xy[:, 1] - q[1]) * q[3]
    inside = (fx >= 0) & (fx < n) & (fy >= 0) & (fy < n)
    ix = np.clip(np.trunc(fx), 0, n - 1).astype(np.int64)
    iy = np.clip(np.trunc(fy), 0, n - 1).astype(np.int64)
    return (_spread(iy) << 1) | _spread(ix), inside


def cell_rows(cov, xy: np.ndarray) -> np.ndarray:
    """Candidate row of each point's boundary cell, -1 for a point in an
    interior cell or off the map."""
    codes, inside = leaf_codes(quant(cov.extent, cov.max_level),
                               cov.max_level, xy)
    lo = np.asarray(cov.lo)
    codes = codes.astype(lo.dtype)        # leaf codes fit the table's ints
    pos = np.clip(np.searchsorted(lo, codes, side="right") - 1, 0, None)
    hit = inside & (codes >= lo[pos]) & (codes <= cov.hi[pos])
    val = np.where(hit, cov.val[pos], 0)
    return np.where(val < 0, -(val + 1), -1)
