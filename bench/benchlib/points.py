"""Query points with their true blocks, drawn from a seed.

* ``uniform``: area-weighted over the map, as far from block edges as the
  ground truth needs (three times the warp's sagitta bound) and no more;
* ``boundary``: every point inside a boundary cell of the covering, the
  points that need point-in-polygon tests.  Proposals are drawn in a band
  along a random side of their block (as GPS pings snapped to the streets
  that bound census blocks are), and only those whose cell is a boundary
  cell are kept.
"""
from __future__ import annotations

import numpy as np

from benchlib.cells import cell_rows

BAND_CELLS = 0.75       # band width, in leaf-cell diagonals


def uniform(smap, rng, n: int, margin: float = 0.0):
    """(xy [n, 2] f32, true block [n] i32)."""
    return smap.sample(rng, n, margin)


def boundary(smap, cov, rng, n: int):
    """(xy [n, 2] f32, true block [n] i32), all in boundary cells."""
    x0, x1, y0, y1 = cov.extent
    side = 1 << cov.max_level
    band = BAND_CELLS * np.hypot((x1 - x0) / side, (y1 - y0) / side)
    floor = 3 * smap.sagitta
    br = smap.rects["blocks"]
    areas = (br[:, 1] - br[:, 0]) * (br[:, 3] - br[:, 2])
    p = areas / areas.sum()
    xs, bs, got = [], [], 0
    while got < n:
        m = 2 * (n - got) + 1024
        bid = rng.choice(len(br), size=m, p=p).astype(np.int32)
        r = br[bid]
        w, h = r[:, 1] - r[:, 0], r[:, 3] - r[:, 2]
        which = rng.integers(0, 4, m)           # left, right, bottom, top
        d = rng.uniform(floor, band, m)
        u = rng.uniform(0.0, 1.0, m)
        dx = np.minimum(d, 0.45 * w)
        dy = np.minimum(d, 0.45 * h)
        fx = r[:, 0] + np.maximum(floor, 0.0) + u * np.maximum(
            w - 2 * floor, 0.0)
        fy = r[:, 2] + np.maximum(floor, 0.0) + u * np.maximum(
            h - 2 * floor, 0.0)
        x = np.select([which == 0, which == 1], [r[:, 0] + dx, r[:, 1] - dx],
                      fx)
        y = np.select([which == 2, which == 3], [r[:, 2] + dy, r[:, 3] - dy],
                      fy)
        xy = smap.warp(np.stack([x, y], axis=-1)).astype(np.float32)
        keep = cell_rows(cov, xy) >= 0
        xs.append(xy[keep])
        bs.append(bid[keep])
        got += int(keep.sum())
    order = rng.permutation(got)[:n]
    return np.concatenate(xs)[order], np.concatenate(bs)[order]
