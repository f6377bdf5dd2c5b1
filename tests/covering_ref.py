"""Plain reference for the cell covering: the recursive walk, one node at
a time, host Python and numpy.

``build_cell_covering`` (``repro/core/cells.py``) builds the same covering
a whole level at a time; the tests require the two to be equal array for
array.  The rules (bbox prune, segment-rectangle test, centre owner,
interior / boundary / split, ``max_cand`` truncation) and the numbering of
candidate rows (boundary cells in walk order, which is descending ``lo``)
are the ones the program followed before the level-synchronous build.
"""
from __future__ import annotations

import numpy as np

from repro.core.cells import CellCovering, morton_np
from repro.core.geometry import CensusMap, point_in_polygon_host


def reference_covering(census: CensusMap, max_level: int = 9,
                       max_cand: int = 8,
                       min_split_level: int = 2) -> CellCovering:
    assert max_level <= 15, "leaf codes must fit int32"
    ctx = _walk_context(census, max_level, max_cand, min_split_level)
    root = (0, 0, 0, np.arange(census.blocks.n_poly, dtype=np.int32),
            np.arange(len(ctx["ex1"]), dtype=np.int32))
    cells = _walk(ctx, [root])

    out_lo, out_hi, out_val, out_lvl = [], [], [], []
    cand_rows: list[np.ndarray] = []
    for lo, hi, lvl, owner, row in cells:
        if row is not None:            # boundary cell: next candidate row
            owner = -(len(cand_rows) + 1)
            cand_rows.append(row)
        out_lo.append(lo)
        out_hi.append(hi)
        out_val.append(owner)
        out_lvl.append(lvl)

    order = np.argsort(np.asarray(out_lo))
    lo = np.asarray(out_lo, np.int32)[order]
    hi = np.asarray(out_hi, np.int32)[order]
    val = np.asarray(out_val, np.int32)[order]
    lvl = np.asarray(out_lvl, np.int8)[order]
    cand = (np.stack(cand_rows) if cand_rows
            else np.zeros((0, max_cand), np.int32))
    return CellCovering(lo=lo, hi=hi, val=val, level=lvl, cand=cand,
                        max_level=max_level, extent=census.extent,
                        n_interior=int((val >= 0).sum()),
                        n_boundary=len(cand_rows))


def _walk_context(census: CensusMap, max_level: int, max_cand: int,
                  min_split_level: int) -> dict:
    """Normalized block geometry the covering walk reads (host, numpy)."""
    x0, x1, y0, y1 = census.extent
    sx, sy = 1.0 / (x1 - x0), 1.0 / (y1 - y0)
    blocks = census.blocks

    # Normalized edge soup of all block polygons.
    verts = blocks.verts.astype(np.float64).copy()
    verts[..., 0] = (verts[..., 0] - x0) * sx
    verts[..., 1] = (verts[..., 1] - y0) * sy
    e1 = verts[:, :-1, :]
    e2 = verts[:, 1:, :]
    # Drop degenerate padding edges.
    keep = ~np.all(e1 == e2, axis=-1)
    poly_of_edge = np.broadcast_to(
        np.arange(blocks.n_poly, dtype=np.int32)[:, None], keep.shape)[keep]

    nbb = blocks.bbox.astype(np.float64).copy()
    nbb[:, 0:2] = (nbb[:, 0:2] - x0) * sx
    nbb[:, 2:4] = (nbb[:, 2:4] - y0) * sy
    return dict(
        ex1=e1[keep][:, 0], ey1=e1[keep][:, 1],
        ex2=e2[keep][:, 0], ey2=e2[keep][:, 1],
        poly_of_edge=poly_of_edge, nbb=nbb,
        rings_n=[verts[p, :blocks.n_verts[p]]
                 for p in range(blocks.n_poly)],
        max_level=max_level, max_cand=max_cand,
        min_split_level=min_split_level)


def _walk(ctx: dict, stack: list) -> list:
    """Depth-first covering walk from ``stack``: cells in walk order as
    (lo, hi, level, owner, cand_row), ``cand_row`` set for boundary cells
    (their value is assigned by the caller, in walk order)."""
    ex1, ey1, ex2, ey2 = ctx["ex1"], ctx["ey1"], ctx["ex2"], ctx["ey2"]
    nbb, rings_n = ctx["nbb"], ctx["rings_n"]
    poly_of_edge = ctx["poly_of_edge"]
    max_level, max_cand = ctx["max_level"], ctx["max_cand"]
    min_split_level = ctx["min_split_level"]

    def center_owner(cx, cy, cand_polys):
        for p in cand_polys:
            if point_in_polygon_host(np.array([cx]), np.array([cy]),
                                     rings_n[p])[0]:
                return int(p)
        return -1

    cells: list = []
    # DFS stack: (level, ix, iy, candidate polys, candidate edges)
    while stack:
        l, ix, iy, cpolys, cedges = stack.pop()
        size = 1.0 / (1 << l)
        rx0, ry0 = ix * size, iy * size
        rx1, ry1 = rx0 + size, ry0 + size
        # Prune candidates to this cell.
        keep_p = ~((nbb[cpolys, 1] < rx0) | (nbb[cpolys, 0] > rx1) |
                   (nbb[cpolys, 3] < ry0) | (nbb[cpolys, 2] > ry1))
        cpolys = cpolys[keep_p]
        if len(cpolys) == 0:
            continue  # outside the map
        hit = _seg_rect_intersect(ex1[cedges], ey1[cedges], ex2[cedges],
                                  ey2[cedges], rx0, rx1, ry0, ry1)
        cedges = cedges[hit]
        shift = 2 * (max_level - l)
        m = int(morton_np(np.array([ix]), np.array([iy]))[0])
        if len(cedges) == 0 and l >= min_split_level:
            owner = center_owner((rx0 + rx1) / 2, (ry0 + ry1) / 2, cpolys)
            if owner < 0:
                continue  # cell fully outside the map
            cells.append((m << shift, ((m + 1) << shift) - 1, l, owner,
                          None))
        elif l == max_level:
            # Boundary cell: candidates = polys owning any crossing edge,
            # plus the centre owner (listed first for approximate mode).
            touch = np.unique(poly_of_edge[cedges])
            owner = center_owner((rx0 + rx1) / 2, (ry0 + ry1) / 2, cpolys)
            cands = [owner] if owner >= 0 else []
            cands += [int(p) for p in touch if p != owner]
            cands = cands[:max_cand]
            if not cands:
                continue
            row = np.full(max_cand, -1, np.int32)
            row[:len(cands)] = cands
            cells.append((m << shift, ((m + 1) << shift) - 1, l, None,
                          row))
        else:
            for dy in (0, 1):
                for dx in (0, 1):
                    stack.append((l + 1, 2 * ix + dx, 2 * iy + dy,
                                  cpolys, cedges))
    return cells


def _seg_rect_intersect(x1, y1, x2, y2, rx0, rx1, ry0, ry1):
    """Vectorized segment-vs-rect intersection (Liang-Barsky clip).

    Endpoints on the rect boundary count as intersecting (conservative:
    over-marking a cell as boundary only costs a PIP test, never wrongness).
    """
    dx = x2 - x1
    dy = y2 - y1
    t0 = np.zeros_like(x1)
    t1 = np.ones_like(x1)
    ok = np.ones_like(x1, dtype=bool)
    for p, q in (((-dx), (x1 - rx0)), ((dx), (rx1 - x1)),
                 ((-dy), (y1 - ry0)), ((dy), (ry1 - y1))):
        r = np.where(p != 0, q / np.where(p == 0, 1.0, p), 0.0)
        # p == 0: parallel; reject iff the segment lies outside this slab.
        ok &= ~((p == 0) & (q < 0))
        is_entry = p < 0
        t0 = np.where((p != 0) & is_entry, np.maximum(t0, r), t0)
        t1 = np.where((p != 0) & ~is_entry, np.minimum(t1, r), t1)
    return ok & (t0 <= t1)
