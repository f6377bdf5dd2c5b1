"""Host-side quadtree cell covering builder (paper §IV, TPU-adapted).

Builds the *true-hit-filter* index: a non-overlapping hierarchical cell
covering of the census map where each cell either

  * lies fully inside one block polygon  -> interior cell (value = block id),
  * or touches >= 1 polygon boundaries   -> boundary cell (candidate list,
    centre-owner first), emitted only at ``max_level``.

Unlike the paper's per-polygon S2 coverings, we build ONE global covering
top-down (the census map is a partition, so cells never belong to two
interiors).  The build is level-synchronous.  A level's frontier is held
as arrays: each group of sibling nodes has its parent's cell (ix, iy) and
CSR lists of the candidate polygons and crossing edges that parent passed
down.  A node's four steps run vectorized over every (node, polygon) and
(node, edge) pair of the level: the bbox prune, the segment-rectangle
test, the centre owner's crossing number, and the interior / boundary /
split decision.  The split nodes are the next level's groups.  The work is
O(pairs visited), not O(polygons x cells), and a level runs in chunks of
at most ``CHUNK_PAIRS`` pairs, so host memory stays bounded.

Cells are identified by Morton (Z-order) codes over a 2^L x 2^L grid in the
map's normalized [0,1)^2 coordinates.  A cell at level l with Morton prefix m
covers leaf codes [m << 2(L-l), (m+1) << 2(L-l)); the index is the sorted
array of these intervals — the TPU-native replacement for the paper's radix
trie (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.geometry import CensusMap
from repro.obs.profile import profile_range

# Elements a chunk of a level expands to, at most: its (node, polygon),
# (node, edge) and (node, polygon edge) pairs together.  Each step holds a
# few float64 temporaries of this length.
CHUNK_PAIRS = 1 << 18

# A split node's children, in Morton order.
_DX = np.array([0, 1, 0, 1], np.int64)
_DY = np.array([0, 0, 1, 1], np.int64)


def part1by1_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.int64) & 0x0000FFFF
    x = (x | (x << 8)) & 0x00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F
    x = (x | (x << 2)) & 0x33333333
    x = (x | (x << 1)) & 0x55555555
    return x


def morton_np(ix: np.ndarray, iy: np.ndarray) -> np.ndarray:
    return (part1by1_np(iy) << 1) | part1by1_np(ix)


def _seg_rect_intersect(x1, y1, x2, y2, rx0, rx1, ry0, ry1):
    """Vectorized segment-vs-rect intersection (Liang-Barsky clip).

    Endpoints on the rect boundary count as intersecting (conservative:
    over-marking a cell as boundary only costs a PIP test, never wrongness).
    """
    dx = x2 - x1
    dy = y2 - y1
    t0, t1, ok = 0.0, 1.0, True
    with np.errstate(divide="ignore", invalid="ignore"):
        for p, q in (((-dx), (x1 - rx0)), ((dx), (rx1 - x1)),
                     ((-dy), (y1 - ry0)), ((dy), (ry1 - y1))):
            # p == 0: parallel; reject iff the segment lies outside this
            # slab.  Otherwise r is where the line enters (p < 0) or
            # leaves (p > 0) the slab.
            ok = ok & ((p != 0) | (q >= 0))
            r = q / p
            t0 = np.where(p < 0, np.maximum(t0, r), t0)
            t1 = np.where(p > 0, np.minimum(t1, r), t1)
    return ok & (t0 <= t1)


@dataclasses.dataclass
class CellCovering:
    """Flat covering arrays (host, numpy), sorted by ``lo``."""

    lo: np.ndarray          # [n_cells] int32 — leaf-code interval start
    hi: np.ndarray          # [n_cells] int32 — inclusive interval end
    val: np.ndarray         # [n_cells] int32 — >=0 block id, <0 -(cand_row+1)
    level: np.ndarray       # [n_cells] int8 — quadtree level of the cell
    cand: np.ndarray        # [n_boundary, max_cand] int32, -1 padded
    max_level: int
    extent: tuple           # (x0, x1, y0, y1) of the map
    n_interior: int
    n_boundary: int
    # The build's record, one dict per level visited: ``level``, ``nodes``
    # (frontier nodes tested), ``edge_pairs`` ((node, edge) pairs tested),
    # ``interior`` and ``boundary`` (cells emitted), ``seconds``.  Empty
    # for a covering loaded from an artifact saved without it.
    build_stats: list = dataclasses.field(default_factory=list)

    def nbytes(self) -> int:
        return (self.lo.nbytes + self.hi.nbytes + self.val.nbytes
                + self.level.nbytes + self.cand.nbytes)

    def validate_partition(self) -> None:
        """Intervals must be sorted, disjoint, and within [0, 4^max_level)."""
        assert np.all(self.lo[1:] > self.lo[:-1])
        assert np.all(self.hi >= self.lo)
        assert np.all(self.hi[:-1] < self.lo[1:])
        assert self.lo[0] >= 0 and self.hi[-1] < (1 << (2 * self.max_level))


@dataclasses.dataclass
class _Csr:
    """Rows of int32 ids (polygons or edges)."""

    ptr: np.ndarray         # [n_rows + 1] int64
    ids: np.ndarray         # [ptr[-1]] int32

    @classmethod
    def of_counts(cls, counts: np.ndarray, ids: np.ndarray) -> "_Csr":
        ptr = np.zeros(len(counts) + 1, np.int64)
        np.cumsum(counts, out=ptr[1:])
        return cls(ptr, ids)

    def lengths(self) -> np.ndarray:
        return np.diff(self.ptr)

    def take(self, rows: np.ndarray):
        """The rows ``rows`` flattened: (position in ``rows`` of each
        element, the element), in row order."""
        start = self.ptr[rows]
        counts = self.ptr[rows + 1] - start
        which = np.repeat(np.arange(len(rows)), counts)
        first = np.cumsum(counts) - counts
        pos = np.arange(len(which)) + np.repeat(start - first, counts)
        return which, self.ids[pos]


@dataclasses.dataclass
class _Blocks:
    """Block geometry in the map's normalized coordinates (host, numpy)."""

    ex1: np.ndarray         # [n_edges] float64 edge soup, padding dropped,
    ey1: np.ndarray         #   in polygon order
    ex2: np.ndarray
    ey2: np.ndarray
    poly_of_edge: np.ndarray  # [n_edges] int32, non-decreasing
    edges: _Csr             # each polygon's edges (row = polygon)
    nbb: np.ndarray         # [n_poly, 4] float64 (x0, x1, y0, y1)

    @classmethod
    def of(cls, census: CensusMap) -> "_Blocks":
        x0, x1, y0, y1 = census.extent
        sx, sy = 1.0 / (x1 - x0), 1.0 / (y1 - y0)
        blocks = census.blocks
        verts = blocks.verts.astype(np.float64)
        verts[..., 0] = (verts[..., 0] - x0) * sx
        verts[..., 1] = (verts[..., 1] - y0) * sy
        e1 = verts[:, :-1, :]
        e2 = verts[:, 1:, :]
        # Drop degenerate padding edges.  A ring's own degenerate edges go
        # too: they cross no ray, so the crossing numbers keep their value.
        keep = ~np.all(e1 == e2, axis=-1)
        poly_of_edge = np.broadcast_to(
            np.arange(blocks.n_poly, dtype=np.int32)[:, None],
            keep.shape)[keep]
        counts = keep.sum(axis=1)
        e1, e2 = e1[keep], e2[keep]
        nbb = blocks.bbox.astype(np.float64)
        nbb[:, 0:2] = (nbb[:, 0:2] - x0) * sx
        nbb[:, 2:4] = (nbb[:, 2:4] - y0) * sy
        return cls(ex1=e1[:, 0], ey1=e1[:, 1], ex2=e2[:, 0], ey2=e2[:, 1],
                   poly_of_edge=poly_of_edge,
                   edges=_Csr.of_counts(counts,
                                        np.arange(len(poly_of_edge),
                                                  dtype=np.int32)),
                   nbb=nbb)


def build_cell_covering(census: CensusMap, max_level: int = 9,
                        max_cand: int = 8,
                        min_split_level: int = 2) -> CellCovering:
    """Build the global covering over the census *block* level.

    Level by level from the root (module docstring).  A node whose bbox-
    pruned candidate polygons are none is outside the map and dropped.  A
    node that no edge crosses, at ``min_split_level`` or deeper, is an
    interior cell of its centre owner (the lowest candidate id whose ring
    holds the cell's centre; none: dropped).  A crossed node at
    ``max_level`` is a boundary cell: its candidates are the centre owner
    first, then the sorted owners of its crossing edges, at most
    ``max_cand``.  Every other node splits into four.  Candidate rows are
    numbered in descending ``lo``.  Records the per-level counters in
    ``build_stats`` and opens the profiler ranges ``geo/cover`` and, inside
    it, ``geo/cover/level`` per level."""
    assert max_level <= 15, "leaf codes must fit int32"
    geo = _Blocks.of(census)
    n_poly, n_edge = census.blocks.n_poly, len(geo.ex1)
    # The frontier's groups: level 0 has one, holding the root itself.
    gx = gy = np.zeros(1, np.int64)
    polys = _Csr.of_counts(np.array([n_poly]),
                           np.arange(n_poly, dtype=np.int32))
    edges = _Csr.of_counts(np.array([n_edge]),
                           np.arange(n_edge, dtype=np.int32))
    interior, boundary, stats = [], [], []
    with profile_range("geo/cover", max_level=max_level, blocks=n_poly):
        for level in range(max_level + 1):
            if len(gx) == 0:
                break
            t0 = time.perf_counter()
            with profile_range("geo/cover/level", level=level):
                step = _LevelStep(geo, level, max_level, max_cand,
                                  min_split_level)
                for rows in _chunks(geo, polys, edges, level > 0):
                    step.run(gx, gy, polys, edges, rows)
                interior += step.interior
                boundary += step.boundary
                gx, gy, polys, edges = step.next_frontier()
            stats.append(dict(level=level, nodes=step.nodes,
                              edge_pairs=step.edge_pairs,
                              interior=step.n_interior,
                              boundary=step.n_boundary,
                              seconds=time.perf_counter() - t0))
    return _assemble(interior, boundary, max_level, max_cand,
                     census.extent, stats)


def _chunks(geo: _Blocks, polys: _Csr, edges: _Csr, split: bool):
    """Group rows of the frontier in runs of at most ``CHUNK_PAIRS`` pairs
    (one group at least)."""
    n_pe = np.concatenate([[0], np.cumsum(geo.edges.lengths()[polys.ids])])
    cost = (polys.lengths() + edges.lengths()
            + n_pe[polys.ptr[1:]] - n_pe[polys.ptr[:-1]])
    cum = np.cumsum(cost * (4 if split else 1))
    start = 0
    while start < len(cum):
        done = cum[start - 1] if start else 0
        stop = max(int(np.searchsorted(cum, done + CHUNK_PAIRS, "right")),
                   start + 1)
        yield np.arange(start, stop)
        start = stop


class _LevelStep:
    """One level of the build: tests the frontier's nodes chunk by chunk,
    keeps the cells they emit and the groups they split into."""

    def __init__(self, geo: _Blocks, level: int, max_level: int,
                 max_cand: int, min_split_level: int):
        self.geo, self.level, self.max_level = geo, level, max_level
        self.max_cand, self.min_split_level = max_cand, min_split_level
        self.size = 1.0 / (1 << level)
        self.shift = 2 * (max_level - level)
        self.interior: list = []        # (lo, hi, level, owner) per chunk
        self.boundary: list = []        # (lo, hi, level, cand) per chunk
        self._split: list = []          # (x, y, n_polys, polys, n_edges,
        #                                  edges) per chunk
        self.nodes = self.edge_pairs = self.n_interior = self.n_boundary = 0

    def run(self, gx, gy, polys: _Csr, edges: _Csr, rows) -> None:
        geo = self.geo
        if self.level == 0:
            nx, ny, ng = gx[rows], gy[rows], rows
        else:
            nx = (2 * gx[rows][:, None] + _DX).ravel()
            ny = (2 * gy[rows][:, None] + _DY).ravel()
            ng = np.repeat(rows, 4)
        n = len(nx)
        rx0, ry0 = nx * self.size, ny * self.size
        rx1, ry1 = rx0 + self.size, ry0 + self.size

        # Prune each node's candidate polygons to its cell.
        pn, pp = polys.take(ng)
        bb = geo.nbb[pp]
        keep = ~((bb[:, 1] < rx0[pn]) | (bb[:, 0] > rx1[pn]) |
                 (bb[:, 3] < ry0[pn]) | (bb[:, 2] > ry1[pn]))
        pn, pp = pn[keep], pp[keep]
        n_polys = np.bincount(pn, minlength=n)
        alive = np.flatnonzero(n_polys)      # the rest lie outside the map

        # Keep the inherited edges that cross the node's cell.
        en, ee = edges.take(ng[alive])
        en = alive[en]
        hit = _seg_rect_intersect(geo.ex1[ee], geo.ey1[ee], geo.ex2[ee],
                                  geo.ey2[ee], rx0[en], rx1[en], ry0[en],
                                  ry1[en])
        en, ee = en[hit], ee[hit]
        n_edges = np.bincount(en, minlength=n)

        is_alive = n_polys > 0
        inner = is_alive & (n_edges == 0) & (self.level
                                             >= self.min_split_level)
        bound = is_alive & ~inner & (self.level == self.max_level)
        split = is_alive & ~inner & ~bound
        owner = _centre_owner(geo, (rx0 + rx1) / 2, (ry0 + ry1) / 2, pn,
                              pp, inner | bound)

        i = np.flatnonzero(inner & (owner >= 0))
        lo, hi = self._interval(nx[i], ny[i])
        self.interior.append((lo, hi, self.level, owner[i]))
        b, cand = self._candidates(bound, owner, en, ee)
        lo, hi = self._interval(nx[b], ny[b])
        self.boundary.append((lo, hi, self.level, cand))

        s = split[pn]
        t = split[en]
        k = np.flatnonzero(split)
        self._split.append((nx[k], ny[k], n_polys[k], pp[s], n_edges[k],
                            ee[t]))
        self.nodes += n
        self.edge_pairs += len(hit)
        self.n_interior += len(i)
        self.n_boundary += len(b)

    def _interval(self, x, y):
        m = morton_np(x, y)
        return ((m << self.shift).astype(np.int32),
                (((m + 1) << self.shift) - 1).astype(np.int32))

    def _candidates(self, bound, owner, en, ee):
        """The boundary nodes that have a candidate, and their rows: the
        centre owner first, then the sorted owners of the crossing edges
        (the owner left out), truncated to ``max_cand``."""
        sel = bound[en]
        tn, tp = en[sel], self.geo.poly_of_edge[ee[sel]]
        # Within a node the edges, and so their owners, are in order.
        new = np.ones(len(tn), bool)
        new[1:] = (tn[1:] != tn[:-1]) | (tp[1:] != tp[:-1])
        tn, tp = tn[new], tp[new]
        other = tp != owner[tn]
        tn, tp = tn[other], tp[other]
        idx = np.arange(len(tn))
        head = np.ones(len(tn), bool)
        head[1:] = tn[1:] != tn[:-1]
        rank = (idx - np.maximum.accumulate(np.where(head, idx, 0))
                + (owner[tn] >= 0))
        n_cand = (owner >= 0) + np.bincount(tn, minlength=len(owner))
        b = np.flatnonzero(bound & (n_cand > 0))
        row = np.full(len(owner), -1, np.int64)
        row[b] = np.arange(len(b))
        cand = np.full((len(b), self.max_cand), -1, np.int32)
        ob = owner[b]
        cand[ob >= 0, 0] = ob[ob >= 0]
        fit = rank < self.max_cand
        cand[row[tn[fit]], rank[fit]] = tp[fit]
        return b, cand

    def next_frontier(self):
        x, y, n_p, p, n_e, e = (np.concatenate(c) for c in
                                zip(*self._split))
        return (x, y, _Csr.of_counts(n_p, p.astype(np.int32)),
                _Csr.of_counts(n_e, e.astype(np.int32)))


def _centre_owner(geo: _Blocks, cx, cy, pn, pp, want) -> np.ndarray:
    """For each node in ``want``, the lowest of its candidate polygons
    (pairs ``pn``, ``pp``, in node order) whose ring holds its centre by
    the crossing-number rule of ``geometry.point_in_polygon_host``; -1
    where none does, and for the other nodes."""
    owner = np.full(len(cx), -1, np.int32)
    sel = want[pn]
    qn, qp = pn[sel], pp[sel]
    k, e = geo.edges.take(qp)
    py = cy[qn[k]]
    # Only an edge that straddles the ray's height can cross it.
    s = np.flatnonzero((geo.ey1[e] > py) != (geo.ey2[e] > py))
    k, e, py = k[s], e[s], py[s]
    px = cx[qn[k]]
    x1, y1, x2, y2 = geo.ex1[e], geo.ey1[e], geo.ex2[e], geo.ey2[e]
    lhs = (px - x1) * (y2 - y1)
    rhs = (py - y1) * (x2 - x1)
    cross = (lhs < rhs) == (y2 > y1)
    odd = np.bincount(k[cross], minlength=len(qn)) % 2 == 1
    qn, qp = qn[odd], qp[odd]
    first = np.ones(len(qn), bool)
    first[1:] = qn[1:] != qn[:-1]
    owner[qn[first]] = qp[first]
    return owner


def _assemble(interior: list, boundary: list, max_level: int,
              max_cand: int, extent, stats: list) -> CellCovering:
    """The covering arrays from the emitted cells, sorted by ``lo``.
    Boundary cells take candidate rows in descending ``lo``, the order in
    which a depth-first walk that visits children in descending Morton
    order would meet them."""
    def cat(parts, k):
        return np.concatenate([p[k] for p in parts])

    def levels(parts):
        return np.concatenate([np.full(len(p[0]), p[2], np.int8)
                               for p in parts])

    b_lo = cat(boundary, 0)
    down = np.argsort(b_lo)[::-1]
    b_val = np.empty(len(b_lo), np.int32)
    b_val[down] = -1 - np.arange(len(b_lo), dtype=np.int32)
    lo = np.concatenate([cat(interior, 0), b_lo])
    order = np.argsort(lo)
    return CellCovering(
        lo=lo[order],
        hi=np.concatenate([cat(interior, 1), cat(boundary, 1)])[order],
        val=np.concatenate([cat(interior, 3), b_val])[order],
        level=np.concatenate([levels(interior), levels(boundary)])[order],
        cand=cat(boundary, 3)[down],
        max_level=max_level, extent=extent,
        n_interior=int(sum(len(p[0]) for p in interior)),
        n_boundary=len(b_lo), build_stats=stats)
