"""Device-side "fast" approach (paper §IV): true-hit-filter cell lookup.

Lookup pipeline per point (all vectorized, jit-able):

  1. fixed-point quantize (lon, lat) -> (ix, iy) on the 2^L grid and Morton-
     interleave to a leaf code (int32 bit arithmetic — the TPU analogue of
     S2 cell ids);
  2. locate the covering cell: top-grid bucket (direct-indexed first 2g bits
     — the radix-trie-fanout analogue; g=0 disables) then a fixed-iteration
     binary search over the sorted interval starts;
  3. interior cell  -> block id, done (paper's "true hit": zero PIP tests);
     boundary cell  -> exact mode: crossing-number kernel against <=K
     candidates (compacted to a static buffer);
                       approx mode: accept the centre-owner candidate —
     error bounded by the leaf cell diagonal (paper's precision guarantee).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cells import CellCovering, morton_np
from repro.core.compact import capacity_for
from repro.core.geometry import CensusMap
from repro.core.resolve import onepass_stats, resolve_candidates
from repro.kernels import ops
from repro.kernels import cascade as _cascade

# One sentinel, two layers: the kernel package owns its copy (core
# imports kernels, never the reverse) — they must never fork.
assert _cascade.OUTSIDE == -2**30

# Sentinel cell value for points outside the map (below any candidate row
# encoding -(row+1)).
OUTSIDE = -2**30


def part1by1(x: jnp.ndarray) -> jnp.ndarray:
    x = x & 0x0000FFFF
    x = (x | (x << 8)) & 0x00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F
    x = (x | (x << 2)) & 0x33333333
    x = (x | (x << 1)) & 0x55555555
    return x


def unpart1by1(x: jnp.ndarray) -> jnp.ndarray:
    x = x & 0x55555555
    x = (x | (x >> 1)) & 0x33333333
    x = (x | (x >> 2)) & 0x0F0F0F0F
    x = (x | (x >> 4)) & 0x00FF00FF
    x = (x | (x >> 8)) & 0x0000FFFF
    return x


def morton(ix: jnp.ndarray, iy: jnp.ndarray) -> jnp.ndarray:
    return (part1by1(iy) << 1) | part1by1(ix)


def demorton(code: jnp.ndarray):
    """Inverse of ``morton``: leaf code -> (ix, iy) grid coordinates."""
    return unpart1by1(code), unpart1by1(code >> 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FastIndex:
    """Device-resident cell index (+ block geometry for exact fallback)."""

    cell_lo: Any        # [n_cells] i32 sorted
    cell_hi: Any        # [n_cells] i32 inclusive ends (gaps = outside map)
    cell_val: Any       # [n_cells] i32
    cand: Any           # [n_boundary, K] i32
    top_start: Any      # [4^g + 1] i32 — bucket ranges into cell_lo
    block_edges: Any    # [Nb, Eb, 4] f32 — exact-mode PIP fallback
    block_parent: Any   # [Nb] i32
    county_parent: Any  # [Nc] i32
    quant: Any          # [4] f32: (x0, y0, sx, sy) with s = 2^L / extent
    edge_pool: Any = None  # blocked-CSR EdgePool over the same blocks
    #                        (fused gather-PIP path; FastConfig.fused)
    block_bbox: Any = None  # [Nb, 4] f32 (xmin, xmax, ymin, ymax) — the
    #                         one-pass cascade kernel's in-VMEM bbox
    #                         filter stage (fused="onepass")
    # -- static --
    max_level: int = dataclasses.field(metadata=dict(static=True), default=9)
    gbits: int = dataclasses.field(metadata=dict(static=True), default=0)
    search_iters: int = dataclasses.field(metadata=dict(static=True),
                                          default=32)

    def tree_flatten(self):
        leaves = (self.cell_lo, self.cell_hi, self.cell_val, self.cand,
                  self.top_start, self.block_edges, self.block_parent,
                  self.county_parent, self.quant, self.edge_pool,
                  self.block_bbox)
        return leaves, (self.max_level, self.gbits, self.search_iters)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, max_level=aux[0], gbits=aux[1],
                   search_iters=aux[2])

    def nbytes(self) -> int:
        return sum(int(np.asarray(a).nbytes)
                   for a in (self.cell_lo, self.cell_hi, self.cell_val,
                             self.cand, self.top_start))

    @classmethod
    def from_covering(cls, cov: CellCovering, census: CensusMap,
                      gbits: int = 4, with_pool: bool = False):
        """gbits = quadtree levels resolved by the direct-indexed top grid
        (the paper's F1/F2/F4 trie-fanout analogue; 2*gbits key bits).

        ``with_pool`` additionally builds the blocked-CSR edge pool the
        fused gather-PIP path needs (FastConfig.fused); off by default so
        legacy callers pay neither the host build nor the device copy.
        """
        assert gbits <= cov.max_level
        nb = 1 << (2 * gbits)
        shift = 2 * (cov.max_level - gbits)
        # Bucket b covers leaf codes [b << shift, (b+1) << shift).  A covering
        # cell larger than a bucket spans several buckets; searchsorted-right
        # on lo gives, for each bucket start, the first cell *after* it, so
        # search ranges [start[b]-1, start[b+1]) — we fold the -1 into start.
        starts = np.searchsorted(cov.lo, np.arange(nb + 1, dtype=np.int64)
                                 << shift, side="left").astype(np.int32)
        # Static iteration count for the in-bucket binary search: the range
        # for bucket b is [starts[b]-1, starts[b+1]) — higher gbits => fewer
        # iterations, the paper's F1/F2/F4 fanout-vs-memory trade.
        max_span = int(np.max(starts[1:] - np.maximum(starts[:-1] - 1, 0))) \
            if len(cov.lo) else 1
        iters = max(1, int(np.ceil(np.log2(max(max_span, 2)))))
        quant = quant_for_extent(cov.extent, cov.max_level)
        block_edges_np = ops.edges_from_soup_np(census.blocks.verts)
        return cls(
            cell_lo=jnp.asarray(cov.lo),
            cell_hi=jnp.asarray(cov.hi),
            cell_val=jnp.asarray(cov.val),
            cand=jnp.asarray(cov.cand),
            top_start=jnp.asarray(starts),
            block_edges=jnp.asarray(block_edges_np),
            block_parent=jnp.asarray(census.blocks.parent),
            county_parent=jnp.asarray(census.counties.parent),
            quant=jnp.asarray(quant),
            edge_pool=(ops.build_edge_pool(block_edges_np)
                       if with_pool else None),
            # Always carried: [Nb, 4] is tiny, and the one-pass cascade
            # needs it whenever a pool is attached (possibly later, via
            # GeoIndexSet.ensure).
            block_bbox=jnp.asarray(census.blocks.bbox, jnp.float32),
            max_level=cov.max_level,
            gbits=gbits,
            search_iters=iters,
        )


def quant_for_extent(extent, max_level: int) -> np.ndarray:
    """THE quant vector: [4] f32 = (x0, y0, sx, sy) with s = 2^L / span.
    Every producer (FastIndex, ShardedFastIndex, engine extent handle,
    serving cell table) derives it here — the hot-cell cache's host/
    device bit-exactness rests on this formula never forking."""
    x0, x1, y0, y1 = extent
    n = 1 << max_level
    return np.array([x0, y0, n / (x1 - x0), n / (y1 - y0)], np.float32)


def quantize_codes(quant: jnp.ndarray, max_level: int,
                   points: jnp.ndarray) -> jnp.ndarray:
    """Fixed-point quantize + Morton-interleave [N, 2] points to leaf codes
    given the bare quant params [4] = (x0, y0, sx, sy) — usable by any
    index flavour (FastIndex, ShardedFastIndex).

    Off-extent coordinates CLIP onto the grid border, so a far-outside
    query maps to a border cell's leaf code.  Every caller that turns a
    code into a block id must therefore also apply ``extent_mask`` —
    otherwise an off-map point silently inherits a border block instead
    of -1 (the simple cascade's answer for the same point).
    """
    n = 1 << max_level
    ix = jnp.clip(((points[:, 0] - quant[0]) * quant[2])
                  .astype(jnp.int32), 0, n - 1)
    iy = jnp.clip(((points[:, 1] - quant[1]) * quant[3])
                  .astype(jnp.int32), 0, n - 1)
    return morton(ix, iy)


def extent_mask(quant: jnp.ndarray, max_level: int,
                points: jnp.ndarray) -> jnp.ndarray:
    """[N] bool — True where the point lies inside the quantization extent
    (the map bbox).  The companion of ``quantize_codes``: codes of points
    outside this mask are border-clipped and must not resolve to a block."""
    n = 1 << max_level
    fx = (points[:, 0] - quant[0]) * quant[2]
    fy = (points[:, 1] - quant[1]) * quant[3]
    return (fx >= 0) & (fx < n) & (fy >= 0) & (fy < n)


def np_quantize_codes(quant, max_level: int, points) -> np.ndarray:
    """Host (numpy) mirror of ``quantize_codes``, op-for-op in fp32
    (subtract, multiply, truncating cast — no FMA contraction on either
    side), so host and device codes agree bit-exactly.  The serving
    layer's cache keys on it without a device trip (DESIGN.md §10)."""
    n = 1 << max_level
    xy = np.asarray(points, np.float32)
    q = np.asarray(quant, np.float32)
    with np.errstate(invalid="ignore", over="ignore"):
        fx = (xy[:, 0] - q[0]) * q[2]
        fy = (xy[:, 1] - q[1]) * q[3]
        ix = np.clip(np.trunc(fx), 0, n - 1).astype(np.int32)
        iy = np.clip(np.trunc(fy), 0, n - 1).astype(np.int32)
    return morton_np(ix, iy).astype(np.int32)


def np_extent_mask(quant, max_level: int, points) -> np.ndarray:
    """Host (numpy) mirror of ``extent_mask`` — the serving router's
    ownership test, zero device traffic."""
    n = 1 << max_level
    xy = np.asarray(points, np.float32)
    q = np.asarray(quant, np.float32)
    fx = (xy[:, 0] - q[0]) * q[2]
    fy = (xy[:, 1] - q[1]) * q[3]
    return (fx >= 0) & (fx < n) & (fy >= 0) & (fy < n)


def leaf_codes(index: FastIndex, points: jnp.ndarray) -> jnp.ndarray:
    return quantize_codes(index.quant, index.max_level, points)


def locate_cells(index: FastIndex, codes: jnp.ndarray) -> jnp.ndarray:
    """Index into cell_lo of the covering cell for each leaf code (-1 =
    outside the map)."""
    n_cells = index.cell_lo.shape[0]
    if index.gbits == 0:
        # Plain vectorized binary search over the full table.
        idx = jnp.searchsorted(index.cell_lo, codes, side="right") - 1
    else:
        shift = 2 * (index.max_level - index.gbits)
        bucket = (codes >> shift).astype(jnp.int32)
        l = jnp.maximum(index.top_start[bucket] - 1, 0)
        h = index.top_start[bucket + 1]         # exclusive
        # Fixed-iteration searchsorted-right within [l, h).
        for _ in range(index.search_iters):
            active = l < h
            mid = (l + h) // 2
            go_right = index.cell_lo[jnp.clip(mid, 0, n_cells - 1)] <= codes
            nl = jnp.where(active & go_right, mid + 1, l)
            nh = jnp.where(active & ~go_right, mid, h)
            l, h = nl, nh
        idx = l - 1
    idx = jnp.clip(idx, 0, n_cells - 1)
    return idx


@dataclasses.dataclass(frozen=True)
class FastConfig:
    mode: str = "exact"          # "exact" | "approx"
    cap_boundary: float = 0.25   # compaction capacity for boundary points
    backend: str | None = None
    fused: Any = False           # exact mode candidate-PIP data path:
    #                              False     — gather + pip_gathered;
    #                              True      — fused gather-PIP kernel
    #                                          (index.edge_pool);
    #                              "onepass" — the one-pass fused cascade
    #                                          kernel (kernels/cascade.py):
    #                                          the whole quantize/lookup/
    #                                          bbox/PIP pipeline in one
    #                                          kernel, no compaction.
    #                              Results are identical in all three.


def cell_values(index: FastIndex, points: jnp.ndarray) -> jnp.ndarray:
    """Covering-cell value per point: >= 0 interior block id ("true hit"),
    -(row+1) boundary candidate row, OUTSIDE if the point is in no cell
    or off the map extent (quantization clips, so the extent test is
    explicit — see ``quantize_codes``)."""
    codes = leaf_codes(index, points)
    cidx = locate_cells(index, codes)
    in_cell = ((index.cell_lo[cidx] <= codes)
               & (codes <= index.cell_hi[cidx]))  # gap => outside the map
    in_cell = in_cell & extent_mask(index.quant, index.max_level, points)
    return jnp.where(in_cell, index.cell_val[cidx], OUTSIDE)


def parents_of(index, bid: jnp.ndarray):
    """Derive (county, state) ids from block ids via the parent tables
    (any index flavour carrying block_parent / county_parent)."""
    cid = jnp.where(bid >= 0, index.block_parent[jnp.clip(bid, 0, None)], -1)
    sid = jnp.where(cid >= 0, index.county_parent[jnp.clip(cid, 0, None)], -1)
    return cid, sid


def assign_fast_onepass(index: FastIndex, points: jnp.ndarray,
                        cfg: FastConfig):
    """Exact-mode assignment through the one-pass fused cascade kernel
    (kernels/cascade.py): quantize, cell lookup, bbox filter, and the
    candidate PIP all in one kernel — no per-stage HBM intermediates and
    no compaction buffers.  Assignments are bit-identical to the
    two-phase ``assign_fast`` path (first matching candidate in slot
    order, centre-owner fallback), and the stats counters match whenever
    the two-phase caps are not overflowing (core.resolve.onepass_stats).
    """
    if index.edge_pool is None or index.block_bbox is None:
        raise ValueError('FastConfig.fused="onepass" needs an index '
                         "built by FastIndex.from_covering with a pool "
                         "(with_pool=True / GeoIndexSet.ensure)")
    bid, flags, nrest, nskip = ops.assign_cascade(
        points, index.quant, index.cell_lo, index.cell_hi, index.cell_val,
        index.top_start, index.cand, index.block_bbox, index.edge_pool,
        max_level=index.max_level, gbits=index.gbits,
        search_iters=index.search_iters, backend=cfg.backend)
    stats = onepass_stats(flags, nrest, nskip)
    cid, sid = parents_of(index, bid)
    return sid, cid, bid, stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def assign_fast(index: FastIndex, points: jnp.ndarray,
                cfg: FastConfig = FastConfig()):
    """Map [N, 2] points -> (state, county, block ids, stats)."""
    n = points.shape[0]
    # Defense in depth for direct callers: engine-built paths already
    # fail this at construction (registry capability validation,
    # DESIGN.md §11), so an engine user never reaches this raise.
    if cfg.fused and cfg.mode == "exact" and index.edge_pool is None:
        raise ValueError("FastConfig.fused needs an index built with "
                         "with_pool=True (FastIndex.from_covering)")
    if cfg.fused == "onepass" and cfg.mode == "exact":
        return assign_fast_onepass(index, points, cfg)
    # Named scopes (geo/locate here, geo/compact and the PIP phases in
    # resolve.py, geo/parents) are op_name metadata only: they name the
    # device ops of a profiler trace by phase and change no op.
    with jax.named_scope("geo/locate"):
        val = cell_values(index, points)
        is_boundary = val < 0
        brow = jnp.clip(-(val + 1), 0, max(index.cand.shape[0] - 1, 0))
        bid = jnp.where(val >= 0, val, -1)
        need = is_boundary & (val > OUTSIDE)
        n_boundary = jnp.sum(need.astype(jnp.int32))

    n_pip = jnp.zeros((), jnp.int32)
    overflow = jnp.zeros((), jnp.int32)
    phase2_miss = jnp.zeros((), jnp.int32)

    if index.cand.shape[0] > 0:
        if cfg.mode == "approx":
            # Centre-owner candidate; error <= leaf cell diagonal.  Gather
            # only slot 0 ([N] i32) instead of the full [N, K] table.
            cand0 = index.cand[brow, 0]
            bid = jnp.where(need, cand0, bid)
        else:
            # Two-phase resolution (§Perf geo iterations 2-3): the centre-
            # owner candidate (slot 0) resolves ~90 % of boundary points,
            # so phase 1 tests ONLY slot 0 for the whole buffer; phase 2
            # batches the remaining K-1 candidates for the ~10 % of misses.
            # Unmatched boundary points fall back to the centre owner
            # (fallback="first").
            bid, rs = resolve_candidates(
                points, lambda idx, _: index.cand[brow[idx]],
                index.block_edges, need,
                cap=capacity_for(n, cfg.cap_boundary),
                backend=cfg.backend, prior=bid, fallback="first",
                two_phase=True,
                edge_pool=index.edge_pool if cfg.fused else None)
            n_pip, overflow = rs.n_pip, rs.overflow
            phase2_miss = rs.phase2_miss

    with jax.named_scope("geo/parents"):
        cid, sid = parents_of(index, bid)
    stats = {"n_boundary": n_boundary, "n_pip": n_pip, "overflow": overflow,
             "phase2_miss": phase2_miss}
    return sid, cid, bid, stats
