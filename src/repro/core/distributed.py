"""Distributed geo-index lookup (beyond-paper; DESIGN.md §2 last row).

The paper's approximate index hits ~90 GiB on a single node (Table I).  On
TPU we remove that wall by sharding the cell table by contiguous Morton
ranges across the "model" axis while points stay batch-sharded across
("pod","data") — the same activation/weight split as the MoE layer:

  * every model-rank holds its Morton slice of (cell_lo, cell_hi, val,
    cand) — 1/16th of the index per chip on the production mesh;
  * points are replicated over "model" (they are only batch-sharded), so
    each rank resolves the points whose leaf code falls in its range — no
    payload all_to_all at all, only an i32 ``pmax`` per point to combine;
  * the PIP fallback for boundary points runs on the owning rank with a
    fixed-capacity compaction, so exact-mode compute is also sharded.

``shard_covering`` splits a host-side CellCovering into equal-cell padded
slices; ``place_sharded`` lays them out one shard per device of a mesh;
``assign_fast_distributed`` is the shard_map lookup.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.core.cells import CellCovering
from repro.core.fast import (FastConfig, extent_mask, quant_for_extent,
                             quantize_codes)
from repro.core.geometry import CensusMap
from repro.core.compact import capacity_for
from repro.core.resolve import ResolveStats, resolve_candidates
from repro.kernels import ops
from repro.compat import shard_map


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedFastIndex:
    """Morton-range-sharded cell index. Arrays are stacked [n_shards, ...]
    and sharded on axis 0 over "model"."""

    cell_lo: Any       # [S, Lmax] i32 (padded with INT32_MAX)
    cell_hi: Any       # [S, Lmax] i32
    cell_val: Any      # [S, Lmax] i32
    cand: Any          # [S, Cmax, K] i32
    range_lo: Any      # [S] i32 — first leaf code owned by each shard
    block_edges: Any   # [Nb, Eb, 4] f32 (replicated; small vs the index)
    block_parent: Any  # [Nb] i32
    county_parent: Any # [Nc] i32
    quant: Any         # [4] f32
    edge_pool: Any = None  # blocked-CSR EdgePool (replicated; fused path)
    max_level: int = dataclasses.field(metadata=dict(static=True), default=9)
    n_shards: int = dataclasses.field(metadata=dict(static=True), default=16)

    def tree_flatten(self):
        leaves = (self.cell_lo, self.cell_hi, self.cell_val, self.cand,
                  self.range_lo, self.block_edges, self.block_parent,
                  self.county_parent, self.quant, self.edge_pool)
        return leaves, (self.max_level, self.n_shards)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, max_level=aux[0], n_shards=aux[1])

    def index_bytes_per_shard(self) -> int:
        per = (np.asarray(self.cell_lo).nbytes
               + np.asarray(self.cell_hi).nbytes
               + np.asarray(self.cell_val).nbytes
               + np.asarray(self.cand).nbytes)
        return per // self.n_shards


INT_MAX = np.int32(2**31 - 1)


def shard_covering(cov: CellCovering, census: CensusMap,
                   n_shards: int, with_pool: bool = False
                   ) -> ShardedFastIndex:
    """Split the covering into ``n_shards`` contiguous Morton slices with
    (approximately) equal cell counts, padded to a common length.

    ``with_pool`` additionally builds the (replicated) blocked-CSR edge
    pool the fused gather-PIP path needs (FastConfig.fused)."""
    n = len(cov.lo)
    bounds = [int(round(i * n / n_shards)) for i in range(n_shards + 1)]
    lmax = max(bounds[i + 1] - bounds[i] for i in range(n_shards))
    cmax = 0
    rows = []
    for i in range(n_shards):
        a, b = bounds[i], bounds[i + 1]
        val = cov.val[a:b]
        brow = -(val[val < 0] + 1)
        cmax = max(cmax, len(brow))
        rows.append((a, b))

    cell_lo = np.full((n_shards, lmax), INT_MAX, np.int32)
    cell_hi = np.full((n_shards, lmax), -1, np.int32)
    cell_val = np.full((n_shards, lmax), -1, np.int32)
    cand = np.full((n_shards, max(cmax, 1), cov.cand.shape[1]), -1, np.int32)
    range_lo = np.zeros((n_shards,), np.int32)
    for i, (a, b) in enumerate(rows):
        m = b - a
        cell_lo[i, :m] = cov.lo[a:b]
        cell_hi[i, :m] = cov.hi[a:b]
        val = cov.val[a:b].copy()
        # Re-base boundary candidate rows into this shard's local table.
        is_b = val < 0
        src_rows = -(val[is_b] + 1)
        local = np.arange(is_b.sum(), dtype=np.int32)
        cand[i, :len(local)] = cov.cand[src_rows]
        val[is_b] = -(local + 1)
        cell_val[i, :m] = val
        range_lo[i] = cov.lo[a]
    range_lo[0] = 0

    quant = quant_for_extent(cov.extent, cov.max_level)
    block_edges_np = ops.edges_from_soup_np(census.blocks.verts)
    return ShardedFastIndex(
        cell_lo=jnp.asarray(cell_lo), cell_hi=jnp.asarray(cell_hi),
        cell_val=jnp.asarray(cell_val), cand=jnp.asarray(cand),
        range_lo=jnp.asarray(range_lo),
        block_edges=jnp.asarray(block_edges_np),
        block_parent=jnp.asarray(census.blocks.parent),
        county_parent=jnp.asarray(census.counties.parent),
        quant=jnp.asarray(quant),
        edge_pool=(ops.build_edge_pool(block_edges_np)
                   if with_pool else None),
        max_level=cov.max_level, n_shards=n_shards)


STACKED = ("cell_lo", "cell_hi", "cell_val", "cand")


def place_sharded(idx: ShardedFastIndex, mesh) -> ShardedFastIndex:
    """``idx`` laid out on ``mesh``: the stacked per-shard tables
    (``STACKED``) split one shard per device along the "model" axis,
    every other array replicated.  Arrays already laid out so stay where
    they are, so placing a placed index moves nothing."""
    if mesh.shape["model"] != idx.n_shards:
        raise ValueError(f"a {idx.n_shards}-shard index cannot be placed "
                         f"on a 'model' axis of {mesh.shape['model']}")
    split = NamedSharding(mesh, PS("model"))
    whole = NamedSharding(mesh, PS())

    def put(name, tree):
        want = split if name in STACKED else whole
        return jax.tree.map(
            lambda a: a if getattr(a, "sharding", None) == want
            else jax.device_put(a, want), tree)

    return dataclasses.replace(idx, **{
        f.name: put(f.name, getattr(idx, f.name))
        for f in dataclasses.fields(idx) if not f.metadata.get("static")})


def local_lookup(block_edges, lo, hi, val, cand, codes, points,
                 mode: str, cap: int, backend, active=None,
                 edge_pool=None):
    """Lookup of ``codes`` against ONE shard's table (padded rows inert).

    ``active`` optionally masks rows (e.g. empty dispatch-buffer slots).
    Boundary points go through the shared resolution core (sequential
    schedule, centre-owner fallback); ``edge_pool`` routes their PIP
    through the fused gather-PIP kernel.  Returns (bid, ResolveStats).
    """
    pos = jnp.searchsorted(lo, codes, side="right") - 1
    pos = jnp.clip(pos, 0, lo.shape[0] - 1)
    found = (lo[pos] <= codes) & (codes <= hi[pos])
    if active is not None:
        found = found & active
    v = jnp.where(found, val[pos], -INT_MAX)
    bid = jnp.where(v >= 0, v, -1)
    is_b = found & (v < 0) & (v > -INT_MAX)
    brow = jnp.clip(-(v + 1), 0, cand.shape[0] - 1)
    if mode == "approx":
        bid = jnp.where(is_b, cand[brow, 0], bid)
        rs = ResolveStats(n_need=jnp.sum(is_b.astype(jnp.int32)),
                          n_pip=jnp.zeros((), jnp.int32),
                          overflow=jnp.zeros((), jnp.int32),
                          phase2_miss=jnp.zeros((), jnp.int32))
    else:
        bid, rs = resolve_candidates(
            points, lambda i, _: cand[brow[i]], block_edges, is_b,
            cap=cap, backend=backend, prior=bid, fallback="first",
            edge_pool=edge_pool)
    return bid, rs


@functools.partial(jax.jit, static_argnames=("mesh", "cfg"))
def assign_fast_distributed(idx: ShardedFastIndex, points: jnp.ndarray,
                            mesh, cfg: FastConfig = FastConfig()):
    """Sharded-index lookup under shard_map.  points [N, 2] batch-sharded
    over ("pod","data"); index sharded over "model".  Returns
    (sid, cid, bid, stats) exactly like assign_fast.  Jitted with the
    index as an argument, so its tables are never baked into the
    program and repeat calls reuse one compile."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    n = points.shape[0]
    n_loc = n // dp_size
    cap = capacity_for(n_loc, cfg.cap_boundary)
    # Defense in depth for direct callers — engine-routed sharded assign
    # builds the pool on demand (GeoIndexSet.sharded_index) and never
    # reaches this raise.
    if cfg.fused and cfg.mode == "exact" and idx.edge_pool is None:
        raise ValueError("FastConfig.fused needs an index built with "
                         "with_pool=True (shard_covering)")
    pool = idx.edge_pool if cfg.fused else None

    def body(points_loc, lo, hi, val, cand, range_lo):
        lo, hi, val, cand = lo[0], hi[0], val[0], cand[0]
        codes = quantize_codes(idx.quant, idx.max_level, points_loc)
        # Off-extent points quantize onto the border (see quantize_codes);
        # mask them so they resolve to -1 instead of a border-cell block.
        ext = extent_mask(idx.quant, idx.max_level, points_loc)
        bid, rs = local_lookup(idx.block_edges, lo, hi, val, cand,
                               codes, points_loc, cfg.mode, cap,
                               cfg.backend, active=ext, edge_pool=pool)
        # Each point is owned by exactly one shard -> pmax combines.
        bid = jax.lax.pmax(bid, "model")
        axes = ("model",) + dp
        n_need = jax.lax.psum(rs.n_need, axes)
        n_pip = jax.lax.psum(rs.n_pip, axes)
        overflow = jax.lax.psum(rs.overflow, axes)
        return bid, n_need, n_pip, overflow

    bspec = dp if dp else None
    bid, n_need, n_pip, overflow = shard_map(
        body, mesh=mesh,
        in_specs=(PS(bspec, None), PS("model", None), PS("model", None),
                  PS("model", None), PS("model", None, None), PS("model")),
        out_specs=(PS(bspec), PS(), PS(), PS()),
        # Pallas kernels in the body declare no per-axis variance (vma)
        # for their outputs, which the check would require.
        check_vma=False,
    )(points, idx.cell_lo, idx.cell_hi, idx.cell_val, idx.cand,
      idx.range_lo)
    cid = jnp.where(bid >= 0, idx.block_parent[jnp.clip(bid, 0, None)], -1)
    sid = jnp.where(cid >= 0, idx.county_parent[jnp.clip(cid, 0, None)], -1)
    return sid, cid, bid, {"n_boundary": n_need, "n_pip": n_pip,
                           "overflow": overflow}
