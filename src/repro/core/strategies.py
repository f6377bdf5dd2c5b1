"""Built-in mapping strategies as registered plugins (DESIGN.md §11).

The execution plans the engine ships — the paper's simple cascade
(§III), the fast cell index (§IV), its one-pass fused-cascade variant
(kernels/cascade.py), the hybrid interior/cascade split, and
the dispatch-routed Morton-sharded lookup — registered through
``core.registry`` exactly like a third-party strategy would be.  The
engine holds no strategy-specific code at all: it resolves names via
``get_strategy`` and calls the protocol.

Each plugin stays a thin driver over ``core.resolve.resolve_candidates``
(the compaction + candidate-PIP + fallback primitive); what differs is
which points need resolution and which candidates they bring.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import fast as fast_mod
from repro.core import simple as simple_mod
from repro.core.compact import capacity_for, compact_indices, scatter_filled
from repro.core.distributed import ShardedFastIndex, local_lookup
from repro.core.fast import (FastConfig, FastIndex, cell_values, parents_of,
                             quantize_codes)
from repro.core.registry import Strategy, register_strategy
from repro.core.resolve import AssignResult, GeoStats
from repro.core.simple import SimpleConfig, SimpleIndex
from repro.distributed.dispatch import (plan_routes, scatter_to_buckets,
                                        slot_tables)
from repro.kernels import ops
from repro.compat import shard_map


@register_strategy("simple", needs=("simple",), needs_edge_pool=True)
class SimpleStrategy(Strategy):
    """The paper's §III hierarchical bbox cascade."""

    def assign(self, indices, points, cfg) -> AssignResult:
        sid, cid, bid, st = simple_mod.assign_simple(
            indices.simple, points, cfg.simple_cfg())
        levels = ("state", "county", "block")
        return AssignResult(sid, cid, bid, GeoStats(
            n_need=sum(st[l]["n_multi"] for l in levels),
            n_pip=sum(st[l]["n_pip"] for l in levels),
            overflow=sum(st[l]["overflow"] for l in levels),
            extra=st))


ONEPASS_ON_TPU = (
    "the one-pass cascade kernel (kernels/cascade.py) does not compile "
    "for the TPU: Mosaic refuses its one-row point and output blocks and "
    "its scalar reads at dynamic indices out of VMEM-resident tables "
    "(ROADMAP §2).  Use strategy 'fast' with fused=True (gather-PIP), or "
    "choose the ref or interpret kernel backend explicitly")


def onepass_runs(backend: str | None = None) -> bool:
    """Whether the one-pass cascade can run on kernel ``backend`` (None:
    the one ``ops.resolve_backend`` picks): everywhere but compiled
    Pallas, for which it does not compile (``ONEPASS_ON_TPU``)."""
    return ops.resolve_backend(backend) != "pallas"


@register_strategy("fast", needs=("fast",), needs_edge_pool=True)
class FastStrategy(Strategy):
    """The paper's §IV true-hit-filter cell index (cfg.mode picks exact /
    approx boundary handling)."""

    def pool_components(self, cfg):
        # Only exact mode runs candidate PIP on the fast index (approx
        # accepts the centre owner), so only it needs the edge pool.
        return ("fast",) if cfg.fused and cfg.mode == "exact" else ()

    def runs_onepass(self, cfg) -> bool:
        return cfg.fused == "onepass" and cfg.mode == "exact"

    def validate(self, indices, cfg) -> None:
        super().validate(indices, cfg)
        if self.runs_onepass(cfg) and not onepass_runs(cfg.backend):
            raise ValueError(f"strategy {self.name!r}: {ONEPASS_ON_TPU}")

    def assign(self, indices, points, cfg) -> AssignResult:
        sid, cid, bid, st = fast_mod.assign_fast(
            indices.fast, points, cfg.fast_cfg())
        return AssignResult(sid, cid, bid, GeoStats(
            n_need=st["n_boundary"], n_pip=st["n_pip"],
            overflow=st["overflow"], extra=st))


@register_strategy("fast_onepass", needs=("fast",), needs_edge_pool=True)
class FastOnepassStrategy(FastStrategy):
    """The one-pass fused cascade (kernels/cascade.py): the whole
    quantize -> cell lookup -> bbox filter -> PIP pipeline in a single
    kernel with double-buffered edge-block DMA.  Semantically this is
    ``fast`` with ``mode="exact", fused="onepass"`` pinned — registered
    under its own name so the planner, benchmarks, and serving configs
    can name the execution plan directly; assignments are bit-identical
    to ``fast_exact`` (and its stats counters match outside the
    two-phase path's capacity-overflow regime)."""

    def pool_components(self, cfg):
        # Always exact, always the in-kernel candidate walk: the edge
        # pool is unconditionally required (and validated at build).
        return ("fast",)

    def runs_onepass(self, cfg) -> bool:
        return True

    def assign(self, indices, points, cfg) -> AssignResult:
        fcfg = dataclasses.replace(cfg.fast_cfg(), mode="exact",
                                   fused="onepass")
        sid, cid, bid, st = fast_mod.assign_fast(indices.fast, points,
                                                 fcfg)
        return AssignResult(sid, cid, bid, GeoStats(
            n_need=st["n_boundary"], n_pip=st["n_pip"],
            overflow=st["overflow"], extra=st))


@functools.partial(jax.jit, static_argnames=("scfg", "cap_frac"))
def _assign_hybrid(findex: FastIndex, sindex: SimpleIndex,
                   points: jnp.ndarray, scfg: SimpleConfig,
                   cap_frac: float):
    """Hybrid strategy: interior true hits from the cell index; boundary
    points re-resolved through the hierarchical cascade."""
    n = points.shape[0]
    val = cell_values(findex, points)
    bid = jnp.where(val >= 0, val, -1)
    need = (val < 0) & (val > fast_mod.OUTSIDE)      # boundary cells
    n_boundary = jnp.sum(need.astype(jnp.int32))

    cap = capacity_for(n, cap_frac)
    idx, slot_ok = compact_indices(need, cap)
    sub_need = need[idx] & slot_ok
    # Unfilled compaction slots alias row 0; feed the cascade FAR points
    # there (and on non-boundary rows) so its stats count only real
    # boundary work — otherwise n_pip would scale with the capacity, and
    # a padded batch (assign_padded) would report different stats than
    # the unpadded call.  Result-identical: only sub_need rows' cascade
    # output is kept below.
    sub_pts = jnp.where(sub_need[:, None], points[idx],
                        jnp.float32(ops.FAR))
    _, _, sub_bid, sub_stats = simple_mod.cascade_assign(
        sindex, sub_pts, scfg)
    bid = scatter_filled(bid, idx, slot_ok,
                         jnp.where(sub_need & (sub_bid >= 0),
                                   sub_bid, bid[idx]))
    overflow = n_boundary - jnp.sum(sub_need.astype(jnp.int32))
    if findex.cand.shape[0] > 0:
        # Cascade misses + capacity overflow degrade to the centre-owner
        # candidate (the fast-approx answer) rather than staying lost.
        brow = jnp.clip(-(val + 1), 0, findex.cand.shape[0] - 1)
        bid = jnp.where(need & (bid < 0), findex.cand[brow, 0], bid)

    cid, sid = parents_of(findex, bid)
    n_pip = sum(lvl["n_pip"] for lvl in sub_stats.values())
    stats = {"n_boundary": n_boundary, "n_pip": n_pip,
             "overflow": overflow, "cascade": sub_stats}
    return sid, cid, bid, stats


@register_strategy("hybrid", needs=("simple", "fast"), needs_edge_pool=True)
class HybridStrategy(Strategy):
    """Fast cell lookup for interior true hits; boundary/overflow points
    routed through the simple cascade's hierarchical PIP instead of the
    flat candidate-list fallback (see the engine module docstring)."""

    def pool_components(self, cfg):
        # The cascade does all candidate PIP in hybrid mode — the fast
        # index's own pool is never consulted.
        return ("simple",) if cfg.fused else ()

    def assign(self, indices, points, cfg) -> AssignResult:
        sid, cid, bid, st = _assign_hybrid(
            indices.fast, indices.simple, points,
            cfg.hybrid_cascade_cfg(), cfg.cap_boundary)
        return AssignResult(sid, cid, bid, GeoStats(
            n_need=st["n_boundary"], n_pip=st["n_pip"],
            overflow=st["overflow"], extra=st))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "cfg", "capacity", "cap_pip"))
def _sharded_assign(sidx: ShardedFastIndex, points: jnp.ndarray, mesh,
                    cfg: FastConfig, capacity: int, cap_pip: int):
    """Dispatch-routed sharded lookup: bucket points by owning Morton
    shard, scatter into per-shard capacity buffers, look up shard-locally
    under shard_map, gather results back by buffer slot."""
    n = points.shape[0]
    s = sidx.n_shards
    codes = quantize_codes(sidx.quant, sidx.max_level, points)
    owner = jnp.clip(
        jnp.searchsorted(sidx.range_lo, codes, side="right") - 1, 0, s - 1
    ).astype(jnp.int32)
    plan = plan_routes(owner, s, capacity)
    item_for_slot, _ = slot_tables(plan, s, capacity)        # [S*cap]
    ok = item_for_slot >= 0
    # Off-extent points carry border-clipped codes (see quantize_codes);
    # deactivate their slots so they come back -1, not a border block.
    ext = fast_mod.extent_mask(sidx.quant, sidx.max_level, points)
    slot_ext = ok & ext[jnp.clip(item_for_slot, 0, n - 1)]
    buf_pts = scatter_to_buckets(plan, points, s, capacity,
                                 item_for_slot=item_for_slot
                                 ).reshape(s, capacity, 2)
    buf_ok = slot_ext.reshape(s, capacity)
    pool = sidx.edge_pool if cfg.fused else None

    def body(pts_loc, ok_loc, lo, hi, val, cand):
        pts_loc, ok_loc = pts_loc[0], ok_loc[0]
        lo, hi, val, cand = lo[0], hi[0], val[0], cand[0]
        codes_loc = quantize_codes(sidx.quant, sidx.max_level, pts_loc)
        bid, rs = local_lookup(
            sidx.block_edges, lo, hi, val, cand, codes_loc, pts_loc,
            cfg.mode, cap_pip, cfg.backend, active=ok_loc,
            edge_pool=pool)
        return (bid[None], jax.lax.psum(rs.n_need, "model"),
                jax.lax.psum(rs.n_pip, "model"),
                jax.lax.psum(rs.overflow, "model"),
                jax.lax.psum(rs.phase2_miss, "model"))

    ps = jax.sharding.PartitionSpec
    bid_buf, n_need, n_pip, pip_of, p2_miss = shard_map(
        body, mesh=mesh,
        in_specs=(ps("model"), ps("model"), ps("model"), ps("model"),
                  ps("model"), ps("model")),
        out_specs=(ps("model"), ps(), ps(), ps(), ps()),
        # Pallas kernels in the body declare no per-axis variance (vma)
        # for their outputs, which the check would require.
        check_vma=False,
    )(buf_pts, buf_ok, sidx.cell_lo, sidx.cell_hi, sidx.cell_val,
      sidx.cand)

    dest = jnp.where(ok, item_for_slot, n)
    bid = jnp.full((n + 1,), -1, jnp.int32).at[dest].set(
        bid_buf.reshape(-1), mode="drop")[:n]
    cid, sid = parents_of(sidx, bid)
    stats = {"n_boundary": n_need, "n_pip": n_pip, "overflow": pip_of,
             "phase2_miss": p2_miss, "n_dropped": plan.n_dropped}
    return sid, cid, bid, stats


@register_strategy("sharded", supports_sharded=True, supports_padded=False)
class ShardedStrategy(Strategy):
    """Morton-sharded cell lookup routed through the capacity-bucketed
    dispatch primitive shared with the MoE layer (DESIGN.md §6) — every
    engine's ``assign_sharded`` resolves to this plugin.

    Capacity per shard is ``cap_shard * N / n_shards`` — routing skew
    beyond that is dropped to bid -1 and counted in stats
    (extra["n_dropped"]), mirroring MoE token dropping.
    """

    def assign_sharded(self, indices, points, mesh, cfg) -> AssignResult:
        if "model" not in mesh.axis_names:
            raise ValueError("assign_sharded expects a mesh with a "
                             "'model' axis")
        n = points.shape[0]
        n_shards = int(mesh.shape["model"])
        sidx = indices.sharded_index(
            n_shards, with_pool=(cfg.fused and cfg.mode == "exact"),
            mesh=mesh)
        capacity = capacity_for(n, cfg.cap_shard / n_shards)
        cap_pip = capacity_for(capacity, cfg.cap_boundary,
                               ceiling=capacity)
        sid, cid, bid, st = _sharded_assign(
            sidx, points, mesh, cfg.fast_cfg(), capacity, cap_pip)
        return AssignResult(sid, cid, bid, GeoStats(
            n_need=st["n_boundary"], n_pip=st["n_pip"],
            overflow=st["overflow"] + st["n_dropped"], extra=st))
