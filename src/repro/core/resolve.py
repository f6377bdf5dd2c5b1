"""Shared resolution core for every mapping strategy (DESIGN.md §3).

Each strategy in this repo — the simple cascade (paper §III), the fast
cell index (paper §IV), the engine's hybrid mode, and the Morton-sharded
distributed lookup — bottoms out in the same compute pattern:

    candidate filter -> fixed-capacity compaction -> crossing-number PIP
    against <= K candidate polygons -> fallback policy -> overflow-counted
    stats.

``resolve_candidates`` implements that pattern exactly once.  Strategy
modules stay thin drivers: they decide *which* points need resolution and
*which* candidates each point brings, then hand both to this primitive.

Two PIP schedules are provided (they return identical assignments — the
first matching candidate in slot order — and differ only in kernel-call
shape):

  * sequential  — K kernel calls over the full compacted buffer; right when
    K is small and the buffer large (the cascade levels).
  * two_phase   — slot 0 (the centre-owner / best candidate) for the whole
    buffer, then one batched call over the remaining K-1 candidates for the
    ~10 % of slot-0 misses (§Perf geo iterations 2-3).  Right when slot 0
    resolves most points (the boundary-cell fallback).

Backend strings are resolved here, once, via ``ops.resolve_backend`` —
callers pass the raw ``cfg.backend`` through and never touch kernel
dispatch themselves.

Candidate PIP has two data paths (identical results):

  * legacy  — gather ``edges_table[pid]`` into an [R, E, 4] HBM buffer,
    then the gathered crossing kernel (``ops.pip_gathered``);
  * fused   — pass ``edge_pool=`` (a blocked-CSR ``ops.EdgePool``) and the
    candidate ids go straight into the fused gather-PIP kernel
    (``ops.pip_candidates``): edge slices are prefetched HBM -> VMEM
    inside the kernel's grid loop and the [R, E, 4] gather is never
    materialized.  Strategies enable it with their ``fused`` config flag.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp

from repro.core.compact import capacity_for, compact_indices, scatter_filled
from repro.kernels import ops

# Candidate table for N points: either a precomputed [N, K] id array or a
# callable evaluated *after* compaction — (idx [R], sub_pts [R, 2]) ->
# [R, K] — so strategies can defer expensive candidate gathering to the
# (much smaller) compacted buffer.
CandidateFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
Candidates = Union[jnp.ndarray, CandidateFn]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ResolveStats:
    """Per-resolve accounting (device scalars, all i32).

    n_need:      points that required candidate resolution.
    n_pip:       candidate PIP tests actually issued.
    overflow:    points dropped by the fixed-capacity compaction — counted,
                 never silent (callers re-run stragglers or size caps up).
    phase2_miss: two-phase schedule only — slot-0 misses that did not get
                 a phase-2 compaction slot and therefore degraded straight
                 to the fallback policy without testing slots 1..K-1.
                 Distinct from ``overflow``: these points still produce an
                 answer (the fallback), but a *less exact* one; a non-zero
                 value says ``cap2`` is undersized for the workload.
                 Always 0 for the sequential schedule.
    """

    n_need: Any
    n_pip: Any
    overflow: Any
    phase2_miss: Any

    def tree_flatten(self):
        return (self.n_need, self.n_pip, self.overflow,
                self.phase2_miss), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def as_dict(self) -> dict:
        return {"n_need": self.n_need, "n_pip": self.n_pip,
                "overflow": self.overflow, "phase2_miss": self.phase2_miss}

    def merge(self, other: "ResolveStats") -> "ResolveStats":
        """Counter-wise sum — aggregates resolves across micro-batches."""
        return ResolveStats(
            n_need=self.n_need + other.n_need,
            n_pip=self.n_pip + other.n_pip,
            overflow=self.overflow + other.overflow,
            phase2_miss=self.phase2_miss + other.phase2_miss)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GeoStats:
    """Unified cross-strategy stats (device scalars unless noted).

    n_need:   points that needed candidate resolution — bbox-ambiguous
              points for the cascade, boundary-cell hits for the cell
              index.  The paper's headline ratios (true-hit rate, PIP
              fraction) read straight off this.
    n_pip:    candidate PIP tests issued (0 for fast-approx).
    overflow: points whose resolution was dropped by a fixed-capacity
              compaction (plus routing drops for assign_sharded); they
              keep their best-effort id, and a non-zero value means the
              ``cap_*`` config fractions are undersized for the workload.
    extra:    the strategy's native breakdown — per-level dicts for the
              cascade, ``n_boundary``/``phase2_miss``/``cascade`` for the
              cell-index flavours, ``n_dropped`` for sharded routing.
    """

    n_need: Any
    n_pip: Any
    overflow: Any
    extra: Any = dataclasses.field(default_factory=dict)

    def tree_flatten(self):
        return (self.n_need, self.n_pip, self.overflow, self.extra), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def merge(self, other: "GeoStats") -> "GeoStats":
        """Counter-wise sum across micro-batches (serving aggregation).

        ``extra`` is summed leaf-wise, so both stats must come from the
        same strategy + config (identical extra tree structure) — the
        serving layer accumulates one running GeoStats per engine.
        """
        return GeoStats(
            n_need=self.n_need + other.n_need,
            n_pip=self.n_pip + other.n_pip,
            overflow=self.overflow + other.overflow,
            extra=jax.tree_util.tree_map(lambda a, b: a + b,
                                         self.extra, other.extra))

    def as_dict(self) -> dict:
        """Flat JSON-ready counters (python ints) for bench rows and
        serving metrics.  ``phase2_miss`` is summed over however the
        strategy nests it (top-level for fast, per-level for the cascade,
        under ``cascade`` for hybrid); ``n_boundary`` falls back to
        ``n_need`` for strategies without a cell index."""
        d = {"n_need": int(self.n_need), "n_pip": int(self.n_pip),
             "overflow": int(self.overflow),
             "phase2_miss": _sum_nested(self.extra, "phase2_miss")}
        if isinstance(self.extra, dict):
            d["n_boundary"] = int(self.extra.get("n_boundary", self.n_need))
            if "n_dropped" in self.extra:
                d["n_dropped"] = int(self.extra["n_dropped"])
        else:
            d["n_boundary"] = d["n_need"]
        return d


def _sum_nested(tree, key: str) -> int:
    """Sum every scalar leaf named ``key`` anywhere in a nested dict."""
    total = 0
    if isinstance(tree, dict):
        for k, v in tree.items():
            if isinstance(v, dict):
                total += _sum_nested(v, key)
            elif k == key:
                total += int(v)
    return total


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AssignResult:
    """(state, county, block) ids plus GeoStats; iterable for tuple-style
    unpacking parity with the legacy ``assign_*`` returns."""

    state: Any
    county: Any
    block: Any
    stats: Any

    def __iter__(self):
        return iter((self.state, self.county, self.block, self.stats))

    def tree_flatten(self):
        return (self.state, self.county, self.block, self.stats), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def onepass_stats(flags: jnp.ndarray, nrest: jnp.ndarray,
                  nskip: jnp.ndarray) -> dict:
    """Stats dict for the one-pass fused cascade (ops.assign_cascade),
    reproducing ``_pip_two_phase``'s accounting from the kernel's
    per-point outputs so ``fast_onepass`` is counter-identical to
    ``fast_exact`` whenever the two-phase caps are not overflowing:

      * n_pip = every boundary point pays its slot-0 test, and each
        slot-0 *miss* additionally counts all its valid slot-1..K-1
        candidates — exactly the phase-2 ``real2 & (rest >= 0)`` sum;
      * overflow / phase2_miss are structurally zero: the kernel walks
        candidates per point with no compaction buffer to overflow (the
        one-pass path is the *more* exact answer when the two-phase caps
        are undersized — the counters make that visible rather than
        papering over it);
      * bbox_skips rides in the strategy's native breakdown only (extra
        dict): candidate slots whose bbox rejected the point before any
        edge DMA — the filter stage's measured win.
    """
    boundary = (flags & 1) == 1
    slot0_hit = (flags & 2) == 2
    n_boundary = jnp.sum(boundary.astype(jnp.int32))
    n_pip = n_boundary + jnp.sum(
        jnp.where(boundary & ~slot0_hit, nrest, 0))
    return {"n_boundary": n_boundary, "n_pip": n_pip,
            "overflow": jnp.zeros((), jnp.int32),
            "phase2_miss": jnp.zeros((), jnp.int32),
            "bbox_skips": jnp.sum(jnp.where(boundary, nskip, 0))}


def first_k_candidates(mask: jnp.ndarray, k: int) -> jnp.ndarray:
    """Slots of the first min(k, C) set bits per row of a [R, C] mask
    (else -1); k is clamped so narrow candidate tables (tiny maps) work."""
    c = mask.shape[1]
    k = min(k, c)
    iota = jnp.arange(c, dtype=jnp.int32)[None, :]
    score = jnp.where(mask != 0, c - iota, 0)       # larger = earlier slot
    vals, _ = jax.lax.top_k(score, k)
    return jnp.where(vals > 0, c - vals, -1)        # [R, k] slot indices


def _pip_ids(points, pid, edges_table, edge_pool, backend):
    """Inside mask of each point vs its own candidate id (pid < 0 = never
    inside).  Fused CSR path when an edge pool is provided; the legacy
    gather-then-kernel flow otherwise.

    The fused call is made in candidate-id-sorted order: the gather-PIP
    kernel skips the HBM->VMEM block DMA when consecutive grid rows map
    to the same pool block, so sorting amortizes edge traffic to near
    zero on repeated candidates (ROADMAP PR 2 item).  The permutation is
    local to this function — rows are inverse-permuted before returning,
    and each row's crossing count depends only on its own (point, id) —
    so every caller sees results bit-identical to the unsorted order,
    including the two-phase schedule's inner compaction.
    """
    if edge_pool is not None:
        order = jnp.argsort(
            jnp.where(pid >= 0, pid, jnp.int32(2**31 - 1)), stable=True)
        inside = ops.pip_candidates(points[order], pid[order], edge_pool,
                                    backend=backend)
        return jnp.zeros_like(inside).at[order].set(inside)
    edges = edges_table[jnp.clip(pid, 0, edges_table.shape[0] - 1)]
    return ops.pip_gathered(points, edges, backend=backend) & (pid >= 0)


def _pip_sequential(points, cand_ids, edges_table, need, backend,
                    edge_pool=None):
    """First matching candidate in slot order, K sequential kernel calls.

    Returns (assign [R] i32 with -1 = no candidate matched, n_pip [] i32,
    phase2_miss [] i32 == 0).
    """
    k = cand_ids.shape[1]
    assign = jnp.full(points.shape[0], -1, jnp.int32)
    n_pip = jnp.zeros((), jnp.int32)
    for kk in range(k):
        pid = cand_ids[:, kk]
        active = need & (pid >= 0) & (assign < 0)
        inside = _pip_ids(points, pid, edges_table, edge_pool, backend)
        assign = jnp.where(active & inside, pid, assign)
        n_pip = n_pip + jnp.sum(active.astype(jnp.int32))
    return assign, n_pip, jnp.zeros((), jnp.int32)


def _pip_two_phase(points, cand_ids, edges_table, need, backend, cap2,
                   edge_pool=None):
    """Same assignment as ``_pip_sequential`` in two batched phases:
    slot 0 for everyone, then the remaining K-1 slots for the ``cap2``
    compacted slot-0 misses.  Misses beyond cap2 degrade to the caller's
    fallback policy (they are not counted as overflow — same contract as
    capacity overflow, the answer is the fallback, not a drop — but they
    ARE counted in phase2_miss so the degradation is visible)."""
    kk = cand_ids.shape[1]
    with jax.named_scope("geo/pip_phase1"):
        pid0 = cand_ids[:, 0]
        in0 = _pip_ids(points, pid0, edges_table, edge_pool, backend)
        in0 = in0 & (pid0 >= 0) & need
        n_pip = jnp.sum(need.astype(jnp.int32))
        assign = jnp.where(in0, pid0, -1)
    if kk == 1:
        return assign, n_pip, jnp.zeros((), jnp.int32)

    with jax.named_scope("geo/compact"):
        miss = need & ~in0
        n_miss = jnp.sum(miss.astype(jnp.int32))
        idx2, ok2 = compact_indices(miss, cap2)
        # Unfilled phase-2 slots alias row 0; guard the counter with ok2
        # so a row-0 miss doesn't phantom-count PIP tests for them (it
        # would make n_pip depend on which row the compaction's buffer
        # order put first).
        real2 = miss[idx2] & ok2
        phase2_miss = n_miss - jnp.sum(real2.astype(jnp.int32))
    with jax.named_scope("geo/phase2_gather"):
        # Whole rows by a 1-D index, then the static column slice: a row
        # index with a column offset in one gather ([idx2, 1:]) is
        # expanded by the TPU compiler into a loop of one row per trip.
        rest = cand_ids[idx2][:, 1:]                 # [R2, K-1]
        flat_pid = rest.reshape(-1)
        pts_rep = jnp.repeat(points[idx2], kk - 1, axis=0)
    with jax.named_scope("geo/pip_phase2"):
        in_r = _pip_ids(pts_rep, flat_pid, edges_table, edge_pool, backend)
        in_r = (in_r & (flat_pid >= 0)).reshape(-1, kk - 1)
        n_pip = n_pip + jnp.sum((real2[:, None]
                                 & (rest >= 0)).astype(jnp.int32))
        score = jnp.where(in_r, kk - jnp.arange(1, kk)[None, :], 0)
        best = jnp.argmax(score, axis=1)
        hit2 = jnp.any(in_r, axis=1) & miss[idx2] & ok2
        val2 = jnp.take_along_axis(rest, best[:, None], axis=1)[:, 0]
    with jax.named_scope("geo/compact"):
        assign = scatter_filled(assign, idx2, ok2,
                                jnp.where(hit2, val2, assign[idx2]))
    return assign, n_pip, phase2_miss


def resolve_candidates(points: jnp.ndarray, cand_ids: Candidates,
                       edges_table: jnp.ndarray, need: jnp.ndarray, *,
                       cap: int, k: int | None = None,
                       backend: str | None = None,
                       prior: jnp.ndarray | None = None,
                       fallback: str = "prior",
                       two_phase: bool = False,
                       cap2: int | None = None,
                       edge_pool=None):
    """THE compaction + candidate-PIP + fallback primitive.

    Args:
      points:      [N, 2] query points (full batch).
      cand_ids:    [N, K] candidate polygon ids (-1 = empty slot), or a
                   callable gathering them post-compaction (see Candidates).
      edges_table: [P, E, 4] edge table the candidate ids index into.
      need:        [N] bool — points requiring resolution.
      cap:         static compaction capacity (see compact.capacity_for).
      k:           optional truncation of the candidate list to its first k
                   slots.
      backend:     kernel backend override (resolved once, here).
      prior:       [N] i32 assignment so far; rows outside ``need`` (and
                   rows whose resolution fails, under fallback="prior")
                   keep it.  Defaults to all -1.
      fallback:    what a needed-but-unmatched point gets:
                     "prior" — its prior value (cascade: the bbox select);
                     "first" — its slot-0 candidate (cell index: the
                     centre owner, error bounded by the leaf diagonal).
      two_phase:   PIP schedule (see module docstring).
      cap2:        two-phase only — capacity of the phase-2 (slot-0 miss)
                   compaction; defaults to a quarter of ``cap`` (the
                   centre-owner hit rate makes misses the minority).
      edge_pool:   optional blocked-CSR ``ops.EdgePool`` over the same
                   polygons as ``edges_table``; when given, candidate PIP
                   runs through the fused gather-PIP kernel instead of
                   gather + ``pip_gathered`` (see module docstring).

    Returns:
      (assign [N] i32, ResolveStats).  Capacity overflow leaves ``prior``
      untouched and is counted in stats.overflow; phase-2 capacity misses
      degrade to ``fallback`` and are counted in stats.phase2_miss.
    """
    n = points.shape[0]
    backend = ops.resolve_backend(backend)
    if prior is None:
        prior = jnp.full((n,), -1, jnp.int32)
    with jax.named_scope("geo/compact"):
        idx, slot_ok = compact_indices(need, cap)
        sub_pts = points[idx]
        sub_need = need[idx] & slot_ok
        sub_cand = cand_ids(idx, sub_pts) if callable(cand_ids) \
            else cand_ids[idx]
        if k is not None:
            sub_cand = sub_cand[:, :k]
    if two_phase:
        if cap2 is None:
            cap2 = capacity_for(cap, 0.25, ceiling=cap)
        resolved, n_pip, p2_miss = _pip_two_phase(
            sub_pts, sub_cand, edges_table, sub_need, backend, cap2,
            edge_pool=edge_pool)
    else:
        resolved, n_pip, p2_miss = _pip_sequential(
            sub_pts, sub_cand, edges_table, sub_need, backend,
            edge_pool=edge_pool)
    with jax.named_scope("geo/compact"):
        if fallback == "first":
            fb = jnp.where(sub_cand[:, 0] >= 0, sub_cand[:, 0], -1)
        elif fallback == "prior":
            fb = prior[idx]
        else:
            raise ValueError(f"unknown fallback policy: {fallback!r}")
        new_val = jnp.where(sub_need,
                            jnp.where(resolved >= 0, resolved, fb),
                            prior[idx])
        assign = scatter_filled(prior, idx, slot_ok, new_val)
        n_need = jnp.sum(need.astype(jnp.int32))
        overflow = n_need - jnp.sum(sub_need.astype(jnp.int32))
    return assign, ResolveStats(n_need=n_need, n_pip=n_pip,
                                overflow=overflow, phase2_miss=p2_miss)
