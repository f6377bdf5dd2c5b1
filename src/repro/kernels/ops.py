"""Public kernel API: natural layouts, padding, backend dispatch.

Backend selection (``REPRO_KERNELS`` env var or explicit ``backend=``):
  * ``pallas``    — compiled Pallas TPU kernels (the deployment target).
  * ``interpret`` — Pallas kernels under ``interpret=True`` (kernel body
                    executed in Python/XLA on CPU; used to validate the
                    kernels off-TPU, incl. in CI).
  * ``ref``       — pure-jnp oracles from ref.py (fast on CPU, and the
                    ground truth the kernels are tested against).
  * ``auto``      — ``pallas`` on TPU, ``ref`` elsewhere (default).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bbox as bbox_kernels
from repro.kernels import cascade as cascade_kernels
from repro.kernels import gather_pip as gather_pip_kernels
from repro.kernels import pip as pip_kernels
from repro.kernels import ref
from repro.kernels import segment as segment_kernels
# re-export: ops.* is the one import surface strategy code and tests
# use for the edge-pool helpers (ops.DEF_BE, ops.build_edge_pool).
# geolint: ignore[unused-import] -- re-export through ops.*
from repro.kernels.gather_pip import (DEF_BE, EdgePool,  # noqa: F401
                                      build_edge_pool)
# (re-exported: ops is the one import surface strategy code uses)

# A padding point guaranteed outside every bbox / polygon we generate.
FAR = 1.0e30


def resolve_backend(backend: str | None = None) -> str:
    b = backend or os.environ.get("REPRO_KERNELS", "auto")
    if b == "auto":
        b = "pallas" if jax.default_backend() == "tpu" else "ref"
    assert b in ("pallas", "interpret", "ref"), b
    return b


def _pad_axis(x: jnp.ndarray, axis: int, mult: int, value) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pip_one(points: jnp.ndarray, edges: jnp.ndarray,
            backend: str | None = None) -> jnp.ndarray:
    """Inside mask of [N, 2] points vs one polygon's [E, 4] edge table."""
    b = resolve_backend(backend)
    if b == "ref":
        return ref.pip_one(points, edges)
    n = points.shape[0]
    bp, be = pip_kernels.DEF_BP, pip_kernels.DEF_BE
    pts = _pad_axis(points.astype(jnp.float32), 0, bp, FAR)
    edges_t = _pad_axis(edges.astype(jnp.float32).T, 1, be, 0.0)
    cross = pip_kernels.crossings_one(pts, edges_t,
                                      interpret=(b == "interpret"))
    return (cross[:n] & 1).astype(jnp.bool_)


def pip_gathered(points: jnp.ndarray, edges: jnp.ndarray,
                 backend: str | None = None) -> jnp.ndarray:
    """Inside mask where each point brings its own [E, 4] edges: [N, E, 4]."""
    b = resolve_backend(backend)
    if b == "ref":
        return ref.pip_gathered(points, edges)
    n = points.shape[0]
    bp, be = pip_kernels.DEF_BP, pip_kernels.DEF_BE
    pts = _pad_axis(points.astype(jnp.float32), 0, bp, FAR)
    edges_t = jnp.swapaxes(edges.astype(jnp.float32), 1, 2)   # [N, 4, E]
    edges_t = _pad_axis(_pad_axis(edges_t, 2, be, 0.0), 0, bp, 0.0)
    cross = pip_kernels.crossings_gathered(pts, edges_t,
                                           interpret=(b == "interpret"))
    return (cross[:n] & 1).astype(jnp.bool_)


def pip_candidates(points: jnp.ndarray, pids: jnp.ndarray, pool: EdgePool,
                   backend: str | None = None) -> jnp.ndarray:
    """Fused gather-PIP: inside mask of [N, 2] points vs their own
    candidate polygon ids [N] (id < 0 = no candidate, never inside).

    The candidate's edge slice is read straight out of ``pool``
    (blocked-CSR; see kernels/gather_pip.py) — no gathered [N, E, 4]
    edge table is ever materialized in HBM.
    """
    return _pip_candidates(points, pids, pool, backend, copies=False)


def pip_candidates_copies(points: jnp.ndarray, pids: jnp.ndarray,
                          pool: EdgePool, backend: str | None = None):
    """``pip_candidates`` plus the kernel's count of HBM block copies:
    returns ``(inside [N] bool, copies [] i32)``.  One copy serves each
    run of rows (in the given order) with the same pool block, so sorted
    ids need fewer.  Kernel backends only."""
    return _pip_candidates(points, pids, pool, backend, copies=True)


def _pip_candidates(points, pids, pool, backend, *, copies: bool):
    b = resolve_backend(backend)
    if copies and b == "ref":
        raise ValueError("the copy count needs a kernel backend")
    if pool.n_poly == 0:               # empty polygon table: nothing matches
        inside = jnp.zeros(points.shape[0], jnp.bool_)
        return (inside, jnp.zeros((), jnp.int32)) if copies else inside
    valid = pids >= 0
    safe = jnp.clip(pids, 0, max(pool.n_poly - 1, 0))
    first = jnp.where(valid, pool.first[safe], 0).astype(jnp.int32)
    nblk = jnp.where(valid, pool.count[safe], 0).astype(jnp.int32)
    if b == "ref":
        cross = ref.crossings_candidates(points, first, nblk, pool.blocks,
                                         pool.max_blocks)
    else:
        cross, n_copies = _crossings_split(
            first, nblk, points.astype(jnp.float32), pool.blocks,
            max_blocks=pool.max_blocks, interpret=(b == "interpret"))
    inside = (cross & 1).astype(jnp.bool_) & valid
    return (inside, n_copies) if copies else inside


@functools.partial(jax.jit, static_argnames=("max_blocks", "interpret"))
def _crossings_split(first, nblk, points, blocks, *, max_blocks: int,
                     interpret: bool):
    """The gather-PIP kernel over any number of rows: padded to whole
    tiles (pad rows have no candidate) and, past
    ``gather_pip.MAX_ROWS``, split into equal calls whose
    scalar-prefetched tables fit SMEM.  The calls run as one ``lax.map``
    so the kernel compiles once.  Returns (crossings [n] i32, HBM block
    copies [] i32)."""
    n = points.shape[0]
    n_calls = -(-n // gather_pip_kernels.MAX_ROWS)
    tp = gather_pip_kernels.tile_rows(-(-n // n_calls))
    per = -(-n // (n_calls * tp)) * tp
    pad = per * n_calls - n
    points = jnp.pad(points, ((0, pad), (0, 0)), constant_values=FAR)
    first = jnp.pad(first, (0, pad))
    nblk = jnp.pad(nblk, (0, pad))

    def call(args):
        f, c, p = args
        return gather_pip_kernels.crossings_candidates(
            f, c, p, blocks, max_blocks=max_blocks, interpret=interpret,
            copies=True)

    if n_calls == 1:
        cross, copies = call((first, nblk, points))
        return cross[:n], jnp.sum(copies)
    cross, copies = jax.lax.map(call, (first.reshape(n_calls, per),
                                       nblk.reshape(n_calls, per),
                                       points.reshape(n_calls, per, 2)))
    return cross.reshape(-1)[:n], jnp.sum(copies)


def assign_cascade(points: jnp.ndarray, quant: jnp.ndarray,
                   cell_lo: jnp.ndarray, cell_hi: jnp.ndarray,
                   cell_val: jnp.ndarray, top_start: jnp.ndarray,
                   cand: jnp.ndarray, bbox: jnp.ndarray, pool: EdgePool, *,
                   max_level: int, gbits: int, search_iters: int,
                   backend: str | None = None):
    """One-pass fused cascade: [N, 2] points -> (bid, flags, nrest,
    nskip), each [N] i32 (kernels/cascade.py has the full encoding).

    The whole quantize -> cell lookup -> bbox filter -> PIP pipeline runs
    in one kernel (or its bit-exact ref oracle); no per-stage HBM
    intermediates.  ``bbox`` is the [P, 4] (xmin, xmax, ymin, ymax)
    table aligned with the pool's polygon ids.  Empty cell/candidate/
    polygon tables are normalized here so both backends see identical
    never-matching sentinels.
    """
    b = resolve_backend(backend)
    if cand.shape[0] == 0 or cand.shape[1] == 0:
        cand = jnp.full((1, max(cand.shape[1], 1)), -1, jnp.int32)
    if cell_lo.shape[0] == 0:
        # One unreachable row (lo > hi never brackets a code).
        cell_lo = jnp.ones((1,), jnp.int32)
        cell_hi = jnp.zeros((1,), jnp.int32)
        cell_val = jnp.zeros((1,), jnp.int32)
    first, count, blocks = pool.first, pool.count, pool.blocks
    if pool.n_poly == 0:
        first = jnp.zeros((1,), jnp.int32)
        count = jnp.zeros((1,), jnp.int32)
        bbox = jnp.array([[1.0, 0.0, 1.0, 0.0]], jnp.float32)  # empty box
    else:
        assert bbox.shape[0] == pool.n_poly, (bbox.shape, pool.n_poly)
    iters = cascade_kernels.effective_iters(cell_lo.shape[0], gbits,
                                            search_iters)
    if b == "ref":
        return ref.assign_cascade(
            points, quant, cell_lo, cell_hi, cell_val, top_start, cand,
            bbox, first, count, blocks, max_level=max_level, gbits=gbits,
            search_iters=iters, max_blocks=pool.max_blocks)
    return cascade_kernels.assign_cascade(
        points, quant, cell_lo, cell_hi, cell_val, top_start, cand, bbox,
        first, count, blocks, max_level=max_level, gbits=gbits,
        search_iters=iters, interpret=(b == "interpret"))


def bbox_mask(points: jnp.ndarray, boxes: jnp.ndarray,
              backend: str | None = None) -> jnp.ndarray:
    """[N, M] int8 membership of points in a shared [M, 4] box table."""
    b = resolve_backend(backend)
    if b == "ref":
        return ref.bbox_mask(points, boxes)
    n, m = points.shape[0], boxes.shape[0]
    bp, bm = bbox_kernels.DEF_BP, bbox_kernels.DEF_BM
    pts = _pad_axis(points.astype(jnp.float32), 0, bp, FAR)
    # Pad with empty boxes (xmin=1 > xmax=0).
    boxes_t = boxes.astype(jnp.float32).T                     # [4, M]
    pad = (-m) % bm
    if pad:
        empty = jnp.tile(jnp.array([[1.0], [0.0], [1.0], [0.0]],
                                   dtype=jnp.float32), (1, pad))
        boxes_t = jnp.concatenate([boxes_t, empty], axis=1)
    out = bbox_kernels.bbox_mask(pts, boxes_t,
                                 interpret=(b == "interpret"))
    return out[:n, :m]


def bbox_mask_gathered(points: jnp.ndarray, boxes: jnp.ndarray,
                       backend: str | None = None) -> jnp.ndarray:
    """[N, C] int8 membership in per-point gathered boxes [N, C, 4].

    All backends lower to the jnp reference: the comparison work is
    bandwidth-bound gather output XLA fuses into its consumers, so a Pallas
    kernel buys nothing here.  The signature still takes ``backend`` so
    callers route every geometry op through this module uniformly.
    """
    resolve_backend(backend)   # validate the override even though unused
    return ref.bbox_mask_gathered(points, boxes)


def bbox_count_select(points: jnp.ndarray, boxes: jnp.ndarray,
                      backend: str | None = None):
    """Fused count+select over per-point gathered boxes [N, C, 4].

    Padded slots must already be empty boxes; C is padded here to a lane
    multiple with empties.  Returns (count [N] i32, sel [N] i32).
    """
    b = resolve_backend(backend)
    if b == "ref":
        return ref.bbox_count_select(points, boxes)
    n, c = points.shape[0], boxes.shape[1]
    bp = bbox_kernels.DEF_BP
    pts = _pad_axis(points.astype(jnp.float32), 0, bp, FAR)
    boxes_t = jnp.swapaxes(boxes.astype(jnp.float32), 1, 2)   # [N, 4, C]
    cpad = (-c) % 128
    if cpad:
        empty = jnp.zeros((boxes_t.shape[0], 4, cpad), jnp.float32)
        empty = empty.at[:, 0, :].set(1.0)                    # xmin=1 > xmax=0
        boxes_t = jnp.concatenate([boxes_t, empty], axis=2)
    boxes_t = _pad_axis(boxes_t, 0, bp, 0.0)
    cnt, sel = bbox_kernels.bbox_count_select(pts, boxes_t,
                                              interpret=(b == "interpret"))
    return cnt[:n], sel[:n]


class SegmentReduce(NamedTuple):
    """Per-segment aggregates of ``segment_reduce`` (all [S]-shaped).
    ``min``/``max`` are only meaningful where ``count > 0`` (empty
    segments carry the +inf/-inf reduction identities)."""

    count: jnp.ndarray                 # i32
    sum: jnp.ndarray                   # f32
    min: jnp.ndarray                   # f32
    max: jnp.ndarray                   # f32


def segment_reduce(ids: jnp.ndarray, values: Optional[jnp.ndarray] = None,
                   *, n_segments: int, backend: str | None = None,
                   bp: int | None = None,
                   bs: int | None = None) -> SegmentReduce:
    """Per-block aggregation of assigned ids (DESIGN.md §16): count /
    sum / min / max of ``values`` grouped by ``ids`` over ``n_segments``
    blocks.  Rows with ids outside [0, n_segments) — the cascade's -1
    "off map" answer included — are ignored in every backend.

    ``values=None`` aggregates a zero column (callers wanting only
    occupancy counts).  The kernel path stable-sorts rows by id first
    (the sort-by-block-id layout kernels/segment.py expects); the ref
    path is the pure-jnp segment-op oracle.  Semantic ground truth is
    ``ref.np_segment_reduce`` (numpy bincount, f64 accumulate).
    """
    b = resolve_backend(backend)
    ids = ids.astype(jnp.int32)
    if values is None:
        values = jnp.zeros(ids.shape, jnp.float32)
    values = values.astype(jnp.float32)
    assert values.shape == ids.shape, (values.shape, ids.shape)
    # Park every invalid row at the scratch segment so all backends see
    # one normalized id range [0, n_segments].
    invalid = (ids < 0) | (ids >= n_segments)
    ids = jnp.where(invalid, n_segments, ids)
    if b == "ref":
        out = ref.segment_reduce(ids, values, n_segments)
    else:
        bp = bp or segment_kernels.DEF_BP
        bs = bs or segment_kernels.DEF_BS
        order = jnp.argsort(ids)           # jax sorts are stable
        ids_s = _pad_axis(ids[order], 0, bp, n_segments)
        vals_s = _pad_axis(values[order], 0, bp, 0.0)
        # Segments padded past the park id so parked/padded rows land in
        # a scratch block that the final slice drops.
        s_pad = ((n_segments + 1 + bs - 1) // bs) * bs
        out = segment_kernels.segment_reduce_sorted(
            ids_s.reshape(-1, bp, 1), vals_s.reshape(-1, bp, 1), s_pad,
            bp=bp, bs=bs, interpret=(b == "interpret"))
        out = tuple(o[:n_segments] for o in out)
    count, total, vmin, vmax = out
    # Normalize empty-segment sentinels once, after any backend, so the
    # three backends are identical by construction even if a backend's
    # reduction identity differs in sign-of-zero or NaN handling.
    empty = count == 0
    return SegmentReduce(
        count.astype(jnp.int32),
        jnp.where(empty, jnp.float32(0.0), total),
        jnp.where(empty, jnp.float32(jnp.inf), vmin),
        jnp.where(empty, jnp.float32(-jnp.inf), vmax))


def segment_counts(ids: jnp.ndarray, *, n_segments: int,
                   backend: str | None = None) -> jnp.ndarray:
    """[S] i32 occupancy counts of assigned ids (invalid ids ignored)."""
    return segment_reduce(ids, None, n_segments=n_segments,
                          backend=backend).count


def assign_aggregate(points: jnp.ndarray, quant: jnp.ndarray,
                     cell_lo: jnp.ndarray, cell_hi: jnp.ndarray,
                     cell_val: jnp.ndarray, top_start: jnp.ndarray,
                     cand: jnp.ndarray, bbox: jnp.ndarray, pool: EdgePool,
                     *, n_segments: int, max_level: int, gbits: int,
                     search_iters: int,
                     values: Optional[jnp.ndarray] = None,
                     backend: str | None = None):
    """Fused assign→aggregate: the one-pass cascade immediately followed
    by the segment reduction, composed device-side so the [N] id vector
    is never materialized back on host — only the [S] per-block
    aggregates (and the cascade's [N] stats words, if the caller keeps
    them) cross the boundary.  Under ``jax.jit`` the two stages compile
    into one XLA computation per backend.

    Returns ``(SegmentReduce, (bid, flags, nrest, nskip))`` — the raw
    cascade outputs ride along for ``onepass_stats`` accounting; callers
    that only fetch the aggregates never pay the [N] transfer.
    """
    bid, flags, nrest, nskip = assign_cascade(
        points, quant, cell_lo, cell_hi, cell_val, top_start, cand, bbox,
        pool, max_level=max_level, gbits=gbits, search_iters=search_iters,
        backend=backend)
    red = segment_reduce(bid, values, n_segments=n_segments,
                         backend=backend)
    return red, (bid, flags, nrest, nskip)


def edges_from_soup_np(verts: np.ndarray) -> np.ndarray:
    """[P, max_v+1, 2] padded rings -> [P, max_v, 4] edge tables (host)."""
    a = verts[:, :-1, :]
    c = verts[:, 1:, :]
    return np.concatenate([a, c], axis=-1)
