"""Fused gather-PIP Pallas kernel: candidate ids in, crossing counts out.

The exact fast path used to run in two device steps: gather each compacted
point's candidate edge table out of ``[P, E, 4]`` into a ``[R, E, 4]``
buffer in HBM, then hand that buffer to the gathered crossing-number
kernel.  The gather output is touched exactly once, so the round-trip
through HBM is pure bandwidth waste — the paper's fast approach wins
precisely because candidate lookup and the crossing test stay fused
(§fast).  This kernel removes the round-trip: it consumes the per-point
candidate *ids* plus a blocked-CSR edge pool directly, and copies each
point's edge slice, chosen by the scalar-prefetched ids, straight from
the pool into VMEM inside the grid loop.

Data layout (``EdgePool``, built host-side by ``build_edge_pool``):

  * ``blocks [NB, 4, BE]`` f32 — the edge pool.  Every polygon's
    non-degenerate edges are packed struct-of-arrays (x1/y1/x2/y2 on the
    4-axis, edges on the BE-wide lane axis), zero-padded to whole blocks.
    Block 0 is reserved all-zero: zero-length edges produce no crossings,
    so it doubles as the "no candidate" (id < 0) target and the oracle's
    masked-gather target.
  * ``first [P]`` / ``count [P]`` i32 — CSR row pointers in block units:
    polygon ``p`` owns pool blocks ``first[p] .. first[p]+count[p]-1``.

Kernel schedule: grid ``(R / TP, max_blocks)``, one tile of ``TP`` rows
per step (``tile_rows``: 512, or the call's row count rounded up to 8
when smaller, so a served bucket's small call is one tile).  Points and
counts move as ``[TP, 2]`` / ``[TP, 1]`` blocks; the pool stays in HBM
(``pl.ANY``).  Step ``(t, b)`` scans its tile's scalar-prefetched
``(first, nblk)`` rows on the scalar unit and splits them into runs of
equal block ``first + b`` (-1 where ``b >= nblk``): each run with a
block gets one HBM -> VMEM copy into a ``[2, TP, 4, BE]`` double buffer,
and a row that repeats the previous row's block reuses that copy.  The
scan and copies of the next step are started before this step's copies
are awaited, so they overlap this step's arithmetic.  The crossing test
then runs per run over its 8-row groups, ``[8, BE]`` at a time with the
run's edges broadcast over the sublanes and rows outside the run masked
out, the same f32 operations in the same order as ``ref``.  A tile whose
rows all have ``nblk <= b`` (a per-tile max, prefetched) skips scan,
copies and arithmetic.  ``_pip_ids`` sorts rows by candidate id, so runs
are long and the id -1 rows are whole empty tiles at the end.  The
prefetched tables sit in SMEM, which bounds R per call (``MAX_ROWS``).

The trade: a step costs a scalar pass over its rows and a vector pass
over its 8-row groups, in place of a grid step per row, and a copy per
run of equal blocks in place of one per block change.  Unsorted ids stay
correct but copy a block per row and test each 8-row group once per row.
The kernel also counts its HBM copies per tile (``copies=True``;
``ops.pip_candidates_copies``).

``crossings_candidates`` is layout-transposed like the other kernels; use
``ops.pip_candidates`` for the public API (id masking, parity -> bool,
backend dispatch).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import CompilerParams

# Edges per pool block (the lane axis).  128 is the f32 lane minimum; the
# default trades padding waste (small polygons zero-fill one block) against
# grid steps for large polygons (ceil(E / BE) blocks each).
DEF_BE = 256
# The f32 sublane tile: rows are tested in groups of ROWS, and tiles are
# whole groups.
ROWS = 8
# Most rows per grid step.  tile_rows() picks min(TP_MAX, R rounded up
# to ROWS), so a small call runs as one tile.
TP_MAX = 512
# Most points per call.  The scalar-prefetched ``first``/``nblk`` [R] i32
# tables live in SMEM (1 MiB on TPU v5e): 2 x 4 B x 65,536 = 512 KiB
# compiles, 262,144 rows does not.  ops.pip_candidates splits larger
# batches into calls of this size.
MAX_ROWS = 1 << 16


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EdgePool:
    """Blocked-CSR edge pool (see module docstring for the layout)."""

    blocks: Any     # [NB, 4, BE] f32 — block 0 reserved all-zero
    first: Any      # [P] i32 — first pool block of polygon p
    count: Any      # [P] i32 — pool blocks owned by polygon p
    # -- static --
    max_blocks: int = dataclasses.field(metadata=dict(static=True),
                                        default=1)
    be: int = dataclasses.field(metadata=dict(static=True), default=DEF_BE)

    def tree_flatten(self):
        return (self.blocks, self.first, self.count), \
            (self.max_blocks, self.be)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, max_blocks=aux[0], be=aux[1])

    @property
    def n_poly(self) -> int:
        return self.first.shape[0]

    def nbytes(self) -> int:
        return sum(int(np.asarray(a).nbytes)
                   for a in (self.blocks, self.first, self.count))


def build_edge_pool(edges: np.ndarray, be: int = DEF_BE) -> EdgePool:
    """Pack a dense ``[P, E, 4]`` edge table into a blocked-CSR EdgePool.

    Degenerate (zero-length) padding edges are dropped, so raggedness in
    the dense table becomes real memory savings; a polygon with ``e`` live
    edges owns ``ceil(e / be)`` blocks.  Host-side (numpy).
    """
    e = np.asarray(edges, np.float32)
    p = e.shape[0]
    live = ~((e[..., 0] == e[..., 2]) & (e[..., 1] == e[..., 3]))
    n_live = live.sum(axis=1).astype(np.int64) if p else np.zeros(0, np.int64)
    count = np.ceil(n_live / be).astype(np.int32)
    first = np.ones(p, np.int32)                 # block 0 is reserved
    if p:
        first[1:] += np.cumsum(count)[:-1].astype(np.int32)
    nb = 1 + int(count.sum())
    blocks = np.zeros((nb, 4, be), np.float32)
    if p and n_live.sum():
        # Vectorized pack: e[live] is polygon-major, so each live edge's
        # (block, lane) destination follows from its rank within its
        # polygon; destinations are unique, plain fancy assignment works.
        el = e[live]                                        # [total, 4]
        poly_of = np.repeat(np.arange(p), n_live)
        starts = np.concatenate([[0], np.cumsum(n_live)[:-1]])
        pos = np.arange(len(el)) - starts[poly_of]          # rank in poly
        blk = first[poly_of] + pos // be
        blocks[blk, :, pos % be] = el
    return EdgePool(blocks=jnp.asarray(blocks), first=jnp.asarray(first),
                    count=jnp.asarray(count),
                    max_blocks=max(int(count.max()) if p else 1, 1), be=be)


def tile_rows(r: int) -> int:
    """Rows per grid step for a call of ``r`` rows: ``TP_MAX``, or ``r``
    rounded up to the sublane tile when the call is smaller."""
    return min(TP_MAX, -(-r // ROWS) * ROWS)


def _scan_tile(first_ref, nblk_ref, tmax_ref, pool_ref, buf, sems, meta,
               run_start, run_key, t, b, slot, *, tp, nb):
    """Split tile ``t`` at block step ``b`` into runs of rows with equal
    pool block (``first + b``, or -1 where ``b >= nblk``), record each
    run's start row and block in ``run_start`` / ``run_key`` [slot], and
    start one HBM copy per run with a block into ``buf[slot, run]``.
    ``meta[slot]`` gets (runs, copies).  A tile whose rows all have
    ``nblk <= b`` (``tmax``) is skipped whole."""

    @pl.when(tmax_ref[t] > b)
    def _scan():
        base = t * tp

        def row(i, carry):
            prev, n_runs, n_copies = carry
            r = base + i
            key = jnp.where(b < nblk_ref[r], first_ref[r] + b, -1)
            new = key != prev
            copy = new & (key >= 0)
            # Entry n_runs is the next run's: a row that starts no run
            # writes it too, and the next run or the end mark overwrites.
            run_start[slot, n_runs] = i
            run_key[slot, n_runs] = key

            @pl.when(copy)
            def _copy():
                pltpu.make_async_copy(pool_ref.at[jnp.minimum(key, nb - 1)],
                                      buf.at[slot, n_runs],
                                      sems.at[slot]).start()

            return (key, n_runs + new.astype(jnp.int32),
                    n_copies + copy.astype(jnp.int32))

        _, n_runs, n_copies = jax.lax.fori_loop(
            0, tp, row, (jnp.int32(-2), jnp.int32(0), jnp.int32(0)))
        run_start[slot, n_runs] = tp
        meta[slot, 0] = n_runs
        meta[slot, 1] = n_copies

    @pl.when(tmax_ref[t] <= b)
    def _empty():
        meta[slot, 0] = 0
        meta[slot, 1] = 0


def _gather_pip_kernel(first_ref, nblk_ref, tmax_ref, pts_ref, pool_ref,
                       out_ref, copies_ref, buf, sems, meta, run_start,
                       run_key, *, tp, max_blocks, n_steps, nb):
    """Block step ``b`` of tile ``t``: pts [TP, 2] and out [TP, 1] i32 are
    the tile's rows (out accumulates over the block axis of the grid),
    the pool stays in HBM and ``copies`` [n_tiles] (SMEM) counts the HBM
    block copies started for each tile.  The next step's runs are scanned
    and their copies started before this step's copies are awaited, so
    the copies overlap this step's arithmetic."""
    t = pl.program_id(0)
    b = pl.program_id(1)
    step = t * max_blocks + b
    slot = jax.lax.rem(step, 2)
    scan = functools.partial(_scan_tile, first_ref, nblk_ref, tmax_ref,
                             pool_ref, buf, sems, meta, run_start, run_key,
                             tp=tp, nb=nb)

    @pl.when(step == 0)
    def _first():
        scan(0, 0, 0)

    @pl.when(step + 1 < n_steps)
    def _next():
        nxt = step + 1
        scan(nxt // max_blocks, jax.lax.rem(nxt, max_blocks), 1 - slot)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        copies_ref[t] = 0

    n_copies = meta[slot, 1]

    def wait(_, c):
        # Every copy is one [4, BE] block: wait once per copy started.
        pltpu.make_async_copy(pool_ref.at[0], buf.at[slot, 0],
                              sems.at[slot]).wait()
        return c

    jax.lax.fori_loop(0, n_copies, wait, 0)
    copies_ref[t] += n_copies

    def run(i, c):
        @pl.when(run_key[slot, i] >= 0)
        def _acc():
            s = run_start[slot, i]
            e = run_start[slot, i + 1]
            blk = buf.at[slot, i]
            x1 = blk[0:1, :]                          # [1, BE]
            y1 = blk[1:2, :]
            x2 = blk[2:3, :]
            y2 = blk[3:4, :]
            dy = y2 - y1
            dx = x2 - x1
            up = y2 > y1

            def group(g, c2):
                r0 = pl.multiple_of(g * ROWS, ROWS)
                px = pts_ref[pl.ds(r0, ROWS), 0:1]    # [ROWS, 1]
                py = pts_ref[pl.ds(r0, ROWS), 1:2]
                rows = r0 + jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
                straddle = (y1 > py) != (y2 > py)     # [ROWS, BE]
                lhs = (px - x1) * dy
                rhs = (py - y1) * dx
                cross = (straddle & ((lhs < rhs) == up)
                         & (rows >= s) & (rows < e))
                out_ref[pl.ds(r0, ROWS), :] += jnp.sum(
                    cross.astype(jnp.int32), axis=1, keepdims=True)
                return c2

            jax.lax.fori_loop(s // ROWS, (e + ROWS - 1) // ROWS, group, 0)
        return c

    jax.lax.fori_loop(0, meta[slot, 0], run, 0)


@functools.partial(jax.jit,
                   static_argnames=("max_blocks", "interpret", "copies"))
def crossings_candidates(first: jnp.ndarray, nblk: jnp.ndarray,
                         points: jnp.ndarray, blocks: jnp.ndarray,
                         max_blocks: int = 1,
                         interpret: bool = False, *,
                         copies: bool = False):
    """Crossing counts of [R, 2] points vs their own pool edge slices.

    ``first``/``nblk`` [R] i32 are per-point block ranges (already resolved
    from candidate ids by ops.py; nblk == 0 means no candidate).  R must
    be a multiple of ``tile_rows(R)`` and at most ``MAX_ROWS`` (ops.py pads
    and splits).  Returns [R] i32, or with ``copies=True`` also the HBM
    block copies started per tile, [R / tile_rows(R)] i32.
    """
    r = points.shape[0]
    tp = tile_rows(r)
    assert r % tp == 0 and r <= MAX_ROWS, (r, tp)
    n_tiles = r // tp
    nb, _, be = blocks.shape
    first = first.astype(jnp.int32)
    nblk = nblk.astype(jnp.int32)
    tmax = jnp.max(nblk.reshape(n_tiles, tp), axis=1)

    kernel = functools.partial(_gather_pip_kernel, tp=tp,
                               max_blocks=max_blocks,
                               n_steps=n_tiles * max_blocks, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles, max_blocks),
        in_specs=[
            pl.BlockSpec((tp, 2), lambda t, b, *_: (t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((tp, 1), lambda t, b, *_: (t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, tp, 4, be), jnp.float32),   # run blocks
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2, 2), jnp.int32),             # (runs, copies)
            pltpu.SMEM((2, tp + 1), jnp.int32),        # run start rows
            pltpu.SMEM((2, tp), jnp.int32),            # run blocks
        ],
    )
    out, n_copies = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((r, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n_tiles,), jnp.int32)),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(first, nblk, tmax, points.astype(jnp.float32), blocks)
    if copies:
        return out[:, 0], n_copies
    return out[:, 0]
