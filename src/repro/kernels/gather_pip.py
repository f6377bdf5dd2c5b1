"""Fused gather-PIP Pallas kernel: candidate ids in, crossing counts out.

The exact fast path used to run in two device steps: gather each compacted
point's candidate edge table out of ``[P, E, 4]`` into a ``[R, E, 4]``
buffer in HBM, then hand that buffer to the gathered crossing-number
kernel.  The gather output is touched exactly once, so the round-trip
through HBM is pure bandwidth waste — the paper's fast approach wins
precisely because candidate lookup and the crossing test stay fused
(§fast).  This kernel removes the round-trip: it consumes the per-point
candidate *ids* plus a blocked-CSR edge pool directly, and the BlockSpec
index map (driven by scalar-prefetched ids) DMAs each point's edge slice
straight from the pool into VMEM inside the grid loop.

Data layout (``EdgePool``, built host-side by ``build_edge_pool``):

  * ``blocks [NB, 4, BE]`` f32 — the edge pool.  Every polygon's
    non-degenerate edges are packed struct-of-arrays (x1/y1/x2/y2 on the
    4-axis, edges on the BE-wide lane axis), zero-padded to whole blocks.
    Block 0 is reserved all-zero: zero-length edges produce no crossings,
    so it doubles as the "no candidate" (id < 0) target and the oracle's
    masked-gather target.
  * ``first [P]`` / ``count [P]`` i32 — CSR row pointers in block units:
    polygon ``p`` owns pool blocks ``first[p] .. first[p]+count[p]-1``.

Kernel schedule: grid ``(R, max_blocks)``, one point per grid row.  The
point and output blocks are the ``ROWS``-row sublane group holding row
``r`` (revisited by its ROWS consecutive grid rows); the step reads and
writes only row ``r % ROWS``.  The
scalar-prefetched ``(first, nblk)`` tables are available before the body
runs, so the pool's index map picks block ``first[r] + b`` (clamped to the
last owned block when ``b >= nblk[r]``; the ``@pl.when`` guard keeps the
over-range steps from accumulating).  Pallas double-buffers the block DMA
across grid steps and skips the fetch entirely when consecutive steps map
to the same block — candidate ids sorted (or merely spatially correlated,
as compacted boundary buffers are) amortize to near-zero edge traffic.
The prefetched tables sit in SMEM, which bounds R per call
(``MAX_ROWS``).

The trade: one point per step uses 1 of 8 sublanes, but the op is
bandwidth-bound — eliminating the HBM materialization beats lane
utilization, and the BE-wide lane axis keeps the VPU fed per step.

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
# Point / output rows per block: the f32 sublane tile.  A grid step
# still tests one point; it reads its row out of the ROWS-row block.
ROWS = 8
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


def _gather_pip_kernel(first_ref, nblk_ref, pts_ref, blk_ref, out_ref):
    """One point vs one prefetched edge block: pts [ROWS, 2] and out
    [ROWS, 1] i32 hold the point's sublane group (Mosaic tiles the
    second-minor axis by 8, so a block cannot be one row); the step
    reads and accumulates row ``r % ROWS`` across the block axis of the
    grid.  blk is [1, 4, BE]."""
    r = pl.program_id(0)
    b = pl.program_id(1)
    row = pl.ds(r % ROWS, 1)

    @pl.when(b == 0)
    def _init():
        out_ref[row, :] = jnp.zeros((1, 1), jnp.int32)

    @pl.when(b < nblk_ref[r])
    def _acc():
        px = pts_ref[row, 0:1]                # [1, 1]
        py = pts_ref[row, 1:2]
        x1 = blk_ref[0, 0:1, :]               # [1, BE]
        y1 = blk_ref[0, 1:2, :]
        x2 = blk_ref[0, 2:3, :]
        y2 = blk_ref[0, 3:4, :]
        straddle = (y1 > py) != (y2 > py)
        lhs = (px - x1) * (y2 - y1)
        rhs = (py - y1) * (x2 - x1)
        cross = straddle & ((lhs < rhs) == (y2 > y1))
        out_ref[row, :] += jnp.sum(cross.astype(jnp.int32), axis=1,
                                   keepdims=True)


@functools.partial(jax.jit, static_argnames=("max_blocks", "interpret"))
def crossings_candidates(first: jnp.ndarray, nblk: jnp.ndarray,
                         points: jnp.ndarray, blocks: jnp.ndarray,
                         max_blocks: int = 1,
                         interpret: bool = False) -> jnp.ndarray:
    """Crossing counts of [R, 2] points vs their own pool edge slices.

    ``first``/``nblk`` [R] i32 are per-point block ranges (already resolved
    from candidate ids by ops.py; nblk == 0 means no candidate).  R must
    be a multiple of ``ROWS`` and at most ``MAX_ROWS`` (ops.py pads and
    splits).  Returns [R] i32.
    """
    r = points.shape[0]
    assert r % ROWS == 0 and r <= MAX_ROWS, r
    nb = blocks.shape[0]

    def blk_map(i, b, first_ref, nblk_ref):
        # Clamp over-range steps onto the last owned (or reserved) block:
        # the revisit costs no DMA and the @pl.when guard discards it.
        last = first_ref[i] + jnp.maximum(nblk_ref[i] - 1, 0)
        blk = jnp.where(b < nblk_ref[i], first_ref[i] + b, last)
        return (jnp.clip(blk, 0, nb - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r, max_blocks),
        in_specs=[
            pl.BlockSpec((ROWS, 2), lambda i, b, *_: (i // ROWS, 0)),
            pl.BlockSpec((1, 4, blocks.shape[2]), blk_map),
        ],
        out_specs=pl.BlockSpec((ROWS, 1), lambda i, b, *_: (i // ROWS, 0)),
    )
    out = pl.pallas_call(
        _gather_pip_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(first.astype(jnp.int32), nblk.astype(jnp.int32),
      points.astype(jnp.float32), blocks)
    return out[:, 0]
