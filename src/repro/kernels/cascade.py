"""One-pass fused cascade kernel: quantize -> Morton/cell lookup -> bbox
filter -> point-in-polygon, one Pallas kernel (DESIGN.md §13).

The exact fast path still runs as separately-JIT'd stages: leaf codes,
cell lookup, boundary compaction, and the (fused) gather-PIP each
materialize their intermediates in HBM between XLA computations.  The
paper's fast approach wins precisely because the whole cascade stays in
registers/cache per point — this kernel is the TPU analogue: a point is
loaded once, and interior points ("true hits", the vast majority) finish
without touching HBM again.

Per grid step (one point):

  1. fixed-point quantize + Morton-interleave to a leaf code (scalar bit
     arithmetic, same fp32 ops as ``core.fast.quantize_codes``);
  2. locate the covering cell: top-grid bucket (2*gbits direct bits) then
     a fixed-iteration binary search over the VMEM-resident interval
     starts — identical integer logic to ``core.fast.locate_cells``;
  3. interior cell -> block id, done;  boundary cell -> walk the <= K
     candidate slots in order: a candidate whose bbox (VMEM [P, 4]
     table) strictly excludes the point is rejected without touching its
     edges; otherwise its blocked-CSR edge slice is DMA'd from the HBM
     ``EdgePool`` into a double-buffered VMEM scratch (block b+1 in
     flight while block b is tested) and the crossing-number test runs.
     First matching candidate wins; no match falls back to the slot-0
     centre owner (same policy as ``resolve_candidates(fallback=
     "first")``).

The candidate DMA is *data dependent* (the block range comes from the
in-kernel cell lookup), which a BlockSpec index map cannot express —
index maps run before the body.  Hence the manual ``make_async_copy``
double buffering; the pool stays in ``pl.ANY`` (HBM) and
only the blocks a boundary point actually needs ever cross into VMEM.
Unlike kernels/gather_pip.py, which shares one copy among a run of rows
with the same block, there is no reuse across points, but interior
points start zero copies, so total edge traffic is bounded by boundary
traffic alone.

Outputs (all [N] i32; ``ops.assign_cascade`` is the public dispatch):

  * bid   — block id (-1 = off map / no covering cell / no candidate);
  * flags — bit 0: boundary-cell hit, bit 1: resolved by slot 0
            (the two bits ``core.resolve.onepass_stats`` needs to
            reproduce the two-phase schedule's n_pip accounting);
  * nrest — count of valid candidates in slots 1..K-1 (what phase 2
            *would* have tested had slot 0 missed);
  * nskip — candidate slots rejected by the bbox filter before any edge
            was fetched (observability: DMA avoided).

Scalar reads out of VMEM-resident tables keep the whole cascade in one
kernel; the interpret backend is the validation target off-TPU (tests
assert bit-identity vs the ``kernels.ref`` CSR oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import CompilerParams

# Sentinel cell value for "off extent / no covering cell".  Must equal
# core.fast.OUTSIDE — core imports kernels (never the reverse), so the
# kernel package owns a copy and core asserts equality.
OUTSIDE = -2**30


def part1by1(x):
    """Spread the low 16 bits of ``x`` over even bit positions (works on
    scalars and arrays alike — the kernel uses the scalar form, the ref
    oracle the vector form)."""
    x = x & 0x0000FFFF
    x = (x | (x << 8)) & 0x00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F
    x = (x | (x << 2)) & 0x33333333
    x = (x | (x << 1)) & 0x55555555
    return x


def morton(ix, iy):
    return (part1by1(iy) << 1) | part1by1(ix)


def effective_iters(n_cells: int, gbits: int, search_iters: int) -> int:
    """Binary-search iteration count for the in-kernel cell locate.  With
    a top grid (gbits > 0) the index's recorded per-bucket bound applies;
    without one the search spans the whole table, so the bound is
    log2(n_cells) — mirroring ``locate_cells``'s full searchsorted."""
    if gbits > 0:
        return max(1, int(search_iters))
    return max(1, int(np.ceil(np.log2(max(int(n_cells), 2)))))


def _pip_dma(pool_ref, buf, sems, first, nblk, px, py):
    """Crossing count of scalar point (px, py) vs pool blocks
    ``first .. first+nblk-1``, double-buffered HBM->VMEM.

    Block 0's copy is started before the loop; iteration b waits on its
    own buffer slot, immediately starts block b+1 into the other slot,
    then runs the crossing test on the just-landed block — the DMA for
    the next block overlaps the VPU work on the current one.  nblk == 0
    (interior point / bbox-rejected candidate) is a zero-trip loop: no
    copy is ever issued.
    """
    @pl.when(nblk > 0)
    def _prologue():
        pltpu.make_async_copy(pool_ref.at[first], buf.at[0],
                              sems.at[0]).start()

    def body(b, acc):
        slot = jax.lax.rem(b, 2)
        pltpu.make_async_copy(pool_ref.at[first + b], buf.at[slot],
                              sems.at[slot]).wait()
        nxt = jax.lax.rem(b + 1, 2)

        @pl.when(b + 1 < nblk)
        def _prefetch():
            pltpu.make_async_copy(pool_ref.at[first + b + 1], buf.at[nxt],
                                  sems.at[nxt]).start()

        x1 = buf[slot, 0:1, :]                    # [1, BE]
        y1 = buf[slot, 1:2, :]
        x2 = buf[slot, 2:3, :]
        y2 = buf[slot, 3:4, :]
        straddle = (y1 > py) != (y2 > py)
        lhs = (px - x1) * (y2 - y1)
        rhs = (py - y1) * (x2 - x1)
        cross = straddle & ((lhs < rhs) == (y2 > y1))
        return acc + jnp.sum(cross.astype(jnp.int32))

    return jax.lax.fori_loop(0, nblk, body, jnp.int32(0))


def _cascade_kernel(pts_ref, quant_ref, lo_ref, hi_ref, val_ref, top_ref,
                    cand_ref, bbox_ref, first_ref, count_ref, pool_ref,
                    bid_ref, flags_ref, nrest_ref, nskip_ref, buf, sems, *,
                    max_level, gbits, iters, k, n_cells, n_brows, n_poly):
    px = pts_ref[0, 0]
    py = pts_ref[0, 1]

    # -- stage 1: quantize + Morton (scalar twin of quantize_codes) --------
    span = jnp.float32(1 << max_level)
    fx = (px - quant_ref[0]) * quant_ref[2]
    fy = (py - quant_ref[1]) * quant_ref[3]
    in_ext = (fx >= 0.0) & (fx < span) & (fy >= 0.0) & (fy < span)
    nmax = jnp.int32((1 << max_level) - 1)
    ix = jnp.clip(fx.astype(jnp.int32), 0, nmax)
    iy = jnp.clip(fy.astype(jnp.int32), 0, nmax)
    code = morton(ix, iy)

    # -- stage 2: cell locate (bucket + fixed-iteration binary search) -----
    if gbits > 0:
        shift = 2 * (max_level - gbits)
        bucket = code >> shift
        lo0 = jnp.maximum(top_ref[bucket] - 1, 0)
        hi0 = top_ref[bucket + 1]
    else:
        lo0 = jnp.int32(0)
        hi0 = jnp.int32(n_cells)

    def search(_, lh):
        l, h = lh
        active = l < h
        mid = (l + h) // 2
        go_right = lo_ref[jnp.clip(mid, 0, n_cells - 1)] <= code
        nl = jnp.where(active & go_right, mid + 1, l)
        nh = jnp.where(active & ~go_right, mid, h)
        return nl, nh

    l, _ = jax.lax.fori_loop(0, iters, search, (lo0, hi0))
    cidx = jnp.clip(l - 1, 0, n_cells - 1)
    in_cell = (lo_ref[cidx] <= code) & (code <= hi_ref[cidx]) & in_ext
    v = jnp.where(in_cell, val_ref[cidx], jnp.int32(OUTSIDE))

    # -- stage 3+4: bbox filter + DMA'd PIP over the candidate slots -------
    boundary = (v < 0) & (v > jnp.int32(OUTSIDE))
    brow = jnp.clip(-(v + 1), 0, n_brows - 1)
    best = jnp.int32(-1)
    slot0_hit = boundary & False
    nrest = jnp.int32(0)
    nskip = jnp.int32(0)
    for s in range(k):
        pid = cand_ref[brow, s]
        valid = boundary & (pid >= 0)
        if s > 0:
            nrest = nrest + valid.astype(jnp.int32)
        attempt = valid & (best < 0)        # first match wins: early exit
        safe = jnp.clip(pid, 0, n_poly - 1)
        inb = ((px > bbox_ref[safe, 0]) & (px < bbox_ref[safe, 1])
               & (py > bbox_ref[safe, 2]) & (py < bbox_ref[safe, 3]))
        do = attempt & inb
        nskip = nskip + (attempt & ~inb).astype(jnp.int32)
        nblk = jnp.where(do, count_ref[safe], 0)
        cross = _pip_dma(pool_ref, buf, sems, first_ref[safe], nblk,
                         px, py)
        inside = do & ((cross & 1) == 1)
        best = jnp.where(inside, pid, best)
        if s == 0:
            slot0_hit = inside

    fb0 = cand_ref[brow, 0]
    fallback = jnp.where(fb0 >= 0, fb0, jnp.int32(-1))
    resolved = jnp.where(best >= 0, best, fallback)
    bid = jnp.where(boundary, resolved,
                    jnp.where(v >= 0, v, jnp.int32(-1)))
    bid_ref[0, 0] = bid
    flags_ref[0, 0] = (boundary.astype(jnp.int32)
                       | (slot0_hit.astype(jnp.int32) << 1))
    nrest_ref[0, 0] = nrest
    nskip_ref[0, 0] = nskip


def _whole(shape):
    """Full-array VMEM residency: one block covering the array, revisited
    every grid step (no per-step refetch)."""
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


@functools.partial(jax.jit, static_argnames=("max_level", "gbits",
                                             "search_iters", "interpret"))
def assign_cascade(points, quant, cell_lo, cell_hi, cell_val, top_start,
                   cand, bbox, first, count, blocks, *, max_level: int,
                   gbits: int, search_iters: int, interpret: bool = False):
    """One-pass fused cascade over [N, 2] points (see module docstring).

    Inputs are assumed well-formed (``ops.assign_cascade`` normalizes
    empty tables before dispatch): ``cand`` [B>=1, K>=1] i32, ``bbox``
    [P, 4] f32 aligned with the pool's ``first``/``count`` [P] i32,
    ``blocks`` [NB, 4, BE] f32 with block 0 reserved all-zero.
    ``search_iters`` must already be ``effective_iters``-normalized.
    Returns (bid, flags, nrest, nskip), each [N] i32.
    """
    n = points.shape[0]
    be = blocks.shape[2]
    kernel = functools.partial(
        _cascade_kernel, max_level=max_level, gbits=gbits,
        iters=search_iters, k=cand.shape[1], n_cells=cell_lo.shape[0],
        n_brows=cand.shape[0], n_poly=first.shape[0])
    out = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i: (i, 0)),             # point
            pl.BlockSpec(memory_space=pltpu.SMEM),               # quant
            _whole(cell_lo.shape), _whole(cell_hi.shape),
            _whole(cell_val.shape), _whole(top_start.shape),
            _whole(cand.shape), _whole(bbox.shape),
            _whole(first.shape), _whole(count.shape),
            pl.BlockSpec(memory_space=pl.ANY),                     # pool
        ],
        out_specs=tuple(pl.BlockSpec((1, 1), lambda i: (i, 0))
                        for _ in range(4)),
        out_shape=tuple(jax.ShapeDtypeStruct((n, 1), jnp.int32)
                        for _ in range(4)),
        scratch_shapes=[pltpu.VMEM((2, 4, be), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(points.astype(jnp.float32), quant.astype(jnp.float32),
      cell_lo.astype(jnp.int32), cell_hi.astype(jnp.int32),
      cell_val.astype(jnp.int32), top_start.astype(jnp.int32),
      cand.astype(jnp.int32), bbox.astype(jnp.float32),
      first.astype(jnp.int32), count.astype(jnp.int32),
      blocks.astype(jnp.float32))
    bid, flags, nrest, nskip = out
    return bid[:, 0], flags[:, 0], nrest[:, 0], nskip[:, 0]
