"""Pallas TPU segment-reduce kernel for per-block aggregation
(DESIGN.md §16).

The analytics layer's device primitive: given assigned block ids
(``ops.assign_cascade`` / fast-exact output) and an optional per-point
value column, produce per-block ``count`` / ``sum`` / ``min`` / ``max``
— the occupancy and attribute aggregates the paper's downstream
workloads (crowding density, encounter counting) are built from.  The
id vector never has to leave the device: ``ops.assign_aggregate``
composes the cascade with this kernel so only the [S]-sized aggregate
crosses back to host.

Layout: the caller (``ops.segment_reduce``) stable-sorts rows by block
id, pads rows to a ``bp`` multiple (pad id = the park segment, sliced
off afterwards) and segments to a ``bs`` multiple, then hands the
kernel row tiles as [bp, 1] columns.  Grid is (segment tiles ×
row tiles): each step matches its row tile against its segment tile
with a broadcast-compare one-hot ([bp, bs] in VMEM, a pure VPU
reduction — counts/sums/extrema all reduce over the row axis), and
accumulates into the output block.  The row-tile axis is sequential
("arbitrary") because output blocks are revisited accumulators; the
segment-tile axis is parallel.  Sorting makes almost every (segment
tile, row tile) pair's one-hot all-false — on TPU those steps are
cheap VPU no-ops, and the sequential revisit order makes the f32 sum's
tile association deterministic for a given sorted layout.

Sentinels: empty segments report ``min = +inf`` / ``max = -inf`` —
the same identities ``jax.ops.segment_min``/``max`` use, so the ref
backend agrees bit-for-bit (``ops.segment_reduce`` additionally
normalizes them so every backend is identical by construction).

Bit-identity contract (tested in tests/test_analytics.py): ``count``,
``min`` and ``max`` are order-free and bit-identical across
pallas/interpret/ref and the numpy ``bincount`` oracle
(``ref.np_segment_reduce``); f32 ``sum`` is bit-identical whenever the
values are exactly representable sums (e.g. integer-valued f32 below
2**24 — the occupancy/count workloads), and reduction-order-rounded
otherwise (tested allclose).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import CompilerParams

DEF_BP = 512       # rows per grid step
DEF_BS = 512       # segments per grid step

_INF = float("inf")


def _segment_kernel(ids_ref, val_ref, cnt_ref, sum_ref, min_ref, max_ref,
                    *, bs: int):
    j = pl.program_id(0)               # segment tile (parallel)
    i = pl.program_id(1)               # row tile (sequential accumulate)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        min_ref[...] = jnp.full_like(min_ref, _INF)
        max_ref[...] = jnp.full_like(max_ref, -_INF)

    local = ids_ref[0] - j * bs                          # [bp, 1] i32
    bp = local.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bp, bs), 1)
    onehot = local == iota                               # [bp, bs] bool
    v = val_ref[0]                                       # [bp, 1] f32
    cnt_ref[0] += jnp.sum(onehot.astype(jnp.int32), axis=0, keepdims=True)
    sum_ref[0] += jnp.sum(jnp.where(onehot, v, 0.0), axis=0, keepdims=True)
    min_ref[0] = jnp.minimum(
        min_ref[0],
        jnp.min(jnp.where(onehot, v, _INF), axis=0, keepdims=True))
    max_ref[0] = jnp.maximum(
        max_ref[0],
        jnp.max(jnp.where(onehot, v, -_INF), axis=0, keepdims=True))


@functools.partial(jax.jit,
                   static_argnames=("n_segments", "bp", "bs", "interpret"))
def segment_reduce_sorted(ids: jnp.ndarray, values: jnp.ndarray,
                          n_segments: int, bp: int = DEF_BP,
                          bs: int = DEF_BS, interpret: bool = False):
    """Per-segment (count, sum, min, max) over pre-sorted, pre-padded
    rows.

    Args:
      ids:    [T, bp, 1] i32 — sorted block ids as row-tile columns
              (rows on sublanes), padded with an out-of-range park id
              (>= ceil-padded segment count is fine: parked rows match
              no segment tile).
      values: [T, bp, 1] f32 — value column aligned with ``ids`` (zeros
              when the caller only wants counts).
      n_segments: padded segment count (``bs`` multiple).
    Returns:
      (count [S] i32, sum [S] f32, min [S] f32, max [S] f32) with
      S = n_segments; empty segments are (0, 0.0, +inf, -inf).
    """
    t = ids.shape[0]
    assert ids.shape == values.shape, (ids.shape, values.shape)
    assert n_segments % bs == 0, (n_segments, bs)
    grid = (n_segments // bs, t)
    # Tiles are 3-D so that their last two block dims are whole array
    # dims or (8, 128) multiples, as Mosaic requires: a [1, bp] row
    # block of a [T, bp] array is refused.
    row_spec = pl.BlockSpec((1, ids.shape[1], 1), lambda j, i: (i, 0, 0))
    out_spec = pl.BlockSpec((1, 1, bs), lambda j, i: (j, 0, 0))
    shape = (n_segments // bs, 1, bs)
    cnt, tot, vmin, vmax = pl.pallas_call(
        functools.partial(_segment_kernel, bs=bs),
        grid=grid,
        in_specs=[row_spec, row_spec],
        out_specs=[out_spec, out_spec, out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.int32),
                   jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct(shape, jnp.float32),
                   jax.ShapeDtypeStruct(shape, jnp.float32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids.astype(jnp.int32), values.astype(jnp.float32))
    s = n_segments
    return (cnt.reshape(s), tot.reshape(s), vmin.reshape(s),
            vmax.reshape(s))
