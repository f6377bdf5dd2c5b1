"""GeoAnalytics benchmark: fused vs unfused per-block aggregation, and
windowed streaming throughput (DESIGN.md §16).

    PYTHONPATH=src python -m benchmarks.analytics_perf            # full
    PYTHONPATH=src python -m benchmarks.analytics_perf --smoke    # verify

Three measurements per run:

* **agg stage** (the headline ``agg_per_sec_*`` pair): per-block
  occupancy aggregation consuming *device-resident* assign outputs —
  the stage fusion actually changes.  *Fused* consumes the jitted
  assign+park program's buffer directly (zero-copy on the CPU backend,
  segment kernel on TPU): no host materialization, no validity
  filtering.  *Unfused* is the naive chain the subsystem replaces:
  ``np.asarray`` the id vector, mask the invalid rows, compact,
  ``np.bincount``.  Both totals are asserted bit-identical before
  either throughput is recorded; the fused ≥ unfused margin is
  structural (fewer passes over the ids), not noise — and on an
  accelerator the unfused side additionally pays a real device→host
  transfer that the CPU backend gets for free.

* **pipeline** (context row, no ratchet): the same two paths end to
  end including the engine assign, which dominates both — recorded so
  the stage numbers can be read against the full-pipeline cost.

* **window**: events/sec through a sliding 4-pane ``WindowedAggregator``
  with the distinct sketch + k-anonymity on, plus snapshot latency.

Appends an ``analytics_*`` row (``"bench": "analytics"``) to
``results/BENCH_geo.json``; ``scripts/check_bench.py`` soft-ratchets
``agg_per_sec_fused`` against trailing history like points/sec.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.analytics import AnalyticsConfig, BlockAggregator, \
    WindowedAggregator
from repro.core.engine import GeoEngine
from repro.compile_cache import enable_compile_cache

OUT_PATH = common.BENCH_GEO_PATH


def bench_agg_stage(agg, engine, batches, repeats: int = 5):
    """(fused_per_sec, unfused_per_sec, equal) over the aggregation
    stage alone: both sides consume pre-computed device-resident assign
    outputs (parked ids for fused, raw ids for unfused).  Interleaved
    repeats, medians, so drift hits both paths alike."""
    parked = [agg.fused_ids(b) for b in batches]
    raw = [engine.assign(b).block for b in batches]
    jax.block_until_ready(parked)
    jax.block_until_ready(raw)
    n_total = sum(len(b) for b in batches)

    def fused_stage():
        total = np.zeros(agg.n_blocks, np.int64)
        for ids in parked:
            total += agg.reduce_counts(ids)
        return total

    def unfused_stage():
        total = np.zeros(agg.n_blocks, np.int64)
        for ids in raw:
            total += agg.counts(np.asarray(ids))
        return total

    equal = bool(np.array_equal(fused_stage(), unfused_stage()))
    inner = max(1, (1 << 21) // n_total)   # ~2M points per timed rep
    ts_f, ts_u = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            fused_stage()
        ts_f.append((time.perf_counter() - t0) / inner)
        t0 = time.perf_counter()
        for _ in range(inner):
            unfused_stage()
        ts_u.append((time.perf_counter() - t0) / inner)
    return (n_total / float(np.median(ts_f)),
            n_total / float(np.median(ts_u)), equal)


def bench_pipeline(agg, engine, batches, repeats: int = 3):
    """(fused_per_sec, unfused_per_sec) end to end — assign included.
    Context only: the assign dominates both sides."""
    n_total = sum(len(b) for b in batches)
    agg.fused_counts(batches[0])           # warm both programs
    np.asarray(engine.assign(batches[0]).block)
    ts_f, ts_u = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        total = np.zeros(agg.n_blocks, np.int64)
        for b in batches:
            total += agg.fused_counts(b)
        ts_f.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        total = np.zeros(agg.n_blocks, np.int64)
        for b in batches:
            total += agg.counts(np.asarray(engine.assign(b).block))
        ts_u.append(time.perf_counter() - t0)
    return (n_total / float(np.median(ts_f)),
            n_total / float(np.median(ts_u)))


def bench_window(bids, n_blocks, batch: int, repeats: int = 3):
    """(events_per_sec, snapshot_ms): stream host ids through a sliding
    4-pane windowed aggregator with the sketch + suppression on, one
    batch per simulated second."""
    rng = np.random.default_rng(5)
    sources = rng.integers(0, 1 << 20, size=len(bids))
    cfg = AnalyticsConfig(window_s=16.0, slide_s=4.0, k_anon=2,
                          sketch_bits=1024, allowed_lateness_s=4.0)
    ts = []
    for _ in range(repeats):
        agg = WindowedAggregator(n_blocks, cfg)
        t0 = time.perf_counter()
        for i in range(0, len(bids), batch):
            agg.observe(float(i // batch), bids[i:i + batch],
                        sources[i:i + batch])
        ts.append(time.perf_counter() - t0)
    events_per_sec = len(bids) / float(np.median(ts))
    snaps = []
    for _ in range(5):
        t0 = time.perf_counter()
        agg.snapshot()
        snaps.append(time.perf_counter() - t0)
    return events_per_sec, float(np.median(snaps)) * 1e3


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="verify-sized run")
    ap.add_argument("--strategy", default="fast")
    args = ap.parse_args()
    batch = 8192 if args.smoke else 32768
    n_batches = 8 if args.smoke else 32
    n_points = batch * n_batches

    census = common.get_census().census
    cov = common.get_covering(9)
    engine = GeoEngine.build(census, args.strategy, covering=cov)
    n_blocks = census.blocks.n_poly
    agg = BlockAggregator.from_engine(engine)
    xy, *_ = common.sample_points(n_points, seed=17)
    batches = [jnp.asarray(xy[i:i + batch])
               for i in range(0, n_points, batch)]
    print(f"{n_points} points / {n_batches} x {batch} batches / "
          f"{n_blocks} blocks" + (" [smoke]" if args.smoke else ""))

    fps, ups, equal = bench_agg_stage(agg, engine, batches)
    print(f"agg stage fused   : {fps / 1e6:7.1f}M agg/s")
    print(f"agg stage unfused : {ups / 1e6:7.1f}M agg/s  "
          f"(fused speedup {fps / ups:.2f}x, bit-identical={equal})")
    if not equal:
        raise SystemExit("FAILED: fused/unfused per-block counts differ")
    pfps, pups = bench_pipeline(agg, engine, batches)
    print(f"pipeline fused    : {pfps / 1e6:7.2f}M pts/s  "
          f"unfused {pups / 1e6:.2f}M pts/s (assign-dominated)")

    bid_host = np.asarray(engine.assign(jnp.asarray(xy)).block)
    eps, snap_ms = bench_window(bid_host, n_blocks, batch=4096)
    print(f"window feed       : {eps / 1e6:7.2f}M events/s  "
          f"snapshot {snap_ms:.2f}ms")

    run = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "bench": "analytics", "smoke": bool(args.smoke),
           "backend": jax.default_backend(),
           "strategy": args.strategy,
           "n_points": n_points, "batch": batch, "n_blocks": n_blocks,
           "agg_per_sec_fused": fps, "agg_per_sec_unfused": ups,
           "fused_speedup": fps / ups, "counts_equal": equal,
           "pipeline_per_sec_fused": pfps,
           "pipeline_per_sec_unfused": pups,
           "window_events_per_sec": eps, "snapshot_ms": snap_ms}
    n_runs = common.append_bench_run(run, OUT_PATH)
    print(f"wrote {os.path.normpath(OUT_PATH)} ({n_runs} runs)")


if __name__ == "__main__":
    main()
