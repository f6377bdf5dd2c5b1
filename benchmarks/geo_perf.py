"""Geo-engine perf hillclimb harness: points/sec for every GeoEngine
strategy plus the fast path's stage-level breakdown, on CPU (the
paper-representative cell of §Perf).

    PYTHONPATH=src python -m benchmarks.geo_perf            # full run
    PYTHONPATH=src python -m benchmarks.geo_perf --smoke    # verify-sized

``--smoke`` caps the batch at BENCH_GEO_SMOKE_N (default 20k) and skips
the gbits stage sweep so scripts/verify.sh can afford to append a row on
every run — the bench trajectory accumulates with the test history.

Emits ``results/BENCH_geo.json`` — machine-readable points/sec + accuracy
per strategy — so the bench trajectory accumulates across PRs.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from benchmarks.roofline import DEVICE_PEAKS, geo_roofline
from repro.core.artifact import GeoIndexSet
from repro.core.engine import EngineConfig, GeoEngine
from repro.core.fast import FastIndex, leaf_codes, locate_cells
from repro.core.strategies import onepass_runs
from repro.compile_cache import enable_compile_cache

N_POINTS = int(os.environ.get("BENCH_GEO_N", 1_000_000))
SMOKE_N = int(os.environ.get("BENCH_GEO_SMOKE_N", 20_000))
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_geo.json")
TUNED_INDEX_PATH = os.path.join(os.path.dirname(__file__), "..",
                                "results", "tuned_index")
# Edge-pool block sizes the one-pass sweep tries (the kernel's DMA
# granularity: bigger blocks amortize DMA issue, smaller ones waste less
# on short polygons).  Smoke keeps two candidates so verify stays cheap.
BE_SWEEP = (128, 256, 512)
BE_SWEEP_SMOKE = (128, 256)


def t(fn, *a, r=5):
    out = fn(*a)
    jax.block_until_ready(out)
    ts = []
    for _ in range(r):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _bench_row(eng, pts, bid, repeats):
    """One bench row for a built engine.  One jitted callable serves
    both timing and the row's stats (one compile per strategy); t()
    blocks on the whole pytree, so the timed quantity includes the stats
    scalars — the serving path computes them anyway, and they are
    reductions over work already done.

    GeoStats counters ride in every row (as_dict: n_need / n_pip /
    overflow / phase2_miss / boundary count) so the bench history catches
    silent degradation — a capacity squeeze or a phase-2 miss creep shows
    up even when points/sec holds steady.  Every row also records the
    engine's plan (strategy/mode/fused + reasons) so bench history ties
    numbers to the execution plan that produced them."""
    n = pts.shape[0]
    f = jax.jit(lambda p, e=eng: e.assign(p))
    dt = t(f, pts, r=repeats)
    res = f(pts)
    acc = float(np.mean(np.asarray(res.block) == bid))
    stats = res.stats.as_dict()
    return {"pts_per_sec": n / dt, "wall_ms": dt * 1e3,
            "accuracy": acc, "plan": eng.explain(), **stats}


def _print_row(name, row, tag=""):
    print(f"{name:16s}: {row['wall_ms']:7.1f}ms "
          f"({row['pts_per_sec']/1e6:5.2f}M pts/s) "
          f"acc {row['accuracy']:.4f} | boundary {row['n_boundary']} "
          f"pip {row['n_pip']} overflow {row['overflow']} "
          f"p2miss {row['phase2_miss']}{tag}")


def bench_strategies(census, cov, pts, bid, repeats=5, tuned_iset=None,
                     roof=None):
    """points/sec + accuracy for simple / fast-exact (legacy + fused) /
    fast-onepass / fast-approx / hybrid, all through the GeoEngine
    facade.  ``tuned_iset`` (from ``autotune_onepass``) supplies the
    fast_onepass row's artifact so it runs at the tuned edge-pool block
    size, with the tuning record and roofline fraction in the row."""
    results = {}
    specs = {
        "simple": ("simple", EngineConfig()),
        "fast_exact": ("fast", EngineConfig(mode="exact")),
        "fast_exact_fused": ("fast", EngineConfig(mode="exact",
                                                  fused=True)),
        "fast_approx": ("fast", EngineConfig(mode="approx")),
        "hybrid": ("hybrid", EngineConfig()),
        # The planner's pick for this device/map/batch — its row records
        # the chosen GeoPlan, so the bench history shows when the auto
        # heuristics and the measured winner disagree.
        "auto": ("auto", EngineConfig()),
    }
    for name, (strategy, cfg) in specs.items():
        eng = GeoEngine.build(census, strategy, cfg, covering=cov)
        row = results[name] = _bench_row(eng, pts, bid, repeats)
        _print_row(name, row,
                   f" -> {eng.strategy}" if strategy == "auto" else "")
    if tuned_iset is not None:
        eng = GeoEngine.from_index_set(tuned_iset, "fast_onepass")
        row = _bench_row(eng, pts, bid, repeats)
        row["tuning"] = dict(tuned_iset.tuning)
        if roof is not None:
            row["roofline_fraction"] = roof["roofline_fraction"]
            row["achieved_bw"] = roof["achieved_bw"]
        results["fast_onepass"] = row
        _print_row("fast_onepass", row,
                   f" be={tuned_iset.pool_be()}")
    return results


def autotune_onepass(census, cov, pts, bid, smoke, repeats=3):
    """Roofline-driven tile sweep for the one-pass cascade: try each
    edge-pool block size, race the winner against the strongest
    two-kernel baseline (fast_exact fused), and persist the measurement
    into a ``GeoIndexSet`` manifest (``results/tuned_index``) — the
    record ``core/plan.py`` reads so ``strategy="auto"`` picks the
    measured winner instead of hard-coded thresholds.

    Returns (tuned GeoIndexSet, roofline row for the tuned kernel, None
    where the device has no published peaks)."""
    n = pts.shape[0]
    iset = GeoIndexSet(census=census, covering=cov)
    sweep = BE_SWEEP_SMOKE if smoke else BE_SWEEP
    best = None
    for be in sweep:
        iset.record_tuning({"be": be})   # drops pools -> repack at be
        eng = GeoEngine.from_index_set(iset, "fast_onepass")
        dt = t(jax.jit(lambda p, e=eng: e.assign(p)), pts, r=repeats)
        rate = n / dt
        print(f"autotune be={be:4d}: {dt*1e3:7.1f}ms "
              f"({rate/1e6:5.2f}M pts/s)")
        if best is None or rate > best[1]:
            best = (be, rate)
    be, rate = best
    iset.record_tuning({"be": be})
    eng_fx = GeoEngine.from_index_set(
        iset, "fast", EngineConfig(mode="exact", fused=True))
    dt_fx = t(jax.jit(lambda p, e=eng_fx: e.assign(p)), pts, r=repeats)
    rate_fx = n / dt_fx
    winner = "fast_onepass" if rate >= rate_fx else "fast_exact"
    device_kind = jax.devices()[0].device_kind
    roof = None
    if device_kind in DEVICE_PEAKS:
        eng_best = GeoEngine.from_index_set(iset, "fast_onepass")
        roof = geo_roofline("fast_onepass",
                            lambda p, e=eng_best: e.assign(p).block,
                            (pts,), n, repeats=repeats)
    frac = None if roof is None else float(roof["roofline_fraction"])
    iset.record_tuning({
        "winner": winner, "be": int(be),
        "device_kind": device_kind,
        "pts_per_sec": float(rate),
        "baseline_pts_per_sec": float(rate_fx),
        "roofline_fraction": frac,
        "recorded": time.strftime("%Y-%m-%dT%H:%M:%S"),
    })
    iset.save(TUNED_INDEX_PATH)
    print(f"autotune winner: {winner} (onepass be={be}: "
          f"{rate/1e6:.2f}M pts/s vs fast_exact_fused "
          f"{rate_fx/1e6:.2f}M pts/s; roofline "
          f"{'not measured' if frac is None else f'{frac:.3f}'}) "
          f"-> {TUNED_INDEX_PATH}")
    return iset, roof


def bench_fast_stages(census, cov, pts, bid):
    """The original gbits sweep: stage-level breakdown of the fast path."""
    n = pts.shape[0]
    for gbits in (0, 4, 6):
        idx = FastIndex.from_covering(cov, census, gbits=gbits)
        dt_codes = t(jax.jit(lambda p: leaf_codes(idx, p)), pts)
        codes = leaf_codes(idx, pts)
        dt_locate = t(jax.jit(lambda c: locate_cells(idx, c)), codes)
        for mode in ("approx", "exact"):
            eng = GeoEngine(
                "fast", EngineConfig(mode=mode, cap_boundary=0.25),
                fast_index=idx)
            f = jax.jit(lambda p, e=eng: e.assign(p).block)
            dt_full = t(f, pts)
            acc = float(np.mean(np.asarray(f(pts)) == bid))
            print(f"G{gbits} {mode:6s}: full {dt_full*1e3:7.1f}ms "
                  f"({n/dt_full/1e6:5.2f}M pts/s) | codes "
                  f"{dt_codes*1e3:5.1f}ms locate {dt_locate*1e3:6.1f}ms "
                  f"(iters={idx.search_iters}) | acc {acc:.4f}")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="verify-sized run: small batch, no stage sweep")
    args = ap.parse_args()
    n_points = min(N_POINTS, SMOKE_N) if args.smoke else N_POINTS

    census = common.get_census().census
    cov = common.get_covering(9)
    xy, bid, *_ = common.sample_points(n_points)
    pts = jnp.asarray(xy)
    print(f"n={n_points} points, {len(cov.lo)} cells"
          + (" [smoke]" if args.smoke else ""))

    tuned_iset = roof = None
    if not onepass_runs():
        print("one-pass autotune skipped: its kernel does not compile "
              "for the TPU")
    else:
        tuned_iset, roof = autotune_onepass(census, cov, pts, bid,
                                            smoke=args.smoke,
                                            repeats=3 if args.smoke else 5)
    results = bench_strategies(census, cov, pts, bid,
                               repeats=3 if args.smoke else 5,
                               tuned_iset=tuned_iset, roof=roof)
    if not args.smoke:
        bench_fast_stages(census, cov, pts, bid)

    run = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "n_points": n_points, "n_cells": int(len(cov.lo)),
           "smoke": bool(args.smoke),
           "backend": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind,
           "strategies": results}
    n_runs = common.append_bench_run(run, OUT_PATH)
    print(f"wrote {os.path.normpath(OUT_PATH)} ({n_runs} runs)")


if __name__ == "__main__":
    main()
