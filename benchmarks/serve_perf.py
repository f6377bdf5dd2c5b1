"""GeoServer throughput/latency harness: a mixed-size, hot-spotted
request stream served through the full serving stack (bucket-ladder
micro-batching + hot-cell cache + metrics), per strategy.

    PYTHONPATH=src python -m benchmarks.serve_perf            # full run
    PYTHONPATH=src python -m benchmarks.serve_perf --smoke    # verify-sized

The stream models serving traffic rather than batch analytics: request
sizes are log-uniform in [1, 4096] (mobile check-ins to bulk uploads) and
a ``--hot`` fraction of requests re-query a small pool of hot locations
(the mContain hot-spot pattern the cache exists for).  Rows record
points/sec, p50/p99 request latency, cache hit rate, batch-fill ratio,
accuracy vs ground truth, and the GeoStats counters (phase2_miss,
overflow, boundary count) so serving-path degradation shows in the bench
history just like the batch path's.

Appends ``serve_*`` rows to ``results/BENCH_geo.json`` alongside the
geo_perf rows (run objects carry ``"bench": "serve"``).
"""
import argparse
import os
import time

import jax
import numpy as np

from benchmarks import common
from repro.core.engine import EngineConfig, GeoEngine
from repro.serving import GeoServer, ServeConfig
from repro.compile_cache import enable_compile_cache

N_POINTS = int(os.environ.get("BENCH_SERVE_N", 500_000))
SMOKE_N = int(os.environ.get("BENCH_SERVE_SMOKE_N", 20_000))
OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_geo.json")

SPECS = {
    "serve_simple": ("simple", EngineConfig()),
    "serve_hybrid": ("hybrid", EngineConfig()),
    "serve_fast_exact_fused": ("fast", EngineConfig(mode="exact",
                                                    fused=True)),
    # Planner-chosen engine behind the same serving stack; its row
    # records the GeoPlan so serve history ties latency to the plan.
    "serve_auto": ("auto", EngineConfig()),
}


def build_stream(n_total: int, hot_frac: float, seed: int = 11):
    """(requests, truths): lists of ([n, 2] f32 points, [n] i32 block
    ids).  Request sizes are log-uniform; ``hot_frac`` of requests draw
    their points from a 256-point hot pool (with replacement).
    ``hot_frac`` is clamped to [0, 0.9]: only non-hot requests consume
    fresh points, so the loop needs a non-hot fraction to terminate.

    ``seed`` drives BOTH the stream-shape rng and (offset, so the two
    streams stay decorrelated) the point sample — one flag pins the
    whole run for apples-to-apples bench comparisons."""
    hot_frac = min(max(hot_frac, 0.0), 0.9)
    rng = np.random.default_rng(seed)
    xy, bid, *_ = common.sample_points(n_total, seed=seed + 2)
    hot_n = min(256, n_total)
    hot_ix = rng.choice(n_total, hot_n, replace=False)
    requests, truths, used = [], [], 0
    while used < n_total:
        size = min(int(np.exp(rng.uniform(0, np.log(4096)))),
                   n_total - used)
        if rng.uniform() < hot_frac:
            ix = hot_ix[rng.integers(0, hot_n, size)]
        else:
            ix = np.arange(used, used + size)
            used += size
        requests.append(xy[ix].astype(np.float32))
        truths.append(bid[ix])
    return requests, truths


def bench_serving(census, cov, requests, truths, buckets,
                  trace_sample=None, trace_out=None):
    """Per-strategy serve run.  ``trace_sample`` attaches a fresh Tracer
    per strategy (the span stream must attribute to one engine) and
    exports ``<trace_out>_<name>.chrome.json`` beside the row."""
    results = {}
    for name, (strategy, ecfg) in SPECS.items():
        tracer = None
        if trace_sample is not None:
            from repro.obs import Tracer
            tracer = Tracer(sample_rate=trace_sample)
        engine = GeoEngine.build(census, strategy, ecfg, covering=cov)
        server = GeoServer(engine, ServeConfig(buckets=buckets),
                           covering=cov, tracer=tracer)
        warm = server.warm()
        t0 = time.perf_counter()
        served = [server.submit(req).block for req in requests]
        wall = time.perf_counter() - t0

        n = sum(len(r) for r in requests)
        acc = float(np.mean(np.concatenate(served)
                            == np.concatenate(truths)))
        snap = server.snapshot()
        lat, c, d = snap["latency_ms"], snap["counters"], snap["derived"]
        results[name] = {
            "pts_per_sec": n / wall, "wall_ms": wall * 1e3,
            "n_requests": len(requests), "accuracy": acc,
            "plan": engine.explain(),
            "p50_ms": lat["p50"], "p99_ms": lat["p99"],
            "cache_hit_rate": d["cache_hit_rate"],
            "batch_fill_ratio": d["batch_fill_ratio"],
            "n_boundary": c.get("geo_n_boundary", 0),
            "n_pip": c.get("geo_n_pip", 0),
            "overflow": c.get("geo_overflow", 0),
            "phase2_miss": c.get("geo_phase2_miss", 0),
            "warm_s": sum(warm.values()),
            **common.stage_breakdown(snap),
        }
        if tracer is not None and trace_out is not None:
            os.makedirs(os.path.dirname(os.path.abspath(trace_out)),
                        exist_ok=True)
            n_ev = tracer.export_chrome(f"{trace_out}_{name}.chrome.json")
            print(f"  trace: {n_ev} chrome events -> "
                  f"{trace_out}_{name}.chrome.json")
        print(f"{name:24s}: {n / wall / 1e6:5.2f}M pts/s "
              f"p50 {lat['p50']:6.2f}ms p99 {lat['p99']:7.2f}ms "
              f"hit {d['cache_hit_rate']:.2f} "
              f"fill {d['batch_fill_ratio']:.2f} acc {acc:.4f} "
              f"p2miss {c.get('geo_phase2_miss', 0)}")
    return results


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="verify-sized run: small stream, small buckets")
    ap.add_argument("--hot", type=float, default=0.3,
                    help="fraction of requests hitting the hot pool")
    ap.add_argument("--seed", type=int, default=11,
                    help="rng seed for the request stream + point sample")
    ap.add_argument("--trace", action="store_true",
                    help="attach a per-strategy Tracer; exports Chrome "
                         "traces beside the BENCH row")
    ap.add_argument("--trace-sample", type=float, default=0.05,
                    help="head-sampling rate for --trace")
    ap.add_argument("--trace-out", default=os.path.join(
                        os.path.dirname(OUT_PATH), "trace_serve"),
                    help="output prefix for --trace exports")
    args = ap.parse_args()
    n_total = SMOKE_N if args.smoke else N_POINTS
    buckets = (256, 1024, 4096) if args.smoke else (256, 1024, 4096, 16384)

    census = common.get_census().census
    cov = common.get_covering(9)
    requests, truths = build_stream(n_total, args.hot, seed=args.seed)
    print(f"{len(requests)} requests / "
          f"{sum(len(r) for r in requests)} points, hot={args.hot}"
          + (" [smoke]" if args.smoke else ""))

    results = bench_serving(
        census, cov, requests, truths, buckets,
        trace_sample=args.trace_sample if args.trace else None,
        trace_out=args.trace_out if args.trace else None)

    run = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"), "bench": "serve",
           "n_points": int(sum(len(r) for r in requests)),
           "n_requests": len(requests), "hot_frac": args.hot,
           "seed": args.seed, "trace": bool(args.trace),
           "smoke": bool(args.smoke), "backend": jax.default_backend(),
           "strategies": results}
    n_runs = common.append_bench_run(run, OUT_PATH)
    print(f"wrote {os.path.normpath(OUT_PATH)} ({n_runs} runs)")


if __name__ == "__main__":
    main()
