"""Tracing overhead guard: the observability budget, enforced.

    PYTHONPATH=src python -m benchmarks.trace_overhead            # full
    PYTHONPATH=src python -m benchmarks.trace_overhead --smoke    # verify

DESIGN.md §15 promises that tracing is cheap enough to leave on: the
serve path with **no tracer** and with a **1%-sampled tracer** must both
stay within ``--tol`` (default 3%, env ``TRACE_OVERHEAD_TOL``) of the
untraced baseline's points/sec.  This harness measures all three modes —

  * ``untraced``   — no tracer attached (the baseline);
  * ``tracer_off`` — tracer attached, sample_rate=0 (pays only the
    per-request sampling gate + the ticket stamps);
  * ``sampled_1pct`` — sample_rate=0.01 (the recommended production
    setting: 1 in 100 requests records a full span timeline);

interleaved across repeats with the mode order ROTATED each round (so
both slow drift and position effects — a pass inheriting its
predecessor's deferred work — hit all modes alike).  The verdict is a
**paired** comparison: each traced mode's slowdown is measured against
the SAME round's untraced pass and the median over rounds is gated —
common-mode machine drift cancels within a round, which a best-of or
mean comparison cannot do on a shared CI box (best-of throughput is
still reported per mode as the clean-machine estimate).  A failing
median escalates to up to 3x the configured rounds before the verdict:
noise is zero-mean so more rounds converge the median — extra data can
only exonerate an unlucky mode, never hide a genuinely slow one.  The per-stage histograms in
``ServerMetrics`` are always on and therefore part of *every* mode,
including the baseline: the budget guards what tracing *adds*.

Appends one ``trace_overhead`` row to ``results/BENCH_geo.json`` and
exits non-zero when a traced mode falls outside the budget — wired into
``scripts/verify.sh`` so an accidentally hot span path fails CI, not a
production SLO.
"""
import argparse
import os
import sys
import time

import jax
import numpy as np

from benchmarks import common
from repro.core.engine import EngineConfig, GeoEngine
from repro.obs import Tracer
from repro.serving import GeoServer, ServeConfig
from repro.compile_cache import enable_compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_geo.json")

MODES = ("untraced", "tracer_off", "sampled_1pct")


def build_server(engine, cov, buckets, mode):
    tracer = {"untraced": None,
              "tracer_off": Tracer(sample_rate=0.0),
              "sampled_1pct": Tracer(sample_rate=0.01)}[mode]
    # Cache off: the cache would absorb most requests after the first
    # pass and the residual device time would swamp the tracer's
    # microseconds — overhead is measured on the full serve path.
    server = GeoServer(engine, ServeConfig(buckets=buckets, cache=False),
                       covering=cov, tracer=tracer)
    server.warm()
    return server


def run_pass(server, requests) -> float:
    """One full pass over the stream; returns points/sec."""
    n = sum(len(r) for r in requests)
    t0 = time.perf_counter()
    for req in requests:
        server.submit(req)
    return n / (time.perf_counter() - t0)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="verify-sized: smaller stream, fewer repeats")
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--repeats", type=int, default=None,
                    help="interleaved repeats per mode (default 5 smoke, "
                         "7 full)")
    ap.add_argument("--tol", type=float, default=float(
                        os.environ.get("TRACE_OVERHEAD_TOL", 0.03)),
                    help="max tolerated fractional slowdown vs untraced")
    args = ap.parse_args()
    repeats = args.repeats or (5 if args.smoke else 7)
    n_requests = 128 if args.smoke else 512
    size = 64            # small requests: per-request overhead maximized
    buckets = (256, 1024)

    census = common.get_census().census
    cov = common.get_covering(9)
    rng = np.random.default_rng(args.seed)
    xy, *_ = common.sample_points(n_requests * size, seed=args.seed + 1)
    requests = [xy[rng.integers(0, len(xy), size)].astype(np.float32)
                for _ in range(n_requests)]

    engine = GeoEngine.build(census, "fast", EngineConfig(mode="exact"),
                             covering=cov)
    servers = {m: build_server(engine, cov, buckets, m) for m in MODES}
    for m in MODES:                        # warm pass (JIT + page-in)
        run_pass(servers[m], requests[:16])

    rates = {m: [] for m in MODES}

    def run_round(r):
        for i in range(len(MODES)):        # rotate: position bias cancels
            m = MODES[(r + i) % len(MODES)]
            rates[m].append(run_pass(servers[m], requests))

    def paired_median(m):
        # Median paired slowdown: round r's traced pass vs round r's
        # untraced pass.
        n = len(rates[m])
        paired = sorted(1.0 - rates[m][r] / rates["untraced"][r]
                        for r in range(n))
        return paired[n // 2] if n % 2 else \
            0.5 * (paired[n // 2 - 1] + paired[n // 2])

    traced = ("tracer_off", "sampled_1pct")
    rounds = 0
    for _ in range(repeats):
        run_round(rounds)
        rounds += 1
    # Escalate on failure: pass-to-pass noise on a shared box is
    # zero-mean, so the median converges with more rounds, while a
    # real regression stays put — extra rounds can only exonerate a
    # mode that was unlucky, never hide a mode that is slow.
    while rounds < 3 * repeats and \
            any(paired_median(m) > args.tol for m in traced):
        run_round(rounds)
        rounds += 1

    best = {m: max(rates[m]) for m in MODES}
    base = best["untraced"]
    verdicts = {}
    ok = True
    print(f"untraced        : {base / 1e6:7.3f}M pts/s  (baseline, "
          f"{rounds} rounds)")
    for m in traced:
        slowdown = paired_median(m)
        passed = slowdown <= args.tol
        ok &= passed
        verdicts[m] = {"pts_per_sec": best[m], "slowdown": slowdown,
                       "pass": passed}
        print(f"{m:16s}: {best[m] / 1e6:7.3f}M pts/s  "
              f"paired median overhead {slowdown * 100:+.2f}% "
              f"(budget {args.tol * 100:.0f}%) "
              f"-> {'PASS' if passed else 'FAIL'}")

    run = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "bench": "trace_overhead", "smoke": bool(args.smoke),
           "seed": args.seed, "repeats": repeats, "rounds": rounds,
           "n_requests": n_requests, "request_size": size,
           "tol": args.tol, "backend": jax.default_backend(),
           "untraced_pts_per_sec": base,
           "tracer_off_pts_per_sec": best["tracer_off"],
           "sampled_pts_per_sec": best["sampled_1pct"],
           "tracer_off_slowdown": verdicts["tracer_off"]["slowdown"],
           "sampled_slowdown": verdicts["sampled_1pct"]["slowdown"],
           "pass": bool(ok)}
    n_runs = common.append_bench_run(run, OUT_PATH)
    print(f"wrote {os.path.normpath(OUT_PATH)} ({n_runs} runs)")
    if not ok:
        print("trace overhead budget exceeded", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
