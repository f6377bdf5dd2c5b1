"""Sustained-load SLO harness for the concurrent serving front-end.

    PYTHONPATH=src python -m benchmarks.load_perf            # full search
    PYTHONPATH=src python -m benchmarks.load_perf --smoke    # verify-sized

Where serve_perf measures the serving stack one request at a time, this
harness asks the production question: **what offered load can the
AsyncGeoServer sustain while still meeting a p99 latency SLO?**  It is
the ratcheting throughput-under-SLO metric the ROADMAP's async-serving
item calls for.

Two generator modes, both with hot-spot key skew (``--hot`` fraction of
requests re-query a small hot pool — the mContain pattern):

  * **open loop** (the SLO measurement): request arrivals follow a
    Poisson process (``--arrival poisson``) or a bursty on/off process
    (``--arrival bursty``: Poisson bursts of ``BURST`` back-to-back
    arrivals) at a target QPS, submitted via ``submit_async`` without
    waiting — so a slow server cannot slow the generator down, and
    latency is measured from the *scheduled* arrival (no coordinated
    omission).  Overload sheds (``policy="shed"``) rather than queueing
    without bound; the shed rate is part of the SLO verdict.
  * **closed loop** (context row): ``--clients`` workers in a
    submit-wait loop — the classic saturation throughput, reported
    alongside so the open-loop number has a ceiling to compare against.

``find_qps_at_slo`` binary-searches the highest QPS whose trial meets
``p99 <= --slo-ms`` and ``shed_rate <= --max-shed``, then appends one
``serve_slo`` row (qps_at_slo, p50/p99, shed rate, cache hit rate,
replica count, arrival mode) to ``results/BENCH_geo.json``;
``scripts/check_bench.py`` ratchets on ``qps_at_slo``.

All RNGs seed from ``--seed`` so the request stream is reproducible;
wall-clock jitter is what the soft ratchet's trailing median absorbs.
"""
import argparse
import os
import threading
import time

import jax
import numpy as np

from benchmarks import common
from repro.core.engine import EngineConfig, GeoEngine
from repro.serving import (AsyncGeoServer, FrontendConfig, QueueFull,
                           ServeConfig)
from repro.compile_cache import enable_compile_cache

OUT_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                        "BENCH_geo.json")
BURST = 8                        # arrivals per burst in --arrival bursty


def build_requests(n_requests: int, size: int, hot_frac: float,
                   seed: int):
    """``n_requests`` request arrays of ``size`` points each; a
    ``hot_frac`` fraction draw from a 256-point hot pool (cacheable
    traffic), the rest from the full sample (cold tail)."""
    rng = np.random.default_rng(seed)
    pool_n = max(n_requests * size // 4, 4096)
    xy, *_ = common.sample_points(pool_n, seed=seed + 1)
    hot = xy[rng.choice(pool_n, min(256, pool_n), replace=False)]
    reqs = []
    for _ in range(n_requests):
        if rng.uniform() < hot_frac:
            reqs.append(hot[rng.integers(0, len(hot), size)]
                        .astype(np.float32))
        else:
            reqs.append(xy[rng.integers(0, pool_n, size)]
                        .astype(np.float32))
    return reqs


def arrival_offsets(qps: float, duration_s: float, rng,
                    arrival: str) -> np.ndarray:
    """Sorted arrival times in [0, duration_s) at mean rate ``qps``."""
    n_max = int(qps * duration_s * 3) + 32
    if arrival == "poisson":
        t = np.cumsum(rng.exponential(1.0 / qps, size=n_max))
    elif arrival == "bursty":
        # Bursts arrive Poisson at qps/BURST; each contributes BURST
        # back-to-back arrivals (0.1 ms apart) — the worst case for the
        # batcher's coalescing and the deadline clock.
        starts = np.cumsum(rng.exponential(BURST / qps,
                                           size=n_max // BURST + 1))
        t = (starts[:, None] + np.arange(BURST) * 1e-4).ravel()
    else:
        raise ValueError(f"unknown arrival process {arrival!r}")
    return t[t < duration_s]


def open_loop_trial(server: AsyncGeoServer, requests, qps: float,
                    duration_s: float, rng, arrival: str) -> dict:
    """Offer ``qps`` for ``duration_s``; returns latency percentiles
    (measured from the scheduled arrival), achieved/offered QPS, and the
    shed rate."""
    offsets = arrival_offsets(qps, duration_s, rng, arrival)
    lat, shed, lock = [], [0], threading.Lock()

    def on_done(sched_abs, fut):
        done = time.perf_counter()
        with lock:
            if isinstance(fut.exception(), QueueFull):
                shed[0] += 1
            else:
                lat.append(done - sched_abs)

    t0 = time.perf_counter()
    for i, off in enumerate(offsets):
        now = time.perf_counter() - t0
        if off > now:
            time.sleep(off - now)
        sched_abs = t0 + off
        try:
            fut = server.submit_async(requests[i % len(requests)])
        except QueueFull:                   # shed can surface either way
            with lock:
                shed[0] += 1
            continue
        fut.add_done_callback(
            lambda f, s=sched_abs: on_done(s, f))
    server.drain(timeout=30.0)
    wall = time.perf_counter() - t0
    n = len(offsets)
    with lock:
        samples = np.asarray(lat) * 1e3
        n_shed = shed[0]
    if len(samples) == 0:
        samples = np.asarray([float("inf")])
    return {"offered_qps": n / duration_s,
            "achieved_qps": len(samples) / wall,
            "p50_ms": float(np.percentile(samples, 50)),
            "p99_ms": float(np.percentile(samples, 99)),
            "shed_rate": n_shed / n if n else 0.0,
            "n_requests": n}


def closed_loop_trial(server: AsyncGeoServer, requests, n_clients: int,
                      duration_s: float) -> dict:
    """``n_clients`` submit-wait workers for ``duration_s`` — saturation
    throughput and its latency, the open-loop search's ceiling."""
    stop = time.perf_counter() + duration_s
    counts = [0] * n_clients
    lats: list[list] = [[] for _ in range(n_clients)]

    def client(ix):
        k = ix
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            try:
                server.submit(requests[k % len(requests)], timeout=30)
            except QueueFull:
                continue
            finally:
                k += n_clients
            lats[ix].append(time.perf_counter() - t0)
            counts[ix] += 1

    threads = [threading.Thread(target=client, args=(ix,), daemon=True)
               for ix in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(duration_s + 30)
    wall = time.perf_counter() - t0
    samples = np.asarray([l for ls in lats for l in ls]) * 1e3
    if len(samples) == 0:
        samples = np.asarray([float("inf")])
    return {"qps": sum(counts) / wall,
            "p50_ms": float(np.percentile(samples, 50)),
            "p99_ms": float(np.percentile(samples, 99)),
            "n_requests": int(sum(counts))}


def find_qps_at_slo(server: AsyncGeoServer, requests, slo_ms: float,
                    max_shed: float, lo: float, hi: float, iters: int,
                    trial_s: float, rng, arrival: str):
    """Binary-search (geometric midpoint) the max sustained QPS whose
    open-loop trial meets the SLO; returns (qps_at_slo, trial metrics at
    that QPS).  ``lo`` must pass — if even ``lo`` misses the SLO, the
    row records qps_at_slo=0 with the failing trial (an honest floor,
    and the ratchet will scream)."""
    best_qps, best = 0.0, None
    m = open_loop_trial(server, requests, lo, trial_s, rng, arrival)
    if m["p99_ms"] <= slo_ms and m["shed_rate"] <= max_shed:
        best_qps, best = lo, m
    else:
        return 0.0, m
    for _ in range(iters):
        mid = (lo * hi) ** 0.5
        m = open_loop_trial(server, requests, mid, trial_s, rng, arrival)
        ok = m["p99_ms"] <= slo_ms and m["shed_rate"] <= max_shed
        print(f"  trial {mid:8.1f} qps: p99 {m['p99_ms']:7.2f}ms "
              f"shed {m['shed_rate']:.3f} -> {'PASS' if ok else 'FAIL'}")
        if ok:
            lo = mid
            if mid > best_qps:
                best_qps, best = mid, m
        else:
            hi = mid
    return best_qps, best


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="verify-sized: short trials, few search iters")
    ap.add_argument("--seed", type=int, default=17,
                    help="seeds every RNG (stream content + arrivals)")
    ap.add_argument("--arrival", choices=("poisson", "bursty"),
                    default="poisson")
    ap.add_argument("--hot", type=float, default=0.5,
                    help="fraction of requests hitting the hot pool")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="p99 latency SLO (default: 100 smoke, 50 full)")
    ap.add_argument("--max-shed", type=float, default=0.01,
                    help="max tolerated shed rate under SLO")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4,
                    help="closed-loop worker count")
    ap.add_argument("--request-size", type=int, default=32)
    ap.add_argument("--trace", action="store_true",
                    help="attach a Tracer and export Chrome-trace + span "
                         "dumps beside the BENCH row (DESIGN.md §15)")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="head-sampling rate for --trace (default 1.0 — "
                         "the smoke validates complete timelines; use "
                         "~0.01 under real load)")
    ap.add_argument("--trace-out", default=os.path.join(
                        os.path.dirname(OUT_PATH), "trace_load"),
                    help="output prefix: writes <prefix>.chrome.json and "
                         "<prefix>.spans.json")
    args = ap.parse_args()

    slo_ms = args.slo_ms if args.slo_ms is not None \
        else (100.0 if args.smoke else 50.0)
    trial_s = 0.6 if args.smoke else 3.0
    iters = 3 if args.smoke else 7
    n_requests = 64 if args.smoke else 512
    lo, hi = (20.0, 2000.0) if args.smoke else (50.0, 20000.0)

    census = common.get_census().census
    cov = common.get_covering(9)
    rng = np.random.default_rng(args.seed)
    requests = build_requests(n_requests, args.request_size, args.hot,
                              args.seed)

    engine = GeoEngine.build(census, "fast", EngineConfig(mode="exact"),
                             covering=cov)
    scfg = ServeConfig(buckets=(256, 1024, 4096), policy="shed",
                       max_queue_points=1 << 15, max_delay_ms=2.0)
    fcfg = FrontendConfig(n_replicas=args.replicas, n_submitters=4)
    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(sample_rate=args.trace_sample)
    with AsyncGeoServer(engine, scfg, covering=cov,
                        frontend=fcfg, tracer=tracer) as server:
        server.warm()
        # Prime the hot-cell cache so the searched steady state is the
        # warmed one (cold-cache trials would understate sustained QPS).
        for req in requests[:16]:
            server.submit(req, timeout=30)

        closed = closed_loop_trial(server, requests, args.clients,
                                   trial_s)
        print(f"closed loop ({args.clients} clients): "
              f"{closed['qps']:8.1f} qps p99 {closed['p99_ms']:.2f}ms")

        qps_at_slo, at = find_qps_at_slo(
            server, requests, slo_ms, args.max_shed, lo, hi, iters,
            trial_s, rng, args.arrival)
        snap = server.snapshot()

    hit_rate = snap["derived"]["cache_hit_rate"]
    breakdown = common.stage_breakdown(snap)
    print(f"qps_at_slo (p99<={slo_ms}ms, shed<={args.max_shed}): "
          f"{qps_at_slo:8.1f} qps "
          f"(p50 {at['p50_ms']:.2f}ms p99 {at['p99_ms']:.2f}ms "
          f"shed {at['shed_rate']:.3f} hit {hit_rate:.2f})")
    def _ms(v):
        return "n/a" if v is None else f"{v:.3f}"
    print(f"stage p99 (ms): queue_wait "
          f"{_ms(breakdown['queue_wait_p99_ms'])} "
          f"host {_ms(breakdown['host_p99_ms'])} "
          f"device {_ms(breakdown['device_p99_ms'])}")
    if tracer is not None:
        os.makedirs(os.path.dirname(os.path.abspath(args.trace_out)),
                    exist_ok=True)
        chrome_path = args.trace_out + ".chrome.json"
        n_ev = tracer.export_chrome(chrome_path)
        n_sp = tracer.export_spans(args.trace_out + ".spans.json")
        st = tracer.stats()
        print(f"trace: {n_sp} spans ({n_ev} chrome events, "
              f"{st['sampled']}/{st['started']} requests sampled, "
              f"{st['dropped']} dropped) -> {os.path.normpath(chrome_path)}")

    run = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"), "bench": "load",
           "kind": "serve_slo", "smoke": bool(args.smoke),
           "seed": args.seed, "arrival": args.arrival,
           "hot_frac": args.hot, "request_size": args.request_size,
           "replicas": args.replicas, "slo_ms": slo_ms,
           "max_shed": args.max_shed, "trial_s": trial_s,
           "backend": jax.default_backend(),
           "qps_at_slo": qps_at_slo,
           "points_per_sec_at_slo": qps_at_slo * args.request_size,
           "p50_ms": at["p50_ms"], "p99_ms": at["p99_ms"],
           "shed_rate": at["shed_rate"], "cache_hit_rate": hit_rate,
           "closed_loop_qps": closed["qps"],
           "closed_loop_p99_ms": closed["p99_ms"],
           "n_clients": args.clients, "trace": bool(args.trace),
           **breakdown}
    n_runs = common.append_bench_run(run, OUT_PATH)
    print(f"wrote {os.path.normpath(OUT_PATH)} ({n_runs} runs)")


if __name__ == "__main__":
    main()
