"""The open-loop knee: one open-loop window per offered rate, in one
process, each on a fresh server over the same engine.

    python3 bench/tools/sweep.py <config> <traffic> <seconds> <seed> <rate>...

``<config>`` names an entry of ``BENCHMARK.json``'s configs, ``<traffic>``
an open-loop mix under ``bench/mixes/``, whose rate each trial replaces.

Prints one JSON line per rate: shed and failed requests, latency
percentiles, how far the mean latency of the window's last third exceeds
its first third (a growing backlog), and how long the last requests took
to resolve after the window closed.  The knee is the highest rate with no
request shed and no growth.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(config: str, traffic: str, seconds: str, seed: str,
         *rates) -> None:
    import numpy as np

    from benchlib import harness, mapstore, stream
    from repro.compile_cache import enable_compile_cache
    harness.require_devices(1)
    enable_compile_cache()
    manifest = harness.load_json(os.path.join(harness.ROOT,
                                              "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"] if c["name"] == config)
    conf = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
    mix = harness.load_json(os.path.join(harness.BENCH, "mixes",
                                         f"{traffic}.json"))
    dep = mapstore.deployment(conf["map"], conf["engine"])
    engine = harness.build_engine(conf, dep)
    gen = harness.plugin("traffic", mix["generator"])
    secs = float(seconds)
    for rate in rates:
        params = dict(mix["params"], rate_per_s=float(rate))
        cell = harness.Cell(f"{config}.{traffic}", int(seed), secs, False,
                            params, conf, dep, engine)
        run = gen.make(cell)
        t0 = time.perf_counter()
        run.window(secs)
        drain = time.perf_counter() - t0 - secs
        lat = run.lat * 1e3
        third = max(len(lat) // 3, 1)
        ok = lat < stream.WAIT_S * 1e3     # resolved requests
        first, last = lat[:third][ok[:third]], lat[-third:][ok[-third:]]
        growth = float(last.mean() / first.mean()) if len(first) and \
            len(last) else None
        cnt = run.window_counters()
        run.close()
        print(json.dumps({
            "rate": float(rate), "requests": run.attempted,
            "failed": run.failed,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "growth_last_over_first": growth, "drain_s": drain,
            "gen_lag_p95_ms": float(np.percentile(run.lag, 95) * 1e3),
            "points_served": cnt["points_served"],
            "batches": cnt["batches"]}), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
