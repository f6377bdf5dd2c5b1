"""Shared benchmark helpers: cached synthetic census + covering, timing,
and the BENCH_geo.json run-trajectory appender."""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import time

import numpy as np

CACHE = os.path.join(os.path.dirname(__file__), "..", "results", "cache")
BENCH_GEO_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                              "BENCH_geo.json")


def append_bench_run(run: dict, out_path: str = BENCH_GEO_PATH) -> int:
    """Append one run object to the bench trajectory file (shared by
    geo_perf and serve_perf so successive rows stay comparable); returns
    the new run count.  A corrupt/absent file restarts the trajectory."""
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    runs = []
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                runs = json.load(f).get("runs", [])
        except (json.JSONDecodeError, AttributeError):
            runs = []
    runs.append(run)
    with open(out_path, "w") as f:
        json.dump({"runs": runs}, f, indent=2)
    return len(runs)

# Benchmark-scale map: 16 states / 128 counties / 3,072 block groups.
SCALE = dict(seed=0, n_states=16, counties_per_state=8, blocks_per_county=24)


# Source files whose code decides what the cached pickles hold.
_BUILDERS = ("synth.py", "geometry.py", "cells.py")


def _cache_path(stem: str, **params) -> str:
    """Cache file keyed by the build parameters and a hash of the source
    that builds it, so a stale pickle (other parameters, older code) is
    never picked up: it simply has another name."""
    core = os.path.join(os.path.dirname(__file__), "..", "src", "repro",
                        "core")
    h = hashlib.sha256(json.dumps(params, sort_keys=True).encode())
    for name in _BUILDERS:
        with open(os.path.join(core, name), "rb") as f:
            h.update(f.read())
    return os.path.join(CACHE, f"{stem}_{h.hexdigest()[:16]}.pkl")


def _cached(path: str, build):
    os.makedirs(CACHE, exist_ok=True)
    if os.path.exists(path):
        with open(path, "rb") as f:
            return pickle.load(f)
    obj = build()
    with open(path, "wb") as f:
        pickle.dump(obj, f)
    return obj


def get_census():
    from repro.core.synth import build_synth_census
    return _cached(_cache_path("census", **SCALE),
                   lambda: build_synth_census(**SCALE))


def get_covering(max_level: int = 9):
    from repro.core.cells import build_cell_covering
    return _cached(
        _cache_path("covering", max_level=max_level, **SCALE),
        lambda: build_cell_covering(get_census().census,
                                    max_level=max_level))


def sample_points(n: int, seed: int = 7):
    return get_census().sample_points(np.random.default_rng(seed), n)


def timeit(fn, *args, repeats: int = 3):
    """Median wall time of fn(*args) after one warm-up (compile) call."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), out


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")


def stage_breakdown(snap: dict) -> dict:
    """Per-stage p50/p99 latency columns (ms) from a ServerMetrics
    snapshot's ``stages`` block — the attributed-latency columns the
    serve_slo/serve_* bench rows carry (DESIGN.md §15).  Missing stages
    (e.g. a cache-less run never observed cache stages) report None so
    rows stay schema-stable."""
    stages = snap.get("stages", {})
    out = {}
    for stage, col in (("queue_wait", "queue_wait"),
                       ("host_prepare", "host"),
                       ("device_assign", "device")):
        s = stages.get(stage) or {}
        out[f"{col}_p50_ms"] = s.get("p50")
        out[f"{col}_p99_ms"] = s.get("p99")
    return out
