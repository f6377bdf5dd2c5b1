"""Quickstart: map locations onto census blocks with every GeoEngine
strategy — the paper's simple (§III) and fast (§IV) approaches plus the
engine's hybrid mode.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineConfig, GeoEngine
from repro.core.synth import build_synth_census
from repro.compile_cache import enable_compile_cache


def timed_assign(engine, pts):
    res = engine.assign(pts)                  # warm up + compile
    res.block.block_until_ready()
    t0 = time.perf_counter()
    res = engine.assign(pts)
    res.block.block_until_ready()
    return res, time.perf_counter() - t0


def main():
    enable_compile_cache()
    # 1. A synthetic census: 16 states / 128 counties / 3,072 block groups
    #    (same structure as the real data; see core/synth.py).
    print("building synthetic census...")
    sc = build_synth_census(seed=0, n_states=16, counties_per_state=8,
                            blocks_per_county=24)
    census = sc.census
    print(f"  states={census.states.n_poly} counties={census.counties.n_poly}"
          f" blocks={census.blocks.n_poly}")

    # 2. A batch of device locations with known ground truth.
    rng = np.random.default_rng(7)
    xy, bid, cid, sid = sc.sample_points(rng, 100_000)
    pts = jnp.asarray(xy)

    # 3. One facade, four strategy/mode combinations.  The covering is
    #    built once and shared by the cell-index strategies.
    print("building cell covering...")
    covering = None
    for label, strategy, cfg in (
        ("simple      ", "simple",
         EngineConfig(cap_state=0.5, cap_county=0.5, cap_block=0.5)),
        ("fast (exact)", "fast", EngineConfig(mode="exact",
                                              cap_boundary=0.5)),
        ("fast (approx)", "fast", EngineConfig(mode="approx")),
        ("hybrid      ", "hybrid", EngineConfig(cap_boundary=0.5)),
    ):
        engine = GeoEngine.build(census, strategy, cfg, covering=covering)
        covering = covering or engine.covering
        res, dt = timed_assign(engine, pts)
        acc = float(np.mean(np.asarray(res.block) == bid))
        print(f"{label}: {len(xy)/dt/1e6:5.2f}M pts/s, accuracy {acc:.4f},"
              f" {int(res.stats.n_pip)/len(xy):.3f} PIP evals/pt,"
              f" overflow {int(res.stats.overflow)}")

    # 4. Or skip the choice entirely: strategy="auto" asks the planner
    #    (device kind, measured boundary fraction, index capabilities)
    #    and explain() says what it chose and why.
    engine = GeoEngine.build(census, "auto", covering=covering)
    plan = engine.explain()
    res, dt = timed_assign(engine, pts)
    acc = float(np.mean(np.asarray(res.block) == bid))
    print(f"auto -> {plan['strategy']:7s}: {len(xy)/dt/1e6:5.2f}M pts/s, "
          f"accuracy {acc:.4f}")
    for reason in plan["reasons"]:
        print(f"  because: {reason}")


if __name__ == "__main__":
    main()
