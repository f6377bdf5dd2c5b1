"""Distributed geo join on 8 simulated devices, two flavours:

  * replicated-points lookup (core/distributed.py): every model-rank scans
    the whole batch against its Morton slice, an i32 pmax combines;
  * dispatch-routed lookup (GeoEngine.assign_sharded): points are bucketed
    by owning shard through the MoE dispatch primitive, so each rank
    resolves only the ~N/S points it owns (DESIGN.md §2, §6).

    PYTHONPATH=src python examples/distributed_geo_join.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.cells import build_cell_covering  # noqa: E402
from repro.core.distributed import assign_fast_distributed, \
    place_sharded, shard_covering  # noqa: E402
from repro.core.engine import EngineConfig, GeoEngine  # noqa: E402
from repro.core.fast import FastConfig  # noqa: E402
from repro.core.synth import build_synth_census  # noqa: E402
from repro.launch.mesh import make_test_mesh, use_mesh  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402


def main():
    enable_compile_cache()
    sc = build_synth_census(seed=0, n_states=16, counties_per_state=8,
                            blocks_per_county=24)
    cov = build_cell_covering(sc.census, max_level=9)
    mesh = make_test_mesh((2, 4))       # ("data", "model")
    sidx = place_sharded(shard_covering(cov, sc.census, n_shards=4), mesh)
    print(f"[dist] {len(cov.lo)} cells -> 4 Morton shards, "
          f"{sidx.index_bytes_per_shard()/1e6:.2f} MB/shard "
          f"(vs {cov.nbytes()/1e6:.2f} MB replicated)")

    rng = np.random.default_rng(7)
    xy, bid, cid, sid = sc.sample_points(rng, 65536)
    cfg = FastConfig(mode="exact", cap_boundary=0.5)
    with use_mesh(mesh):
        f = jax.jit(lambda p: assign_fast_distributed(sidx, p, mesh, cfg))
        s, c, b, stats = f(jnp.asarray(xy))   # compile
        t0 = time.perf_counter()
        s, c, b, stats = f(jnp.asarray(xy))
        b.block_until_ready()
        dt = time.perf_counter() - t0
    acc = float(np.mean(np.asarray(b) == bid))
    print(f"[dist] {len(xy)/dt/1e6:.2f}M pts/s on {mesh.devices.size} "
          f"devices, accuracy {acc:.4f}, "
          f"PIP evals/pt {int(stats['n_pip'])/len(xy):.3f}")
    assert acc == 1.0

    # Same lookup through the engine facade, dispatch-routed: each shard
    # receives only its own points (capacity-bucketed, drops counted).
    engine = GeoEngine.build(sc.census, "fast",
                             EngineConfig(mode="exact", cap_boundary=0.5),
                             covering=cov)
    with use_mesh(mesh):
        g = jax.jit(lambda p: engine.assign_sharded(p, mesh))
        res = g(jnp.asarray(xy))      # compile
        t0 = time.perf_counter()
        res = g(jnp.asarray(xy))
        res.block.block_until_ready()
        dt = time.perf_counter() - t0
    acc = float(np.mean(np.asarray(res.block) == bid))
    print(f"[engine] {len(xy)/dt/1e6:.2f}M pts/s dispatch-routed, "
          f"accuracy {acc:.4f}, dropped {int(res.stats.extra['n_dropped'])}")
    assert acc == 1.0


if __name__ == "__main__":
    main()
