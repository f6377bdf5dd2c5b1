"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig4,fig6,...] [--fast]

Prints ``name,us_per_call,derived`` CSV rows.  Sections:
  fig4   simple approach: single-device rate vs number of points
  fig5   simple approach: scaling over the devices this process holds
  fig6   fast approach: single-device rate vs number of points (F-variants)
  fig7   fast approach: device scaling + sharded index
  table1 index memory for exact/approx/fanout/sharded variants
  claim  ~0.2 PIP evaluations per point (paper §III)
  lm     train/serve step times for reduced LM archs
  roofline  (separate: python -m benchmarks.roofline results/dryrun.json)
"""
import argparse

from benchmarks import common
from benchmarks.common import emit, sample_points, timeit
from repro.compile_cache import enable_compile_cache


# ------------------------------------------------------------------ fig4
def fig4(quick=False):
    """Paper Fig 4: simple-approach rate vs N_pt (single core: 45K/s peak)."""
    import jax.numpy as jnp
    from repro.core.simple import SimpleConfig, SimpleIndex, assign_simple
    idx = SimpleIndex.from_census(common.get_census().census)
    cfg = SimpleConfig(cap_state=0.5, cap_county=0.5, cap_block=0.5)
    sizes = [10_000, 100_000] if quick else [1_000, 10_000, 100_000,
                                             1_000_000]
    for n in sizes:
        xy, *_ = sample_points(n)
        dt, _ = timeit(lambda p: assign_simple(idx, p, cfg)[2],
                       jnp.asarray(xy))
        emit(f"fig4_simple_n{n}", dt * 1e6,
             f"{n/dt:.0f} pts/s (paper single-core peak ~45K/s)")


# ------------------------------------------------------------------ fig5
def _device_counts(quick: bool) -> list:
    """Powers of two up to the devices this process holds (the paper's
    processing-unit sweep).  For simulated CPU devices, launch with
    XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    import jax
    n = len(jax.devices())
    counts = [c for c in (1, 2, 4, 8) if c <= n]
    return counts[:2] if quick else counts


def _scaling_run(n_dev: int, mode: str, n_pts: int) -> float:
    """Sharded assign over the first n_dev devices of this process (one
    process holds the devices: no child process touches them)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.launch.mesh import use_mesh
    sc = common.get_census()
    xy, *_ = sc.sample_points(np.random.default_rng(7), n_pts)
    pts = jnp.asarray(xy)
    devs = np.array(jax.devices()[:n_dev])
    if mode == "simple":
        from repro.core.simple import SimpleConfig, SimpleIndex, assign_simple
        idx = SimpleIndex.from_census(sc.census)
        cfg = SimpleConfig(cap_state=0.5, cap_county=0.5, cap_block=0.5)
        mesh = Mesh(devs.reshape(n_dev, 1), ("data", "model"))
        pts = jax.device_put(pts, NamedSharding(mesh, P("data", None)))

        def f(p):
            return assign_simple(idx, p, cfg)[2]
    else:
        from repro.core.distributed import (assign_fast_distributed,
                                            shard_covering)
        from repro.core.fast import FastConfig
        n_model = min(n_dev, 2)
        mesh = Mesh(devs.reshape(max(n_dev // n_model, 1), n_model),
                    ("data", "model"))
        sidx = shard_covering(common.get_covering(9), sc.census,
                              n_shards=n_model)
        cfg = FastConfig(mode="exact", cap_boundary=0.5)

        def f(p):
            return assign_fast_distributed(sidx, p, mesh, cfg)[2]
    with use_mesh(mesh):
        f(pts).block_until_ready()
        t0 = time.perf_counter()
        f(pts).block_until_ready()
        return time.perf_counter() - t0


def fig5(quick=False):
    """Paper Fig 5: simple-approach scaling with processing units, over
    the devices this process holds (simulated CPU devices validate the
    sharded program structure, not its speed)."""
    n = 100_000
    for nd in _device_counts(quick):
        dt = _scaling_run(nd, "simple", n)
        emit(f"fig5_simple_dev{nd}", dt * 1e6,
             f"{n/dt:.0f} pts/s on {nd} devices")


# ------------------------------------------------------------------ fig6
def fig6(quick=False):
    """Paper Fig 6: fast-approach rate vs N_pt, exact + approx, with the
    top-grid depth sweep standing in for the paper's F1/F2/F4 fanouts."""
    import jax.numpy as jnp
    from repro.core.fast import FastConfig, FastIndex, assign_fast
    cov = common.get_covering(9)
    census = common.get_census().census
    sizes = [100_000] if quick else [10_000, 100_000, 1_000_000]
    for gbits in (0, 4, 6):
        idx = FastIndex.from_covering(cov, census, gbits=gbits)
        for n in sizes:
            xy, *_ = sample_points(n)
            for mode in ("exact", "approx"):
                cfg = FastConfig(mode=mode, cap_boundary=0.5)
                dt, _ = timeit(lambda p: assign_fast(idx, p, cfg)[2],
                               jnp.asarray(xy))
                emit(f"fig6_fast_{mode}_G{gbits}_n{n}", dt * 1e6,
                     f"{n/dt:.0f} pts/s, search_iters={idx.search_iters} "
                     f"(paper: few M pts/s/core)")


# ------------------------------------------------------------------ fig7
def fig7(quick=False):
    """Paper Fig 7: fast-approach thread scaling -> device scaling with the
    Morton-sharded index (beyond-paper distribution), over the devices
    this process holds."""
    n = 100_000
    for nd in [c for c in _device_counts(quick) if c >= 2]:
        dt = _scaling_run(nd, "fast", n)
        emit(f"fig7_fast_dev{nd}", dt * 1e6,
             f"{n/dt:.0f} pts/s on {nd} devices")


# ---------------------------------------------------------------- table1
def table1(quick=False):
    """Paper Table I: index memory.  Exact at L9 with G0/G4/G6 top grids,
    approx-precision variants via deeper leaves, plus per-shard bytes."""
    from repro.core.distributed import shard_covering
    from repro.core.fast import FastIndex
    census = common.get_census().census
    for lvl in ([9] if quick else [8, 9, 10]):
        cov = common.get_covering(lvl)
        for gbits in (0, 4, 6):
            idx = FastIndex.from_covering(cov, census, gbits=gbits)
            emit(f"table1_L{lvl}_G{gbits}", 0.0,
                 f"{idx.nbytes()/1e6:.2f} MB | cells={len(cov.lo)} "
                 f"interior={cov.n_interior} boundary={cov.n_boundary}")
        sidx = shard_covering(cov, census, n_shards=16)
        emit(f"table1_L{lvl}_sharded16", 0.0,
             f"{sidx.index_bytes_per_shard()/1e6:.2f} MB/shard x16")


# ----------------------------------------------------------------- claim
def claim(quick=False):
    """Paper §III: ~20 % of points need a PIP test (~0.2 evals/pt)."""
    import jax.numpy as jnp
    from repro.core.fast import FastConfig, FastIndex, assign_fast
    from repro.core.simple import SimpleConfig, SimpleIndex, assign_simple
    census = common.get_census().census
    xy, *_ = sample_points(100_000)
    idx = SimpleIndex.from_census(census)
    *_, stats = assign_simple(idx, jnp.asarray(xy),
                              SimpleConfig(cap_state=1.0, cap_county=1.0,
                                           cap_block=1.0))
    for lvl in ("state", "county", "block"):
        frac = int(stats[lvl]["n_multi"]) / len(xy)
        emit(f"claim_multibbox_{lvl}", 0.0,
             f"{frac:.3f} of points in >1 bbox (paper ~0.2)")
    total = sum(int(stats[k]["n_pip"]) for k in stats) / len(xy)
    emit("claim_simple_pip_per_pt", 0.0,
         f"{total:.3f} candidate PIP tests/pt")
    fidx = FastIndex.from_covering(common.get_covering(9), census, gbits=4)
    *_, fstats = assign_fast(fidx, jnp.asarray(xy),
                             FastConfig(mode="exact", cap_boundary=1.0))
    emit("claim_fast_pip_per_pt", 0.0,
         f"{int(fstats['n_pip'])/len(xy):.3f} (true-hit filtering, "
         f"boundary frac {int(fstats['n_boundary'])/len(xy):.3f})")


# -------------------------------------------------------------------- lm
def lm(quick=False):
    """Train/serve step times for reduced LM archs (CPU smoke scale)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced_config
    from repro.configs.base import RunConfig
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import build_model
    from repro.models.module import init_params
    from repro.optim import adamw
    from repro.runtime.steps import make_serve_step, make_train_step
    run = RunConfig(remat="none", attn_chunk_q=64, attn_chunk_kv=64,
                    ssm_chunk=32)
    for name in (("qwen1.5-0.5b",) if quick
                 else ("qwen1.5-0.5b", "mixtral-8x7b", "zamba2-1.2b")):
        cfg = get_reduced_config(name)
        model = build_model(cfg)
        params = init_params(model.specs, jax.random.key(0))
        opt = adamw.init(params)
        src = SyntheticLM(cfg=cfg, batch=4, seq=128)
        step = jax.jit(make_train_step(model, run))
        batch = src.batch_at(0)
        dt, _ = timeit(lambda: step(params, opt, batch)[2]["loss"])
        emit(f"lm_train_{name}", dt * 1e6,
             f"{4*128/dt:.0f} tok/s (reduced cfg, CPU)")
        serve = jax.jit(make_serve_step(model, run))
        cache = model.init_cache(4, 256)
        tok = jnp.ones((4, 1), jnp.int32)
        dt, _ = timeit(lambda: serve(params, tok, cache)[0])
        emit(f"lm_decode_{name}", dt * 1e6, f"{4/dt:.0f} tok/s decode")


SECTIONS = {"fig4": fig4, "fig5": fig5, "fig6": fig6, "fig7": fig7,
            "table1": table1, "claim": claim, "lm": lm}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes for CI")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(SECTIONS)
    print("name,us_per_call,derived")
    for n in names:
        SECTIONS[n](quick=args.fast)


if __name__ == "__main__":
    main()
