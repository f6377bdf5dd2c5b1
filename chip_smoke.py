"""Smoke run of the geo-assignment serving path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip.  A seeded synthetic census (56 states / 896 counties / 21,504
blocks) and its level-11 cell covering are built on the host.  The
planner's engine (``GeoEngine.build(strategy="auto")``) then serves
requests of mixed sizes through ``AsyncGeoServer.submit_async`` and
assigns one direct batch of 262,144 points.  The ``simple`` cascade,
``fast`` with and without the fused gather-PIP kernel, and the analytics
mount (windowed counts beside the segment-reduce kernel) run on the
same points.  Every id is checked against the ``ref`` kernel backend on
the same points and against the map's seeded ground truth.  The one-pass
cascade must be refused when its engine is built.

Four chips.  ``GeoEngine.assign_sharded`` and ``assign_fast_distributed``
run on a (1, 4) mesh over the four chips, on the index as
``assign_sharded`` laid it out (its tables checked to sit one shard per
chip), and their ids are compared with one-chip ``engine.assign``.

The script needs a TPU and the compiled Pallas kernels.  It exits
non-zero and prints no result on any other platform or kernel backend,
and when any check fails.  Its last line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_BLOCKS = 220_864                 # the paper's national block count


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of one smoke run (defaults: the chip run)."""

    seed: int = 0
    n_states: int = 56
    counties_per_state: int = 16
    blocks_per_county: int = 24
    max_level: int = 11                # covering depth: boundary cells
    #                                    cover ~18 % of the map's area
    n_big: int = 1 << 18               # direct batch: splits gather-PIP
    n_mix: int = 1 << 16               # half uniform, half boundary-cell
    request_sizes: tuple = (1, 7, 100, 256, 300, 1000, 1024, 2000, 4096,
                            5000, 12000, 16384, 20000)


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """The local devices, if they are TPUs served by the Pallas kernels."""
    import jax

    from repro.kernels import ops
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX found {devices[0].platform!r} devices")
    backend = ops.resolve_backend()
    check(backend == "pallas",
          f"kernel backend resolves to {backend!r}, not 'pallas' "
          f"(REPRO_KERNELS={os.environ.get('REPRO_KERNELS')!r})")
    log(f"devices: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); kernel backend: {backend}")
    return devices


def engine_cfg(c: SmokeConfig, **kw):
    """Engine knobs of the run: the covering depth, and compaction caps
    at 1.0 so boundary-heavy requests never overflow to a degraded
    answer (the run checks exact ids)."""
    from repro.core.engine import EngineConfig
    return EngineConfig(max_level=c.max_level, cap_boundary=1.0,
                        cap_state=1.0, cap_county=1.0, cap_block=1.0, **kw)


def build_map(c: SmokeConfig):
    """Seeded census + covering, with the facts the run reports."""
    from repro.core.cells import build_cell_covering
    from repro.core.plan import covering_boundary_fraction
    from repro.core.synth import build_synth_census

    t0 = time.perf_counter()
    sc = build_synth_census(seed=c.seed, n_states=c.n_states,
                            counties_per_state=c.counties_per_state,
                            blocks_per_county=c.blocks_per_county)
    t1 = time.perf_counter()
    cov = build_cell_covering(sc.census, max_level=c.max_level)
    t2 = time.perf_counter()
    blocks = sc.census.blocks
    n_blocks = blocks.n_poly
    log(f"map: {sc.census.states.n_poly} states / "
        f"{sc.census.counties.n_poly} counties / {n_blocks} blocks, "
        f"{int(blocks.n_verts.sum())} block edges (seed {c.seed}); "
        f"census built in {t1 - t0:.1f} s")
    log(f"covering: level {c.max_level}, {len(cov.lo)} cells "
        f"({cov.n_interior} interior, {cov.n_boundary} boundary), "
        f"boundary fraction {covering_boundary_fraction(cov):.4f}, "
        f"built in {t2 - t1:.1f} s (one host process)")
    log(f"scale: {n_blocks} blocks is {PAPER_BLOCKS / n_blocks:.1f}x "
        f"short of the paper's {PAPER_BLOCKS}, a smoke size; the "
        f"benchmark's batch220k-uniform cell runs the paper's map at "
        f"covering level 13")
    return sc, cov


def sample(sc, cov, quant, max_level: int, n: int, seed: int):
    """n points with ground truth: half uniform over the map, half drawn
    inside boundary cells of the covering (points that need PIP), in
    shuffled order.  Returns (xy, block, county, state)."""
    import numpy as np

    from repro.core.fast import np_quantize_codes
    rng = np.random.default_rng(seed)
    uni = sc.sample_points(rng, n - n // 2)
    parts = [[], [], [], []]
    got = 0
    while got < n // 2:
        xy, *truth = sc.sample_points(rng, 4 * n, margin=0.0)
        codes = np_quantize_codes(quant, max_level, xy)
        pos = np.clip(np.searchsorted(cov.lo, codes, side="right") - 1, 0,
                      None)
        keep = (codes <= cov.hi[pos]) & (cov.val[pos] < 0)
        for part, arr in zip(parts, (xy, *truth)):
            part.append(arr[keep])
        got += int(keep.sum())
    near = [np.concatenate(p)[:n // 2] for p in parts]
    order = rng.permutation(n)
    return tuple(np.concatenate([u, b])[order] for u, b in zip(uni, near))


def ids(res) -> tuple:
    """(state, county, block) id arrays of an AssignResult."""
    return res.state, res.county, res.block


def check_ids(name: str, got, want, truth) -> None:
    """Every (state, county, block) id in ``got`` equals ``want`` (the
    ref backend) and the ground truth, row for row over the truth's
    length."""
    import numpy as np
    n = len(truth[0])
    for level, g, w, t in zip(("state", "county", "block"), got, want,
                              truth):
        g = np.asarray(g)[:n]
        n_ref = int((g == np.asarray(w)[:n]).sum())
        n_truth = int((g == t).sum())
        if level == "block":
            log(f"  {name}: {n} points, {n_ref} match ref, {n_truth} "
                f"match ground truth")
        check(n_ref == n and n_truth == n,
              f"{name}: {level} ids differ (ref {n_ref}/{n}, ground "
              f"truth {n_truth}/{n})")


def timed_assign(name: str, assign, pts):
    """``assign(pts)`` (an AssignResult) twice: the first call compiles."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(assign(pts))
    t1 = time.perf_counter()
    res = assign(pts)
    jax.block_until_ready(res)
    t2 = time.perf_counter()
    st = res.stats.as_dict()
    log(f"  {name}: first call (compiles) {t1 - t0:.2f} s, second "
        f"{t2 - t1:.3f} s; n_boundary {st['n_boundary']} n_pip "
        f"{st['n_pip']} overflow {st['overflow']} phase2_miss "
        f"{st['phase2_miss']}")
    return res


def serve(engine, cfg, xy, sizes):
    """Serve ``xy`` as requests of ``sizes`` through submit_async; returns
    the server (closed) and the served (state, county, block) ids."""
    import numpy as np

    from repro.serving import AsyncGeoServer
    server = AsyncGeoServer(engine, cfg)
    try:
        t0 = time.perf_counter()
        warm = server.warm()
        log(f"  warm (compiles each bucket): "
            + ", ".join(f"{b}: {s:.2f} s" for b, s in warm.items())
            + f"; total {time.perf_counter() - t0:.1f} s")
        offs = np.concatenate([[0], np.cumsum(sizes)])
        check(offs[-1] <= len(xy), "request sizes exceed the batch")
        t0 = time.perf_counter()
        futs = [server.submit_async(xy[a:b])
                for a, b in zip(offs[:-1], offs[1:])]
        results = [f.result(timeout=600) for f in futs]
        dt = time.perf_counter() - t0
    finally:
        server.close()
    ids = tuple(np.concatenate([getattr(r, f) for r in results])
                for f in ("state", "county", "block"))
    log(f"  served {len(futs)} requests ({int(offs[-1])} points, sizes "
        f"{min(sizes)}..{max(sizes)}) in {dt:.2f} s; cache hit rate "
        f"{server.cache_snapshot()['hit_rate']:.3f}")
    return server, ids, int(offs[-1])


def truth_mix_served(truth, c: SmokeConfig) -> list:
    """Ground truth of the served prefix of the mixed batch."""
    n = sum(c.request_sizes)
    return [t[:n] for t in truth]


def run_one_chip(c: SmokeConfig) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analytics import AnalyticsConfig, BlockAggregator
    from repro.core.engine import GeoEngine
    from repro.core.strategies import onepass_runs
    from repro.kernels import ops
    from repro.serving import ServeConfig

    sc, cov = build_map(c)
    census = sc.census

    log("phase auto: GeoEngine.build(strategy='auto')")
    t0 = time.perf_counter()
    auto = GeoEngine.build(census, "auto", engine_cfg(c), covering=cov)
    log(f"  built in {time.perf_counter() - t0:.1f} s; plan: "
        f"{json.dumps(auto.explain())}")
    log(f"  kernel backend: {ops.resolve_backend(auto.cfg.backend)}; "
        f"index device bytes: {auto.indices.memory_footprint()}")
    ref = GeoEngine.from_index_set(
        auto.indices, auto.strategy,
        dataclasses.replace(auto.cfg, backend="ref"))

    quant, _ = auto.extent_quant()
    xy_big, *truth_big = sc.sample_points(
        np.random.default_rng(c.seed + 1), c.n_big)
    xy_mix, *truth_mix = sample(sc, cov, quant, c.max_level, c.n_mix,
                                c.seed + 2)
    # sample_points returns (block, county, state); ids run the other way.
    truth_big, truth_mix = truth_big[::-1], truth_mix[::-1]
    big, mix = jnp.asarray(xy_big), jnp.asarray(xy_mix)

    log(f"phase direct: engine.assign, {c.n_big} uniform points and "
        f"{c.n_mix} mixed (half in boundary cells)")
    ref_big = ids(timed_assign("ref big", ref.assign, big))
    check_ids("ref big", ref_big, ref_big, truth_big)
    check_ids("auto big", ids(timed_assign("auto big", auto.assign, big)),
              ref_big, truth_big)
    ref_mix = ids(timed_assign("ref mix", ref.assign, mix))
    check_ids("ref mix", ref_mix, ref_mix, truth_mix)
    check_ids("auto mix", ids(timed_assign("auto mix", auto.assign, mix)),
              ref_mix, truth_mix)

    log("phase serve: AsyncGeoServer.submit_async over the auto engine")
    _, served, _ = serve(auto, ServeConfig(), xy_mix, c.request_sizes)
    check_ids("served", served, ref_mix, truth_mix_served(truth_mix, c))

    log("phase strategies: simple, fast fused and unfused (mixed batch)")
    simple = GeoEngine.build(census, "simple", engine_cfg(c), covering=cov)
    simple_ref = GeoEngine.build(census, "simple",
                                 engine_cfg(c, backend="ref"), covering=cov)
    ref_simple = ids(timed_assign("simple ref", simple_ref.assign, mix))
    check_ids("simple ref", ref_simple, ref_mix, truth_mix)
    check_ids("simple", ids(timed_assign("simple", simple.assign, mix)),
              ref_simple, truth_mix)
    fast_ref = GeoEngine.from_index_set(auto.indices, "fast",
                                        engine_cfg(c, backend="ref"))
    ref_fast = ids(timed_assign("fast ref", fast_ref.assign, mix))
    check_ids("fast ref", ref_fast, ref_mix, truth_mix)
    for fused in (True, False):
        eng = GeoEngine.from_index_set(auto.indices, "fast",
                                       engine_cfg(c, fused=fused))
        check_ids(f"fast fused={fused}",
                  ids(timed_assign(f"fast fused={fused}", eng.assign,
                                   mix)), ref_fast, truth_mix)

    runs = onepass_runs()
    log("phase onepass: the one-pass cascade must be refused at build on "
        "the pallas backend")
    for strategy, kw in (("fast_onepass", {}),
                         ("fast", {"fused": "onepass"})):
        try:
            GeoEngine.from_index_set(auto.indices, strategy,
                                     engine_cfg(c, **kw))
        except ValueError as e:
            check(not runs and "does not compile for the TPU" in str(e),
                  f"{strategy} {kw}: refused: {e}")
            log(f"  {strategy} {kw}: refused at build: {e}")
        else:
            check(runs, f"{strategy} {kw} was not refused")
            log(f"  {strategy} {kw}: built ({ops.resolve_backend()} "
                f"backend)")

    log("phase analytics: ServeConfig(analytics=...) and segment kernels")
    acfg = AnalyticsConfig(window_s=3600.0, clock=lambda: 0.0)
    server, served, n = serve(auto, ServeConfig(analytics=acfg), xy_mix,
                              c.request_sizes)
    check_ids("served+analytics", served, ref_mix,
              truth_mix_served(truth_mix, c))
    n_blocks = census.blocks.n_poly
    window = server.regions[0].analytics.current().counts
    want = np.bincount(truth_mix[2][:n], minlength=n_blocks)
    kernel = BlockAggregator.from_engine(auto,
                                         backend=ops.resolve_backend())
    oracle = BlockAggregator.from_engine(auto, backend="ref")
    block_ids = jnp.asarray(served[2])
    red_k, red_r = kernel.reduce(block_ids), oracle.reduce(block_ids)
    for name, counts in (("window", window),
                         ("segment kernel", red_k.count),
                         ("segment ref", red_r.count)):
        same = int((np.asarray(counts) == want).sum())
        log(f"  {name}: {same}/{n_blocks} block counts match ground truth")
        check(same == n_blocks, f"analytics {name} counts differ")
    for f in ("sum", "min", "max"):
        check(np.array_equal(np.asarray(getattr(red_k, f)),
                             np.asarray(getattr(red_r, f))),
              f"segment kernel {f} differs from ref")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"device peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


def run_four_chips(c: SmokeConfig, devices) -> None:
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.distributed import STACKED, assign_fast_distributed
    from repro.core.engine import GeoEngine

    check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
          f"{len(devices)}")
    devs = list(devices[:4])
    sc, cov = build_map(c)
    engine = GeoEngine.build(sc.census, "fast",
                             engine_cfg(c, fused=True, cap_shard=4.0),
                             covering=cov)
    quant, _ = engine.extent_quant()
    xy, *truth = sample(sc, cov, quant, c.max_level, c.n_big, c.seed + 3)
    truth = truth[::-1]
    pts = jnp.asarray(xy)

    log(f"phase one-chip: engine.assign on {devs[0]}, {c.n_big} points "
        f"(half in boundary cells)")
    one = ids(timed_assign("one chip", engine.assign, pts))
    check_ids("one chip", one, one, truth)

    mesh = Mesh(np.array(devs).reshape(1, 4), ("data", "model"))
    log("phase sharded: GeoEngine.assign_sharded on a (1, 4) mesh")
    res = timed_assign("assign_sharded",
                       lambda p: engine.assign_sharded(p, mesh), pts)
    log(f"  result on {len(res.block.sharding.device_set)} devices")
    check(int(res.stats.extra["n_dropped"]) == 0, "routing dropped points")
    check_ids("assign_sharded", ids(res), one, truth)

    # The index as assign_sharded laid it out on the mesh.
    sidx = engine.indices.sharded[4]
    for name in STACKED:
        arr = getattr(sidx, name)
        homes = {s.device for s in arr.addressable_shards}
        rows = {s.data.shape[0] for s in arr.addressable_shards}
        check(homes == set(devs) and rows == {1},
              f"{name}: shards on {sorted(map(str, homes))}, rows {rows}")
    log(f"  index shards: {', '.join(STACKED)} split 1 row per chip over "
        f"{sorted(str(d) for d in devs)}; "
        f"{sidx.index_bytes_per_shard()} index bytes per shard")

    log("phase distributed: assign_fast_distributed (replicated points)")
    fcfg = engine.cfg.fast_cfg()
    dist = timed_assign(
        "assign_fast_distributed",
        lambda p: _as_result(assign_fast_distributed(sidx, p, mesh,
                                                     fcfg)), pts)
    log(f"  result on {len(dist.block.sharding.device_set)} devices")
    check_ids("assign_fast_distributed", ids(dist), one, truth)
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"  {d}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def _as_result(out):
    from repro.core.resolve import AssignResult, GeoStats
    sid, cid, bid, st = out
    return AssignResult(sid, cid, bid, GeoStats(
        n_need=st["n_boundary"], n_pip=st["n_pip"],
        overflow=st["overflow"], extra=st))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths on four chips")
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.compile_cache import enable_compile_cache
        devices = require_tpu()
        log(f"compile cache: {enable_compile_cache()}")
        t0 = time.perf_counter()
        if args.chips == 4:
            run_four_chips(SmokeConfig(), devices)
        else:
            run_one_chip(SmokeConfig())
        log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
