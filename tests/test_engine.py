"""GeoEngine facade: strategy agreement (simple == fast(exact) == hybrid),
hybrid accuracy ordering, the dispatch-routed sharded assign, off-extent
rejection, and fused-kernel routing (EngineConfig.fused).
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fast as fast_mod
from repro.core.distributed import STACKED, place_sharded, shard_covering
from repro.core.engine import EngineConfig, GeoEngine
from repro.launch.mesh import make_test_mesh

EXACT_CFG = EngineConfig(backend="ref", cap_state=1.0, cap_county=1.0,
                         cap_block=1.0, cap_boundary=1.0, max_level=8)


@pytest.fixture(scope="module")
def engines(synth_small):
    census = synth_small.census
    simple = GeoEngine.build(census, "simple", EXACT_CFG)
    fast = GeoEngine.build(census, "fast", EXACT_CFG)
    # Reuse fast's covering so the hybrid build skips the host BFS.
    hybrid = GeoEngine.build(census, "hybrid", EXACT_CFG,
                             covering=fast.covering)
    return {"simple": simple, "fast": fast, "hybrid": hybrid}


def test_three_way_agreement_on_interior_points(engines, points_small):
    """simple == fast(exact) == hybrid on every non-boundary (true-hit)
    point; all three == ground truth there too."""
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    out = {name: np.asarray(eng.assign(pts).block)
           for name, eng in engines.items()}
    val = np.asarray(fast_mod.cell_values(engines["fast"].fast_index, pts))
    interior = val >= 0
    assert interior.mean() > 0.5          # the paper's true-hit majority
    np.testing.assert_array_equal(out["simple"][interior],
                                  out["fast"][interior])
    np.testing.assert_array_equal(out["fast"][interior],
                                  out["hybrid"][interior])
    np.testing.assert_array_equal(out["hybrid"][interior], bid[interior])


def test_hybrid_matches_fast_exact_everywhere_on_synth(engines,
                                                       points_small):
    """On the synthetic map generous caps make both hybrid and fast(exact)
    fully exact, so they agree on boundary points as well."""
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    f = engines["fast"].assign(pts)
    h = engines["hybrid"].assign(pts)
    np.testing.assert_array_equal(np.asarray(f.block), bid)
    np.testing.assert_array_equal(np.asarray(h.block), bid)
    np.testing.assert_array_equal(np.asarray(h.state), np.asarray(f.state))
    np.testing.assert_array_equal(np.asarray(h.county),
                                  np.asarray(f.county))


def test_hybrid_beats_approx_accuracy(engines, synth_small, points_small):
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    approx = GeoEngine.build(
        synth_small.census, "fast",
        EngineConfig(backend="ref", mode="approx", max_level=8),
        covering=engines["fast"].covering)
    acc_a = (np.asarray(approx.assign(pts).block) == bid).mean()
    acc_h = (np.asarray(engines["hybrid"].assign(pts).block) == bid).mean()
    assert acc_h >= acc_a


def test_assign_result_unpacks_like_legacy_tuple(engines, points_small):
    xy, *_ = points_small
    res = engines["simple"].assign(jnp.asarray(xy))
    s, c, b, stats = res
    assert np.asarray(s).shape == (len(xy),)
    assert int(stats.overflow) == 0
    assert int(stats.n_pip) > 0
    assert set(stats.extra) == {"state", "county", "block"}


def test_assign_sharded_matches_fast_exact(engines, points_small):
    """Dispatch-routed sharded lookup == single-mesh exact lookup (1-device
    mesh; conftest pins the process to one device)."""
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    mesh = make_test_mesh((1, 1))
    res = engines["fast"].assign_sharded(pts, mesh)
    np.testing.assert_array_equal(np.asarray(res.block), bid)
    assert int(res.stats.extra["n_dropped"]) == 0
    f = engines["fast"].assign(pts)
    np.testing.assert_array_equal(np.asarray(res.state),
                                  np.asarray(f.state))


def test_engine_validation():
    with pytest.raises(ValueError, match="unknown strategy"):
        GeoEngine("warp", EngineConfig())
    with pytest.raises(ValueError, match="needs a simple_index"):
        GeoEngine("simple", EngineConfig())
    with pytest.raises(ValueError, match="needs a fast_index"):
        GeoEngine("fast", EngineConfig())
    # The sharded plugin has no single-mesh assign; an engine built on
    # it would only fail at the first assign — reject at construction.
    with pytest.raises(ValueError, match="single-mesh"):
        GeoEngine("sharded", EngineConfig())


def test_assign_sharded_requires_model_axis(engines, points_small):
    xy, *_ = points_small
    mesh = make_test_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="model"):
        engines["fast"].assign_sharded(jnp.asarray(xy), mesh)


def _far_points(census, n_pad: int = 8):
    """Points far outside the map extent (padded so compaction caps and
    dispatch capacities stay sane)."""
    x0, x1, y0, y1 = census.extent
    w, h = x1 - x0, y1 - y0
    base = np.array([[x1 + w, (y0 + y1) / 2],       # east, clips onto border
                     [x0 - 2 * w, y0 - h],          # far southwest corner
                     [(x0 + x1) / 2, y1 + 0.5 * h],  # north
                     [x0 - 0.01 * w, (y0 + y1) / 2]], np.float32)  # grazing
    reps = int(np.ceil(n_pad / len(base)))
    return jnp.asarray(np.tile(base, (reps, 1))[:n_pad])


def test_off_extent_points_rejected_every_strategy(engines, synth_small):
    """ROADMAP extent-rejection item: quantization clips off-extent points
    onto the grid border, so without an explicit extent test a far-outside
    query lands in a border cell and gets that cell's block id.  Every
    strategy must return -1 instead — matching the simple cascade."""
    far = _far_points(synth_small.census, 64)
    for name, eng in engines.items():
        bid = np.asarray(eng.assign(far).block)
        np.testing.assert_array_equal(bid, -1, err_msg=name)


def test_off_extent_points_rejected_sharded(engines, synth_small):
    far = _far_points(synth_small.census, 64)
    mesh = make_test_mesh((1, 1))
    res = engines["fast"].assign_sharded(far, mesh)
    np.testing.assert_array_equal(np.asarray(res.block), -1)
    np.testing.assert_array_equal(np.asarray(res.state), -1)


def test_approx_mode_rejects_off_extent(engines, synth_small):
    approx = GeoEngine.build(
        synth_small.census, "fast",
        EngineConfig(backend="ref", mode="approx", max_level=8),
        covering=engines["fast"].covering)
    far = _far_points(synth_small.census, 64)
    np.testing.assert_array_equal(np.asarray(approx.assign(far).block), -1)


def test_fused_flag_matches_legacy_all_strategies(engines, synth_small,
                                                  points_small):
    """EngineConfig(fused=True) routes every strategy's candidate PIP
    through the fused gather-PIP kernel; assignments are identical."""
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    fused_cfg = dataclasses.replace(EXACT_CFG, fused=True)
    for name, eng in engines.items():
        feng = GeoEngine.build(synth_small.census, name, fused_cfg,
                               covering=engines["fast"].covering)
        np.testing.assert_array_equal(
            np.asarray(feng.assign(pts).block),
            np.asarray(eng.assign(pts).block), err_msg=name)


def test_fused_exact_matches_ground_truth(engines, synth_small,
                                          points_small):
    xy, bid, *_ = points_small
    fused_cfg = dataclasses.replace(EXACT_CFG, fused=True)
    eng = GeoEngine.build(synth_small.census, "fast", fused_cfg,
                          covering=engines["fast"].covering)
    np.testing.assert_array_equal(
        np.asarray(eng.assign(jnp.asarray(xy)).block), bid)


def test_fused_sharded_matches_ground_truth(engines, synth_small,
                                            points_small):
    """fused=True is honored by assign_sharded too (the pool rides the
    sharded index, replicated like block_edges)."""
    xy, bid, *_ = points_small
    fused_cfg = dataclasses.replace(EXACT_CFG, fused=True)
    eng = GeoEngine.build(synth_small.census, "fast", fused_cfg,
                          covering=engines["fast"].covering)
    res = eng.assign_sharded(jnp.asarray(xy), make_test_mesh((1, 1)))
    np.testing.assert_array_equal(np.asarray(res.block), bid)


def test_assign_sharded_places_index_on_its_mesh(engines, synth_small,
                                                 points_small):
    """assign_sharded lays the sharded index out on its mesh: the stacked
    tables split over "model", everything else (edge pool included)
    replicated.  The placed index is the cached one, so a second call on
    the same mesh moves nothing."""
    xy, *_ = points_small
    pts = jnp.asarray(xy)
    fused_cfg = dataclasses.replace(EXACT_CFG, fused=True)
    eng = GeoEngine.build(synth_small.census, "fast", fused_cfg,
                          covering=engines["fast"].covering)
    mesh = make_test_mesh((1, 1))
    eng.assign_sharded(pts, mesh)
    sidx = eng.indices.sharded[1]
    for name in STACKED:
        assert getattr(sidx, name).sharding == NamedSharding(mesh, P("model"))
    for arr in (sidx.range_lo, sidx.block_edges, sidx.edge_pool.blocks):
        assert arr.sharding == NamedSharding(mesh, P())
    eng.assign_sharded(pts, mesh)
    assert eng.indices.sharded[1].cell_lo is sidx.cell_lo
    four = shard_covering(engines["fast"].covering, synth_small.census, 4)
    with pytest.raises(ValueError, match="cannot be placed"):
        place_sharded(four, mesh)


def test_fused_without_pool_raises_at_construction(engines):
    """A fused config over a pool-less index is a *build-time* error
    (registry capability validation) — it must never survive to the
    first assign as a trace-time surprise."""
    with pytest.raises(ValueError, match="with_pool"):
        GeoEngine("fast", dataclasses.replace(EXACT_CFG, fused=True),
                  fast_index=engines["fast"].fast_index)
    # approx mode never PIPs, so fused needs no pool there.
    GeoEngine("fast",
              dataclasses.replace(EXACT_CFG, fused=True, mode="approx"),
              fast_index=engines["fast"].fast_index)


def test_third_party_strategy_registers_without_engine_changes(
        engines, points_small):
    """The registry is the engine's whole dispatch surface: a strategy
    registered from outside core/ builds, validates, and assigns through
    the unchanged GeoEngine."""
    from repro.core.registry import (Strategy, available_strategies,
                                     register_strategy)
    from repro.core.resolve import AssignResult, GeoStats

    @register_strategy("centre-owner", needs=("fast",))
    class CentreOwner(Strategy):
        def assign(self, indices, points, cfg):
            fcfg = dataclasses.replace(cfg.fast_cfg(), mode="approx")
            sid, cid, bid, st = fast_mod.assign_fast(indices.fast,
                                                     points, fcfg)
            return AssignResult(sid, cid, bid, GeoStats(
                n_need=st["n_boundary"], n_pip=st["n_pip"],
                overflow=st["overflow"], extra=st))

    assert "centre-owner" in available_strategies()
    xy, *_ = points_small
    eng = GeoEngine("centre-owner", EXACT_CFG,
                    fast_index=engines["fast"].fast_index)
    approx = GeoEngine("fast",
                       dataclasses.replace(EXACT_CFG, mode="approx"),
                       fast_index=engines["fast"].fast_index)
    np.testing.assert_array_equal(
        np.asarray(eng.assign(jnp.asarray(xy)).block),
        np.asarray(approx.assign(jnp.asarray(xy)).block))
