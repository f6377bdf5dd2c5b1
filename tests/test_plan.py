"""GeoPlan auto-planner + GeoIndexSet artifact (DESIGN.md §11):
plan/explicit bit-identity across maps, batch sizes, and cache settings;
capability-constrained replanning; save/load round trips (bit-identical
assignments, schema-version rejection) incl. GeoServer cold start.
"""
import dataclasses
import json
import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.artifact import (ARRAYS_NAME, MANIFEST_NAME,
                                 SCHEMA_VERSION, GeoIndexSet)
from repro.core.engine import EngineConfig, GeoEngine
from repro.core.plan import (HYBRID_BOUNDARY_FRAC, SHARD_MIN_POINTS,
                             covering_boundary_fraction, plan_for)
from repro.core.synth import build_synth_census
from repro.serving import GeoServer, ServeConfig

CFG = EngineConfig(backend="ref", cap_state=1.0, cap_county=1.0,
                   cap_block=1.0, cap_boundary=1.0, max_level=7)


def _assert_assign_equal(a, b):
    for field in ("state", "county", "block"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)),
                                      err_msg=field)
    assert a.stats.as_dict() == b.stats.as_dict()


# -- planner unit behaviour --------------------------------------------------

def test_covering_boundary_fraction_area_weighted():
    """Interior cells count their whole leaf span; boundary cells are
    leaves — the fraction is area-share, not cell-count-share."""
    cov = SimpleNamespace(lo=np.array([0, 16, 17]),
                          hi=np.array([15, 16, 17]),
                          val=np.array([3, -1, -2]))
    assert covering_boundary_fraction(cov) == pytest.approx(2 / 18)


def test_plan_picks_hybrid_on_heavy_boundary_and_fast_on_light():
    light = SimpleNamespace(lo=np.array([0, 64]), hi=np.array([63, 64]),
                            val=np.array([1, -1]))
    heavy = SimpleNamespace(lo=np.array([0, 4]), hi=np.array([3, 7]),
                            val=np.array([1, -1]))
    p_light = plan_for(EngineConfig(), covering=light, platform="cpu")
    p_heavy = plan_for(EngineConfig(), covering=heavy, platform="cpu")
    assert p_light.strategy == "fast"
    assert p_heavy.strategy == "hybrid"
    assert p_heavy.boundary_fraction >= HYBRID_BOUNDARY_FRAC
    assert any("boundary fraction" in r for r in p_heavy.reasons)


def test_plan_fuses_on_tpu_not_cpu():
    cov = SimpleNamespace(lo=np.array([0, 64]), hi=np.array([63, 64]),
                          val=np.array([1, -1]))
    assert plan_for(EngineConfig(), covering=cov,
                    platform="tpu").fused
    assert not plan_for(EngineConfig(), covering=cov,
                        platform="cpu").fused


def test_plan_respects_capabilities():
    """Replanning against a built artifact never emits a plan the
    artifact cannot execute: no fast index -> cascade; no pool and no
    census to build one -> fused dropped even on TPU (with a census the
    pool is buildable via ensure(), so fused stays)."""
    caps_simple_only = {"census": True, "covering": False, "simple": True,
                        "fast": False, "simple_pool": False,
                        "fast_pool": False}
    p = plan_for(EngineConfig(), capabilities=caps_simple_only,
                 platform="tpu")
    assert p.strategy == "simple" and p.fused    # pool buildable
    cov = SimpleNamespace(lo=np.array([0, 64]), hi=np.array([63, 64]),
                          val=np.array([1, -1]))
    # No pool and no census to build one from -> fused dropped on TPU.
    caps_no_pool = {"census": False, "covering": True, "simple": False,
                    "fast": True, "simple_pool": False,
                    "fast_pool": False}
    p = plan_for(EngineConfig(), covering=cov,
                 capabilities=caps_no_pool, platform="tpu")
    assert p.strategy == "fast" and not p.fused
    assert any("pool" in r or "unusable" in r for r in p.reasons)
    # With the census present the pool is buildable (ensure() attaches
    # it after planning), so a TPU cold start keeps the fused kernel.
    caps_buildable = dict(caps_no_pool, census=True)
    p = plan_for(EngineConfig(), covering=cov,
                 capabilities=caps_buildable, platform="tpu")
    assert p.fused


def test_plan_recommends_sharding_on_big_batches_only():
    cov = SimpleNamespace(lo=np.array([0, 64]), hi=np.array([63, 64]),
                          val=np.array([1, -1]))
    big = plan_for(EngineConfig(), covering=cov, platform="cpu",
                   n_points=SHARD_MIN_POINTS, n_devices=4)
    small = plan_for(EngineConfig(), covering=cov, platform="cpu",
                     n_points=1024, n_devices=4)
    assert big.sharded and big.n_shards == 4
    assert not small.sharded and small.n_shards == 1


def test_explicit_build_records_pinned_plan(synth_small):
    eng = GeoEngine.build(synth_small.census, "simple", CFG)
    info = eng.explain()
    assert info["strategy"] == "simple" and info["auto"] is False
    # Capability-constrained replanning for a batch hint cannot leave
    # what the engine has built (no covering here -> cascade).
    hint = eng.explain(n_points=100_000)
    assert hint["strategy"] == "simple"


# -- auto == explicit bit-identity (satellite property test) -----------------

@pytest.mark.parametrize("seed,shape", [
    (3, dict(n_states=4, counties_per_state=3, blocks_per_county=6)),
    (9, dict(n_states=6, counties_per_state=2, blocks_per_county=10)),
])
def test_auto_plan_bit_identical_to_explicit(seed, shape):
    """Across maps with different (random) extents and batch sizes, the
    auto-built engine names a plan, and an engine explicitly configured
    to that plan produces bit-identical assignments and stats."""
    sc = build_synth_census(seed=seed, **shape)
    auto = GeoEngine.build(sc.census, "auto", CFG)
    info = auto.explain()
    assert info["auto"] is True and info["strategy"] in (
        "simple", "fast", "hybrid")
    explicit = GeoEngine.build(sc.census, info["strategy"],
                               auto.plan.apply(CFG),
                               covering=auto.covering)
    rng = np.random.default_rng(seed)
    for n in (64, 1000, 4096):
        xy, *_ = sc.sample_points(rng, n)
        _assert_assign_equal(auto.assign(jnp.asarray(xy)),
                             explicit.assign(jnp.asarray(xy)))


@pytest.mark.parametrize("cache", [False, True])
def test_auto_served_bit_identical_to_direct(synth_small, points_small,
                                             cache):
    """The auto plan holds through the serving stack, cache on and off:
    served ids == the auto engine's own direct assign."""
    auto = GeoEngine.build(synth_small.census, "auto",
                           dataclasses.replace(CFG, max_level=8))
    server = GeoServer(auto, ServeConfig(buckets=(64, 256, 1024),
                                         cache=cache))
    xy, *_ = points_small
    res = server.submit(xy[:900])
    direct = auto.assign(jnp.asarray(xy[:900]))
    np.testing.assert_array_equal(res.block, np.asarray(direct.block))
    np.testing.assert_array_equal(res.state, np.asarray(direct.state))


# -- GeoIndexSet artifact ----------------------------------------------------

def test_index_set_save_load_round_trip(synth_small, points_small,
                                        tmp_path):
    """Reloaded artifact -> re-derived indices -> bit-identical
    assignments, for the cascade and the (fused) cell index alike."""
    path = str(tmp_path / "art")
    idx = GeoIndexSet.build(synth_small.census,
                            components=("simple", "fast"),
                            pools=("simple", "fast"), max_level=7)
    idx.save(path)
    assert os.path.exists(os.path.join(path, MANIFEST_NAME))
    assert os.path.exists(os.path.join(path, ARRAYS_NAME))
    loaded = GeoIndexSet.load(path)
    assert loaded.max_level == 7
    np.testing.assert_array_equal(loaded.covering.lo, idx.covering.lo)
    np.testing.assert_array_equal(loaded.covering.val, idx.covering.val)
    assert loaded.census.extent == synth_small.census.extent
    xy, *_ = points_small
    pts = jnp.asarray(xy[:1500])
    fused_cfg = dataclasses.replace(CFG, fused=True)
    for strategy, cfg in (("simple", CFG), ("fast", fused_cfg),
                          ("hybrid", CFG)):
        before = GeoEngine.from_index_set(idx, strategy, cfg)
        after = GeoEngine.from_index_set(loaded, strategy, cfg)
        _assert_assign_equal(before.assign(pts), after.assign(pts))


def test_index_set_rejects_wrong_schema_and_foreign_dirs(synth_small,
                                                         tmp_path):
    path = str(tmp_path / "art")
    GeoIndexSet.build(synth_small.census, components=("fast",),
                      max_level=7).save(path)
    mpath = os.path.join(path, MANIFEST_NAME)
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["schema_version"] = SCHEMA_VERSION + 1
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="schema_version"):
        GeoIndexSet.load(path)
    with pytest.raises(ValueError, match="manifest"):
        GeoIndexSet.load(str(tmp_path / "empty"))


def test_geoserver_cold_start_from_artifact(synth_small, points_small,
                                            tmp_path):
    """The acceptance path: save, reload through GeoServer.from_artifact,
    and serve bit-identically to a server built from the live census."""
    path = str(tmp_path / "art")
    cfg = dataclasses.replace(CFG, max_level=8)
    live_eng = GeoEngine.build(synth_small.census, "auto", cfg)
    live_eng.indices.save(path)
    live = GeoServer(live_eng, ServeConfig(buckets=(64, 256, 1024)))
    cold = GeoServer.from_artifact(path, strategy="auto", engine_cfg=cfg,
                                   cfg=ServeConfig(buckets=(64, 256,
                                                            1024)))
    assert cold.regions[0].engine.explain()["strategy"] == \
        live_eng.explain()["strategy"]
    xy, *_ = points_small
    for lo, hi in ((0, 700), (700, 703), (703, 2048)):
        a = live.submit(xy[lo:hi])
        b = cold.submit(xy[lo:hi])
        np.testing.assert_array_equal(a.block, b.block)
        np.testing.assert_array_equal(a.county, b.county)
        np.testing.assert_array_equal(a.state, b.state)
        np.testing.assert_array_equal(a.region, b.region)


def test_engine_build_auto_names_plan(synth_small):
    """Acceptance: build(census, strategy='auto') returns a working
    engine whose explain() names the chosen plan with reasons."""
    eng = GeoEngine.build(synth_small.census, "auto", CFG)
    info = eng.explain()
    assert info["strategy"] in ("simple", "fast", "hybrid")
    assert info["reasons"] and all(isinstance(r, str)
                                   for r in info["reasons"])
    assert json.loads(json.dumps(info)) == info      # JSON-clean
    assert eng.strategy == info["strategy"]
