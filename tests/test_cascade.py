"""One-pass fused cascade (kernels/cascade.py, strategy "fast_onepass"):
backend bit-identity (interpret vs the ref oracle), engine-level
agreement with the simple / fast / hybrid drivers, accounting parity
(``onepass_stats`` vs the two-phase schedule), exactness where
fast_exact's compaction caps overflow, padded / off-extent handling, and
the autotune manifest round trip (schema v2) with the planner reading
the recorded winner.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fast as fast_mod
from repro.core.artifact import SCHEMA_VERSION, GeoIndexSet
from repro.core.engine import EngineConfig, GeoEngine
from repro.core.plan import plan_for
from repro.core.resolve import onepass_stats
from repro.kernels import ops
from repro.serving.server import GeoServer

CFG = EngineConfig(backend="ref", cap_state=1.0, cap_county=1.0,
                   cap_block=1.0, cap_boundary=1.0, max_level=8)


@pytest.fixture(scope="module")
def engines(synth_small):
    census = synth_small.census
    fast = GeoEngine.build(census, "fast", CFG)
    cov = fast.covering            # share the host BFS across builds
    return {
        "fast": fast,
        "simple": GeoEngine.build(census, "simple", CFG, covering=cov),
        "hybrid": GeoEngine.build(census, "hybrid", CFG, covering=cov),
        "onepass": GeoEngine.build(census, "fast_onepass", CFG,
                                   covering=cov),
        # The EngineConfig spelling of the same plan.
        "onepass_cfg": GeoEngine.build(
            census, "fast", dataclasses.replace(CFG, fused="onepass"),
            covering=cov),
    }


def _ids(res):
    return tuple(np.asarray(a) for a in (res.state, res.county, res.block))


def _stats(res):
    return {k: int(v) for k, v in res.stats.as_dict().items()}


# ------------------------------------------------ engine-level bit-identity
def test_onepass_bitexact_vs_fast_exact(engines, points_small):
    """The acceptance bar: fast_onepass == fast_exact on ids AND the
    GeoStats counters (n_pip accounting included), not just accuracy."""
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    f = engines["fast"].assign(pts)
    o = engines["onepass"].assign(pts)
    for a, b in zip(_ids(f), _ids(o)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(_ids(o)[2], bid)
    assert _stats(f) == _stats(o)
    assert _stats(o)["overflow"] == 0 and _stats(o)["phase2_miss"] == 0


def test_onepass_cfg_spelling_is_the_same_plan(engines, points_small):
    """GeoEngine.build(census, "fast", EngineConfig(fused="onepass"))
    and strategy="fast_onepass" run the identical kernel path."""
    xy, *_ = points_small
    pts = jnp.asarray(xy)
    a = engines["onepass"].assign(pts)
    b = engines["onepass_cfg"].assign(pts)
    for x, y in zip(_ids(a), _ids(b)):
        np.testing.assert_array_equal(x, y)
    assert _stats(a) == _stats(b)


def test_onepass_agrees_with_simple_and_hybrid(engines, points_small):
    """Cross-driver agreement: the one-pass ids match the cascade and the
    hybrid drivers wherever those are exact (generous caps make them
    exact everywhere on the synthetic map)."""
    xy, bid, *_ = points_small
    pts = jnp.asarray(xy)
    o = np.asarray(engines["onepass"].assign(pts).block)
    np.testing.assert_array_equal(
        o, np.asarray(engines["simple"].assign(pts).block))
    np.testing.assert_array_equal(
        o, np.asarray(engines["hybrid"].assign(pts).block))
    np.testing.assert_array_equal(o, bid)


def test_onepass_padded_parity(engines, points_small):
    """assign_padded == assign on the valid prefix (ids and stats); pad
    rows come back -1."""
    xy, *_ = points_small
    pts = jnp.asarray(xy[:1000])
    padded = jnp.pad(pts, ((0, 24), (0, 0)))
    rp = engines["onepass"].assign_padded(padded, 1000)
    ru = engines["onepass"].assign(pts)
    for a, b in zip(_ids(rp), _ids(ru)):
        np.testing.assert_array_equal(a[:1000], b)
        assert (a[1000:] == -1).all()
    assert _stats(rp) == _stats(ru)


def test_onepass_rejects_off_extent(engines):
    """Points outside the quantization extent answer -1 at every level
    and never enter the boundary path (flags stay 0 in the raw op)."""
    x0, x1, y0, y1 = engines["fast"].census.extent
    far = jnp.asarray([[x1 + 1.0, y0], [x0 - 1.0, y1],
                       [x0, y1 + 2.0], [1e30, 1e30]], jnp.float32)
    res = engines["onepass"].assign(far)
    for a in _ids(res):
        assert (a == -1).all()
    idx = engines["onepass"].fast_index
    _, flags, nrest, nskip = ops.assign_cascade(
        far, idx.quant, idx.cell_lo, idx.cell_hi, idx.cell_val,
        idx.top_start, idx.cand, idx.block_bbox, idx.edge_pool,
        max_level=idx.max_level, gbits=idx.gbits,
        search_iters=idx.search_iters, backend="ref")
    assert (np.asarray(flags) == 0).all()
    assert (np.asarray(nrest) == 0).all()
    assert (np.asarray(nskip) == 0).all()


# ----------------------------------------------- kernel backend bit-identity
def test_interpret_matches_ref_bitexact(engines, points_small):
    """The Pallas kernel under interpret=True produces bit-identical
    (bid, flags, nrest, nskip) to the vectorized ref oracle — same fp32
    arithmetic, same candidate schedule, same DMA'd edge blocks."""
    xy, *_ = points_small
    idx = engines["onepass"].fast_index
    x0, _, y0, _ = engines["fast"].census.extent
    pts = np.concatenate([xy[:252].astype(np.float32),
                          [[x0 - 5.0, y0], [1e30, 1e30],
                           [x0 - 1.0, y0 - 1.0], [0.0, 1e30]]],
                         axis=0)
    outs = {}
    for backend in ("ref", "interpret"):
        outs[backend] = ops.assign_cascade(
            jnp.asarray(pts), idx.quant, idx.cell_lo, idx.cell_hi,
            idx.cell_val, idx.top_start, idx.cand, idx.block_bbox,
            idx.edge_pool, max_level=idx.max_level, gbits=idx.gbits,
            search_iters=idx.search_iters, backend=backend)
    for a, b in zip(outs["interpret"], outs["ref"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The slice must exercise the boundary path for the parity to mean
    # anything.
    assert (np.asarray(outs["ref"][1]) & 1).sum() > 0


# ------------------------------------------------------ accounting parity
def test_onepass_stats_accounting():
    """``onepass_stats`` reproduces the two-phase n_pip formula from the
    kernel's raw counters: every boundary point pays the slot-0 test;
    slot-0 misses additionally pay each valid rest slot."""
    flags = jnp.asarray([0, 1, 3, 1, 0, 1], jnp.int32)   # bit0=boundary,
    nrest = jnp.asarray([9, 2, 4, 0, 9, 3], jnp.int32)   # bit1=slot0 hit
    nskip = jnp.asarray([9, 1, 0, 2, 9, 0], jnp.int32)
    st = onepass_stats(flags, nrest, nskip)
    assert int(st["n_boundary"]) == 4
    # slot-0 hits (row 2) pay 1 PIP; misses (rows 1, 3, 5) pay 1 + nrest.
    assert int(st["n_pip"]) == 4 + (2 + 0 + 3)
    assert int(st["overflow"]) == 0
    assert int(st["phase2_miss"]) == 0
    # Non-boundary rows (0, 4) never contribute, whatever their counters.
    assert int(st["bbox_skips"]) == 1 + 0 + 2


def test_onepass_exact_where_two_phase_overflows(engines, synth_small):
    """Feed more boundary-cell points than the two-phase compaction cap:
    fast_exact overflows (counted, degraded to the fallback candidate);
    the one-pass kernel has no compaction buffer, so it reports zero
    overflow and stays bit-identical to an uncapped fast_exact."""
    census = synth_small.census
    cov = engines["fast"].covering
    idx = engines["fast"].fast_index
    lo = np.asarray(cov.lo)
    codes = lo[np.asarray(cov.val) < 0][:512]
    ix, iy = fast_mod.demorton(jnp.asarray(codes.astype(np.int32)))
    q = np.asarray(idx.quant)
    pts = np.stack([q[0] + (np.asarray(ix) + 0.5) / q[2],
                    q[1] + (np.asarray(iy) + 0.5) / q[3]],
                   -1).astype(np.float32)
    pts = jnp.asarray(np.tile(pts, (2, 1)))          # ~1024 boundary pts
    small_cap = GeoEngine.build(
        census, "fast", dataclasses.replace(CFG, cap_boundary=0.01),
        covering=cov)
    capped = small_cap.assign(pts)
    assert _stats(capped)["overflow"] > 0
    one = engines["onepass"].assign(pts)
    full = engines["fast"].assign(pts)
    assert _stats(one)["overflow"] == 0
    np.testing.assert_array_equal(np.asarray(one.block),
                                  np.asarray(full.block))
    assert _stats(one)["n_boundary"] == pts.shape[0]


# ------------------------------------------- autotune manifest round trip
def test_tuning_roundtrip_and_planner(engines, synth_small, tmp_path):
    """record_tuning -> save -> load round-trips the autotune block
    (schema v2) and a reloaded artifact's auto plan follows the recorded
    winner for the matching device kind."""
    path = str(tmp_path / "tuned")
    iset = GeoIndexSet(census=synth_small.census,
                       covering=engines["fast"].covering, max_level=8)
    tuning = {"winner": "fast_onepass", "be": 128,
              "device_kind": jax.devices()[0].device_kind,
              "pts_per_sec": 1.5e6, "roofline_fraction": 0.25,
              "recorded": "2026-08-08T00:00:00"}
    iset.record_tuning(tuning)
    iset.save(path)
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    assert manifest["schema_version"] == SCHEMA_VERSION == 2
    assert manifest["tuning"] == tuning

    i2 = GeoIndexSet.load(path)
    assert i2.tuning == tuning
    assert i2.pool_be() == 128
    eng = GeoEngine.from_index_set(i2, strategy="auto")
    assert eng.strategy == "fast_onepass"
    assert eng.plan.fused == "onepass"
    assert any("autotune" in r for r in eng.plan.reasons)
    # The tuned block size reaches the actual pool packing.
    assert eng.fast_index.edge_pool.be == 128


def test_roofline_peaks_keyed_by_device_kind():
    """Roofline shares use published peaks of the chip JAX reports; a
    device kind with no published peaks is an error, never a default."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "roofline.py")
    spec = importlib.util.spec_from_file_location("_roofline", path)
    roofline = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roofline)
    assert roofline.device_peaks("TPU v5 lite") == (197e12, 819e9)
    for kind in ("cpu", "tpu", "TPU v4"):
        with pytest.raises(ValueError, match="no published peaks"):
            roofline.device_peaks(kind)


def test_planner_ignores_foreign_device_tuning():
    """A winner recorded on another device kind must not transfer, and
    on compiled Pallas kernels (a TPU's backend) a recorded one-pass win
    is never followed: that kernel does not compile for the TPU."""
    caps = {"census": True, "covering": True, "fast": True,
            "fast_pool": True, "simple": False, "simple_pool": False,
            "sharded": []}
    tune = {"winner": "fast_onepass", "be": 256, "device_kind": "cpu"}
    here = plan_for(EngineConfig(), capabilities=caps, tuning=tune,
                    platform="tpu", device_kind="TPU v5 lite")
    assert here.strategy != "fast_onepass"
    there = plan_for(EngineConfig(backend="ref"), capabilities=caps,
                     tuning=tune, platform="cpu", device_kind="cpu")
    assert there.strategy == "fast_onepass"
    assert there.fused == "onepass"
    assert there.device_kind == "cpu" and there.platform == "cpu"
    chip = dict(tune, device_kind="TPU v5 lite")
    on_chip = plan_for(EngineConfig(backend="pallas"), capabilities=caps,
                       tuning=chip, platform="tpu",
                       device_kind="TPU v5 lite")
    assert on_chip.strategy == "fast" and on_chip.fused is True
    assert any("does not compile for the TPU" in r
               for r in on_chip.reasons)


def test_load_accepts_v1_manifest(engines, synth_small, tmp_path):
    """A pre-tuning artifact (schema v1, no tuning block) still loads,
    with an empty tuning record and the default pool block size."""
    path = str(tmp_path / "v1")
    GeoIndexSet(census=synth_small.census,
                covering=engines["fast"].covering, max_level=8).save(path)
    mpath = os.path.join(path, "manifest.json")
    manifest = json.load(open(mpath))
    manifest["schema_version"] = 1
    del manifest["tuning"]
    json.dump(manifest, open(mpath, "w"))
    iset = GeoIndexSet.load(path)
    assert iset.tuning == {}
    assert iset.pool_be() == ops.DEF_BE


def test_load_rejects_unknown_schema(synth_small, tmp_path):
    path = str(tmp_path / "future")
    GeoIndexSet(census=synth_small.census).save(path)
    mpath = os.path.join(path, "manifest.json")
    manifest = json.load(open(mpath))
    manifest["schema_version"] = SCHEMA_VERSION + 1
    json.dump(manifest, open(mpath, "w"))
    with pytest.raises(ValueError, match="schema_version"):
        GeoIndexSet.load(path)


# ------------------------------------------------------- serving surface
def test_server_surfaces_footprint_gauges(engines):
    """GeoServer exposes the built index's memory footprint (edge-pool
    bytes + chosen block size) as per-region gauges at construction."""
    srv = GeoServer(engines["onepass"])
    gauges = srv.metrics.snapshot()["gauges"]
    assert gauges["region0_pool_be"] == ops.DEF_BE
    assert gauges["region0_edge_pool_bytes"] > 0
    assert gauges["region0_edge_pool_blocks"] > 0
    assert gauges["region0_index_bytes"] > 0
