"""The level-synchronous covering build (core/cells.py) against the plain
recursive walk (tests/covering_ref.py), array for array; its per-level
counters; and their round trip through the saved artifact's manifest."""
import json
import os

import numpy as np
import pytest

from covering_ref import reference_covering

from repro.core import cells
from repro.core.artifact import MANIFEST_NAME, GeoIndexSet
from repro.core.cells import build_cell_covering
from repro.core.synth import build_synth_census

FIELDS = ("lo", "hi", "val", "level", "cand")

# name -> the synth census's arguments
MAPS = {
    "small": dict(seed=0, n_states=8, counties_per_state=4,
                  blocks_per_county=16),
    "mid": dict(seed=1, n_states=16, counties_per_state=8,
                blocks_per_county=24),
    # Four large blocks: interior cells from level 3 on, so
    # min_split_level decides cells.
    "coarse": dict(seed=3, n_states=2, counties_per_state=2,
                   blocks_per_county=2),
}


@pytest.fixture(scope="module")
def censuses():
    built = {}

    def get(name):
        if name not in built:
            built[name] = build_synth_census(**MAPS[name]).census
        return built[name]
    return get


@pytest.mark.parametrize("name,kw,chunk", [
    ("small", dict(max_level=5), None),
    ("small", dict(max_level=7), None),
    ("small", dict(max_level=8), None),
    ("small", dict(max_level=9), None),
    ("mid", dict(max_level=7), None),
    # Boundary cells with up to 7 candidates at level 5, 4 at level 7.
    ("small", dict(max_level=5, max_cand=3), None),
    ("small", dict(max_level=7, max_cand=2), None),
    ("coarse", dict(max_level=6, min_split_level=4), None),
    # Boundary cells at the root's level, and above min_split_level.
    ("coarse", dict(max_level=1), None),
    # Levels split into many chunks, and groups larger than a chunk.
    ("small", dict(max_level=7), 64),
], ids=lambda v: "" if v is None else str(v))
def test_covering_matches_reference_walk(censuses, monkeypatch, name, kw,
                                         chunk):
    census = censuses(name)
    if chunk is not None:
        monkeypatch.setattr(cells, "CHUNK_PAIRS", chunk)
    got = build_cell_covering(census, **kw)
    want = reference_covering(census, **kw)
    for f in FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype, f
        np.testing.assert_array_equal(a, b, err_msg=f)
    assert (got.n_interior, got.n_boundary) == (want.n_interior,
                                                want.n_boundary)
    got.validate_partition()


@pytest.mark.parametrize("name,kw", [("small", dict(max_level=8)),
                                     ("coarse", dict(max_level=6,
                                                     min_split_level=4))])
def test_build_stats_add_up(censuses, name, kw):
    cov = build_cell_covering(censuses(name), **kw)
    stats = cov.build_stats
    assert [s["level"] for s in stats] == list(range(len(stats)))
    assert len(stats) == kw["max_level"] + 1
    assert sum(s["interior"] + s["boundary"] for s in stats) == len(cov.lo)
    assert sum(s["boundary"] for s in stats) == cov.n_boundary
    assert sum(s["interior"] for s in stats) == cov.n_interior
    # Boundary cells only at the deepest level, and each level's cells
    # at that level.
    assert all(s["boundary"] == 0 for s in stats[:-1])
    for s in stats:
        assert int((cov.level == s["level"]).sum()) == (s["interior"]
                                                        + s["boundary"])
        assert s["nodes"] >= s["interior"] + s["boundary"]
        assert s["edge_pairs"] >= 0 and s["seconds"] >= 0
    assert stats[0]["nodes"] == 1


def test_manifest_round_trips_build_stats(censuses, tmp_path):
    path = str(tmp_path / "art")
    cov = build_cell_covering(censuses("small"), max_level=6)
    GeoIndexSet(census=censuses("small"), covering=cov,
                max_level=6).save(path)
    with open(os.path.join(path, MANIFEST_NAME)) as f:
        assert json.load(f)["covering_build"] == cov.build_stats
    loaded = GeoIndexSet.load(path)
    assert loaded.covering.build_stats == cov.build_stats
    for f in FIELDS:
        np.testing.assert_array_equal(getattr(loaded.covering, f),
                                      getattr(cov, f))


def test_manifest_without_build_stats_loads(censuses, tmp_path):
    """An artifact saved before the build recorded its counters."""
    path = str(tmp_path / "old")
    cov = build_cell_covering(censuses("small"), max_level=6)
    GeoIndexSet(census=censuses("small"), covering=cov,
                max_level=6).save(path)
    mpath = os.path.join(path, MANIFEST_NAME)
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["covering_build"]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    loaded = GeoIndexSet.load(path)
    assert loaded.covering.build_stats == []
    assert (loaded.covering.n_interior,
            loaded.covering.n_boundary) == (cov.n_interior, cov.n_boundary)
    np.testing.assert_array_equal(loaded.covering.cand, cov.cand)


def test_build_opens_its_profiler_ranges(censuses, monkeypatch):
    from repro.obs import profile
    seen = []

    class Range:
        def __init__(self, name, **kw):
            seen.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(profile, "range_factory", Range)
    build_cell_covering(censuses("small"), max_level=4)
    assert seen[0] == ("geo/cover", {"max_level": 4, "blocks": 512})
    assert seen[1:] == [("geo/cover/level", {"level": lvl})
                        for lvl in range(5)]
