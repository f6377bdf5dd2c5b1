"""The gather-PIP byte count against a brute-force count of candidate
edges, and the benchmark's map generator against the program's."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)
from benchlib import census, mapstore, points  # noqa: E402
from benchlib import harness  # noqa: E402


@pytest.fixture(scope="module")
def dep(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("maps"))
    return mapstore.deployment(bench_tiny.MAP, bench_tiny.ENGINE, cache)


def crosses(px, py, ring):
    """Crossing-number test of one point against one closed ring."""
    inside = False
    for (x1, y1), (x2, y2) in zip(ring, np.roll(ring, -1, axis=0)):
        if (y1 > py) != (y2 > py) and \
                px < x1 + (py - y1) * (x2 - x1) / (y2 - y1):
            inside = not inside
    return inside


def brute_force(dep, xy):
    """Bytes by walking every cell and every candidate, point by point."""
    cov = dep.indices.census.blocks, dep.indices.covering
    blocks, cov = cov
    q = np.array([cov.extent[0], cov.extent[2],
                  (1 << cov.max_level) / (cov.extent[1] - cov.extent[0]),
                  (1 << cov.max_level) / (cov.extent[3] - cov.extent[2])],
                 np.float32)
    total = 0
    for px, py in xy:
        fx = (np.float32(px) - q[0]) * q[2]
        fy = (np.float32(py) - q[1]) * q[3]
        ix, iy = int(fx), int(fy)
        code = 0
        for bit in range(cov.max_level):
            code |= ((ix >> bit) & 1) << (2 * bit)
            code |= ((iy >> bit) & 1) << (2 * bit + 1)
        rows = [i for i in range(len(cov.lo))
                if cov.lo[i] <= code <= cov.hi[i]]
        assert len(rows) == 1
        val = cov.val[rows[0]]
        if val >= 0:
            continue
        total += 8 + 4
        for b in cov.cand[-(val + 1)]:
            if b < 0:
                continue
            nv = blocks.n_verts[b]
            total += 16 * int(nv)
            if crosses(px, py, blocks.verts[b, :nv].astype(np.float64)):
                break
    return total


@pytest.mark.parametrize("sampler", ["uniform", "boundary"])
def test_gather_pip_bytes_match_brute_force(dep, sampler):
    rng = np.random.default_rng(11)
    if sampler == "uniform":
        xy, bid = points.uniform(dep.smap, rng, 300)
    else:
        xy, bid = points.boundary(dep.smap, dep.indices.covering, rng, 300)
    count = harness.plugin("counts", "gather_pip")
    got = count.needed(dep.indices.covering, dep.indices.census.blocks.n_verts,
                       xy, bid)
    assert got["bytes"] == brute_force(dep, xy)
    assert got["pip_points"] > 0
    if sampler == "boundary":
        assert got["pip_points"] == len(xy)


def test_boundary_points_lie_in_boundary_cells_and_truth_holds(dep):
    from benchlib.cells import cell_rows
    rng = np.random.default_rng(5)
    xy, bid = points.boundary(dep.smap, dep.indices.covering, rng, 500)
    assert (cell_rows(dep.indices.covering, xy) >= 0).all()
    blocks = dep.indices.census.blocks
    for (px, py), b in zip(xy[:100], bid[:100]):
        assert crosses(px, py, blocks.verts[b, :blocks.n_verts[b]])


def test_the_map_is_the_programs_map():
    from repro.core.synth import build_synth_census
    m = dict(bench_tiny.MAP)
    sc = build_synth_census(**m)
    mine = mapstore._census_of(census.build_map(**m))
    for lvl in ("states", "counties", "blocks"):
        a, b = getattr(sc.census, lvl), getattr(mine, lvl)
        assert np.array_equal(a.verts, b.verts)
        assert np.array_equal(a.parent, b.parent)
    assert sc.census.extent == mine.extent
