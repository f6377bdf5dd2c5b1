"""The deployment's fixed data: its census map and the map's covering.

A census release does not change per query, so the map is built from the
configuration's own map seed, and the query points come from ``--seed``.
The covering walk is host Python and takes about a minute on 13 cores, so
the first run of a configuration in a checkout saves what it built under
``bench/.cache/maps/<key>/``: the program's own artifact
(``GeoIndexSet.save``: census and covering) and the benchmark's ground-truth
tables (chart rectangles, parents, warp).  Later runs load both.  The key
hashes the map parameters, the covering depth, and the source of the code
that builds them, so a change to that code builds afresh.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import time

import numpy as np

from benchlib import census as census_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(BENCH, ".cache", "maps")
# The code whose output is cached: this package's generator and the
# program's geometry, covering walk and artifact format.
BUILDERS = (os.path.join(BENCH, "benchlib", "census.py"),
            os.path.join(ROOT, "src", "repro", "core", "geometry.py"),
            os.path.join(ROOT, "src", "repro", "core", "cells.py"),
            os.path.join(ROOT, "src", "repro", "core", "artifact.py"))
MAP_KEYS = ("seed", "n_states", "counties_per_state", "blocks_per_county")


@dataclasses.dataclass
class Deployment:
    """What every cell of a configuration shares."""

    smap: census_mod.SynthMap   # ground truth (rings dropped when loaded)
    indices: object             # repro GeoIndexSet: census + covering
    built_s: float              # seconds spent building (0 when loaded)


def cache_key(map_cfg: dict, index_cfg: dict) -> str:
    h = hashlib.sha256(json.dumps([map_cfg, index_cfg],
                                  sort_keys=True).encode())
    for path in BUILDERS:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def _census_of(smap: census_mod.SynthMap):
    """The map in the program's input format (``CensusMap``)."""
    from repro.core.geometry import CensusMap, pack_rings
    soups = {}
    for lvl, base in (("states", 1_000), ("counties", 10_000),
                      ("blocks", 100_000_000)):
        n = len(smap.rects[lvl])
        soups[lvl] = pack_rings(smap.rings[lvl], parent=smap.parents[lvl],
                                fips=base + np.arange(n, dtype=np.int64))
    boxes = [s.bbox for s in soups.values()]
    extent = (min(float(b[:, 0].min()) for b in boxes),
              max(float(b[:, 1].max()) for b in boxes),
              min(float(b[:, 2].min()) for b in boxes),
              max(float(b[:, 3].max()) for b in boxes))
    return CensusMap(extent=extent, **soups)


def _save_truth(path: str, smap: census_mod.SynthMap) -> None:
    w = smap.warp
    arrays = {f"rects_{k}": v for k, v in smap.rects.items()}
    arrays.update({f"parents_{k}": v for k, v in smap.parents.items()})
    arrays.update(warp=np.stack([w.ax, w.ay, w.kx, w.ky, w.px, w.py]),
                  sagitta=np.float64(smap.sagitta))
    np.savez(os.path.join(path, "truth.npz"), **arrays)


def _load_truth(path: str) -> census_mod.SynthMap:
    with np.load(os.path.join(path, "truth.npz")) as z:
        levels = ("states", "counties", "blocks")
        return census_mod.SynthMap(
            warp=census_mod.Warp(*z["warp"]),
            rects={k: z[f"rects_{k}"] for k in levels},
            parents={k: z[f"parents_{k}"] for k in levels},
            rings={}, sagitta=float(z["sagitta"]))


def deployment(map_cfg: dict, index_cfg: dict,
               cache: str = CACHE) -> Deployment:
    """Load the configuration's map and covering, building them once.

    ``map_cfg`` holds ``MAP_KEYS``; ``index_cfg`` holds ``max_level``,
    ``gbits`` and ``max_cand``."""
    from repro.core.artifact import GeoIndexSet
    from repro.core.cells import build_cell_covering
    m = {k: map_cfg[k] for k in MAP_KEYS}
    ix = {k: index_cfg[k] for k in ("max_level", "gbits", "max_cand")}
    path = os.path.join(cache, cache_key(m, ix))
    if os.path.exists(os.path.join(path, "truth.npz")):
        return Deployment(_load_truth(path), GeoIndexSet.load(path), 0.0)
    t0 = time.perf_counter()
    smap = census_mod.build_map(**m)
    census = _census_of(smap)
    cov = build_cell_covering(census, max_level=ix["max_level"],
                              max_cand=ix["max_cand"])
    indices = GeoIndexSet(census=census, covering=cov, **ix)
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    indices.save(tmp)
    _save_truth(tmp, smap)
    shutil.rmtree(path, ignore_errors=True)
    os.replace(tmp, path)
    return Deployment(smap, indices, time.perf_counter() - t0)
