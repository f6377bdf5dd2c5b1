"""GeoIndexSet: the unified index artifact behind every strategy
(DESIGN.md §11).

One object owns everything a ``GeoEngine`` (or a registered third-party
strategy) can look points up against:

  * the host census geometry (``CensusMap``) and the quadtree cell
    covering (``CellCovering``) — the expensive-to-build host artifacts;
  * the device indices derived from them: ``SimpleIndex`` (cascade),
    ``FastIndex`` (cell lookup), ``ShardedFastIndex`` per shard count —
    each with or without the blocked-CSR edge pools the fused gather-PIP
    kernel needs;
  * a capability snapshot (``capabilities()``) the registry's build-time
    validation and the planner read, so a fused config meeting a
    pool-less index fails at construction, never at the first assign.

Components build lazily through ``ensure`` — strategies declare what
they need (registry capability flags) and the engine ensures exactly
that, so nothing is built twice and nothing unused is built at all.

**Persistence** (``save``/``load``): the artifact serializes its *host*
primitives — census polygon soups and covering arrays — as one
compressed npz beside a JSON manifest (schema-version checked).  Device
indices are deliberately NOT serialized: they are cheap, deterministic
functions of the saved arrays (``SimpleIndex.from_census``,
``FastIndex.from_covering``), so a reload followed by ``ensure``
reconstructs them bit-identically while the artifact on disk stays
small, portable across jax versions, and independent of device layout.
What cold start actually buys is skipping the covering BFS — the one
build step that scales with map complexity rather than array size.

    idx = GeoIndexSet.build(census, components=("fast",), gbits=4)
    idx.save("artifacts/national")
    ...
    idx = GeoIndexSet.load("artifacts/national")
    eng = GeoEngine.from_index_set(idx, strategy="auto")
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Optional

import numpy as np

from repro.core.cells import CellCovering, build_cell_covering
from repro.core.distributed import (ShardedFastIndex, place_sharded,
                                    shard_covering)
from repro.core.fast import FastIndex
from repro.core.geometry import CensusMap, PolygonSoup
from repro.core.simple import SimpleIndex
from repro.kernels import ops

# v2 adds the ``tuning`` manifest block (autotuned one-pass kernel
# config, DESIGN.md §13); v1 artifacts load with empty tuning.
SCHEMA_VERSION = 2
ACCEPTED_SCHEMA_VERSIONS = (1, 2)
MANIFEST_NAME = "manifest.json"
ARRAYS_NAME = "arrays.npz"
FORMAT_NAME = "geo-index-set"

_SOUP_FIELDS = ("verts", "n_verts", "bbox", "parent", "fips")
_COVER_FIELDS = ("lo", "hi", "val", "level", "cand")
_LEVELS = ("states", "counties", "blocks")


@dataclasses.dataclass
class GeoIndexSet:
    """Unified, lazily-built index artifact (see module docstring).

    ``max_level`` / ``gbits`` / ``max_cand`` are the covering/index build
    parameters (the same knobs ``EngineConfig`` carries); they are fixed
    per artifact so every component agrees on quantization.
    """

    census: Optional[CensusMap] = None
    covering: Optional[CellCovering] = None
    simple: Optional[SimpleIndex] = None
    fast: Optional[FastIndex] = None
    sharded: Dict[int, ShardedFastIndex] = \
        dataclasses.field(default_factory=dict)
    max_level: int = 9
    gbits: int = 4
    max_cand: int = 8
    # Autotune record (benchmarks/geo_perf.py --autotune): winning
    # strategy + edge-pool block size + the measurement context.  Rides
    # in the manifest (schema v2) so a reloaded artifact plans from
    # recorded measurements, not hard-coded thresholds.  Keys (all
    # optional): "winner", "be", "device_kind", "pts_per_sec",
    # "roofline_fraction", "recorded".
    tuning: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, census: CensusMap, components=(), pools=(), *,
              max_level: int = 9, gbits: int = 4, max_cand: int = 8,
              covering: Optional[CellCovering] = None) -> "GeoIndexSet":
        """Build the requested ``components`` ("simple" | "fast" |
        "covering") from a host census; ``pools`` names the components
        that additionally need their blocked-CSR edge pools (the fused
        gather-PIP path)."""
        self = cls(census=census, covering=covering, max_level=max_level,
                   gbits=gbits, max_cand=max_cand)
        for comp in components:
            self.ensure(comp)
        for comp in pools:
            self.ensure(comp, pool=True)
        return self

    def ensure(self, component: str, pool: bool = False) -> None:
        """Build ``component`` if missing (and its edge pool, when
        ``pool``).  Pools attach to an already-built index in place —
        bit-identical to building with pools up front, since both paths
        pack the same edge arrays through ``ops.build_edge_pool``."""
        if component == "covering":
            if self.covering is None:
                self._need_census("the cell covering")
                self.covering = build_cell_covering(
                    self.census, max_level=self.max_level,
                    max_cand=self.max_cand)
        elif component == "simple":
            if self.simple is None:
                self._need_census("the simple (cascade) index")
                self.simple = SimpleIndex.from_census(self.census,
                                                      with_pools=False)
            if pool and self.simple.state_pool is None:
                be = self.pool_be()
                self.simple = dataclasses.replace(
                    self.simple,
                    state_pool=ops.build_edge_pool(
                        np.asarray(self.simple.state_edges), be=be),
                    county_pool=ops.build_edge_pool(
                        np.asarray(self.simple.county_edges), be=be),
                    block_pool=ops.build_edge_pool(
                        np.asarray(self.simple.block_edges), be=be))
        elif component == "fast":
            if self.fast is None:
                self._need_census("the fast (cell) index")
                self.ensure("covering")
                self.fast = FastIndex.from_covering(
                    self.covering, self.census, gbits=self.gbits,
                    with_pool=False)
            if pool and self.fast.edge_pool is None:
                self.fast = dataclasses.replace(
                    self.fast,
                    edge_pool=ops.build_edge_pool(
                        np.asarray(self.fast.block_edges),
                        be=self.pool_be()))
        else:
            raise ValueError(f"unknown index component {component!r}; "
                             f"expected 'simple', 'fast', or 'covering'")

    def _need_census(self, what: str) -> None:
        if self.census is None:
            raise ValueError(f"building {what} needs a census "
                             f"(GeoIndexSet built from arrays only?)")

    def sharded_index(self, n_shards: int, with_pool: bool = False,
                      mesh=None) -> ShardedFastIndex:
        """The Morton-sharded index for ``n_shards``, built once per
        shard count (pool attached on demand, like ``ensure``).  With a
        ``mesh``, the cached index is laid out on it (``place_sharded``:
        one table shard per device of its "model" axis), so repeat calls
        on that mesh hand jit arrays that are already in place."""
        if n_shards not in self.sharded:
            if self.covering is None or self.census is None:
                raise ValueError("assign_sharded needs the engine built "
                                 "from a census with a cell covering "
                                 "(strategy 'fast' or 'hybrid')")
            self.sharded[n_shards] = shard_covering(
                self.covering, self.census, n_shards, with_pool=False)
        if with_pool and self.sharded[n_shards].edge_pool is None:
            sidx = self.sharded[n_shards]
            self.sharded[n_shards] = dataclasses.replace(
                sidx, edge_pool=ops.build_edge_pool(
                    np.asarray(sidx.block_edges), be=self.pool_be()))
        if mesh is not None:
            self.sharded[n_shards] = place_sharded(self.sharded[n_shards],
                                                   mesh)
        return self.sharded[n_shards]

    # -- autotune record ----------------------------------------------------

    def pool_be(self) -> int:
        """Edge-pool block size (edges per CSR block): the autotuned
        value when one is recorded, ``ops.DEF_BE`` otherwise.  Every
        pool this artifact attaches (simple / fast / sharded) is packed
        at this size, so the one-pass kernel's DMA granularity matches
        the recorded winner."""
        return int(self.tuning.get("be") or 0) or ops.DEF_BE

    def record_tuning(self, tuning: Dict[str, Any]) -> None:
        """Merge an autotune result into the artifact (persisted by
        ``save``).  When the recorded ``be`` differs from the pools
        already built, the built pools are dropped so the next
        ``ensure(..., pool=True)`` repacks at the tuned size."""
        old_be = self.pool_be()
        self.tuning = {**self.tuning, **tuning}
        if self.pool_be() != old_be:
            if self.fast is not None and self.fast.edge_pool is not None:
                self.fast = dataclasses.replace(self.fast, edge_pool=None)
            if self.simple is not None \
                    and self.simple.state_pool is not None:
                self.simple = dataclasses.replace(
                    self.simple, state_pool=None, county_pool=None,
                    block_pool=None)
            for n, sidx in list(self.sharded.items()):
                if sidx.edge_pool is not None:
                    self.sharded[n] = dataclasses.replace(
                        sidx, edge_pool=None)

    def memory_footprint(self) -> Dict[str, int]:
        """Flat numeric snapshot of the built device-index memory (bytes
        + chosen tile sizes), for serving metrics gauges.  Only counts
        what is built right now — a lazy artifact reports 0s."""
        fp = {"pool_be": self.pool_be(), "edge_pool_bytes": 0,
              "edge_pool_blocks": 0, "edge_pool_max_blocks": 0,
              "index_bytes": 0}
        if self.fast is not None:
            for leaf in (self.fast.cell_lo, self.fast.cell_hi,
                         self.fast.cell_val, self.fast.top_start,
                         self.fast.cand, self.fast.block_bbox):
                if leaf is not None:
                    fp["index_bytes"] += int(np.asarray(leaf).nbytes)
            pool = self.fast.edge_pool
            if pool is not None:
                fp["edge_pool_bytes"] = int(
                    np.asarray(pool.blocks).nbytes
                    + np.asarray(pool.first).nbytes
                    + np.asarray(pool.count).nbytes)
                fp["edge_pool_blocks"] = int(pool.blocks.shape[0])
                fp["edge_pool_max_blocks"] = int(pool.max_blocks)
        return fp

    # -- capability snapshot (registry validation, planner) -----------------

    def capabilities(self) -> Dict[str, Any]:
        """What is built right now — the dict the registry's build-time
        validation and the planner's capability-constrained replanning
        read (keys: census, covering, simple, fast, simple_pool,
        fast_pool, sharded: list of shard counts)."""
        return {
            "census": self.census is not None,
            "covering": self.covering is not None,
            "simple": self.simple is not None,
            "fast": self.fast is not None,
            "simple_pool": (self.simple is not None
                            and self.simple.state_pool is not None),
            "fast_pool": (self.fast is not None
                          and self.fast.edge_pool is not None),
            "sharded": sorted(self.sharded),
        }

    # -- persistence --------------------------------------------------------

    def save(self, path: str) -> str:
        """Write the artifact under directory ``path`` (created if
        missing): ``manifest.json`` + ``arrays.npz``.  Saves the host
        primitives (census soups, covering intervals) — see the module
        docstring for why device indices are derived, not stored."""
        if self.census is None:
            raise ValueError("GeoIndexSet.save needs at least a census")
        os.makedirs(path, exist_ok=True)
        arrays: Dict[str, np.ndarray] = {}
        for lvl in _LEVELS:
            soup = getattr(self.census, lvl)
            for f in _SOUP_FIELDS:
                arrays[f"census_{lvl}_{f}"] = np.asarray(getattr(soup, f))
        # Extent rides in the npz (float64, exact) — the quant-vector
        # formula (fast.quant_for_extent) must see bit-identical bounds
        # after a reload or host/device cache keys fork.
        arrays["extent"] = np.asarray(self.census.extent, np.float64)
        components = ["census"]
        if self.covering is not None:
            for f in _COVER_FIELDS:
                arrays[f"covering_{f}"] = np.asarray(
                    getattr(self.covering, f))
            components.append("covering")
        manifest = {
            "format": FORMAT_NAME,
            "schema_version": SCHEMA_VERSION,
            "components": components,
            "max_level": int(self.max_level),
            "gbits": int(self.gbits),
            "max_cand": int(self.max_cand),
            "counts": {
                "states": self.census.states.n_poly,
                "counties": self.census.counties.n_poly,
                "blocks": self.census.blocks.n_poly,
                "cells": (0 if self.covering is None
                          else int(len(self.covering.lo))),
            },
            # Informational only — load() re-derives device indices.
            "built": self.capabilities(),
            # Autotune record (schema v2): round-trips verbatim so a
            # reloaded artifact plans from recorded measurements.
            "tuning": self.tuning,
            # The covering build's per-level counters (informational;
            # absent from artifacts saved before it was recorded).
            "covering_build": ([] if self.covering is None
                               else self.covering.build_stats),
        }
        np.savez_compressed(os.path.join(path, ARRAYS_NAME), **arrays)
        with open(os.path.join(path, MANIFEST_NAME), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "GeoIndexSet":
        """Reload an artifact directory; ValueError on a missing/foreign/
        newer-schema manifest.  Device indices rebuild lazily via
        ``ensure`` (bit-identical — see ``save``)."""
        mpath = os.path.join(path, MANIFEST_NAME)
        if not os.path.exists(mpath):
            raise ValueError(f"no {MANIFEST_NAME} under {path!r} — not a "
                             f"saved GeoIndexSet")
        with open(mpath) as f:
            manifest = json.load(f)
        if manifest.get("format") != FORMAT_NAME:
            raise ValueError(f"manifest format {manifest.get('format')!r} "
                             f"is not {FORMAT_NAME!r}")
        version = manifest.get("schema_version")
        if version not in ACCEPTED_SCHEMA_VERSIONS:
            raise ValueError(
                f"unsupported schema_version {version!r} (this build "
                f"reads versions {sorted(ACCEPTED_SCHEMA_VERSIONS)}); "
                f"re-save the artifact with a matching build")
        with np.load(os.path.join(path, ARRAYS_NAME)) as z:
            arrays = {k: z[k] for k in z.files}
        extent = tuple(float(v) for v in arrays["extent"])
        soups = {}
        for lvl in _LEVELS:
            soups[lvl] = PolygonSoup(
                **{f: arrays[f"census_{lvl}_{f}"] for f in _SOUP_FIELDS})
        census = CensusMap(states=soups["states"],
                           counties=soups["counties"],
                           blocks=soups["blocks"], extent=extent)
        covering = None
        if "covering" in manifest.get("components", ()):
            val = arrays["covering_val"]
            covering = CellCovering(
                **{f: arrays[f"covering_{f}"] for f in _COVER_FIELDS},
                max_level=int(manifest["max_level"]), extent=extent,
                n_interior=int((val >= 0).sum()),
                n_boundary=int((val < 0).sum()),
                build_stats=list(manifest.get("covering_build") or []))
        return cls(census=census, covering=covering,
                   max_level=int(manifest["max_level"]),
                   gbits=int(manifest["gbits"]),
                   max_cand=int(manifest["max_cand"]),
                   # v1 manifests predate the tuning block: empty record.
                   tuning=dict(manifest.get("tuning") or {}))
