"""GeoEngine: plan-and-execute facade over registered mapping strategies
(DESIGN.md §3, §11).

The engine composes three replaceable layers:

  * a **strategy registry** (core/registry.py + core/strategies.py):
    simple | fast | fast_onepass | hybrid | sharded ship as registered
    plugins over the
    shared resolution core, and third-party strategies register without
    touching engine code;
  * a **unified index artifact** (core/artifact.py): one ``GeoIndexSet``
    owns every index + edge pool a strategy can need, builds components
    lazily from declared capability flags, and persists to disk
    (versioned npz + manifest) so services cold-start without re-running
    the covering BFS;
  * an **auto-planner** (core/plan.py): ``build(census, strategy="auto")``
    inspects device kind, batch-size hints, index capabilities, and the
    measured boundary fraction to choose an explainable ``GeoPlan`` —
    ``engine.explain()`` says what was chosen and why.

Entry points:

  * ``engine.assign(points)``               — single-mesh lookup;
  * ``engine.assign_padded(points, n)``     — shape-stable serving batches;
  * ``engine.assign_sharded(points, mesh)`` — the cell table Morton-
    sharded over the mesh's "model" axis via the registered "sharded"
    plugin (points routed to their owning shard through the MoE dispatch
    primitive, distributed/dispatch.py).

Typical use::

    eng = GeoEngine.build(census, strategy="auto")
    eng.explain()                     # {"strategy": ..., "reasons": [...]}
    res = eng.assign(points)          # AssignResult
    res.block                         # [N] i32 block ids (-1 = off-map)

    eng.indices.save("artifacts/map")              # persist the artifact
    eng2 = GeoEngine.from_index_set(               # cold start
        GeoIndexSet.load("artifacts/map"), strategy="auto")

The legacy explicit form ``GeoEngine.build(census, strategy="fast",
cfg=EngineConfig(...))`` keeps working unchanged — it is now a thin
wrapper that pins the plan instead of asking the planner.

Everything in ``EngineConfig`` is static (part of the jit cache key);
``fused=True`` swaps the candidate PIP data path for the fused gather-PIP
Pallas kernel (kernels/gather_pip.py) in every strategy — results are
identical, only the memory traffic changes (DESIGN.md §9).
``fused="onepass"`` goes one further on the exact fast path: the whole
quantize -> cell lookup -> bbox filter -> PIP pipeline runs in ONE kernel
with double-buffered edge DMA (kernels/cascade.py, DESIGN.md §13); the
``"fast_onepass"`` strategy name pins the same plan.  Capability gaps (a
fused config over a pool-less index, a missing index) surface as
ValueError at *construction*, never at the first assign.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import plan as plan_mod
from repro.core import strategies as _strategies  # noqa: F401  (registers
#                                                  the built-in plugins)
from repro.core.artifact import GeoIndexSet
from repro.core import fast as fast_mod
from repro.core.geometry import CensusMap
from repro.core.registry import available_strategies, get_strategy
from repro.core.resolve import AssignResult
from repro.core.simple import SimpleConfig
from repro.core.fast import FastConfig
from repro.kernels import ops

# Names an explicit ``GeoEngine.build(strategy=...)`` accepts (the
# registry may hold more — anything registered works through the
# constructor; "auto" additionally asks the planner).
STRATEGIES = ("simple", "fast", "fast_onepass", "hybrid")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static engine knobs (part of every jit cache key).

    The per-strategy configs (SimpleConfig / FastConfig) are derived from
    this one surface so callers tune a single object.
    """

    backend: str | None = None   # kernel backend override
    k_cand: int = 4              # cascade PIP candidates per level
    cap_state: float = 0.25      # cascade compaction fractions
    cap_county: float = 0.5
    cap_block: float = 0.5
    mode: str = "exact"          # fast boundary handling: exact | approx
    cap_boundary: float = 0.25   # fast/hybrid boundary compaction fraction
    max_level: int = 9           # covering depth (fast/hybrid)
    gbits: int = 4               # top-grid bits (fast/hybrid)
    max_cand: int = 8            # boundary candidate list width
    cap_shard: float = 2.0       # sharded assign: capacity factor vs N/S
    fused: bool | str = False    # False | True | "onepass".  True routes
    #                              candidate PIP through the fused
    #                              gather-PIP kernel (kernels/gather_pip.py)
    #                              in every strategy; results identical,
    #                              the gathered [R, E, 4] HBM buffer gone.
    #                              "onepass" additionally fuses the whole
    #                              exact fast path into the single-kernel
    #                              cascade (kernels/cascade.py) — other
    #                              strategies treat it as True.

    def simple_cfg(self) -> SimpleConfig:
        return SimpleConfig(k_cand=self.k_cand, cap_state=self.cap_state,
                            cap_county=self.cap_county,
                            cap_block=self.cap_block, backend=self.backend,
                            fused=bool(self.fused))

    def fast_cfg(self) -> FastConfig:
        return FastConfig(mode=self.mode, cap_boundary=self.cap_boundary,
                          backend=self.backend, fused=self.fused)

    def hybrid_cascade_cfg(self) -> SimpleConfig:
        # The cascade only sees the (already compacted) boundary buffer, so
        # run it at full capacity — the buffer IS the capacity limit.
        return SimpleConfig(k_cand=self.k_cand, cap_state=1.0,
                            cap_county=1.0, cap_block=1.0,
                            backend=self.backend, fused=bool(self.fused))


class GeoEngine:
    """Facade: plan once, build once, assign many (see module docstring)."""

    def __init__(self, strategy: str, cfg: Optional[EngineConfig] = None, *,
                 indices: Optional[GeoIndexSet] = None,
                 simple_index=None, fast_index=None,
                 covering=None, census: Optional[CensusMap] = None,
                 plan: Optional[plan_mod.GeoPlan] = None):
        """Wrap already-built indices.  ``indices`` is the unified
        artifact; the ``simple_index``/``fast_index``/``covering``/
        ``census`` keywords are the legacy spelling and are folded into
        one.  Capability validation (missing index, fused without pools)
        happens HERE — a misconfigured engine never constructs."""
        self.cfg = cfg or EngineConfig()
        self._impl = get_strategy(strategy)      # ValueError on unknown
        self.strategy = strategy
        if indices is None:
            indices = GeoIndexSet(census=census, covering=covering,
                                  simple=simple_index, fast=fast_index,
                                  max_level=self.cfg.max_level,
                                  gbits=self.cfg.gbits,
                                  max_cand=self.cfg.max_cand)
        self.indices = indices
        self._impl.validate(indices, self.cfg)
        self.plan = plan if plan is not None \
            else plan_mod.explicit_plan(strategy, self.cfg)

    @classmethod
    def build(cls, census: CensusMap, strategy: str = "simple",
              cfg: Optional[EngineConfig] = None,
              covering=None) -> "GeoEngine":
        """Build the indices ``strategy`` needs from a host-side census.

        ``strategy="auto"`` asks the planner (core/plan.py): the covering
        is built first (it is both an index component and the planner's
        boundary-fraction measurement), a ``GeoPlan`` is chosen, and the
        engine is built to that plan — ``explain()`` tells you what
        happened.  Any registered strategy name pins the plan instead.
        """
        cfg = cfg or EngineConfig()
        indices = GeoIndexSet(census=census, covering=covering,
                              max_level=cfg.max_level, gbits=cfg.gbits,
                              max_cand=cfg.max_cand)
        plan = None
        if strategy == "auto":
            indices.ensure("covering")
            plan = plan_mod.plan_for(cfg, covering=indices.covering,
                                     tuning=indices.tuning)
            cfg = plan.apply(cfg)
            strategy = plan.strategy
        impl = get_strategy(strategy)
        for comp in impl.required_components(cfg):
            indices.ensure(comp)
        for comp in impl.pool_components(cfg):
            indices.ensure(comp, pool=True)
        return cls(strategy, cfg, indices=indices, plan=plan)

    @classmethod
    def from_index_set(cls, indices: GeoIndexSet, strategy: str = "auto",
                       cfg: Optional[EngineConfig] = None) -> "GeoEngine":
        """Build over an existing artifact (typically ``GeoIndexSet.load``
        — the serving cold-start path).  The artifact's build parameters
        (max_level / gbits / max_cand) override the config's so device
        components rebuild exactly as saved; ``strategy="auto"`` plans
        against the artifact's capabilities."""
        cfg = dataclasses.replace(cfg or EngineConfig(),
                                  max_level=indices.max_level,
                                  gbits=indices.gbits,
                                  max_cand=indices.max_cand)
        plan = None
        if strategy == "auto":
            if indices.census is not None:
                indices.ensure("covering")
            plan = plan_mod.plan_for(cfg, covering=indices.covering,
                                     capabilities=indices.capabilities(),
                                     tuning=indices.tuning)
            cfg = plan.apply(cfg)
            strategy = plan.strategy
        impl = get_strategy(strategy)
        if indices.census is not None:
            for comp in impl.required_components(cfg):
                indices.ensure(comp)
            for comp in impl.pool_components(cfg):
                indices.ensure(comp, pool=True)
        return cls(strategy, cfg, indices=indices, plan=plan)

    # -- index views (legacy attribute spelling) ----------------------------

    @property
    def simple_index(self):
        return self.indices.simple

    @property
    def fast_index(self):
        return self.indices.fast

    @property
    def covering(self):
        return self.indices.covering

    @property
    def census(self):
        return self.indices.census

    # -- planning introspection ---------------------------------------------

    def explain(self, n_points: Optional[int] = None) -> dict:
        """The engine's plan as a JSON-ready dict.  With no argument:
        the plan this engine was built under (the planner's choice for
        ``"auto"`` builds, the pinned explicit plan otherwise).  With a
        batch-size hint: what the planner would choose for that batch
        against this engine's *built* capabilities — e.g. whether a
        sharded route or a different strategy would win — without
        touching the engine."""
        if n_points is None:
            return self.plan.as_dict()
        return plan_mod.plan_for(
            self.cfg, covering=self.indices.covering,
            capabilities=self.indices.capabilities(),
            n_points=n_points, tuning=self.indices.tuning).as_dict()

    # -- single-mesh assign ------------------------------------------------

    def assign(self, points: jnp.ndarray) -> AssignResult:
        """Map [N, 2] (lon, lat) points -> AssignResult.

        The result's ``.state/.county/.block`` are [N] i32 ids (-1 = not
        on the map: outside the extent, in no state bbox, or dropped by a
        capacity overflow).  ``.stats`` is a GeoStats whose three core
        counters are comparable across strategies; the strategy's native
        breakdown (per-level dicts for simple, ``n_boundary``/
        ``phase2_miss`` for fast/hybrid) rides in ``stats.extra``.
        """
        return self._impl.assign(self.indices, points, self.cfg)

    def assign_padded(self, points: jnp.ndarray,
                      n_valid) -> AssignResult:
        """Shape-stable assign over a padded batch: rows >= ``n_valid``
        are padding and must not perturb results or stats.

        The serving layer pads every micro-batch up to a small ladder of
        bucket sizes so each strategy JIT-compiles once per bucket instead
        of once per request shape (DESIGN.md §10).  Pad rows are rewritten
        to ``ops.FAR`` before dispatch — a FAR point is outside every
        extent, bbox, and polygon by the padding convention (DESIGN.md §9),
        so it resolves to -1 without entering any ``need`` mask, candidate
        compaction, or PIP call: the returned ``GeoStats`` counters are
        identical to an unpadded ``assign`` over ``points[:n_valid]``
        (capacities permitting — caps are sized from the padded batch, so
        a padded call can only see *less* overflow, never more).  Pad rows
        come back -1 in all three id arrays.
        """
        if not self._impl.caps.supports_padded:
            raise ValueError(f"strategy {self.strategy!r} does not "
                             f"support padded batches")
        b = points.shape[0]
        valid = jnp.arange(b, dtype=jnp.int32) < n_valid
        masked = jnp.where(valid[:, None], points.astype(jnp.float32),
                           jnp.float32(ops.FAR))
        res = self.assign(masked)
        neg = jnp.int32(-1)
        return AssignResult(jnp.where(valid, res.state, neg),
                            jnp.where(valid, res.county, neg),
                            jnp.where(valid, res.block, neg), res.stats)

    # -- index / extent handles (serving layer) ----------------------------

    def extent_quant(self) -> tuple[np.ndarray, int]:
        """(quant [4] f32 = (x0, y0, sx, sy), max_level) — the quantization
        handle serving-layer routers and caches key on.  Taken from the
        fast index when one exists (bit-identical to the device lookup);
        derived from the census extent otherwise, with the same formula
        ``FastIndex.from_covering`` uses."""
        if self.fast_index is not None:
            return (np.asarray(self.fast_index.quant),
                    self.fast_index.max_level)
        if self.census is None:
            raise ValueError("extent_quant needs a fast index or a census "
                             "(engine built via GeoEngine.build)")
        return (fast_mod.quant_for_extent(self.census.extent,
                                          self.cfg.max_level),
                self.cfg.max_level)

    def extent_contains(self, points) -> np.ndarray:
        """[N] bool (host) — True where the point lies inside this
        engine's map extent; the serving router's ownership test.  Pure
        numpy (``fast.np_extent_mask``, the bit-exact host mirror of the
        ``extent_mask`` every strategy applies internally) — it runs per
        micro-batch on the serving hot path, so no device round trip."""
        quant, max_level = self.extent_quant()
        return fast_mod.np_extent_mask(quant, max_level, points)

    def host_parents(self) -> tuple[np.ndarray, np.ndarray]:
        """(block_parent [Nb], county_parent [Nc]) as host arrays, so the
        serving cache can derive county/state ids without a device trip —
        the same tables ``parents_of`` gathers on device."""
        index = self.fast_index if self.fast_index is not None \
            else self.simple_index
        return (np.asarray(index.block_parent),
                np.asarray(index.county_parent))

    # -- sharded assign ----------------------------------------------------

    def assign_sharded(self, points: jnp.ndarray, mesh) -> AssignResult:
        """Sharded lookup over ``mesh``'s "model" axis, routed through the
        registered "sharded" strategy plugin (or the engine's own
        strategy, if it declares ``supports_sharded``) — see
        core/strategies.py for capacity and drop accounting."""
        impl = self._impl if self._impl.caps.supports_sharded \
            else get_strategy("sharded")
        return impl.assign_sharded(self.indices, points, mesh, self.cfg)


__all__ = ["EngineConfig", "GeoEngine", "GeoIndexSet", "STRATEGIES",
           "available_strategies"]
