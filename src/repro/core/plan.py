"""GeoPlan: the explainable auto-planner behind ``strategy="auto"``
(DESIGN.md §11).

The paper's core observation is that the *same* projection problem wants
different execution plans in different regimes: the simple cascade when
an index isn't worth building, the cell index when true hits dominate,
the hybrid split when boundary traffic is heavy, the sharded layout when
the index outgrows one device.  The deployment follow-up (Samuel et al.,
arXiv:2108.11525) shows those regimes shifting live — so the choice
belongs in a planner, not in caller code.

``plan_for`` inspects these signals and emits a ``GeoPlan``:

  * **platform** (``jax.default_backend()``) — the fused gather-PIP
    kernel is a TPU bandwidth win; on CPU the ref path is faster;
  * **kernel backend** (``cfg.backend``, resolved as the kernels resolve
    it) — on compiled Pallas, what a TPU runs, the one-pass cascade is
    never planned: its kernel does not compile for the TPU
    (``strategies.onepass_runs``);
  * **batch size hint** — a batch smaller than ``SMALL_BATCH`` doesn't
    amortize the covering BFS if no covering exists yet;
  * **index capabilities** (``GeoIndexSet.capabilities()``) — replanning
    against an already-built artifact never picks a plan the artifact
    cannot execute (no simple index -> no hybrid; no pool -> no fused);
  * **measured boundary fraction** — the area share of boundary cells in
    the covering (``covering_boundary_fraction``).  For uniform traffic
    this is the expected fraction of points that pay candidate PIP; above
    ``HYBRID_BOUNDARY_FRAC`` the hybrid cascade's hierarchical PIP beats
    the fast path's flat candidate lists;
  * **recorded autotune** (``GeoIndexSet.tuning``, written by
    ``geo_perf --autotune``) — when the artifact carries a measured
    winner for this device kind (``jax.devices()[0].device_kind``, which
    tells chip generations apart), that measurement overrides the
    threshold heuristics above: a recorded ``fast_onepass`` win routes
    straight to the one-pass fused cascade at its tuned edge-pool block
    size.

Every decision appends a human-readable reason, so
``GeoEngine.explain()`` answers *why* a plan was chosen, and bench rows
(``geo_perf`` / ``serve_perf``) can record the plan next to the numbers
it produced.  Thresholds are module constants on purpose: the ROADMAP's
"pick a crossover heuristic" follow-ups land here, in one place.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import numpy as np

from repro.core.strategies import onepass_runs

# Planner thresholds (see DESIGN.md §11 for the rationale and how to
# retune them from bench rows).
HYBRID_BOUNDARY_FRAC = 0.35   # boundary area share above which the
#                               cascade resolves boundaries cheaper than
#                               flat candidate lists.  Below it the
#                               two-phase schedule (§Perf geo 2-3) puts
#                               ~90 % of boundary points through ONE
#                               slot-0 PIP, which no 3-level cascade can
#                               beat; above it candidate lists saturate
#                               (max_cand) and hierarchical pruning wins.
#                               Measured on the CPU bench map (bf 0.28:
#                               fast_exact 4.5x hybrid) — the auto bench
#                               row records plan-vs-winner so this stays
#                               retunable from history.
SMALL_BATCH = 1024            # below this, a covering BFS is not worth
#                               building for a one-shot batch
SHARD_MIN_POINTS = 1 << 17    # batch size where multi-device routing
#                               beats replicated lookup (CPU-sim measured
#                               crossover is above this; see ROADMAP)


@dataclasses.dataclass(frozen=True)
class GeoPlan:
    """One chosen execution plan, with its inputs and reasons.

    ``strategy``/``mode``/``fused`` feed straight into the engine build;
    ``sharded``/``n_shards`` are a routing recommendation (honored by
    callers that hold a mesh — ``assign`` itself stays single-mesh).
    ``auto`` is False for plans that merely record an explicit request.
    """

    strategy: str
    mode: str = "exact"
    fused: Union[bool, str] = False   # False | True | "onepass"
    sharded: bool = False
    n_shards: int = 1
    platform: str = "cpu"             # jax.default_backend(): what runs
    device_kind: str = "cpu"          # jax.devices()[0].device_kind
    n_points: Optional[int] = None
    boundary_fraction: Optional[float] = None
    auto: bool = True
    reasons: Tuple[str, ...] = ()

    def as_dict(self) -> dict:
        """JSON-ready rendering (bench rows, ``GeoEngine.explain``)."""
        return {
            "strategy": self.strategy, "mode": self.mode,
            "fused": self.fused, "sharded": self.sharded,
            "n_shards": self.n_shards, "platform": self.platform,
            "device_kind": self.device_kind,
            "n_points": (None if self.n_points is None
                         else int(self.n_points)),
            "boundary_fraction": (None if self.boundary_fraction is None
                                  else float(self.boundary_fraction)),
            "auto": self.auto, "reasons": list(self.reasons),
        }

    def apply(self, cfg):
        """Fold the plan into an EngineConfig (replaces mode + fused)."""
        return dataclasses.replace(cfg, mode=self.mode, fused=self.fused)


def covering_boundary_fraction(covering) -> float:
    """Area share of the covering owned by boundary cells: the sum of
    boundary-cell leaf spans over the total covered span.  Under uniform
    on-map traffic this is the expected candidate-PIP fraction — the
    planner's one *measured* (not configured) input."""
    lo = np.asarray(covering.lo, np.int64)
    hi = np.asarray(covering.hi, np.int64)
    val = np.asarray(covering.val)
    span = hi - lo + 1
    total = int(span.sum())
    if total == 0:
        return 0.0
    return float(span[val < 0].sum() / total)


def default_device() -> Tuple[str, str]:
    """(platform, device_kind) of the device plans are made for."""
    return jax.default_backend(), jax.devices()[0].device_kind


def explicit_plan(strategy: str, cfg) -> GeoPlan:
    """The degenerate plan recording a caller-pinned strategy, so
    ``engine.explain()`` has one answer shape whether or not the planner
    ran."""
    platform, device_kind = default_device()
    return GeoPlan(strategy=strategy, mode=cfg.mode,
                   # fast_onepass pins the one-pass kernel regardless of
                   # what the config says — record what actually runs.
                   fused=("onepass" if strategy == "fast_onepass"
                          else cfg.fused),
                   platform=platform, device_kind=device_kind,
                   auto=False, reasons=("explicit strategy request",))


def plan_for(cfg, *, covering=None, capabilities: Optional[dict] = None,
             n_points: Optional[int] = None,
             platform: Optional[str] = None,
             device_kind: Optional[str] = None,
             n_devices: Optional[int] = None,
             tuning: Optional[dict] = None) -> GeoPlan:
    """Choose an execution plan (see module docstring).

    ``capabilities=None`` means "planning a fresh build — anything is
    buildable from the census"; a dict (``GeoIndexSet.capabilities()``)
    constrains the plan to what an existing artifact can execute.
    ``tuning`` is the artifact's recorded autotune block
    (``GeoIndexSet.tuning``) — a measured winner there beats the
    threshold heuristics.  ``platform`` decides the kernel data path
    (fused gather-PIP on a TPU); ``device_kind`` only matches tuning
    records.  Both default to the first local device.  Whether the
    one-pass cascade can run follows ``cfg.backend``.
    """
    if platform is None or device_kind is None:
        here = default_device()
        platform = platform or here[0]
        device_kind = device_kind or here[1]
    n_devices = n_devices if n_devices is not None \
        else jax.local_device_count()
    fresh = capabilities is None
    caps = capabilities or {}
    reasons = []

    bf = None
    if covering is not None:
        bf = covering_boundary_fraction(covering)

    has_cell_index = fresh or covering is not None or caps.get("fast")
    can_cascade = fresh or caps.get("simple") or caps.get("census")
    # The fast index's edge pool is usable when built OR buildable (an
    # artifact carrying its census rebuilds pools on demand).
    fast_pool_ok = (fresh or caps.get("fast_pool", False)
                    or caps.get("census", False))
    tune = dict(tuning or {})
    # A recorded autotune win only transfers within its measurement
    # context: same device kind (a CPU-recorded winner says nothing
    # about TPU DMA behaviour, one chip generation nothing about
    # another).  On compiled Pallas kernels the one-pass kernel cannot
    # run at all.
    tuned_onepass = (tune.get("winner") == "fast_onepass"
                     and tune.get("device_kind", device_kind)
                     == device_kind)
    if tuned_onepass and not onepass_runs(cfg.backend):
        tuned_onepass = False
        reasons.append("recorded fast_onepass win ignored: the one-pass "
                       "cascade kernel does not compile for the TPU")

    # -- strategy -----------------------------------------------------------
    if not has_cell_index:
        strategy = "simple"
        reasons.append("no covering or fast index available: only the "
                       "cascade can run")
    elif (n_points is not None and n_points < SMALL_BATCH
          and covering is None and not caps.get("fast")):
        strategy = "simple"
        reasons.append(f"batch hint {n_points} < {SMALL_BATCH}: the "
                       f"covering BFS would dominate a one-shot batch")
    elif tuned_onepass and cfg.mode == "exact" and fast_pool_ok:
        strategy = "fast_onepass"
        reasons.append(
            f"recorded autotune on {device_kind!r} measured fast_onepass "
            f"fastest (be={tune.get('be')}, "
            f"{tune.get('pts_per_sec', 0):.3g} pts/s): measurement "
            f"overrides threshold heuristics")
    elif bf is not None and bf >= HYBRID_BOUNDARY_FRAC and can_cascade:
        strategy = "hybrid"
        reasons.append(f"measured boundary fraction {bf:.3f} >= "
                       f"{HYBRID_BOUNDARY_FRAC}: cascade PIP beats flat "
                       f"candidate lists on heavy boundary traffic")
    else:
        strategy = "fast"
        if bf is not None:
            reasons.append(f"measured boundary fraction {bf:.3f} < "
                           f"{HYBRID_BOUNDARY_FRAC}: true hits dominate")
        else:
            reasons.append("no covering to measure boundary traffic yet; "
                           "cell index is the paper's default winner")

    # -- mode ---------------------------------------------------------------
    mode = cfg.mode
    if mode == "approx":
        reasons.append("approx mode kept from config (error bounded by "
                       "the leaf cell diagonal)")

    # -- fused kernel -------------------------------------------------------
    runs_candidate_pip = (strategy in ("simple", "hybrid")
                          or (strategy in ("fast", "fast_onepass")
                              and mode == "exact"))
    pool_cap = {"simple": "simple_pool", "hybrid": "simple_pool",
                "fast": "fast_pool",
                "fast_onepass": "fast_pool"}[strategy]
    # A pool is usable when built OR buildable: an artifact that carries
    # its census rebuilds pools on demand (GeoIndexSet.ensure, which
    # from_index_set runs after planning) — a TPU cold start must not be
    # condemned to the gather path just because device-side pools are
    # never serialized.
    pool_available = (fresh or caps.get(pool_cap, False)
                      or caps.get("census", False))
    onepass_ok = (strategy in ("fast", "fast_onepass")
                  and mode == "exact" and pool_available)
    if strategy == "fast_onepass":
        fused = "onepass"
        reasons.append("fast_onepass pins the one-pass fused cascade "
                       "kernel (kernels/cascade.py)")
    elif cfg.fused == "onepass":
        if onepass_ok:
            fused = "onepass"
            reasons.append("one-pass fused cascade requested by config")
        else:
            fused = bool(runs_candidate_pip and pool_available)
            reasons.append(
                "onepass requested but it needs the exact fast path with "
                "an edge pool: "
                + ("kept the two-kernel fused path" if fused
                   else "dropped (no candidate PIP or no edge pool)"))
    elif cfg.fused:
        fused = runs_candidate_pip and pool_available
        reasons.append("fused requested by config"
                       if fused else
                       "fused requested but unusable here (no candidate "
                       "PIP or no edge pool built): dropped")
    elif platform == "tpu" and runs_candidate_pip and pool_available:
        fused = True
        reasons.append("TPU device: fused gather-PIP removes the "
                       "gathered-edges HBM round trip")
    else:
        fused = False
        if runs_candidate_pip and platform == "tpu":
            reasons.append("TPU device but no edge pool built for this "
                           "index: fused unusable, running the gather "
                           "path")
        elif runs_candidate_pip:
            reasons.append(f"platform {platform!r}: the legacy gather "
                           f"path wins off-TPU")

    # -- sharding recommendation --------------------------------------------
    sharded = False
    n_shards = 1
    if (n_devices > 1 and n_points is not None
            and n_points >= SHARD_MIN_POINTS and has_cell_index):
        sharded = True
        n_shards = n_devices
        reasons.append(f"{n_devices} devices and batch hint {n_points} >= "
                       f"{SHARD_MIN_POINTS}: route via assign_sharded")

    return GeoPlan(strategy=strategy, mode=mode, fused=fused,
                   sharded=sharded, n_shards=n_shards,
                   platform=platform, device_kind=device_kind,
                   n_points=n_points,
                   boundary_fraction=bf, auto=True,
                   reasons=tuple(reasons))
