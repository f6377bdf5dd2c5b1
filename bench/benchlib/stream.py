"""What the served traffic kinds share: the request mix, the server, the
window's counters, and the comparison with the ground truth.

The request mix (the mix file's ``params``):

    size_min, size_max   request sizes, log-uniform between them
    size_cycle           requests per cycle: each cycle holds the same set
                         of sizes and of hot requests, in its own order
    hot_share            share of requests that re-query the hot pool
    hot_pool             points in the hot pool (venue check-ins)
    uniform_pool         uniform points the other requests take in turn
    requests             requests in the mix (a closed loop cycles them)
    warm_requests        requests served before the window
    trace_seconds        the window of a traced run (the harness reads it)

Every cycle of ``size_cycle`` requests holds the same set of sizes and the
same number of hot requests, in an order of its own, and every request
takes its own points: the work is fixed, the seed changes which points and
in which order, and a window that serves a few cycles serves nearly the
same mix whatever the seed.
"""
from __future__ import annotations

import threading

import numpy as np

from benchlib import points as points_mod

# How long after the window closes a request may still resolve.
WAIT_S = 60.0
STAGES = ("queue_wait", "host_prepare", "device_assign", "analytics_observe",
          "merge", "request")
COUNTERS = ("cache_hits_total", "cache_misses_total", "valid_slots",
            "padded_slots", "points_served", "batches", "requests")


def log_sizes(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` sizes at the midpoints of ``n`` equal steps of log-uniform
    probability between ``lo`` and ``hi``."""
    q = (np.arange(n) + 0.5) / n
    return np.floor(np.exp(np.log(lo) + q * (np.log(hi + 1) - np.log(lo)))
                    ).astype(np.int64).clip(lo, hi)


class Mix:
    """Requests as (points [k, 2] f32, true blocks [k] i32)."""

    def __init__(self, smap, params: dict, rng, n: int):
        hot_xy, hot_b = points_mod.uniform(smap, rng, int(params["hot_pool"]))
        uni_xy, uni_b = points_mod.uniform(smap, rng,
                                           int(params["uniform_pool"]))
        cycle = int(params["size_cycle"])
        base = log_sizes(cycle, int(params["size_min"]),
                         int(params["size_max"]))
        base_hot = np.arange(cycle) < round(cycle * float(params["hot_share"]))
        k = -(-n // cycle)
        sizes = np.concatenate([rng.permutation(base) for _ in range(k)])[:n]
        hot = np.concatenate([rng.permutation(base_hot)
                              for _ in range(k)])[:n]
        self.xy, self.block = [], []
        at = 0
        for size, is_hot in zip(sizes, hot):
            if is_hot:
                ix = rng.integers(0, len(hot_xy), size)
                self.xy.append(hot_xy[ix])
                self.block.append(hot_b[ix])
            else:
                ix = (at + np.arange(size)) % len(uni_xy)
                at += int(size)
                self.xy.append(uni_xy[ix])
                self.block.append(uni_b[ix])

    def __len__(self) -> int:
        return len(self.xy)


class Served:
    """The configuration's AsyncGeoServer, warmed, and what a window of
    served traffic records.  Subclasses drive the window."""

    def __init__(self, cell, policy: str, n_requests: int):
        from repro.analytics import AnalyticsConfig
        from repro.serving import AsyncGeoServer, FrontendConfig, ServeConfig
        self.cell = cell
        conf = cell.config
        rng = np.random.default_rng(cell.seed)
        self.mix = Mix(cell.deployment.smap, cell.params, rng, n_requests)
        warm = Mix(cell.deployment.smap, cell.params, rng,
                   int(cell.params["warm_requests"]))
        serve = dict(conf["serve"])
        serve["buckets"] = tuple(serve["buckets"])
        self.server = AsyncGeoServer(
            cell.engine,
            ServeConfig(policy=policy, trace_device=cell.trace,
                        analytics=AnalyticsConfig(**conf["analytics"]),
                        **serve),
            covering=cell.deployment.indices.covering,
            frontend=FrontendConfig(**conf["frontend"]))
        self.server.warm()
        self.lock = threading.Lock()
        # Completed requests: (mix, index, result) with mix 0 = warm-up.
        self.done = []
        self.attempted = 0
        self.failed = 0
        futs = [self.server.submit_async(xy) for xy in warm.xy]
        for i, f in enumerate(futs):
            self.done.append((warm, i, f.result(timeout=WAIT_S)))
        self._before = self._snapshot()
        self._after = None

    # -- window bookkeeping ----------------------------------------------

    def _snapshot(self) -> dict:
        m = self.server.metrics
        hists = {}
        for name in STAGES:
            h = m.stage(name)
            hists[name] = (h.counts.copy(), h.sum, h.uppers, h.per_octave)
        return {"counters": dict(m.snapshot()["counters"]), "hists": hists}

    def finish_window(self) -> None:
        """Call after every request of the window has resolved."""
        self._after = self._snapshot()

    def window_counters(self) -> dict:
        a, b = self._after["counters"], self._before["counters"]
        return {k: a.get(k, 0) - b.get(k, 0) for k in COUNTERS}

    def window_hists(self) -> dict:
        """stage -> (bucket counts, seconds summed, bucket upper bounds,
        buckets per octave), over the window only."""
        out = {}
        for name in STAGES:
            ca, sa, up, po = self._after["hists"][name]
            cb, sb, _, _ = self._before["hists"][name]
            out[name] = (ca - cb, sa - sb, up, po)
        return out

    def record(self, i: int, fut) -> bool:
        """Keep request ``i``'s result; False if it failed or was shed."""
        if not fut.done() or fut.exception() is not None:
            return False
        with self.lock:
            self.done.append((self.mix, i, fut.result()))
        return True

    def close(self) -> None:
        an = self.server.regions[0].analytics.current()
        self.window_counts = None if an is None else np.asarray(an.counts)
        self.overflow = int(self.server.stats[0].as_dict()["overflow"]) \
            if self.server.stats[0] is not None else 0
        self.server.close()
        self.server = None
        self.cell.engine = None

    def compare(self) -> dict:
        """The ids of every completed request (cache hits included), the
        analytics window's per-block counts, and the overflow counter."""
        smap = self.cell.deployment.smap
        wrong = 0
        want = np.zeros(smap.n_blocks, np.int64)
        for mix, i, res in self.done:
            s, c, b = smap.truth_of(mix.block[i])
            wrong += int(((res.state != s) | (res.county != c)
                          | (res.block != b)).sum())
            want += np.bincount(b, minlength=smap.n_blocks)
        got = self.window_counts
        counts_wrong = smap.n_blocks if got is None \
            else int((got != want).sum())
        return {"id_mismatches": {"value": wrong, "limit": 0},
                "window_count_mismatches": {"value": counts_wrong,
                                            "limit": 0},
                "overflow": {"value": self.overflow, "limit": 0}}


def hist_quantile(counts, uppers, per_octave: int, q: float):
    """Seconds at quantile ``q`` of a window's bucket counts: the
    geometric midpoint of the owning bucket (None when empty)."""
    total = int(counts.sum())
    if total == 0:
        return None
    cum = np.cumsum(counts)
    ix = int(np.searchsorted(cum, max(q * total, 1), side="left"))
    if ix >= len(uppers):
        return float(uppers[-1])
    return float(uppers[ix] * 2 ** (-0.5 / per_octave))


def percentile(values, q: float):
    """The ``q``-th percentile (0-100) of ``values``, None when empty."""
    v = np.asarray(values, np.float64)
    return None if v.size == 0 else float(np.percentile(v, q))
