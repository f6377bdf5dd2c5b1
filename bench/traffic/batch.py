"""Batch projection: back-to-back ``GeoEngine.assign`` calls, one in flight.

Parameters (the mix file's ``params``):

    points_per_call   points in each call
    distinct_calls    distinct point sets drawn from the seed; the calls
                      cycle through them
    sampler           "uniform" (over the map's area) or "boundary"
                      (inside boundary cells of the covering)
    trace_seconds     the window of a traced run (the harness reads it)

Each call puts a host array of points on the device, assigns it, and
copies the state, county and block ids and the call's counters back to
the host.  ``batch_points_per_s`` is every point whose ids came back,
over the whole window.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import points as points_mod


def make(cell):
    return BatchRun(cell)


class BatchRun:
    def __init__(self, cell):
        import jax
        self.cell = cell
        p = cell.params
        dep = cell.deployment
        rng = np.random.default_rng(cell.seed)
        n = int(p["points_per_call"])
        if p["sampler"] == "uniform":
            draw = lambda: points_mod.uniform(dep.smap, rng, n)  # noqa: E731
        elif p["sampler"] == "boundary":
            draw = lambda: points_mod.boundary(  # noqa: E731
                dep.smap, dep.indices.covering, rng, n)
        else:
            raise ValueError(f"unknown sampler {p['sampler']!r}")
        self.inputs = [draw() for _ in range(int(p["distinct_calls"]))]
        self.calls = []        # (input index, (state, county, block), stats)
        self.elapsed = 0.0
        self.attempted = 0
        self.failed = 0
        self._jax = jax
        self._call(0)             # compiles, or loads from the cache

    def _call(self, k: int):
        jax, span = self._jax, self.cell.span
        with span("bench/put"):
            x = jax.device_put(self.inputs[k][0])
        with span("bench/assign"):
            res = self.cell.engine.assign(x)
        with span("bench/fetch"):
            ids = (np.asarray(res.state), np.asarray(res.county),
                   np.asarray(res.block))
            stats = res.stats.as_dict()
        return ids, stats

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        i = 0
        while True:
            k = i % len(self.inputs)
            ids, stats = self._call(k)
            self.calls.append((k, ids, stats))
            i += 1
            t = time.perf_counter()
            if t - t0 >= seconds:
                break
        self.elapsed = t - t0
        self.attempted = i

    def end_to_end(self) -> dict:
        n = sum(len(ids[2]) for _, ids, _ in self.calls)
        return {"batch_points_per_s": n / self.elapsed}

    def layer_inputs(self) -> dict:
        uses = np.bincount([k for k, _, _ in self.calls],
                           minlength=len(self.inputs))
        return {
            "points": sum(len(ids[2]) for _, ids, _ in self.calls),
            "n_boundary": sum(st["n_boundary"] for _, _, st in self.calls),
            # For the kernel's byte count: each distinct input, its true
            # blocks, and how many calls of the window used it.
            "inputs": [(xy, bid, int(u))
                       for (xy, bid), u in zip(self.inputs, uses)],
        }

    def close(self) -> None:
        self.cell.engine = None

    def compare(self) -> dict:
        """Every id of every call in the window against the ground truth,
        and the counters the configuration guarantees to be zero."""
        smap = self.cell.deployment.smap
        truth = [smap.truth_of(bid) for _, bid in self.inputs]
        wrong = 0
        for k, ids, _ in self.calls:
            bad = np.zeros(len(ids[2]), bool)
            for got, want in zip(ids, truth[k]):
                bad |= got != want
            wrong += int(bad.sum())
        overflow = sum(st["overflow"] for _, _, st in self.calls)
        return {"id_mismatches": {"value": wrong, "limit": 0},
                "overflow": {"value": int(overflow), "limit": 0}}
