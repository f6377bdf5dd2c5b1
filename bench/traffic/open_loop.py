"""Served traffic, open loop: Poisson arrivals at a fixed rate.

Parameters: the request mix of ``benchlib/stream.py``, plus

    rate_per_s   offered requests per second (fixed in the mix file)

One generator thread sends each request at its scheduled time through
``AsyncGeoServer.submit_async`` without waiting (policy "shed"), so a slow
server cannot slow the generator.  A request is timed from its scheduled
arrival to its future's resolution.  ``serve_p95_ms`` is the 95th
percentile of every request scheduled in the window; a shed or failed
request, or one that never resolves, counts at the wait limit.  Every seed
gets the same set of inter-arrival gaps, in another order.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import stream


def make(cell):
    return OpenLoop(cell)


def arrival_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Arrival times in [0, seconds): ``rate * seconds`` exponential gaps
    at the midpoints of equal steps of probability, shuffled."""
    n = max(int(round(rate * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    t = np.cumsum(gaps)
    return t * (seconds * (1 - 0.5 / n) / t[-1])


class OpenLoop(stream.Served):
    def __init__(self, cell):
        rate = float(cell.params["rate_per_s"])
        rng = np.random.default_rng([cell.seed, 1])
        self.offsets = arrival_offsets(rate, cell.seconds, rng)
        super().__init__(cell, "shed", len(self.offsets))
        n = len(self.offsets)
        self.lat = np.full(n, stream.WAIT_S)    # missing: the wait limit
        self.lag = np.zeros(n)

    def window(self, seconds: float) -> None:
        server, span = self.server, self.cell.span
        futs = []
        t0 = time.perf_counter()

        def resolved(i, sched, fut):
            if self.record(i, fut):
                self.lat[i] = time.perf_counter() - sched

        for i, off in enumerate(self.offsets):
            sched = t0 + off
            wait = sched - time.perf_counter()
            if wait > 0:
                with span("bench/sleep"):
                    time.sleep(wait)
            self.lag[i] = time.perf_counter() - sched
            with span("bench/submit"):
                fut = server.submit_async(self.mix.xy[i])
            fut.add_done_callback(
                lambda f, i=i, s=sched: resolved(i, s, f))
            futs.append(fut)
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            time.sleep(rest)
        with span("bench/wait"):
            deadline = t0 + seconds + stream.WAIT_S
            for f in futs:
                try:
                    f.exception(timeout=max(deadline - time.perf_counter(),
                                            0.0))
                except TimeoutError:
                    pass
        self.attempted = len(futs)
        self.failed = len(futs) - sum(1 for f in futs if f.done()
                                      and f.exception() is None)
        self.finish_window()

    def end_to_end(self) -> dict:
        return {"serve_p95_ms": float(np.percentile(self.lat, 95)) * 1e3}

    def layer_inputs(self) -> dict:
        return {"latency_s": self.lat, "lag_s": self.lag,
                "counters": self.window_counters(),
                "hists": self.window_hists()}
