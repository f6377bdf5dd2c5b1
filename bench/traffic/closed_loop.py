"""Served traffic, closed loop: clients that each wait for their answer.

Parameters: the request mix of ``benchlib/stream.py``, plus

    clients   client threads; each sends its next request when the last
              is answered (policy "block"), taking the mix's requests in
              turn (client c takes c, c + clients, ...; the mix cycles)

``serve_points_per_s`` is the points of every request completed inside
the window, over the window.  Requests still open when it closes are
waited for and checked, but not counted.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchlib import stream


def make(cell):
    return ClosedLoop(cell)


class ClosedLoop(stream.Served):
    def __init__(self, cell):
        super().__init__(cell, "block", int(cell.params["requests"]))
        self.clients = int(cell.params["clients"])
        self.points_in_window = 0
        self.window_s = 0.0

    def window(self, seconds: float) -> None:
        server, span, mix = self.server, self.cell.span, self.mix
        n_mix = len(mix)
        counts = [[0, 0, 0] for _ in range(self.clients)]  # sent, failed, pts
        t0 = time.perf_counter()
        end = t0 + seconds

        def client(c):
            k = c
            while time.perf_counter() < end:
                i = k % n_mix
                k += self.clients
                counts[c][0] += 1
                with span("bench/submit"):
                    fut = server.submit_async(mix.xy[i])
                with span("bench/wait"):
                    try:
                        fut.exception(timeout=stream.WAIT_S)
                    except TimeoutError:
                        pass
                if not self.record(i, fut):
                    counts[c][1] += 1
                elif time.perf_counter() <= end:
                    counts[c][2] += len(mix.xy[i])

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}", daemon=True)
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(seconds + 2 * stream.WAIT_S)
        self.window_s = seconds
        self.attempted = sum(c[0] for c in counts)
        self.failed = sum(c[1] for c in counts)
        self.points_in_window = sum(c[2] for c in counts)
        self.finish_window()

    def end_to_end(self) -> dict:
        return {"serve_points_per_s": self.points_in_window / self.window_s}

    def layer_inputs(self) -> dict:
        return {"counters": self.window_counters(),
                "hists": self.window_hists(), "window_s": self.window_s}
