"""A tiny copy of the benchmark's data files, for runs on the CPU.

``tiny_root(tmp)`` writes a ``BENCHMARK.json``, a 16-block map's
configuration and one traffic mix per cell under ``tmp``, one cell per
generator, with the real
manifest's metric entries, and returns the root.  ``run_cell`` runs one of
its cells through ``harness.execute`` on the CPU devices, with the
Pallas kernels interpreted: everything of a chip run but the look for a
chip.
"""
from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MAP = {"seed": 3, "n_states": 2, "counties_per_state": 2,
       "blocks_per_county": 4}
ENGINE = {"strategy": "auto", "max_level": 6, "gbits": 2, "max_cand": 8,
          "mode": "exact", "cap_boundary": 1.0, "cap_state": 1.0,
          "cap_county": 1.0, "cap_block": 1.0, "backend": "interpret"}
MIX = {"size_min": 1, "size_max": 64, "size_cycle": 16, "hot_share": 0.5,
       "hot_pool": 16,
       "uniform_pool": 2048, "warm_requests": 4}
CELLS = {
    "tiny-uniform": ("batch", {"points_per_call": 512, "distinct_calls": 2,
                               "sampler": "uniform"}),
    "tiny-boundary": ("batch", {"points_per_call": 512,
                                "distinct_calls": 2,
                                "sampler": "boundary"}),
    "tiny-open": ("open_loop", dict(MIX, rate_per_s=40.0)),
    "tiny-closed": ("closed_loop", dict(MIX, clients=4, requests=64)),
}
# Which real cell's metrics each tiny cell reports.
AS = {"tiny-uniform": "batch-uniform", "tiny-boundary": "batch-boundary",
      "tiny-open": "stream-open", "tiny-closed": "stream-closed"}
# A reader's suffix names the end-to-end metric it moves and the kinds of
# cell that report it; readers that no cell of BENCHMARK.json lists are
# rehearsed too, on these.
SUFFIX = {"batch": ("batch_points_per_s", "points/s",
                    ["tiny-uniform", "tiny-boundary"]),
          "open": ("serve_p95_ms", "ms", ["tiny-open"]),
          "closed": ("serve_points_per_s", "points/s", ["tiny-closed"])}


def tiny_root(tmp) -> str:
    root = str(tmp)
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "mixes"), exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    stream = json.load(open(os.path.join(BENCH, "configs",
                                         "conus21k-stream.json")))
    conf = {"map": MAP, "engine": ENGINE,
            "serve": dict(stream["serve"], buckets=[64, 256],
                          max_queue_points=4096),
            "frontend": stream["frontend"], "analytics": stream["analytics"]}
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    workloads = []
    for name, (kind, params) in CELLS.items():
        with open(os.path.join(root, "bench", "mixes", f"{name}.json"),
                  "w") as f:
            json.dump({"generator": kind, "params": params}, f)
        workloads.append({"name": name, "config": "tiny", "traffic": name,
                          "chips": 1, "why": "CPU rehearsal"})

    def remap(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [t for t, r in AS.items() if r in m["workloads"]]
        return m

    e2e = [remap(m) for m in real["end_to_end"]]
    layer = [remap(m) for m in real["per_layer"]]
    for moves, unit, cells in SUFFIX.values():
        if moves not in {m["name"] for m in e2e}:
            e2e.append({"name": moves, "unit": unit, "better": "lower",
                        "bound": 0.25, "source": "host_clock",
                        "workloads": cells})
    listed = {m["name"] for m in layer}
    for f in sorted(os.listdir(os.path.join(BENCH, "metrics"))):
        name = f[:-len(".py")]
        if not f.endswith(".py") or name in listed:
            continue
        moves, _, cells = SUFFIX[name.rsplit(".", 1)[1]]
        layer.append({"name": name, "unit": "ms" if "_ms" in name else "%",
                      "better": "lower", "layer": "test", "moves": moves,
                      "workloads": cells,
                      "source": "device_trace" if name.startswith(
                          ("device_idle", "gather_pip")) else "host_clock"})
    manifest = dict(real, workloads=workloads,
                    configs=[{"name": "tiny", "source": "test",
                              "file": "bench/configs/tiny.json",
                              "reduced": [], "why": "test"}],
                    end_to_end=e2e, per_layer=layer)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def run_cell(root: str, cell: str, seed: int = 7, seconds: float = 1.0,
             **kw) -> dict:
    import jax

    from benchlib import harness
    return harness.execute(cell, seed, seconds, False,
                           t_start=time.perf_counter(), root=root,
                           devices=jax.devices(),
                           map_cache=os.path.join(root, "maps"), **kw)
