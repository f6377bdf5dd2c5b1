"""The control of ``correct``: a cell run with the program's approximate
mode switched on (each boundary point takes its cell's centre owner, the
guarantee of exact ids given up), at the cell's own size, one seed after
another in one process.  Every run must come out not correct.

    python3 bench/tools/control.py <cell> <seconds> <seed>...

Prints one JSON line per seed: the seed, ``correct`` and the numbers
compared.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(cell: str, seconds: str, *seeds) -> None:
    from benchlib import harness
    devices = harness.require_devices(1)
    for seed in seeds:
        out = harness.execute(cell, int(seed), float(seconds), False,
                              t_start=time.perf_counter(), devices=devices,
                              engine_overrides={"mode": "approx"})
        print(json.dumps({"cell": cell, "seed": int(seed),
                          "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
