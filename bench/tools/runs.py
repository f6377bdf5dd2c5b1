"""Runs of one cell, one process each, from the checkout root.

    python3 bench/tools/runs.py <tag> <cell> <seconds> <trace> <seed>...

Appends a record of each run (seed, exit code, wall time, its last line of
standard output) to ``chiprun_out/<tag>.jsonl`` and the end of its standard
error to ``chiprun_out/<tag>.log``.  This process never imports JAX, so
each run has the chip to itself.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def main(tag: str, cell: str, seconds: str, trace: str, *seeds) -> None:
    os.makedirs("chiprun_out", exist_ok=True)
    for seed in seeds:
        t0 = time.perf_counter()
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", cell, "--seed",
             seed, "--seconds", seconds, "--trace", trace],
            capture_output=True, text=True)
        wall = time.perf_counter() - t0
        lines = p.stdout.strip().splitlines()
        try:
            out = json.loads(lines[-1]) if lines else None
        except ValueError:
            out = None
        rec = {"cell": cell, "seed": int(seed), "trace": int(trace),
               "rc": p.returncode, "wall_s": wall, "out": out}
        with open(f"chiprun_out/{tag}.jsonl", "a") as f:
            f.write(json.dumps(rec) + "\n")
        with open(f"chiprun_out/{tag}.log", "a") as f:
            f.write(f"== {cell} seed {seed} trace {trace} rc "
                    f"{p.returncode} wall {wall:.1f}\n")
            f.write("\n".join(p.stderr.splitlines()[-40:]) + "\n")
        m = (out or {}).get("metrics", {})
        print(f"{cell} seed {seed} trace {trace} rc {p.returncode} wall "
              f"{wall:.1f} correct {(out or {}).get('correct')} "
              + " ".join(f"{k}={v['value']:.6g}" for k, v in m.items()),
              flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
