"""What tracing costs a cell: one traced run (the profiler on over the
window, the program's ``geo/`` ranges open), printed as ``bench/run.py
--trace 1`` prints it, plus ``traced_end_to_end``, the end-to-end metrics
of that traced window.  Compare them with untraced runs of the same seeds
(``bench/run.py --trace 0``).  A traced window is the mix's
``trace_seconds`` where it sets one.

    python3 bench/tools/trace_cost.py --workload <cell> --seed <n> \
        --seconds <s>
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".cache",
                                                      "tpu_logs"))
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    from benchlib import harness
    runs = []
    plugin = harness.plugin

    def keep_the_run(kind, name):
        mod = plugin(kind, name)
        if kind != "traffic":
            return mod

        class Traffic:
            @staticmethod
            def make(cell):
                runs.append(mod.make(cell))
                return runs[-1]
        return Traffic

    harness.plugin = keep_the_run
    p = harness.plan(args.workload)
    devices = harness.require_devices(int(p.cell["chips"]))
    out = harness.execute(args.workload, args.seed, args.seconds, True,
                          t_start=T_START, devices=devices)
    out["traced_end_to_end"] = runs[0].end_to_end()
    harness.report_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
