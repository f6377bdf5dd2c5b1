"""The chip benchmark of the census-block mapping system: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Each invocation is one process.  It loads the cell's deployment (the map and
its covering, built once per checkout under ``bench/.cache/``), builds the
engine and, for served traffic, the server, generates its inputs from
``--seed``, warms every shape the traffic uses, then measures for
``--seconds``.  Afterwards it compares what the timed path returned with the
map's ground truth, prints each number compared beside its limit as the last
lines of standard error, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the program's counters.

It runs only on a TPU with the Pallas kernels, and otherwise exits non-zero
and prints no result.

The harness is driven by data.  A new configuration, cell or per-layer
metric is new files plus new entries in ``BENCHMARK.json``, never an edit
to a file under ``bench/``:

    bench/configs/<config>.json     a deployment (``configs[].file``)
    bench/mixes/<traffic>.json      a traffic mix: {"generator", "params"}
    bench/traffic/<generator>.py    a generator: make(cell) -> run
    bench/metrics/<metric>.py       a reader: read(ctx) -> value or None
    bench/counts/<kernel>.py        a kernel's bytes from the index
    bench/peaks.json                peaks by device kind, with the source
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The TPU runtime's logs go inside the checkout, not to a fixed /tmp.
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, ".cache",
                                                      "tpu_logs"))
    sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
    from benchlib import harness
    try:
        p = harness.plan(args.workload)
        devices = harness.require_devices(int(p.cell["chips"]))
        out = harness.execute(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              devices=devices)
    except harness.Refused as e:
        print(f"refused: {e}", file=sys.stderr, flush=True)
        return 2
    harness.report_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
