"""Print what a profiler trace holds: its planes, their lines, and the
longest events by name, to check what the reduction matches on.

    python3 bench/tools/trace_names.py bench/.cache/trace/<cell>
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(logdir: str) -> None:
    from jax.profiler import ProfileData

    from benchlib import tracereduce
    path = tracereduce.find_xplane(logdir)
    print("trace:", path, os.path.getsize(path), "bytes")
    prof = ProfileData.from_file(path)
    print("python tracer off:", tracereduce.profile_options()
          .python_tracer_level == 0)
    for plane in prof.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            acc = {}
            for e in evs:
                acc[e.name] = acc.get(e.name, 0.0) + e.duration_ns
            top = sorted(acc.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{evs[0].start_ns:.0f}..{evs[-1].end_ns:.0f} ns")
            for name, ns in top:
                print(f"    {ns / 1e6:12.3f} ms  {name[:100]}")
            if plane.name.startswith(tracereduce.DEVICE_PREFIX):
                first = next((e for e in evs if "custom-call" in e.name),
                             evs[0])
                print("    a custom call:", first.name[:400])
                print("    its stats:",
                      [(k, str(v)[:200]) for k, v in first.stats][:20])
    s = tracereduce.summarize(tracereduce.read_xplane(path))
    print("window_s", s.window_s, "busy_s", s.busy_s, "devices",
          s.n_devices)
    print("gather-pip kernel s", s.kernel_s("crossings_candidates"))
    print("breakdown", tracereduce.breakdown(s))


if __name__ == "__main__":
    main(sys.argv[1])
