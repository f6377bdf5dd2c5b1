"""Reduction of a profiler trace to device busy time, kernel time and the
host's share of the device's idle gaps.

A traced run writes one ``.xplane.pb`` (``jax.profiler``, Python function
tracing off).  Its device planes (``/device:TPU:<n>``) hold a line of XLA
operations, named by their HLO text (``%name.N = <shape> op(...)``); its
host plane holds one line per thread, with the ``TraceAnnotation`` ranges
the harness and the program open.  The harness opens ``bench/window``
around the traced window; everything below is clipped to it.

* busy: the union of the intervals in which an operation ran on a device,
  averaged over the devices that ran any;
* kernel time: the summed, clipped durations of the operations whose name
  contains the kernel's;
* op time (for the breakdown): each operation's self time, its duration
  less the operations nested inside it (a loop and its body), by short
  name (``while.20``);
* idle gaps: the complement of the busy union within the window, each gap
  named by the innermost host range covering its midpoint.

A trace in which the profiler dropped buffers inside the window is refused:
its busy time would read low.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINES = ("XLA Ops",)
WINDOW = "bench/window"
HOST_LABELS = ("bench/", "geo_device_assign")
NO_LABEL = "host: no range"
DROPPED = "Trace Buffers Dropped"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def find_xplane(logdir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def profile_options():
    """Profiler options of a traced run: no Python function tracing (it
    floods the host plane and is not read)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def events_of(profile) -> list:
    """The Events the reduction reads from a ``jax.profiler.ProfileData``:
    device operations, dropped-buffer marks, and host ranges."""
    out = []
    for plane in profile.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            ops = device and line.name in OPS_LINES
            for ev in line.events:
                name = ev.name
                if ops or name == DROPPED or (
                        not device and name.startswith(HOST_LABELS)):
                    out.append(Event(plane.name, line.name, name,
                                     ev.start_ns, ev.end_ns))
    return out


def read_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(path))


def short_name(op: str) -> str:
    """``%while.20 = (s32[]...) while(...)`` -> ``while.20``."""
    return op.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def self_times(spans) -> dict:
    """name -> self time of (name, start, end) spans of one device: each
    span's length less the parts of it that spans nested in it cover."""
    acc: dict = {}
    stack: list = []                     # [end, name, self]
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            acc[n] = acc.get(n, 0.0) + own
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    for end, n, own in stack:
        acc[n] = acc.get(n, 0.0) + own
    return acc


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the devices used
    n_devices: int
    op_s: dict                    # short op name -> self seconds, summed
    kernels: list                 # (full op name, clipped seconds)
    gaps: list                    # [(label, seconds)] per gap, in order

    def kernel_s(self, *names: str) -> float:
        """Seconds of ops whose name contains any of ``names``, averaged
        over the devices."""
        total = sum(sec for op, sec in self.kernels
                    if any(n in op for n in names))
        return total / max(self.n_devices, 1)

    def idle_by_label(self) -> list:
        """[(label, seconds)] idle time per host range, largest first."""
        acc: dict = {}
        for label, sec in self.gaps:
            acc[label] = acc.get(label, 0.0) + sec
        return sorted(acc.items(), key=lambda kv: -kv[1])


def summarize(events: list, window: str = WINDOW) -> Summary:
    """Reduce a trace's events (see the module docstring)."""
    wins = [e for e in events if e.name == window
            and not e.plane.startswith(DEVICE_PREFIX)]
    if not wins:
        raise ValueError(f"no {window!r} range in the trace")
    t0, t1 = wins[-1].start_ns, wins[-1].end_ns
    if any(e.name == DROPPED and e.end_ns > t0 and e.start_ns < t1
           for e in events):
        raise ValueError("the profiler dropped trace buffers inside the "
                         "window: trace a shorter window")
    per_plane: dict = {}
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line in OPS_LINES \
                and e.end_ns > t0 and e.start_ns < t1:
            per_plane.setdefault(e.plane, []).append(
                (e.name, max(e.start_ns, t0), min(e.end_ns, t1)))
    planes = sorted(per_plane)
    busy = 0.0
    op_s: dict = {}
    kernels: dict = {}
    merged_of = {}
    for p in planes:
        spans = per_plane[p]
        merged_of[p] = union((s, f) for _, s, f in spans)
        busy += sum(f - s for s, f in merged_of[p])
        for name, own in self_times(spans).items():
            k = short_name(name)
            op_s[k] = op_s.get(k, 0.0) + own / 1e9
        for name, s, f in spans:
            kernels[name] = kernels.get(name, 0.0) + (f - s) / 1e9
    busy = busy / max(len(planes), 1) / 1e9
    host = sorted((e for e in events
                   if not e.plane.startswith(DEVICE_PREFIX)
                   and e.name.startswith(HOST_LABELS) and e.name != window),
                  key=lambda e: e.start_ns)
    # The gaps of the first device used (all devices run the same program),
    # in time order; a sweep keeps the host ranges open at each midpoint.
    merged = merged_of[planes[0]] if planes else []
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    gaps, active, nxt = [], [], 0
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        while nxt < len(host) and host[nxt].start_ns <= mid:
            active.append(host[nxt])
            nxt += 1
        active = [e for e in active if e.end_ns > mid]
        # Ranges open in start order: the last one is the innermost.
        gaps.append((active[-1].name if active else NO_LABEL,
                     (b - a) / 1e9))
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=busy,
                   n_devices=len(planes), op_s=op_s,
                   kernels=list(kernels.items()), gaps=gaps)


def breakdown(summary: Summary, n: int = 10) -> dict:
    """The result line's ``breakdown``: the ops with most device self
    time, and idle time per host range."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_by_label()[:n]]}
