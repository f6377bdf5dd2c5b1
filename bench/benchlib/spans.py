"""The program's own phases on the profiler's clock: the ``geo/`` named
scopes of the resolution core and the ``geo/`` host ranges of the served
path (``ServeConfig.trace_device``), read from a traced run's xplane.

Two readings, each checked on hand-made traces in ``tests/test_spans.py``:

* scope shares (batch cells): each device op's self time (its duration
  less the ops nested in it, ``tracereduce.self_times``) goes to the
  ``geo/`` scope of its ``op_name``.  The profiler's op events carry no
  name stack, so the names come from the compiled HLO text of the same
  jitted assign at the cell's shape: an instruction's own ``op_name``, or
  else that of the instruction whose computation it runs in (a loop's
  body, a fusion's root).  That assign is compiled afresh here, with the
  metadata in the compile cache's key: the cache's key leaves metadata
  out, so an executable loaded from it may carry another build's names.
* idle split (served cells): the device's idle time inside the window
  (the complement of the union of its ops) is split by what the replica
  threads were doing, by overlap and in priority order: inside a
  ``geo/device_stage``; else inside a ``geo/complete_batch`` or
  ``geo/cache_gauges``; else with no replica range open.  The three parts
  add up to the device's idle time.  A replica thread is a host line that
  holds ``geo/complete_batch`` ranges.

Everything is clipped to ``bench/window``.  The xplane is parsed once per
process and the HLO compiled once per cell; a reading with nothing to
read is None.
"""
from __future__ import annotations

import os
import re

from benchlib import harness, tracereduce as tr

PREFIX = "geo/"
DEVICE_STAGE = ("geo/device_stage",)
REPLICA = ("geo/complete_batch", "geo/cache_gauges")
REPLICA_MARK = "geo/complete_batch"
IDLE_PARTS = ("device_stage", "after_device", "replica_wait")

_events: dict = {}          # xplane path -> Events
_scopes: dict = {}          # cell name -> {instruction: scope}


def events_of(profile) -> list:
    """Device ops, the window and the program's ``geo/`` host ranges of a
    ``jax.profiler.ProfileData``, as ``tracereduce.Event``s."""
    out = []
    for plane in profile.planes:
        device = plane.name.startswith(tr.DEVICE_PREFIX)
        for i, line in enumerate(plane.lines):
            ops = device and line.name in tr.OPS_LINES
            # Host threads can share a name: a host line is also keyed by
            # its place in the plane.
            key = line.name if device else f"{line.name} #{i}"
            for ev in line.events:
                name = ev.name
                if ops or (not device and (name == tr.WINDOW
                                           or name.startswith(PREFIX))):
                    out.append(tr.Event(plane.name, key, name,
                                        ev.start_ns, ev.end_ns))
    return out


def trace_events(ctx):
    """The traced run's Events (None when it wrote no trace)."""
    cell = ctx.get("cell")
    if ctx.get("trace") is None or cell is None:
        return None
    path = tr.find_xplane(os.path.join(harness.TRACE_DIR, cell.name))
    if path is None:
        return None
    if path not in _events:
        from jax.profiler import ProfileData
        _events.clear()
        _events[path] = events_of(ProfileData.from_file(path))
    return _events[path]


def _window(events):
    wins = [e for e in events if e.name == tr.WINDOW
            and not e.plane.startswith(tr.DEVICE_PREFIX)]
    if not wins:
        raise ValueError(f"no {tr.WINDOW!r} range in the trace")
    return wins[-1].start_ns, wins[-1].end_ns


def _device_ops(events, t0, t1) -> dict:
    """plane -> [(name, start, end)] of its ops, clipped to the window."""
    per: dict = {}
    for e in events:
        if e.plane.startswith(tr.DEVICE_PREFIX) and e.line in tr.OPS_LINES \
                and e.end_ns > t0 and e.start_ns < t1:
            per.setdefault(e.plane, []).append(
                (e.name, max(e.start_ns, t0), min(e.end_ns, t1)))
    return per


# -- scopes --------------------------------------------------------------

def scope_of(op_name: str):
    """The ``geo/<phase>`` scope of an op_name (matched by whole name-stack
    components), or None."""
    parts = op_name.split("/")
    for i, part in enumerate(parts[:-1]):
        if part == "geo":
            return f"geo/{parts[i + 1]}"
    return None


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:body|condition|calls|to_apply|branch_computations)"
                    r"=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"[\w.\-]+")


def hlo_scopes(text: str) -> dict:
    """instruction -> ``geo/`` scope, from compiled HLO text: that of its
    own op_name, else that of the instruction that calls its computation
    (a loop's body and condition, a fusion, a reduction), nearest first;
    None under no scope."""
    own, computation_of, caller = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        computation_of[name] = comp
        o = _OP_NAME.search(line)
        own[name] = scope_of(o.group(1)) if o else None
        for group in _CALLS.findall(line):
            for callee in _NAME.findall(group):
                caller[callee] = name
    out = {}
    for name in own:
        n, seen = name, set()
        while own[n] is None and n not in seen:
            seen.add(n)
            up = caller.get(computation_of[n])
            if up is None:
                break
            n = up
        out[name] = own[n]
    return out


def scope_seconds(events, scopes: dict) -> dict:
    """scope -> device self seconds in the window (None: ops under no
    ``geo/`` scope), and "busy" -> busy seconds, summed over devices."""
    t0, t1 = _window(events)
    acc = {"busy": 0.0}
    for spans in _device_ops(events, t0, t1).values():
        acc["busy"] += sum(b - a for a, b in
                           tr.union((s, f) for _, s, f in spans)) / 1e9
        for name, own in tr.self_times(spans).items():
            sc = scopes.get(tr.short_name(name))
            acc[sc] = acc.get(sc, 0.0) + own / 1e9
    return acc


def compiled_scopes(ctx) -> dict:
    """instruction -> ``geo/`` scope of the batch cell's jitted assign,
    compiled at the cell's shape ({} when the engine runs another
    strategy)."""
    cell = ctx["cell"]
    if cell.name not in _scopes:
        import jax
        import jax.numpy as jnp

        from repro.core import fast
        engine = harness.build_engine(cell.config, ctx["deployment"])
        if engine.strategy != "fast":
            _scopes[cell.name] = {}
            return _scopes[cell.name]
        x = jax.ShapeDtypeStruct((int(cell.params["points_per_call"]), 2),
                                 jnp.float32)
        key = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            text = fast.assign_fast.lower(
                engine.indices.fast, x,
                cfg=engine.cfg.fast_cfg()).compile().as_text()
        finally:
            jax.config.update(key, before)
        _scopes[cell.name] = hlo_scopes(text)
    return _scopes[cell.name]


def scope_share(ctx, scope: str):
    """Percent of the device's busy time in ops under ``scope``."""
    events = trace_events(ctx)
    if not events or not _device_ops(events, *_window(events)):
        return None
    acc = scope_seconds(events, compiled_scopes(ctx))
    if not acc.get(scope) or acc["busy"] <= 0:
        return None
    return 100.0 * acc[scope] / acc["busy"]


# -- idle split ----------------------------------------------------------

def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_split(events):
    """{part: percent of the window} for ``IDLE_PARTS``, averaged over the
    devices that ran ops (with none, the whole window is idle, as
    ``tracereduce``'s gaps have it); None without replica ranges."""
    t0, t1 = _window(events)
    replicas = {(e.plane, e.line) for e in events
                if e.name == REPLICA_MARK
                and not e.plane.startswith(tr.DEVICE_PREFIX)}
    if not replicas or t1 <= t0:
        return None

    def ranges(names):
        return tr.union((max(e.start_ns, t0), min(e.end_ns, t1))
                        for e in events if e.name in names
                        and (e.plane, e.line) in replicas)

    stage = ranges(DEVICE_STAGE)
    replica = tr.union(stage + ranges(REPLICA))
    per_device = _device_ops(events, t0, t1)
    busy_lists = [tr.union((s, f) for _, s, f in spans)
                  for spans in per_device.values()] or [[]]
    parts = dict.fromkeys(IDLE_PARTS, 0.0)
    for busy in busy_lists:
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        in_stage = _overlap(idle, stage)
        in_replica = _overlap(idle, replica)
        parts["device_stage"] += in_stage
        parts["after_device"] += in_replica - in_stage
        parts["replica_wait"] += sum(b - a for a, b in idle) - in_replica
    scale = 100.0 / (t1 - t0) / len(busy_lists)
    return {k: v * scale for k, v in parts.items()}


def idle_share(ctx, part: str):
    """Percent of the window the device idled during ``part``."""
    events = trace_events(ctx)
    split = idle_split(events) if events else None
    return None if split is None else split[part]
