"""One run of one cell: set up, measure a window, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in files of its own, found by the names in ``BENCHMARK.json``:

    bench/configs/<config>.json     the deployment (map, engine, serving)
    bench/mixes/<traffic>.json      a traffic mix: the generator that
                                    reads it and its parameters
    bench/traffic/<generator>.py    one general generator of traffic
    bench/metrics/<metric>.py       the reader of one per-layer metric
    bench/counts/<kernel>.py        a kernel's bytes from shapes and index
    bench/peaks.json                peaks by device kind, with their source

This module names none of them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(BENCH, ".cache", "trace")


class Refused(Exception):
    """This run cannot be measured here; no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def plugin(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise Refused(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Plan:
    """What the manifest and the cell's files say about one cell."""

    cell: dict                  # the BENCHMARK.json workloads entry
    mix: dict                   # bench/mixes/<traffic>.json
    config: dict                # the configuration's file
    end_to_end: list            # manifest entries this cell reports
    per_layer: list


def plan(cell_name: str, root: str = ROOT) -> Plan:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell_name not in cells:
        raise Refused(f"no workload {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    mix = load_json(os.path.join(root, "bench", "mixes",
                                 f"{cell['traffic']}.json"))
    confs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, confs[cell["config"]]["file"]))

    def mine(m):
        return cell_name in m.get("workloads", [cell_name])

    e2e = [m for m in manifest["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    # A per-layer metric without a cell list is read wherever the metric
    # it moves is reported.
    layer = [m for m in manifest["per_layer"]
             if (mine(m) if "workloads" in m else m["moves"] in reported)]
    return Plan(cell, mix, config, e2e, layer)


def require_devices(chips: int):
    """The local TPUs, served by the Pallas kernels; Refused otherwise."""
    import jax

    from repro.kernels import ops
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devices)}")
    backend = ops.resolve_backend()
    if backend != "pallas":
        raise Refused(f"kernel backend resolves to {backend!r}, not "
                      f"'pallas'")
    return devices


@dataclasses.dataclass
class Cell:
    """What a traffic generator is handed."""

    name: str
    seed: int
    seconds: float
    trace: bool
    params: dict                # the mix file's "params"
    config: dict
    deployment: object          # benchlib.mapstore.Deployment
    engine: object              # repro GeoEngine, built to the config

    def span(self, name: str):
        """A named host range in a traced run, nothing otherwise."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def build_engine(config: dict, dep, overrides: dict | None = None):
    """The configuration's engine over the deployment's index."""
    from repro.core.engine import EngineConfig, GeoEngine
    eng = dict(config["engine"])
    eng.update(overrides or {})
    strategy = eng.pop("strategy")
    for k in ("gbits", "max_cand"):
        eng.pop(k, None)
    return GeoEngine.from_index_set(dep.indices, strategy,
                                    EngineConfig(**eng))


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _window(cell: Cell, run, seconds: float):
    """Drive the window, tracing it when asked; returns the trace's
    Summary (None untraced)."""
    from benchlib import tracereduce
    if not cell.trace:
        run.window(seconds)
        return None
    import jax
    logdir = os.path.join(TRACE_DIR, cell.name)
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir,
                             profiler_options=tracereduce.profile_options())
    try:
        with cell.span(tracereduce.WINDOW):
            run.window(seconds)
    finally:
        jax.profiler.stop_trace()
    path = tracereduce.find_xplane(logdir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {logdir}")
    return tracereduce.summarize(tracereduce.read_xplane(path))


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            t_start: float, root: str = ROOT, devices=None,
            engine_overrides: dict | None = None,
            map_cache: str | None = None) -> dict:
    """One run of ``cell_name``; returns the result line's object.

    ``devices`` are the chips the run may use (``require_devices``);
    ``t_start`` is the process's start on the host clock."""
    from benchlib import mapstore, tracereduce
    from repro.compile_cache import enable_compile_cache
    p = plan(cell_name, root)
    enable_compile_cache()
    kind = p.mix["generator"]
    traffic = plugin("traffic", kind)
    peaks = None
    if trace:
        table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
        kind_name = devices[0].device_kind
        if kind_name not in table:
            raise Refused(f"no peaks for device kind {kind_name!r} in "
                          f"bench/peaks.json")
        peaks = table[kind_name]
    conf = p.config
    params = p.mix["params"]
    if trace:
        # A traced run traces a shorter window where the cell says so: the
        # profiler keeps every device op, and its buffers are bounded.
        seconds = min(seconds, float(params.get("trace_seconds", seconds)))
    t0 = time.perf_counter()
    dep = mapstore.deployment(conf["map"], conf["engine"],
                              **({"cache": map_cache} if map_cache else {}))
    t1 = time.perf_counter()
    engine = build_engine(conf, dep, engine_overrides)
    cell = Cell(cell_name, seed, seconds, trace, params, conf, dep, engine)
    t2 = time.perf_counter()
    run = traffic.make(cell)
    try:
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.2f} s: imports {t0 - t_start:.2f} s, map "
            f"{t1 - t0:.2f} s (built in {dep.built_s:.2f} s), engine "
            f"{t2 - t1:.2f} s, inputs and warm-up "
            f"{time.perf_counter() - t2:.2f} s; plan "
            f"{json.dumps(engine.explain())}")
        summary = _window(cell, run, seconds)
        mem = _peak_bytes(devices)
        e2e = run.end_to_end()
        layer_in = run.layer_inputs()
    finally:
        run.close()
    checks = run.compare()
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics = {}
    if not trace:
        e2e["setup_s"] = setup_s
        for m in p.end_to_end:
            if m["name"] not in e2e:
                raise RuntimeError(f"traffic {kind!r} did not report "
                                   f"{m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        ctx = {"trace": summary, "peaks": peaks, "layer": layer_in,
               "deployment": dep, "cell": cell}
        for m in p.per_layer:
            value = plugin("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = tracereduce.breakdown(summary)
    out["checks"] = checks
    return out


def report_checks(checks: dict, stream=sys.stderr) -> None:
    """Each number compared, beside its limit, one per line."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=stream, flush=True)
