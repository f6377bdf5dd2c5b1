"""Opt-in ``jax.profiler`` hooks for the serving stack (DESIGN.md §15).

The tracer (obs/trace.py) attributes *host-observed* wall time; when a
device stage itself needs opening up (which kernel, which fusion, how
much HBM traffic), the JAX profiler is the right tool.  This module is
the thin, failure-proof seam between the two:

* ``profile_range(name, **kw)`` — context manager wrapping
  ``jax.profiler.TraceAnnotation(name, **kw)`` (the keyword args become
  the range's event stats), so serving stages show up as named host
  ranges on the same clock as the device ops of a captured trace
  (TensorBoard / Perfetto).  ``GeoServer`` opens its ``geo/`` stage
  ranges through ``ranges(cfg.trace_device)``: the real helper when
  ``ServeConfig.trace_device=True``, a no-op otherwise.
* ``start_profile(logdir)`` / ``stop_profile()`` — the capture pair
  (``jax.profiler.start_trace``/``stop_trace``), exposed on
  ``GeoServer`` so a load run can bracket its SLO trial with a device
  trace capture.

Every entry point degrades to a no-op (with a one-line warning once)
if the profiler is unavailable or refuses — observability must never
be able to take the serve path down.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = ["profile_range", "ranges", "start_profile", "stop_profile",
           "profiler_available"]

_warned = set()
_warn_lock = threading.Lock()


def _warn_once(key: str, msg: str) -> None:
    with _warn_lock:
        if key in _warned:
            return
        _warned.add(key)
    print(f"obs.profile: {msg}")


def profiler_available() -> bool:
    try:
        import jax.profiler  # noqa: F401
        return hasattr(jax.profiler, "TraceAnnotation")
    except Exception:                      # pragma: no cover - env-specific
        return False


def _trace_annotation(name: str, **kw):
    import jax.profiler
    return jax.profiler.TraceAnnotation(name, **kw)


# What ``profile_range`` opens its ranges with; a test substitutes a
# recorder here.
range_factory = _trace_annotation

_NO_RANGE = contextlib.nullcontext()


@contextlib.contextmanager
def profile_range(name: str, **kw):
    """Named profiler range (keyword args stored as its event stats);
    no-op when the profiler is unavailable."""
    try:
        ctx = range_factory(name, **kw)
    except Exception:                      # pragma: no cover - env-specific
        _warn_once("annotation", "jax.profiler.TraceAnnotation "
                                 "unavailable — profiler ranges off")
        yield
        return
    with ctx:
        yield


def _no_range(name: str, **kw):
    return _NO_RANGE


def ranges(enabled: bool):
    """``profile_range`` when ``enabled``, else a no-op of the same
    signature: the one switch a caller checks, once."""
    return profile_range if enabled else _no_range


def start_profile(logdir: str) -> bool:
    """Begin a device trace capture into ``logdir``; True if it
    started.  Refusals (already active, missing profiler) warn once and
    return False instead of raising."""
    try:
        import jax.profiler
        jax.profiler.start_trace(logdir)
        return True
    except Exception as e:                 # pragma: no cover - env-specific
        _warn_once("start", f"start_trace failed ({e}) — profiling off")
        return False


def stop_profile() -> bool:
    """End the active capture; True if one was stopped."""
    try:
        import jax.profiler
        jax.profiler.stop_trace()
        return True
    except Exception as e:                 # pragma: no cover - env-specific
        _warn_once("stop", f"stop_trace failed ({e})")
        return False
