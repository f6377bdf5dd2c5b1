"""Arithmetic the per-layer metric readers share.

A reader (``bench/metrics/<metric>.py``) gets the run's context:

    trace        the trace Summary (benchlib.tracereduce), None untraced
    peaks        the device's row of bench/peaks.json
    layer        what the traffic kind recorded over the window
    deployment   the map and its index
    cell         the cell (name, params, config)

and returns a number, or None when it finds nothing to read.
"""
from __future__ import annotations

from benchlib import stream


def device_idle(ctx):
    """Percent of the window in which no operation ran on the device."""
    t = ctx.get("trace")
    if t is None or t.window_s <= 0 or t.n_devices == 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def kernel_share(ctx, names: tuple):
    """Percent of the device's busy time in ops named by ``names``."""
    t = ctx.get("trace")
    if t is None or t.busy_s <= 0:
        return None
    k = t.kernel_s(*names)
    return None if k <= 0 else 100.0 * k / t.busy_s


def bytes_roofline(ctx, names: tuple, needed_bytes: float):
    """Percent of the trace time of the ops named by ``names`` that their
    bytes need at the device's peak memory bandwidth."""
    t = ctx.get("trace")
    if t is None or not needed_bytes:
        return None
    k = t.kernel_s(*names)
    if k <= 0:
        return None
    return 100.0 * needed_bytes / ctx["peaks"]["hbm_bytes_per_s"] / k


def stage_ms(ctx, stage: str, q: float):
    """Milliseconds at quantile ``q`` of a serve stage over the window."""
    h = ctx["layer"].get("hists", {}).get(stage)
    if h is None:
        return None
    counts_, _, uppers, per_octave = h
    s = stream.hist_quantile(counts_, uppers, per_octave, q)
    return None if s is None else s * 1e3


def stage_share(ctx, stage: str):
    """Percent of the window spent in a serve stage (summed seconds)."""
    h = ctx["layer"].get("hists", {}).get(stage)
    w = ctx["layer"].get("window_s")
    if h is None or not w:
        return None
    return 100.0 * h[1] / w


def ratio(ctx, num: str, den: tuple):
    """Percent: window counter ``num`` over the sum of counters ``den``."""
    c = ctx["layer"].get("counters")
    if c is None:
        return None
    d = sum(c[k] for k in den)
    return None if d <= 0 else 100.0 * c[num] / d
