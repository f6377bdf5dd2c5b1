"""Median latency of every request in the open-loop window, from its
scheduled arrival to its future's resolution."""
from benchlib import stream


def read(ctx):
    lat = ctx["layer"].get("latency_s")
    v = None if lat is None else stream.percentile(lat, 50)
    return None if v is None else v * 1e3
