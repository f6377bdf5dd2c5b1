"""95th percentile of how late the open-loop generator sent a request,
against its schedule (host clock)."""
from benchlib import stream


def read(ctx):
    lag = ctx["layer"].get("lag_s")
    v = None if lag is None else stream.percentile(lag, 95)
    return None if v is None else v * 1e3
