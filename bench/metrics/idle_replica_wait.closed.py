"""Percent of the closed-loop window in which the device ran no op and no
replica thread had a ``geo/`` range open: the replica waited for the
flusher's next batch (``benchlib/spans.py``)."""
from benchlib import spans


def read(ctx):
    return spans.idle_share(ctx, "replica_wait")
