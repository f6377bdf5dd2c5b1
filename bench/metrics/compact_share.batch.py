"""Percent of the device's busy time in the batch window spent in ops under
the ``geo/compact`` scope: both compactions, the gathers of the compacted
rows and the scatters that write results back (``benchlib/spans.py``)."""
from benchlib import spans


def read(ctx):
    return spans.scope_share(ctx, "geo/compact")
