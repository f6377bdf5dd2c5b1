"""Percent of the device's busy time in the batch window spent in ops under
the ``geo/locate`` scope: quantize, the bucketed cell search, the cell test
and the extent mask (``benchlib/spans.py``: self time by scope)."""
from benchlib import spans


def read(ctx):
    return spans.scope_share(ctx, "geo/locate")
