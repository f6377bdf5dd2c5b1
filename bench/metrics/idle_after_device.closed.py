"""Percent of the closed-loop window in which the device ran no op while a
replica thread was inside ``geo/complete_batch`` or ``geo/cache_gauges``
but in no device stage: analytics, ticket merge, cache gauges
(``benchlib/spans.py``)."""
from benchlib import spans


def read(ctx):
    return spans.idle_share(ctx, "after_device")
