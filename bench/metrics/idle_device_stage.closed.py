"""Percent of the closed-loop window in which the device ran no op while a
replica thread was inside ``geo/device_stage`` (dispatch, the wait for the
ids and their copies, the counter folds) (``benchlib/spans.py``)."""
from benchlib import spans


def read(ctx):
    return spans.idle_share(ctx, "device_stage")
