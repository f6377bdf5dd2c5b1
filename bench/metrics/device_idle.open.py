"""Percent of the open-loop stream window in which the device ran no
operation (profiler trace: 1 - union of device op intervals / window)."""
from benchlib import readers


def read(ctx):
    return readers.device_idle(ctx)
