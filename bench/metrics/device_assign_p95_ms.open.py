"""95th percentile of the ``device_assign`` serve stage over the open-loop
window (the server's stage histogram, differenced across the window)."""
from benchlib import readers


def read(ctx):
    return readers.stage_ms(ctx, "device_assign", 0.95)
