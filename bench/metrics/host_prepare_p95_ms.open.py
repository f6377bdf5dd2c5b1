"""95th percentile of the ``host_prepare`` serve stage over the open-loop
window (the server's stage histogram, differenced across the window)."""
from benchlib import readers


def read(ctx):
    return readers.stage_ms(ctx, "host_prepare", 0.95)
