"""95th percentile of the ``analytics_observe`` serve stage over the open-loop
window (the server's stage histogram, differenced across the window)."""
from benchlib import readers


def read(ctx):
    return readers.stage_ms(ctx, "analytics_observe", 0.95)
