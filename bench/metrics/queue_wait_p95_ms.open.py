"""95th percentile of the ``queue_wait`` serve stage over the open-loop window
(the server's stage histogram, differenced across the window)."""
from benchlib import readers


def read(ctx):
    return readers.stage_ms(ctx, "queue_wait", 0.95)
