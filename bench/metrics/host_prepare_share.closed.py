"""Percent of the closed-loop window the server's host stage ran
(``host_prepare`` histogram sum over the window / window)."""
from benchlib import readers


def read(ctx):
    return readers.stage_share(ctx, "host_prepare")
