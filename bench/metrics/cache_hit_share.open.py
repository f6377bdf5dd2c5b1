"""Percent of eligible points the hot-cell cache answered in the open-loop
window (``cache_hits_total`` / hits + ``cache_misses_total``)."""
from benchlib import readers


def read(ctx):
    return readers.ratio(ctx, "cache_hits_total",
                         ("cache_hits_total", "cache_misses_total"))
