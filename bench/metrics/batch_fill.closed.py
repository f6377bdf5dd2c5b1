"""Percent of padded device slots that held a real point in the
closed-loop window (``valid_slots`` / ``padded_slots``)."""
from benchlib import readers


def read(ctx):
    return readers.ratio(ctx, "valid_slots", ("padded_slots",))
