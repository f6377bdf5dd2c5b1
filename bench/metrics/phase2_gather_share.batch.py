"""Percent of the device's busy time in the batch window spent in ops under
the ``geo/phase2_gather`` scope: the phase-2 candidate gather
``cand_ids[idx2, 1:]`` and the repeat of its points, with the loop the
compiler makes of the gather and that loop's body (``benchlib/spans.py``)."""
from benchlib import spans


def read(ctx):
    return spans.scope_share(ctx, "geo/phase2_gather")
