"""The gather-PIP kernel's share of its roofline in the batch window: the
time its needed bytes (``bench/counts/gather_pip.py``) take at the peak
memory bandwidth, over the trace time of its ops (named as in
``gather_pip_share.batch``).  Bounded by bytes: no operation peak is
used."""
from benchlib import harness, readers

NAMES = ("crossings_candidates", "_gather_pip_kernel")


def read(ctx):
    layer = ctx["layer"]
    if "inputs" not in layer:
        return None
    dep = ctx["deployment"]
    cov = dep.indices.covering
    n_edges = dep.indices.census.blocks.n_verts
    count = harness.plugin("counts", "gather_pip")
    need = sum(uses * count.needed(cov, n_edges, xy, bid)["bytes"]
               for xy, bid, uses in layer["inputs"] if uses)
    return readers.bytes_roofline(ctx, NAMES, need)
