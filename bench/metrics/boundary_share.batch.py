"""Percent of the window's points whose covering cell is a boundary cell
(GeoStats ``n_boundary`` / points), the points the PIP kernel resolves."""


def read(ctx):
    layer = ctx["layer"]
    if not layer.get("points"):
        return None
    return 100.0 * layer["n_boundary"] / layer["points"]
