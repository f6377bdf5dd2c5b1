"""The deployment's synthetic census map and its ground truth (numpy only).

The benchmark makes its own data: this is a copy of the program's map
generator (``repro/core/synth.py``), kept here so that the answers the
program is judged against come from nothing the program made.  The map is
a strict state -> county -> block partition of a CONUS-like extent:
recursive guillotine cuts in a rectilinear chart space, every edge
subdivided on one global grid step (neighbours share identical vertices),
then a smooth multi-octave warp that keeps the partition exact while
making edges curvy and bounding boxes overlap.

Ground truth is known by construction: a point is drawn in chart space
inside a known block rectangle, at least three times the warp's
chord-sagitta bound from its edges, and then warped.  So its block is
unambiguous even in float32, and no point-in-polygon test is needed to
know the answer.

For the same parameters this builds the same map, vertex for vertex, as
``repro.core.synth.build_synth_census`` (same random draws in the same
order); ``bench/tests/test_counts.py`` checks it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

EXTENT = (-125.0, -66.0, 24.0, 49.0)    # chart-space extent (degrees)


@dataclasses.dataclass(frozen=True)
class Warp:
    """Multi-octave sinusoidal displacement field (a homeomorphism)."""

    ax: np.ndarray
    ay: np.ndarray
    kx: np.ndarray
    ky: np.ndarray
    px: np.ndarray
    py: np.ndarray

    def __call__(self, xy: np.ndarray) -> np.ndarray:
        x, y = xy[..., 0], xy[..., 1]
        dx = np.zeros_like(x)
        dy = np.zeros_like(y)
        for i in range(len(self.ax)):
            dx = dx + self.ax[i] * np.sin(self.ky[i] * y + self.px[i])
            dy = dy + self.ay[i] * np.sin(self.kx[i] * x + self.py[i])
        return np.stack([x + dx, y + dy], axis=-1)


def _make_warp(rng, octaves: int, grad: float, k_finest: float) -> Warp:
    cols = [[] for _ in range(6)]
    for o in range(octaves):
        frq = k_finest / (4.0 ** o)
        amp = grad / frq
        for col, v in zip(cols, (amp * rng.uniform(0.6, 1.0),
                                 amp * rng.uniform(0.6, 1.0),
                                 frq * rng.uniform(0.8, 1.2),
                                 frq * rng.uniform(0.8, 1.2),
                                 rng.uniform(0, 2 * np.pi),
                                 rng.uniform(0, 2 * np.pi))):
            col.append(v)
    return Warp(*(np.array(c) for c in cols))


def _snap(c: float, lo: float, hi: float, step: float) -> float:
    t = np.round(c / step) * step
    if t <= lo + step * 0.5 or t >= hi - step * 0.5:
        return c
    return float(t)


def _bsp(rng, rect: tuple, n: int, step: float) -> list:
    rects = [rect]
    while len(rects) < n:
        areas = [(r[1] - r[0]) * (r[3] - r[2]) for r in rects]
        x0, x1, y0, y1 = rects.pop(int(np.argmax(areas)))
        if (x1 - x0) >= (y1 - y0):
            c = _snap(x0 + (x1 - x0) * rng.uniform(0.35, 0.65), x0, x1, step)
            rects += [(x0, c, y0, y1), (c, x1, y0, y1)]
        else:
            c = _snap(y0 + (y1 - y0) * rng.uniform(0.35, 0.65), y0, y1, step)
            rects += [(x0, x1, y0, c), (x0, x1, c, y1)]
    return rects


def _rect_ring(rect: tuple, step: float) -> np.ndarray:
    """Open counter-clockwise ring, subdivided on the global grid step."""
    x0, x1, y0, y1 = rect
    eps = step * 1e-9

    def ticks(lo, hi, ascending):
        t = np.arange(np.ceil((lo - eps) / step) * step, hi, step)
        t = t[(t > lo + eps) & (t < hi - eps)]
        return t if ascending else t[::-1]

    ring = [(x0, y0)] + [(t, y0) for t in ticks(x0, x1, True)]
    ring += [(x1, y0)] + [(x1, t) for t in ticks(y0, y1, True)]
    ring += [(x1, y1)] + [(t, y1) for t in ticks(x0, x1, False)]
    ring += [(x0, y1)] + [(x0, t) for t in ticks(y0, y1, False)]
    return np.array(ring, dtype=np.float64)


@dataclasses.dataclass(frozen=True)
class SynthMap:
    """The map as rings (what the program is given) plus what the
    ground truth needs (chart-space rectangles, parents and the warp)."""

    warp: Warp
    rects: dict          # level -> [n, 4] chart-space (x0, x1, y0, y1)
    parents: dict        # level -> [n] i32 parent ids (-1 for states)
    rings: dict          # level -> list of warped open rings [k, 2] f64
    sagitta: float

    @property
    def n_blocks(self) -> int:
        return len(self.rects["blocks"])

    def truth_of(self, bid: np.ndarray):
        """(state, county, block) i32 ids of block ids ``bid``."""
        cid = self.parents["blocks"][bid]
        return (self.parents["counties"][cid].astype(np.int32),
                cid.astype(np.int32), np.asarray(bid, np.int32))

    def chart_points(self, rng, n: int, margin: float = 0.0):
        """``n`` chart-space points, area-weighted over the blocks, each at
        least ``max(margin * side, 3 * sagitta)`` from its block's edges;
        returns (chart xy [n, 2] f64, block ids [n] i32)."""
        br = self.rects["blocks"]
        areas = (br[:, 1] - br[:, 0]) * (br[:, 3] - br[:, 2])
        bid = rng.choice(len(br), size=n, p=areas / areas.sum()).astype(
            np.int32)
        r = br[bid]
        w, h = r[:, 1] - r[:, 0], r[:, 3] - r[:, 2]
        mx = np.minimum(np.maximum(w * margin, 3 * self.sagitta), 0.45 * w)
        my = np.minimum(np.maximum(h * margin, 3 * self.sagitta), 0.45 * h)
        x = rng.uniform(r[:, 0] + mx, r[:, 1] - mx)
        y = rng.uniform(r[:, 2] + my, r[:, 3] - my)
        return np.stack([x, y], axis=-1), bid

    def sample(self, rng, n: int, margin: float = 0.0):
        """``n`` warped float32 points with their true block ids."""
        chart, bid = self.chart_points(rng, n, margin)
        return self.warp(chart).astype(np.float32), bid


def build_map(seed: int, n_states: int, counties_per_state: int,
              blocks_per_county: int, grad: float = 0.2,
              extent: tuple = EXTENT) -> SynthMap:
    """The seeded map (same draws as ``repro.core.synth``)."""
    rng = np.random.default_rng(seed)
    x0, x1, y0, y1 = extent
    n_blocks = n_states * counties_per_state * blocks_per_county
    step = np.sqrt((x1 - x0) * (y1 - y0) / n_blocks) / 2.0
    k_finest = np.pi / (4.0 * step)
    k_coarsest = 2.0 * np.pi / max(x1 - x0, y1 - y0)
    octaves = max(2, int(np.ceil(np.log(k_finest / k_coarsest)
                                 / np.log(4.0))))
    warp = _make_warp(rng, octaves, grad, k_finest)
    states = _bsp(rng, (x0, x1, y0, y1), n_states, step)
    counties, c_par = [], []
    for si, sr in enumerate(states):
        for cr in _bsp(rng, sr, counties_per_state, step):
            counties.append(cr)
            c_par.append(si)
    blocks, b_par = [], []
    for ci, cr in enumerate(counties):
        for br in _bsp(rng, cr, blocks_per_county, step):
            blocks.append(br)
            b_par.append(ci)
    rects = {"states": np.array(states), "counties": np.array(counties),
             "blocks": np.array(blocks)}
    parents = {"states": np.full(len(states), -1, np.int32),
               "counties": np.array(c_par, np.int32),
               "blocks": np.array(b_par, np.int32)}
    rings = {lvl: [warp(_rect_ring(tuple(r), step)) for r in rs]
             for lvl, rs in rects.items()}
    sag = float(max(sum(a * (k * step / 2) ** 2 / 2 for a, k in zip(am, ks))
                    for am, ks in ((warp.ax, warp.ky), (warp.ay, warp.kx))))
    return SynthMap(warp=warp, rects=rects, parents=parents, rings=rings,
                    sagitta=sag)
