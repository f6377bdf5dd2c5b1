"""Per-kernel validation: Pallas (interpret=True) vs the ref.py oracle,
sweeping shapes and dtypes, plus fp64 host-oracle ground truth and
hypothesis property tests on the crossing-number geometry.

The property tests require ``hypothesis``; without it they are not
collected and a single placeholder skip reports their absence.  The
oracle/shape tests always run (the interpret backend works on CPU).
"""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:          # pragma: no cover - CI image has no hypothesis
    hypothesis = st = None

from repro.core.geometry import point_in_polygon_host
from repro.kernels import ops, ref

if hypothesis is not None:
    hypothesis.settings.register_profile(
        "ci", deadline=None, max_examples=25,
        suppress_health_check=[hypothesis.HealthCheck.too_slow])
    hypothesis.settings.load_profile("ci")


def star_polygon(rng, n_verts, cx=0.0, cy=0.0, r0=0.5, r1=1.5):
    """Random star-shaped (hence simple) polygon with n_verts vertices."""
    th = np.sort(rng.uniform(0, 2 * np.pi, n_verts))
    # Ensure distinct angles.
    th += np.arange(n_verts) * 1e-9
    r = rng.uniform(r0, r1, n_verts)
    return np.stack([cx + r * np.cos(th), cy + r * np.sin(th)], -1)


def ring_to_edges(ring):
    nxt = np.roll(ring, -1, axis=0)
    return np.concatenate([ring, nxt], axis=-1).astype(np.float32)


# ---------------------------------------------------------------- pip_one
@pytest.mark.parametrize("n_pts", [7, 256, 1000])
@pytest.mark.parametrize("n_verts", [3, 17, 600])
def test_pip_one_shapes(n_pts, n_verts):
    rng = np.random.default_rng(n_pts * 1000 + n_verts)
    ring = star_polygon(rng, n_verts)
    pts = rng.uniform(-2, 2, (n_pts, 2)).astype(np.float32)
    edges = ring_to_edges(ring)
    want = np.asarray(ref.pip_one(jnp.asarray(pts), jnp.asarray(edges)))
    got = np.asarray(ops.pip_one(jnp.asarray(pts), jnp.asarray(edges),
                                 backend="interpret"))
    np.testing.assert_array_equal(got, want)
    # fp64 host oracle (points are generic, nowhere near edges w.p. 1).
    host = point_in_polygon_host(pts[:, 0], pts[:, 1], ring)
    assert (got == host).mean() > 0.999


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("n_pts,n_edges", [(64, 40), (300, 513)])
def test_pip_gathered_matches_ref(n_pts, n_edges, dtype):
    rng = np.random.default_rng(0)
    pts = rng.uniform(-2, 2, (n_pts, 2)).astype(dtype)
    # Each point gets its own random star polygon, padded with degenerate
    # (zero-length) edges like production edge tables.
    edges = np.zeros((n_pts, n_edges, 4), dtype)
    for i in range(n_pts):
        nv = int(rng.integers(3, min(n_edges, 12) + 1))
        e = ring_to_edges(star_polygon(rng, nv))
        edges[i, :nv] = e
        edges[i, nv:] = e[0, 0:1].repeat(4)[None, :] * 0 + np.array(
            [e[0, 0], e[0, 1], e[0, 0], e[0, 1]], dtype)
    want = np.asarray(ref.pip_gathered(jnp.asarray(pts), jnp.asarray(edges)))
    got = np.asarray(ops.pip_gathered(jnp.asarray(pts), jnp.asarray(edges),
                                      backend="interpret"))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- fused gather-PIP (CSR)
@pytest.fixture(scope="module")
def ragged_world():
    """Ragged polygons (one spanning multiple 128-edge pool blocks), their
    dense [P, E, 4] table, and a be=128 EdgePool built from it."""
    rng = np.random.default_rng(11)
    nvs = [5, 17, 40, 300, 9, 128]
    rings = [star_polygon(rng, nv, cx=(i % 3) * 1.5, cy=(i // 3) * 1.5)
             for i, nv in enumerate(nvs)]
    e = max(nvs)
    dense = np.zeros((len(rings), e, 4), np.float32)
    for p, ring in enumerate(rings):
        er = ring_to_edges(ring)
        dense[p, :len(er)] = er
        # Degenerate padding edges, as production tables carry.
        dense[p, len(er):] = np.array([er[0, 0], er[0, 1],
                                       er[0, 0], er[0, 1]], np.float32)
    pool = ops.build_edge_pool(dense, be=128)
    return rings, dense, pool


def test_edge_pool_layout(ragged_world):
    rings, dense, pool = ragged_world
    first = np.asarray(pool.first)
    count = np.asarray(pool.count)
    blocks = np.asarray(pool.blocks)
    # Block 0 is the reserved all-zero block (the no-candidate target).
    assert (blocks[0] == 0).all()
    # ceil(live_edges / be) blocks per polygon, contiguous from block 1.
    nvs = [len(r) for r in rings]
    np.testing.assert_array_equal(count, np.ceil(np.array(nvs) / 128))
    np.testing.assert_array_equal(first, 1 + np.concatenate(
        [[0], np.cumsum(count)[:-1]]))
    assert pool.max_blocks == int(count.max())


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_pip_candidates_matches_host_oracle(ragged_world, backend):
    """Fused gather-PIP == per-point fp64-free pip_one ground truth,
    including multi-block polygons and id -1 (never inside)."""
    rings, dense, pool = ragged_world
    rng = np.random.default_rng(5)
    n = 64
    pts = rng.uniform(-2, 4, (n, 2)).astype(np.float32)
    pids = rng.integers(-1, len(rings), n).astype(np.int32)
    got = np.asarray(ops.pip_candidates(jnp.asarray(pts),
                                        jnp.asarray(pids), pool,
                                        backend=backend))
    want = np.zeros(n, bool)
    for i in range(n):
        if pids[i] >= 0:
            want[i] = bool(np.asarray(ref.pip_one(
                jnp.asarray(pts[i:i + 1]),
                jnp.asarray(ring_to_edges(rings[pids[i]]))))[0])
    np.testing.assert_array_equal(got, want)


def test_pip_candidates_interpret_bitexact_vs_ref(ragged_world):
    """The acceptance bar: the Pallas kernel under interpret matches the
    CSR ref oracle bit-exactly (same fp32 arithmetic, same results)."""
    rings, dense, pool = ragged_world
    rng = np.random.default_rng(6)
    n = 96
    pts = rng.uniform(-3, 5, (n, 2)).astype(np.float32)
    pids = rng.integers(-1, len(rings), n).astype(np.int32)
    a = np.asarray(ops.pip_candidates(jnp.asarray(pts), jnp.asarray(pids),
                                      pool, backend="interpret"))
    b = np.asarray(ops.pip_candidates(jnp.asarray(pts), jnp.asarray(pids),
                                      pool, backend="ref"))
    np.testing.assert_array_equal(a, b)


def test_pip_candidates_matches_legacy_gather_flow(ragged_world):
    """Fused path == the two-step gather-edges-then-pip_gathered flow it
    replaces, on identical candidate ids."""
    rings, dense, pool = ragged_world
    rng = np.random.default_rng(7)
    n = 64
    pts = rng.uniform(-2, 4, (n, 2)).astype(np.float32)
    pids = rng.integers(0, len(rings), n).astype(np.int32)
    gathered = dense[pids]                       # the HBM buffer we remove
    legacy = np.asarray(ref.pip_gathered(jnp.asarray(pts),
                                         jnp.asarray(gathered)))
    fused = np.asarray(ops.pip_candidates(jnp.asarray(pts),
                                          jnp.asarray(pids), pool,
                                          backend="ref"))
    np.testing.assert_array_equal(fused, legacy)


def _pool_rows(pool, pids):
    """The kernel's per-row (first, nblk) for candidate ids (ops.py)."""
    valid = pids >= 0
    safe = np.clip(pids, 0, None)
    first = np.where(valid, np.asarray(pool.first)[safe], 0)
    nblk = np.where(valid, np.asarray(pool.count)[safe], 0)
    return first.astype(np.int32), nblk.astype(np.int32)


def _tile_cases(n_poly):
    """Candidate-id layouts for the tiled kernel, by name: row counts off
    the tile size, long runs of one id, unsorted ids, whole tiles of -1,
    a multi-block polygon and the pool's last block."""
    rng = np.random.default_rng(21)
    big = 3                                 # 300 edges: 3 blocks of 128
    return {
        "n1": rng.integers(-1, n_poly, 1),
        "n8": rng.integers(-1, n_poly, 8),
        "n300_unsorted": rng.integers(-1, n_poly, 300),
        "n1000_unsorted": rng.integers(-1, n_poly, 1000),
        "n1000_sorted": np.sort(rng.integers(0, n_poly, 1000)),
        "long_run": np.concatenate([np.full(700, 2), np.full(300, 4)]),
        "runs_across_tiles": np.repeat(rng.permutation(n_poly), 171)[:1000],
        "empty_tiles_tail": np.concatenate(
            [np.sort(rng.integers(0, n_poly, 200)), np.full(1400, -1)]),
        "all_empty": np.full(1100, -1),
        "multi_block": np.full(600, big),
        "last_block": np.full(300, n_poly - 1),
    }


def _host_copies(first, nblk, max_blocks):
    """HBM block copies the tiled kernel starts, from its schedule: per
    call, tile and block step b, one per run of rows (in row order) with
    the same valid block ``first + b``."""
    from repro.kernels import gather_pip
    n = len(first)
    n_calls = -(-n // gather_pip.MAX_ROWS)
    tp = gather_pip.tile_rows(-(-n // n_calls))
    total = -(-n // (n_calls * tp)) * tp * n_calls
    first = np.pad(first, (0, total - n))
    nblk = np.pad(nblk, (0, total - n))
    copies = 0
    for lo in range(0, total, tp):
        for b in range(max_blocks):
            key = np.where(b < nblk[lo:lo + tp], first[lo:lo + tp] + b, -1)
            new = key != np.concatenate([[-2], key[:-1]])
            copies += int((new & (key >= 0)).sum())
    return copies


@pytest.mark.parametrize("case", list(_tile_cases(6)))
def test_pip_candidates_tiles_bitexact_vs_ref(ragged_world, case):
    """The tiled kernel's crossing counts equal the CSR ref oracle's bit
    for bit on every id layout; sorting changes the copies, not the
    answer."""
    rings, dense, pool = ragged_world
    pids = _tile_cases(len(rings))[case].astype(np.int32)
    n = len(pids)
    rng = np.random.default_rng(n)
    pts = rng.uniform(-2, 4, (n, 2)).astype(np.float32)
    first, nblk = _pool_rows(pool, pids)
    args = (jnp.asarray(first), jnp.asarray(nblk), jnp.asarray(pts))
    got, _ = ops._crossings_split(*args, pool.blocks,
                                  max_blocks=pool.max_blocks,
                                  interpret=True)
    want = ref.crossings_candidates(args[2], args[0], args[1], pool.blocks,
                                    pool.max_blocks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    a = ops.pip_candidates(jnp.asarray(pts), jnp.asarray(pids), pool,
                           backend="interpret")
    b = ops.pip_candidates(jnp.asarray(pts), jnp.asarray(pids), pool,
                           backend="ref")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["n1000_unsorted", "n1000_sorted",
                                  "runs_across_tiles", "empty_tiles_tail",
                                  "multi_block", "all_empty"])
def test_pip_candidates_copies_counts_runs(ragged_world, case):
    """One HBM copy per run of equal valid blocks in row order (counted
    per tile, as the kernel scans), none for an all -1 batch; the mask
    is pip_candidates'."""
    rings, dense, pool = ragged_world
    pids = _tile_cases(len(rings))[case].astype(np.int32)
    pts = np.random.default_rng(3).uniform(
        -2, 4, (len(pids), 2)).astype(np.float32)
    inside, copies = ops.pip_candidates_copies(
        jnp.asarray(pts), jnp.asarray(pids), pool, backend="interpret")
    first, nblk = _pool_rows(pool, pids)
    want = _host_copies(first, nblk, pool.max_blocks)
    assert int(copies) == want
    if case == "all_empty":
        assert want == 0
    np.testing.assert_array_equal(
        np.asarray(inside),
        np.asarray(ops.pip_candidates(jnp.asarray(pts), jnp.asarray(pids),
                                      pool, backend="ref")))


def test_pip_candidates_copies_needs_a_kernel(ragged_world):
    """The ref oracle makes no copies, so it has no count to give."""
    rings, dense, pool = ragged_world
    with pytest.raises(ValueError):
        ops.pip_candidates_copies(jnp.zeros((8, 2), jnp.float32),
                                  jnp.zeros((8,), jnp.int32), pool,
                                  backend="ref")


def test_edge_pool_empty_table():
    pool = ops.build_edge_pool(np.zeros((0, 4, 4), np.float32))
    out = np.asarray(ops.pip_candidates(
        jnp.zeros((3, 2), jnp.float32),
        jnp.full((3,), -1, jnp.int32), pool, backend="ref"))
    assert not out.any()


# ------------------------------------------------------------------ bbox
@pytest.mark.parametrize("n_pts,n_boxes", [(10, 3), (600, 130), (512, 512)])
def test_bbox_mask_shapes(n_pts, n_boxes):
    rng = np.random.default_rng(n_pts + n_boxes)
    pts = rng.uniform(-2, 2, (n_pts, 2)).astype(np.float32)
    lo = rng.uniform(-2, 1.5, (n_boxes, 2))
    wh = rng.uniform(0.1, 1.0, (n_boxes, 2))
    boxes = np.stack([lo[:, 0], lo[:, 0] + wh[:, 0],
                      lo[:, 1], lo[:, 1] + wh[:, 1]], -1).astype(np.float32)
    want = np.asarray(ref.bbox_mask(jnp.asarray(pts), jnp.asarray(boxes)))
    got = np.asarray(ops.bbox_mask(jnp.asarray(pts), jnp.asarray(boxes),
                                   backend="interpret"))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_pts,c", [(16, 4), (300, 33), (512, 128)])
def test_bbox_count_select_shapes(n_pts, c):
    rng = np.random.default_rng(n_pts + c)
    pts = rng.uniform(-2, 2, (n_pts, 2)).astype(np.float32)
    lo = rng.uniform(-2, 1.5, (n_pts, c, 2))
    wh = rng.uniform(0.1, 1.5, (n_pts, c, 2))
    boxes = np.stack([lo[..., 0], lo[..., 0] + wh[..., 0],
                      lo[..., 1], lo[..., 1] + wh[..., 1]],
                     -1).astype(np.float32)
    wc, ws = ref.bbox_count_select(jnp.asarray(pts), jnp.asarray(boxes))
    gc, gs = ops.bbox_count_select(jnp.asarray(pts), jnp.asarray(boxes),
                                   backend="interpret")
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(wc))
    np.testing.assert_array_equal(np.asarray(gs), np.asarray(ws))


def test_pip_point_outside_bbox_is_outside():
    rng = np.random.default_rng(3)
    ring = star_polygon(rng, 12)
    far = np.array([[10.0, 10.0], [-10.0, 0.0], [0.0, 99.0]], np.float32)
    got = np.asarray(ref.pip_one(jnp.asarray(far),
                                 jnp.asarray(ring_to_edges(ring))))
    assert not got.any()


# --------------------------------------------------------------- property
if hypothesis is None:
    def test_property_suite_requires_hypothesis():
        """Visible marker that the 4 property tests below are absent."""
        pytest.skip("hypothesis not installed; property tests omitted")

if hypothesis is not None:
    @hypothesis.given(
        n_verts=st.integers(3, 40),
        n_pts=st.integers(1, 50),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_pip_property_matches_fp64_host(n_verts, n_pts, seed):
        """Kernel agrees with the fp64 host oracle on random stars."""
        rng = np.random.default_rng(seed)
        ring = star_polygon(rng, n_verts)
        pts = rng.uniform(-2, 2, (n_pts, 2))
        host = point_in_polygon_host(pts[:, 0], pts[:, 1], ring)
        got = np.asarray(ref.pip_one(jnp.asarray(pts.astype(np.float32)),
                                     jnp.asarray(ring_to_edges(ring))))
        # fp32 vs fp64 can disagree only within ~1e-6 of an edge; measure-
        # zero for uniform points, but tolerate a single straddler.
        assert (got == host).mean() >= 1.0 - 1.0 / max(n_pts, 1) * 0.999 \
            or (got == host).all()

    @hypothesis.given(
        n_verts=st.integers(3, 30),
        seed=st.integers(0, 2**31 - 1),
        dx=st.floats(-5, 5), dy=st.floats(-5, 5),
    )
    def test_pip_translation_invariance(n_verts, seed, dx, dy):
        rng = np.random.default_rng(seed)
        ring = star_polygon(rng, n_verts)
        pts = rng.uniform(-2, 2, (16, 2)).astype(np.float32)
        base = np.asarray(ref.pip_one(jnp.asarray(pts),
                                      jnp.asarray(ring_to_edges(ring))))
        shift = np.array([dx, dy], np.float32)
        moved = np.asarray(ref.pip_one(jnp.asarray(pts + shift),
                                       jnp.asarray(ring_to_edges(
                                           (ring + shift)
                                           .astype(np.float64)))))
        # Allow fp rounding flips right at edges: >= 15/16 agreement.
        assert (base == moved).sum() >= 15

    @hypothesis.given(
        n_verts=st.integers(3, 30),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_pip_orientation_invariance(n_verts, seed):
        """Reversing the ring (CW vs CCW) must not change inside/out."""
        rng = np.random.default_rng(seed)
        ring = star_polygon(rng, n_verts)
        pts = rng.uniform(-2, 2, (32, 2)).astype(np.float32)
        a = np.asarray(ref.pip_one(jnp.asarray(pts),
                                   jnp.asarray(ring_to_edges(ring))))
        b = np.asarray(ref.pip_one(jnp.asarray(pts),
                                   jnp.asarray(ring_to_edges(ring[::-1]))))
        np.testing.assert_array_equal(a, b)

    @hypothesis.given(seed=st.integers(0, 2**31 - 1))
    def test_bbox_count_matches_mask_rowsum(seed):
        rng = np.random.default_rng(seed)
        pts = rng.uniform(-2, 2, (64, 2)).astype(np.float32)
        lo = rng.uniform(-2, 1.5, (64, 8, 2))
        wh = rng.uniform(0.1, 1.5, (64, 8, 2))
        boxes = np.stack([lo[..., 0], lo[..., 0] + wh[..., 0],
                          lo[..., 1], lo[..., 1] + wh[..., 1]],
                         -1).astype(np.float32)
        cnt, sel = ref.bbox_count_select(jnp.asarray(pts),
                                         jnp.asarray(boxes))
        mask = np.asarray(ref.bbox_mask_gathered(jnp.asarray(pts),
                                                 jnp.asarray(boxes)))
        np.testing.assert_array_equal(np.asarray(cnt), mask.sum(1))
        has = mask.any(1)
        sel = np.asarray(sel)
        assert (sel[~has] == -1).all()
        rows = np.arange(64)[has]
        assert mask[rows, sel[has]].all()
