"""Unit tests for the shared resolution core (core/resolve.py): overflow
accounting, sentinel candidates, PIP-schedule equivalence, and parity with
the fp64 host oracle.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.geometry import point_in_polygon_host
from repro.core.resolve import (ResolveStats, first_k_candidates,
                                resolve_candidates)
from repro.kernels import ops


def star_polygon(rng, n_verts, cx=0.0, cy=0.0, r0=0.5, r1=1.5):
    th = np.sort(rng.uniform(0, 2 * np.pi, n_verts))
    th += np.arange(n_verts) * 1e-9
    r = rng.uniform(r0, r1, n_verts)
    return np.stack([cx + r * np.cos(th), cy + r * np.sin(th)], -1)


@pytest.fixture(scope="module")
def poly_world():
    """Four star polygons on a 2x2 grid + points + the [P, E, 4] table."""
    rng = np.random.default_rng(0)
    centers = [(-2.0, -2.0), (2.0, -2.0), (-2.0, 2.0), (2.0, 2.0)]
    rings = [star_polygon(rng, 24, cx, cy) for cx, cy in centers]
    e = max(len(r) for r in rings)
    edges = np.zeros((len(rings), e, 4), np.float32)
    for p, ring in enumerate(rings):
        nxt = np.roll(ring, -1, axis=0)
        edges[p, :len(ring)] = np.concatenate([ring, nxt], -1)
        edges[p, len(ring):] = np.concatenate([ring[:1], ring[:1]], -1)
    pts = rng.uniform(-4.0, 4.0, (512, 2)).astype(np.float32)
    return rings, jnp.asarray(edges), pts


def oracle_first_match(rings, pts, cand_ids):
    """First candidate (slot order) containing each point, per fp64 host
    oracle; -1 if none."""
    out = np.full(len(pts), -1, np.int32)
    for i, (x, y) in enumerate(pts):
        for pid in cand_ids[i]:
            if pid < 0:
                continue
            if point_in_polygon_host(np.array([x]), np.array([y]),
                                     rings[pid])[0]:
                out[i] = pid
                break
    return out


def all_cands(n, n_poly):
    return jnp.tile(jnp.arange(n_poly, dtype=jnp.int32)[None, :], (n, 1))


def test_parity_with_host_oracle(poly_world):
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings))
    need = jnp.ones((n,), bool)
    expect = oracle_first_match(rings, pts, np.asarray(cand))
    for two_phase in (False, True):
        assign, stats = resolve_candidates(
            jnp.asarray(pts), cand, edges, need, cap=n, backend="ref",
            two_phase=two_phase, cap2=n)
        np.testing.assert_array_equal(np.asarray(assign), expect)
        assert int(stats.overflow) == 0
        assert int(stats.n_need) == n


def shuffled_cands(n, n_poly, k, seed=0):
    """[n, k] candidate rows holding each polygon id once, each row in its
    own random slot order, -1 in the other k - n_poly slots."""
    rng = np.random.default_rng(seed)
    row = np.full(k, -1, np.int32)
    row[:n_poly] = np.arange(n_poly)
    return jnp.asarray(np.stack([rng.permutation(row) for _ in range(n)]))


@pytest.mark.parametrize("k", [4, 8])
def test_two_phase_matches_sequential(poly_world, k):
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings)) if k == len(rings) \
        else shuffled_cands(n, len(rings), k)
    need = jnp.asarray(np.arange(n) % 3 != 0)     # a non-trivial subset
    seq, _ = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                cap=n, backend="ref", two_phase=False)
    two, _ = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                cap=n, backend="ref", two_phase=True,
                                cap2=n)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(two))
    # Phase 2 decides: matches land in every slot past slot 0.
    seq, c = np.asarray(seq), np.asarray(cand)
    hit = (seq >= 0) & np.asarray(need)
    slots = np.argmax(c[hit] == seq[hit, None], axis=1)
    assert set(range(1, k)) <= set(slots.tolist())


def test_overflow_accounting_exact(poly_world):
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings))
    need = jnp.ones((n,), bool)
    cap = 256
    assign, stats = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                       cap=cap, backend="ref")
    assert int(stats.n_need) == n
    assert int(stats.overflow) == n - cap
    # Overflowed rows (beyond the first `cap` needed points) keep prior.
    np.testing.assert_array_equal(np.asarray(assign)[cap:], -1)


def test_sentinel_candidates_never_match(poly_world):
    rings, edges, pts = poly_world
    n = len(pts)
    cand = jnp.full((n, 4), -1, jnp.int32)
    need = jnp.ones((n,), bool)
    prior = jnp.arange(n, dtype=jnp.int32)
    assign, stats = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                       cap=n, backend="ref", prior=prior,
                                       fallback="prior")
    np.testing.assert_array_equal(np.asarray(assign), np.asarray(prior))
    assert int(stats.n_pip) == 0


def test_fallback_first_uses_slot0(poly_world):
    """Points outside every candidate get the slot-0 candidate under
    fallback="first" (the centre-owner policy of the cell index)."""
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings))
    need = jnp.ones((n,), bool)
    expect = oracle_first_match(rings, pts, np.asarray(cand))
    assign, _ = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                   cap=n, backend="ref", fallback="first")
    a = np.asarray(assign)
    np.testing.assert_array_equal(a[expect >= 0], expect[expect >= 0])
    np.testing.assert_array_equal(a[expect < 0], 0)   # slot-0 candidate


def test_candidate_callable_after_compaction(poly_world):
    """A callable candidate table sees only compacted rows and must agree
    with the precomputed-array form."""
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings))
    need = jnp.asarray(np.arange(n) % 2 == 0)
    a1, _ = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                               cap=n, backend="ref")
    seen_rows = []

    def cand_fn(idx, sub_pts):
        seen_rows.append(sub_pts.shape[0])
        return cand[idx]

    a2, _ = resolve_candidates(jnp.asarray(pts), cand_fn, edges, need,
                               cap=256, backend="ref")
    np.testing.assert_array_equal(np.asarray(a1)[np.asarray(need)],
                                  np.asarray(a2)[np.asarray(need)])
    assert seen_rows == [256]      # evaluated on the compacted buffer only


def test_first_k_candidates_slots():
    mask = jnp.asarray(np.array([[0, 1, 0, 1, 1],
                                 [0, 0, 0, 0, 0],
                                 [1, 0, 0, 0, 1]], np.int8))
    out = np.asarray(first_k_candidates(mask, 2))
    np.testing.assert_array_equal(out, [[1, 3], [-1, -1], [0, 4]])


def test_resolve_stats_is_pytree():
    import jax
    st = ResolveStats(n_need=jnp.int32(3), n_pip=jnp.int32(5),
                      overflow=jnp.int32(0), phase2_miss=jnp.int32(0))
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == 4


# ------------------------------------------------------- phase-2 capacity
def test_phase2_miss_counted_not_silent(poly_world):
    """Slot-0 misses beyond cap2 degrade to the fallback AND are counted
    in the dedicated phase2_miss stat (ROADMAP: no silent degradation)."""
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings))
    need = jnp.ones((n,), bool)
    # Generous cap2: every slot-0 miss gets a phase-2 slot.
    _, full = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                 cap=n, backend="ref", two_phase=True,
                                 cap2=n)
    assert int(full.phase2_miss) == 0
    # How many points actually miss slot 0?
    in0 = np.asarray(ops.pip_gathered(
        jnp.asarray(pts), edges[np.asarray(cand)[:, 0]], backend="ref"))
    n_miss = int((~in0).sum())
    assert n_miss > 0                     # the fixture guarantees misses
    cap2 = 8
    a_tight, tight = resolve_candidates(jnp.asarray(pts), cand, edges,
                                        need, cap=n, backend="ref",
                                        two_phase=True, cap2=cap2)
    assert int(tight.phase2_miss) == n_miss - cap2
    # Missed points still answered via the fallback, not dropped.
    assert int(tight.overflow) == 0
    assert (np.asarray(a_tight) >= -1).all()


def test_phase2_miss_zero_for_sequential(poly_world):
    rings, edges, pts = poly_world
    n = len(pts)
    cand = all_cands(n, len(rings))
    _, stats = resolve_candidates(jnp.asarray(pts), cand, edges,
                                  jnp.ones((n,), bool), cap=n,
                                  backend="ref", two_phase=False)
    assert int(stats.phase2_miss) == 0


# ------------------------------------------------------- fused gather-PIP
@pytest.mark.parametrize("two_phase", [False, True])
def test_fused_edge_pool_matches_legacy(poly_world, two_phase):
    """resolve_candidates(edge_pool=...) routes PIP through the fused
    gather-PIP kernel and must reproduce the legacy gather flow exactly,
    on both schedules."""
    rings, edges, pts = poly_world
    pool = ops.build_edge_pool(np.asarray(edges), be=128)
    n = len(pts)
    cand = all_cands(n, len(rings))
    need = jnp.asarray(np.arange(n) % 3 != 0)
    legacy, ls = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                    cap=n, backend="ref",
                                    two_phase=two_phase, cap2=n)
    fused, fs = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                   cap=n, backend="ref",
                                   two_phase=two_phase, cap2=n,
                                   edge_pool=pool)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(fused))
    assert int(ls.n_pip) == int(fs.n_pip)


@pytest.mark.parametrize("two_phase", [False, True])
@pytest.mark.parametrize("cap2", [256, 8])
def test_fused_sort_by_candidate_bit_identical_under_compaction(
        poly_world, two_phase, cap2):
    """The fused path runs each kernel call in candidate-id-sorted order
    for block-DMA reuse (PR 2 open item); the permutation is unwound
    inside the call, so with a real capacity compaction in play — and
    even with a tiny cap2 that overflows the phase-2 compaction — the
    assignments AND stats stay bit-identical to the legacy unsorted
    gather flow."""
    rings, edges, pts = poly_world
    pool = ops.build_edge_pool(np.asarray(edges), be=128)
    n = len(pts)
    rng = np.random.default_rng(5)
    # Shuffled candidate rows -> the sort actually permutes the buffer.
    cand = jnp.asarray(rng.permuted(
        np.tile(np.arange(len(rings), dtype=np.int32), (n, 1)), axis=1))
    need = jnp.asarray(rng.random(n) < 0.7)
    cap = 256
    assert cap < int(np.asarray(need).sum())     # compaction overflows
    legacy, ls = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                    cap=cap, backend="ref",
                                    two_phase=two_phase, cap2=cap2)
    fused, fs = resolve_candidates(jnp.asarray(pts), cand, edges, need,
                                   cap=cap, backend="ref",
                                   two_phase=two_phase, cap2=cap2,
                                   edge_pool=pool)
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(fused))
    for field in ("n_need", "n_pip", "overflow", "phase2_miss"):
        assert int(getattr(ls, field)) == int(getattr(fs, field)), field


def test_fused_edge_pool_interpret_backend(poly_world):
    """The fused path under the Pallas interpret backend is bit-exact with
    the ref oracle end-to-end through resolve_candidates (small buffer:
    the per-point interpret grid is unrolled at trace time)."""
    rings, edges, pts = poly_world
    pool = ops.build_edge_pool(np.asarray(edges), be=128)
    n = 64
    cand = all_cands(n, len(rings))
    need = jnp.ones((n,), bool)
    sub = jnp.asarray(pts[:n])
    a, _ = resolve_candidates(sub, cand, edges, need, cap=n,
                              backend="ref", edge_pool=pool)
    b, _ = resolve_candidates(sub, cand, edges, need, cap=n,
                              backend="interpret", edge_pool=pool)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
