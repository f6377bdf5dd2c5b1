"""``correct`` comes out false when the timed path is broken underneath:
the control (the program's own approximate mode, which gives up the exact
ids the configuration guarantees), and each fault a cell can have.  Runs
the whole harness on the CPU at a tiny size, past its look for a chip."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(tmp_path_factory.mktemp("tiny"))


def broken(monkeypatch, fault):
    """Make every engine the harness builds return ``fault(result)``."""
    from benchlib import harness
    from repro.core.resolve import AssignResult
    build = harness.build_engine

    def build_broken(*a, **kw):
        engine = build(*a, **kw)
        assign = engine.assign

        def wrong(points):
            res = assign(points)
            s, c, b = fault(np.asarray(res.state), np.asarray(res.county),
                            np.asarray(res.block))
            return AssignResult(s, c, b, res.stats)

        engine.assign = wrong
        return engine

    monkeypatch.setattr(harness, "build_engine", build_broken)


def alter_one(s, c, b):
    """One answer altered where it is produced."""
    b = b.copy()
    b[0] = b[0] + 1 if b[0] >= 0 else 0
    return s, c, b


def drop_half(s, c, b):
    """Half of the batch left out: its rows come back unassigned."""
    s, c, b = s.copy(), c.copy(), b.copy()
    for a in (s, c, b):
        a[len(a) // 2:] = -1
    return s, c, b


@pytest.mark.parametrize("cell", ["tiny-boundary", "tiny-open"])
def test_the_control_fails(root, cell):
    out = bench_tiny.run_cell(root, cell,
                              engine_overrides={"mode": "approx"})
    assert out["correct"] is False
    assert out["checks"]["id_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
@pytest.mark.parametrize("fault", [alter_one, drop_half],
                         ids=["answer-altered", "half-left-out"])
def test_a_fault_is_caught(root, monkeypatch, cell, fault):
    broken(monkeypatch, fault)
    out = bench_tiny.run_cell(root, cell, seconds=0.5)
    assert out["correct"] is False
    assert out["checks"]["id_mismatches"]["value"] > 0
