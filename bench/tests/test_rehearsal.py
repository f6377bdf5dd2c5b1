"""A CPU rehearsal of each traffic kind at a tiny size, kernels
interpreted: the generators, the window, the comparison and the result
line.  The harness itself refuses to report off a TPU."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


class FakeChip:
    """A CPU device that calls itself a v5e, so a traced run can find its
    peaks: only the device's name is faked, the work runs on the CPU."""

    def __init__(self, dev):
        self.platform = dev.platform
        self.device_kind = "TPU v5 lite"

    def memory_stats(self):
        return None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", sorted(bench_tiny.CELLS))
def test_each_traffic_kind_runs_and_reports(root, cell):
    from benchlib import harness
    out = bench_tiny.run_cell(root, cell)
    assert list(out) == KEYS                       # checks come last
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    plan = harness.plan(cell, root)
    assert set(out["metrics"]) == {m["name"] for m in plan.end_to_end}
    for m in plan.end_to_end:
        got = out["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(out)


@pytest.mark.parametrize("cell", ["tiny-uniform", "tiny-open",
                                  "tiny-closed"])
def test_a_traced_run_reads_its_per_layer_metrics(root, cell):
    import jax
    import time

    from benchlib import harness
    out = harness.execute(cell, 9, 1.0, True, t_start=time.perf_counter(),
                          root=root,
                          devices=[FakeChip(d) for d in jax.devices()],
                          map_cache=os.path.join(root, "maps"))
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks" and "breakdown" in out
    assert out["device"]["window_s"] > 0
    plan = harness.plan(cell, root)
    allowed = {m["name"] for m in plan.per_layer}
    assert set(out["metrics"]) <= allowed
    # The CPU has no device plane: device-trace metrics are left out,
    # never reported as 0; the host's counters and spans are read.
    trace_only = {m["name"] for m in plan.per_layer
                  if m["source"] == "device_trace"}
    assert not set(out["metrics"]) & trace_only
    assert set(out["metrics"]) == allowed - trace_only, out["metrics"]


def test_the_harness_refuses_off_a_tpu(capsys, monkeypatch):
    import run
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    rc = run.main(["--workload", "batch-uniform", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_an_unknown_device_kind_has_no_peaks(root):
    import jax
    import time

    from benchlib import harness
    with pytest.raises(harness.Refused, match="no peaks"):
        harness.execute("tiny-uniform", 1, 1.0, True,
                        t_start=time.perf_counter(), root=root,
                        devices=jax.devices(),
                        map_cache=os.path.join(root, "maps"))


def test_without_the_program_there_is_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and bench/, the run
    fails and prints nothing on standard output."""
    import shutil
    import subprocess
    bench = bench_tiny.BENCH
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "batch-uniform", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
