"""chip_smoke.py rehearsed without a chip: its phases at a tiny map on the
CPU with the Pallas kernels in interpret mode (one device, and four
virtual devices for the sharded paths), and its refusal to report a
result when no TPU is present.
"""
import importlib.util
import os
import sys

from subproc import REPO_ROOT, assert_subprocess_ok

from repro.kernels import gather_pip


def _load_smoke():
    """chip_smoke.py lives at the repo root, outside the test path."""
    if "chip_smoke" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


TINY = dict(n_states=4, counties_per_state=2, blocks_per_county=6,
            max_level=6, n_big=1536, n_mix=1024,
            request_sizes=(1, 7, 100, 256, 300))


def test_smoke_refuses_without_tpu(capsys):
    smoke = _load_smoke()
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert '"ok"' not in out


def test_smoke_one_chip_phases_interpret(monkeypatch, capsys):
    """Every one-chip phase passes on the CPU with interpret kernels; a
    small gather-PIP call limit makes the batch split across calls."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setattr(gather_pip, "MAX_ROWS", 512)
    smoke = _load_smoke()
    smoke.run_one_chip(smoke.SmokeConfig(**TINY))
    out = capsys.readouterr().out
    for phase in ("auto", "direct", "serve", "strategies", "onepass",
                  "analytics"):
        assert f"phase {phase}" in out
    assert "auto big: 1536 points, 1536 match ref, 1536 match ground" in out
    assert "fast fused=False: 1024 points" in out
    # Off the pallas backend the one-pass cascade builds.
    assert "fast_onepass {}: built (interpret backend)" in out


def test_smoke_four_chip_phases_on_virtual_devices():
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_KERNELS"] = "interpret"
import jax
import chip_smoke
cfg = chip_smoke.SmokeConfig(**{TINY!r})
chip_smoke.run_four_chips(cfg, jax.devices())
print("FOUR_OK")
"""
    out = assert_subprocess_ok(code, "FOUR_OK")
    assert "assign_sharded: 1536 points, 1536 match ref" in out
    assert "assign_fast_distributed: 1536 points, 1536 match ref" in out
    assert "split 1 row per chip" in out
