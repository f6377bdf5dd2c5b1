"""Compile-only checks of the geo kernels for a TPU v5e that is described,
not attached: each kernel at the sizes ``chip_smoke.py`` drives is lowered
and compiled by the TPU compiler, which refuses what interpret mode lets
through (block shapes off the (8, 128) tiling, SMEM or VMEM overuse).
Nothing runs, so these say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and with several test
workers only the worker given this file may try.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import fast
from repro.core.artifact import GeoIndexSet
from repro.core.engine import EngineConfig
from repro.core.registry import get_strategy
from repro.kernels import bbox, gather_pip, ops, pip, segment

# Sizes of the one-chip smoke run (chip_smoke.py): the 56 / 16 / 24
# synthetic map (21,504 blocks, <= 16 edges each, so one 256-edge pool
# block per block; states <= 196 edges), the 262,144-point batch.
N_BIG = 1 << 18
N_BLOCKS = 21_504
N_SMALL = 1 << 14


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32 = jnp.float32, jnp.int32


def test_gather_pip_compiles_at_max_rows(one_chip):
    r = gather_pip.MAX_ROWS
    assert r == 65_536
    nb = N_BLOCKS + 1                      # block 0 reserved
    fn = functools.partial(gather_pip.crossings_candidates, max_blocks=1)
    _compile(fn, one_chip, ((r,), I32), ((r,), I32), ((r, 2), F32),
             ((nb, 4, gather_pip.DEF_BE), F32))


def test_segment_reduce_compiles(one_chip):
    bp, bs = segment.DEF_BP, segment.DEF_BS
    s_pad = ((N_BLOCKS + 1 + bs - 1) // bs) * bs
    fn = functools.partial(segment.segment_reduce_sorted,
                           n_segments=s_pad)
    _compile(fn, one_chip, ((N_BIG // bp, bp, 1), I32),
             ((N_BIG // bp, bp, 1), F32))


def test_pip_crossings_one_compiles(one_chip):
    _compile(pip.crossings_one, one_chip, ((N_BIG, 2), F32),
             ((4, pip.DEF_BE), F32))


def test_pip_crossings_gathered_compiles(one_chip):
    _compile(pip.crossings_gathered, one_chip, ((N_SMALL, 2), F32),
             ((N_SMALL, 4, pip.DEF_BE), F32))


def test_bbox_count_select_compiles(one_chip):
    _compile(bbox.bbox_count_select, one_chip, ((N_BIG, 2), F32),
             ((N_BIG, 4, 128), F32))


def test_bbox_mask_compiles(one_chip):
    _compile(bbox.bbox_mask, one_chip, ((N_BIG, 2), F32),
             ((4, bbox.DEF_BM), F32))


_GATHER_LOOP = re.compile(r' while\(.*op_name="[^"]*/gather"')


@pytest.mark.timeout(300)
def test_fast_assign_gathers_without_loops(one_chip, synth_small):
    """The exact fused fast assign at the serving bucket compiles with no
    gather expanded into a loop: the TPU compiler turns a gather it cannot
    lower natively into a ``while`` of one slice per trip, which ran 0.6 s
    of each 1,048,576-point call on a v5e.  The metadata rides in the
    compile key, as the benchmark's scope reader compiles it, so the
    op_names are this build's own."""
    index = GeoIndexSet.build(synth_small.census, ("fast",), ("fast",),
                              max_level=6).fast
    assert index.cand.shape[1] == 8
    spec = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        index)
    x = jax.ShapeDtypeStruct((N_SMALL, 2), F32, sharding=one_chip)
    cfg = fast.FastConfig(mode="exact", cap_boundary=1.0, backend="pallas",
                          fused=True)
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        text = fast.assign_fast.lower(spec, x, cfg=cfg).compile().as_text()
    finally:
        jax.config.update(key, before)
    assert "tpu_custom_call" in text
    assert [ln for ln in text.splitlines() if _GATHER_LOOP.search(ln)] == []


@pytest.mark.parametrize("strategy,fused", [("fast_onepass", False),
                                            ("fast", "onepass")])
def test_onepass_refused_on_tpu(topo, synth_small, monkeypatch, strategy,
                                fused):
    """The one-pass cascade kernel does not compile for the TPU: an engine
    validated against a TPU device refuses it at build, loudly, rather
    than falling back to another backend."""
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    monkeypatch.setattr(jax, "default_backend",
                        lambda: topo.devices[0].platform)
    assert ops.resolve_backend() == "pallas"
    cfg = EngineConfig(max_level=6, fused=fused)
    indices = GeoIndexSet.build(synth_small.census, ("fast",), ("fast",),
                                max_level=6)
    with pytest.raises(ValueError, match="does not compile for the TPU"):
        get_strategy(strategy).validate(indices, cfg)
    # The same index on an explicitly chosen ref backend stays valid.
    get_strategy(strategy).validate(
        indices, EngineConfig(max_level=6, fused=fused, backend="ref"))
