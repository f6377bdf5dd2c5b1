"""The mesh / shard_map surface (repro/compat.py, DESIGN.md §12):
ambient-mesh tracking, shard_act edge cases as direct unit tests (these
previously had coverage only through full-model smokes), and a
multi-device regression test that the activation constraint is actually
applied inside ``use_mesh(...)`` scopes and inside raw ``jax.set_mesh``
scopes that bypassed it.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from subproc import assert_subprocess_ok

from repro import compat
from repro.launch.mesh import make_mesh
from repro.models.layers import BATCH, act_spec, shard_act


class StubMesh:
    """act_spec only needs axis_names + a name->size mapping."""

    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


# ----------------------------------------------------- act_spec unit tests
def test_act_spec_axis_absent_from_mesh_is_dropped():
    # "pod" is not on the single-pod mesh: BATCH collapses to "data".
    spec = act_spec((8, 16), (BATCH, None), StubMesh(data=2, model=4))
    assert spec == PartitionSpec("data", None)


def test_act_spec_all_axes_absent_is_replicated():
    spec = act_spec((8, 16), (("pod",), "ring"), StubMesh(data=2, model=4))
    assert spec == PartitionSpec(None, None)


def test_act_spec_non_divisible_dim_is_replicated():
    # 6 % 4 != 0 -> replicate that entry; the divisible one still shards.
    spec = act_spec((6, 8), ("data", "model"), StubMesh(data=4, model=4))
    assert spec == PartitionSpec(None, "model")


def test_act_spec_multi_axis_extent():
    # ("pod","data") both present: extent 2*2=4 divides 8 -> tuple entry.
    spec = act_spec((8, 5), (BATCH, "model"), StubMesh(pod=2, data=2, model=4))
    assert spec == PartitionSpec(("pod", "data"), None)


def test_act_spec_fewer_parts_than_dims_pads_replicated():
    spec = act_spec((4, 4, 4), ("data",), StubMesh(data=2))
    assert spec == PartitionSpec("data")


# ------------------------------------------------- shard_act + ambient mesh
def test_shard_act_no_mesh_is_identity():
    x = jnp.ones((8, 16))
    assert compat.get_abstract_mesh() is None
    assert shard_act(x, BATCH, None) is x


def test_use_mesh_records_and_restores_ambient_mesh():
    mesh = make_mesh((1,), ("data",))
    assert compat.get_abstract_mesh() is None
    with compat.use_mesh(mesh):
        got = compat.get_abstract_mesh()
        assert got is not None and got.axis_names == ("data",)
        with compat.use_mesh(mesh):        # nests
            assert compat.get_abstract_mesh() is not None
    assert compat.get_abstract_mesh() is None


def test_shard_act_applies_constraint_under_single_device_mesh():
    mesh = make_mesh((1,), ("data",))
    x = jnp.ones((8, 16))
    with compat.use_mesh(mesh):
        y = jax.jit(lambda a: shard_act(a, BATCH, None))(x)
    want = NamedSharding(mesh, PartitionSpec("data", None))
    assert y.sharding.is_equivalent_to(want, y.ndim), y.sharding
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_with_sharding_constraint_no_mesh_noop():
    x = jnp.ones((4,))
    assert compat.with_sharding_constraint(x, PartitionSpec("data")) is x


def test_param_shardings_resolves_ambient_concrete_mesh():
    """``param_shardings(mesh=None)`` resolves the concrete mesh of the
    enclosing ``use_mesh`` scope, and is a loud error outside one."""
    import pytest

    from repro.models.module import P
    from repro.sharding.rules import param_shardings

    specs = {"w": P((4, 8), ("embed", None))}
    mesh = make_mesh((1,), ("data",))
    with compat.use_mesh(mesh):
        assert compat.concrete_mesh() is mesh
        sh = param_shardings(specs)
    assert sh["w"].mesh is mesh
    assert compat.concrete_mesh() is None
    with pytest.raises(ValueError, match="no ambient mesh"):
        param_shardings(specs)


# ------------------------------------------- multi-device regression tests
def test_shard_act_actually_shards_in_use_mesh_scope():
    """The satellite regression: on a fake (2,4) multi-device mesh the
    constraint must place the batch on "data" (4-row shards), not no-op."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec
from repro.compat import get_abstract_mesh, use_mesh
from repro.launch.mesh import make_test_mesh
from repro.models.layers import BATCH, shard_act

mesh = make_test_mesh((2, 4))
x = jnp.ones((8, 16))

with use_mesh(mesh):
    y = jax.jit(lambda a: shard_act(a, BATCH, None))(x)
# 4-row shards over the 2-way "data" axis — the constraint was applied
# (shard_shape, not is_equivalent_to: CPU jit outputs carry an explicit
# memory_kind that fails strict equivalence on some jax versions).
assert y.sharding.shard_shape(y.shape) == (4, 16), ("use_mesh", y.sharding)

# Raw `jax.set_mesh(...)` scopes (never went through use_mesh) resolve
# through jax's own abstract context mesh: the bare PartitionSpec path.
with jax.set_mesh(mesh):
    assert get_abstract_mesh().axis_names == ("data", "model")
    z = jax.jit(lambda a: shard_act(a, BATCH, None, None))(
        jnp.ones((8, 4, 16)))
assert z.sharding.shard_shape(z.shape) == (4, 4, 16), ("set_mesh", z.sharding)

# Non-divisible batch (7 rows on the 2-way data axis) replicates instead
# of crashing.
with use_mesh(mesh):
    w = jax.jit(lambda a: shard_act(a, BATCH, None))(jnp.ones((7, 16)))
assert w.sharding.shard_shape(w.shape) == (7, 16), w.sharding

# Outside every scope the ambient mesh is gone.
assert get_abstract_mesh() is None
print("AMBIENT_MESH_OK")
"""
    assert_subprocess_ok(code, "AMBIENT_MESH_OK")


def test_compat_shard_map_resolves_ambient_mesh_and_vma_kwarg():
    """compat.shard_map resolves mesh=None from the ambient mesh or a raw
    jax.set_mesh scope, forwards check_vma, and is a loud ValueError
    outside every mesh scope."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as PS
from repro.compat import shard_map, use_mesh
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((2, 4))
x = jnp.ones((8, 16))

def body(a):
    # x is batch-sharded over "data" and replicated over "model": psum
    # over "data" alone gives the global sum.
    return jax.lax.psum(jnp.sum(a), "data")

with use_mesh(mesh):
    total = shard_map(body, in_specs=PS("data", None), out_specs=PS(),
                      check_vma=True)(x)
assert float(total) == 128.0, float(total)

with jax.set_mesh(mesh):
    total = shard_map(body, in_specs=PS("data", None), out_specs=PS(),
                      check_vma=False)(x)
assert float(total) == 128.0, float(total)

try:
    shard_map(body, in_specs=PS("data", None), out_specs=PS())
    raised = False
except ValueError:
    raised = True
assert raised
print("COMPAT_SHARD_MAP_OK")
"""
    assert_subprocess_ok(code, "COMPAT_SHARD_MAP_OK")
