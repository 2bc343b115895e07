"""Mesh / sharding / compiled-collective tests on the 8-device CPU mesh
(SURVEY.md §4: fake accelerator topology via
xla_force_host_platform_device_count)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import (Logical, MeshSpec, make_mesh, shard_tree,
                              spec_from_logical, tree_shardings)
from ray_tpu.collective import (mesh_allgather, mesh_allreduce,
                                mesh_all_to_all, mesh_broadcast,
                                mesh_ppermute, mesh_reducescatter)


def test_mesh_resolve_fill():
    spec = MeshSpec(dp=-1, tp=2).resolve(8)
    assert spec.dp == 4 and spec.tp == 2


def test_mesh_build_shapes():
    mesh = make_mesh(dp=2, tp=4)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    assert mesh.shape["pp"] == 1


def test_mesh_bad_shape():
    with pytest.raises(ValueError):
        make_mesh(dp=3, tp=3)  # 9 != 8


def test_spec_from_logical_collapses_size1_axes():
    mesh = make_mesh(dp=8)  # tp has size 1
    s = spec_from_logical(("embed", "heads", "head_dim"), mesh=mesh)
    # embed->fsdp (size 1 -> None), heads->tp (size 1 -> None)
    assert s == P()
    mesh2 = make_mesh(fsdp=2, tp=4)
    s2 = spec_from_logical(("embed", "heads", "head_dim"), mesh=mesh2)
    assert s2 == P("fsdp", "tp")


def test_tree_sharding_placement():
    mesh = make_mesh(fsdp=2, tp=4)
    params = {"w": np.ones((8, 16), np.float32),
              "b": np.zeros((16,), np.float32)}
    logical = {"w": Logical("embed", "mlp"), "b": Logical("mlp")}
    placed = shard_tree(params, logical, mesh)
    assert placed["w"].sharding.spec == P("fsdp", "tp")
    assert np.allclose(np.asarray(placed["w"]), 1.0)


def test_mesh_allreduce_sum():
    mesh = make_mesh(dp=8)
    x = jnp.arange(16.0)  # 2 per device
    out = mesh_allreduce(x, mesh, "dp")
    # each device chunk replaced by sum over devices of its chunk-position
    chunks = np.asarray(x).reshape(8, 2)
    expected = np.tile(chunks.sum(0), 8)
    assert np.allclose(np.asarray(out), expected)


def test_mesh_allgather():
    mesh = make_mesh(dp=8)
    x = jnp.arange(8.0)
    out = mesh_allgather(x, mesh, "dp")
    assert np.allclose(np.asarray(out), np.arange(8.0))
    assert out.sharding.is_fully_replicated


def test_mesh_reducescatter():
    mesh = make_mesh(dp=8)
    x = jnp.ones((8, 16))  # 8 contributions of 16 values
    out = mesh_reducescatter(x, mesh, "dp")
    assert out.shape == (8, 2)  # each device owns its reduced chunk of 2
    assert np.allclose(np.asarray(out), 8.0)


def test_mesh_broadcast():
    mesh = make_mesh(dp=8)
    x = jnp.arange(8.0)
    out = mesh_broadcast(x, mesh, "dp", root=3)
    assert np.allclose(np.asarray(out), 3.0)


def test_mesh_ppermute_ring():
    mesh = make_mesh(dp=8)
    n = 8
    perm = [(i, (i + 1) % n) for i in range(n)]
    x = jnp.arange(8.0)
    out = mesh_ppermute(x, mesh, perm, "dp")
    assert np.allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_mesh_all_to_all():
    mesh = make_mesh(dp=8)
    # [8, 8]: row-sharded; all_to_all(split dim1, concat dim0, tiled) == transpose of blocks
    x = jnp.arange(64.0).reshape(8, 8)
    out = mesh_all_to_all(x, mesh, "dp", split_axis=1, concat_axis=0)
    assert out.shape == (64, 1)
    got = np.asarray(out).reshape(8, 8)
    assert np.allclose(got, np.asarray(x).T)


def test_multi_axis_collective():
    mesh = make_mesh(dp=2, tp=4)
    x = jnp.ones((8, 8))

    @jax.jit
    def step(v):
        def f(shard):
            s = jax.lax.psum(shard, "dp")
            return jax.lax.psum(s, "tp")

        from jax import shard_map
        return shard_map(f, mesh=mesh, in_specs=P(("dp",), "tp"),
                         out_specs=P(("dp",), "tp"))(v)

    out = step(x)
    assert np.allclose(np.asarray(out), 8.0)


def test_multislice_mesh_layout():
    """DCN axis spans slices; every ICI axis stays inside one slice
    (megascale layout: cross-slice traffic only on the dcn axis)."""
    import numpy as np

    from ray_tpu.parallel import make_multislice_mesh

    devs = jax.devices()[:8]
    mesh = make_multislice_mesh(dcn={"dp": 2},
                                ici={"fsdp": 2, "tp": 2},
                                devices=devs, num_slices=2)
    assert mesh.shape["dp"] == 2 and mesh.shape["fsdp"] == 2 \
        and mesh.shape["tp"] == 2
    arr = mesh.devices
    slice0 = set(devs[:4])
    # dp index 0 must hold exactly slice 0's devices
    dp_axis = list(mesh.axis_names).index("dp")
    first = np.take(arr, 0, axis=dp_axis).ravel()
    assert set(first.tolist()) == slice0

    # a dp-psum over the multislice mesh compiles and runs
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def f(x):
        return jax.lax.psum(x, "dp")

    g = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P())
    out = jax.jit(g)(jnp.arange(8.0))
    np.testing.assert_allclose(
        np.asarray(out), np.arange(8.0).reshape(2, 4).sum(0))


def test_multislice_mesh_validation():
    import pytest as _pytest

    from ray_tpu.parallel import make_multislice_mesh

    devs = jax.devices()[:8]
    with _pytest.raises(ValueError, match="exactly one DCN axis"):
        make_multislice_mesh(dcn={"dp": 2, "pp": 2}, ici={},
                             devices=devs)
    with _pytest.raises(ValueError, match="slices"):
        make_multislice_mesh(dcn={"dp": 3}, ici={"tp": 2},
                             devices=devs, num_slices=2)
    with _pytest.raises(ValueError, match="devices not divisible"):
        make_multislice_mesh(dcn={"dp": 3}, ici={"tp": 2},
                             devices=devs, num_slices=3)
