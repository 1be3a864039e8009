"""Mesh + collective facade tests (reference: tests/unit/comm/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from jax import shard_map

from deepspeed_tpu import comm


def test_default_mesh_all_data():
    comm.destroy()
    mesh = comm.init_distributed(verbose=False)
    assert mesh.shape["data"] == jax.device_count()
    assert comm.get_world_size() == jax.device_count()
    assert comm.get_rank() == 0


def test_mesh_shape_wildcard():
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"data": 2, "tensor": -1}, verbose=False)
    assert mesh.shape["data"] == 2
    assert mesh.shape["tensor"] == jax.device_count() // 2


def test_mesh_shape_invalid():
    comm.destroy()
    with pytest.raises(ValueError):
        comm.init_distributed(mesh_shape={"data": 3}, verbose=False)
    comm.destroy()
    with pytest.raises(ValueError):
        comm.init_distributed(mesh_shape={"bogus_axis": 2}, verbose=False)


def test_all_reduce_inside_shard_map():
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"data": -1}, verbose=False)
    n = mesh.shape["data"]
    x = jnp.arange(n, dtype=jnp.float32)

    def f(x):
        return comm.all_reduce(x, group="data")

    y = shard_map(f, mesh=mesh, in_specs=PartitionSpec("data"), out_specs=PartitionSpec("data"))(x)
    np.testing.assert_allclose(np.asarray(y), np.full(n, x.sum()))


def test_reduce_scatter_matches_allreduce_shard():
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"data": -1}, verbose=False)
    n = mesh.shape["data"]
    x = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n)

    def f(x):  # each rank holds one row; scatter the sum
        return comm.reduce_scatter(x.reshape(-1), group="data").reshape(1, -1)

    y = shard_map(f, mesh=mesh, in_specs=PartitionSpec("data"), out_specs=PartitionSpec("data"))(x)
    expected = np.asarray(x).sum(axis=0).reshape(n, -1).sum(axis=1)  # summed rows, chunked
    np.testing.assert_allclose(np.asarray(y).reshape(-1), np.asarray(x).sum(0))


def test_all_to_all_transposes_shards():
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"expert": -1, "data": 1}, verbose=False)
    n = mesh.shape["expert"]
    x = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n)

    def f(x):
        return comm.all_to_all(x, group="expert", split_axis=1, concat_axis=0)

    y = shard_map(f, mesh=mesh, in_specs=PartitionSpec("expert", None), out_specs=PartitionSpec("expert", None))(x)
    # rank r ends up holding column r => global result is x transposed
    np.testing.assert_allclose(np.asarray(y).reshape(n, n), np.asarray(x).T)


def test_hybrid_dcn_mesh_shapes():
    """Multi-slice mesh: per-axis size = dcn x ici, DCN outermost (the
    scaling-book layout: data over DCN, fsdp/tensor intra-slice)."""
    from deepspeed_tpu import comm

    comm.destroy()
    mesh = comm.init_distributed(
        mesh_shape={"data": 1, "fsdp": 4}, dcn_mesh_shape={"data": 2}, verbose=False
    )
    assert mesh.shape["data"] == 2 and mesh.shape["fsdp"] == 4
    # DCN-outer layout: the two data-axis groups are contiguous device blocks
    devs = mesh.devices.reshape(2, 4)
    ids = [[d.id for d in row] for row in devs]
    assert ids[0] == sorted(ids[0]) and max(ids[0]) < min(ids[1])


def test_hybrid_dcn_mesh_via_config_key():
    from deepspeed_tpu import comm

    comm.destroy()
    mesh = comm.init_distributed(
        mesh_shape={"data": 1, "fsdp": 2, "tensor": 2, "dcn": {"data": 2}}, verbose=False
    )
    assert dict(mesh.shape)["data"] == 2
    assert mesh.devices.size == 8


def test_hybrid_dcn_mesh_trains():
    import deepspeed_tpu
    import jax.numpy as jnp
    from deepspeed_tpu import comm

    comm.destroy()
    cfg = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "mesh": {"data": 1, "fsdp": 4, "dcn": {"data": 2}},
    }

    def loss_fn(params, batch, rng):
        return jnp.mean((batch["x"] @ params["w"]) ** 2)

    params = {"w": jnp.ones((8, 8), jnp.float32)}
    engine, *_ = deepspeed_tpu.initialize(loss_fn=loss_fn, params=params, config=cfg)
    batch = {"x": np.ones((8, 8), np.float32)}
    loss = engine(batch)
    engine.backward(loss)
    engine.step()
    assert engine.global_steps == 1


def test_broadcast_from_src():
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"data": -1}, verbose=False)
    n = mesh.shape["data"]
    x = jnp.arange(n, dtype=jnp.float32) + 1.0

    def f(x):
        return comm.broadcast(x, src=2, group="data")

    y = shard_map(f, mesh=mesh, in_specs=PartitionSpec("data"), out_specs=PartitionSpec("data"))(x)
    np.testing.assert_allclose(np.asarray(y), np.full(n, 3.0))


def test_group_world_sizes():
    comm.destroy()
    comm.init_distributed(mesh_shape={"data": 2, "fsdp": 2, "tensor": 2}, verbose=False)
    assert comm.get_world_size("data") == 2
    assert comm.get_world_size(("data", "fsdp")) == 4
    assert comm.get_world_size() == 8
    assert comm.dp_world_size() == 4


def test_dstpu_bench_comm_sweep():
    """dstpu_bench (reference bin/ds_bench): every collective produces a
    bandwidth record over the sweep on the virtual mesh."""
    from deepspeed_tpu import comm
    from deepspeed_tpu.launcher.bench_comm import run

    comm.destroy()
    report = run(sizes_mb=[0.125], iters=1, axis="data")
    assert report["devices"] == 8
    ops = {r["op"] for r in report["results"]}
    assert ops == {"all_reduce", "all_gather", "reduce_scatter", "all_to_all", "ppermute"}
    for r in report["results"]:
        assert "error" not in r, r
        assert r["algbw_gbps"] >= 0 and r["busbw_gbps"] >= 0
