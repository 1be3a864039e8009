"""ZeRO sharding-policy tests (reference: tests/unit/runtime/zero/test_zero.py
partitioning semantics, re-expressed as placement assertions)."""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from deepspeed_tpu import comm
from deepspeed_tpu.runtime.zero.sharding import ShardingPolicy, add_fsdp_axis, logical_to_mesh_spec


def _abstract(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_logical_rules():
    assert logical_to_mesh_spec(("batch", "seq", "embed")) == PartitionSpec(("data", "fsdp"), "sequence", None)
    assert logical_to_mesh_spec(("embed", "mlp")) == PartitionSpec(None, "tensor")
    assert logical_to_mesh_spec(None) == PartitionSpec()


def test_add_fsdp_picks_largest_free_dim(mesh8):
    spec = add_fsdp_axis((16, 64), PartitionSpec(), mesh8)
    assert spec == PartitionSpec(None, "fsdp")
    # dim already tensor-sharded: fsdp goes to the free dim
    spec = add_fsdp_axis((64, 32), PartitionSpec(None, "tensor"), mesh8)
    assert spec == PartitionSpec("fsdp", "tensor")


def test_add_fsdp_indivisible_stays_replicated(mesh8):
    spec = add_fsdp_axis((3, 5), PartitionSpec(), mesh8)
    assert spec == PartitionSpec()


def test_stage_policies(mesh8):
    params = {"w": _abstract((64, 128)), "b": _abstract((128,))}

    s0 = ShardingPolicy(mesh8, stage=0)
    assert s0.param_pspecs(params)["w"] == PartitionSpec()
    assert s0.opt_pspecs(params)["w"] == PartitionSpec()
    assert s0.grad_pspecs(params)["w"] == PartitionSpec()

    s1 = ShardingPolicy(mesh8, stage=1)
    assert s1.param_pspecs(params)["w"] == PartitionSpec()
    assert s1.opt_pspecs(params)["w"] == PartitionSpec(None, "fsdp")
    assert s1.grad_pspecs(params)["w"] == PartitionSpec()

    s2 = ShardingPolicy(mesh8, stage=2)
    assert s2.grad_pspecs(params)["w"] == PartitionSpec(None, "fsdp")
    assert s2.param_pspecs(params)["w"] == PartitionSpec()

    s3 = ShardingPolicy(mesh8, stage=3)
    assert s3.param_pspecs(params)["w"] == PartitionSpec(None, "fsdp")
    assert s3.opt_pspecs(params)["w"] == PartitionSpec(None, "fsdp")


def test_stage3_small_param_persistence(mesh8):
    params = {"b": _abstract((128,))}
    s3 = ShardingPolicy(mesh8, stage=3, min_shard_elems=1024)
    # below threshold -> replicated (param_persistence_threshold analogue)
    assert s3.param_pspecs(params)["b"] == PartitionSpec()
    # but optimizer state still shards (stage>=1 ignores persistence)
    assert s3.opt_pspecs(params)["b"] == PartitionSpec("fsdp")


def test_stage3_sharded_param_memory(mesh8):
    """Placing params with stage-3 shardings actually splits bytes across devices."""
    policy = ShardingPolicy(mesh8, stage=3)
    x = jnp.ones((8, 64), jnp.float32)
    sharded = jax.device_put(x, policy.param_shardings({"w": x})["w"])
    shard = sharded.addressable_shards[0]
    assert shard.data.shape == (8, 8)  # 64 / 8 devices on last dim


def test_tp_plus_fsdp_composition():
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"fsdp": 4, "tensor": 2}, verbose=False)
    params = {"wi": _abstract((256, 512))}
    logical = {"wi": ("embed", "mlp")}
    s3 = ShardingPolicy(mesh, stage=3, logical_specs=logical)
    assert s3.param_pspecs(params)["wi"] == PartitionSpec("fsdp", "tensor")


def test_indivisible_dim_degrades_to_replicated():
    """GPT-2's vocab (50257, odd) cannot split over any tensor width: the
    vocab dim replicates instead of jax refusing the placement — and the
    engine with it — while a dim that divides keeps its axis."""
    comm.destroy()
    mesh = comm.init_distributed(mesh_shape={"fsdp": 2, "tensor": 4}, verbose=False)
    params = {"tok": _abstract((50257, 64)), "even": _abstract((50264, 64))}
    logical = {"tok": ("vocab", "embed"), "even": ("vocab", "embed")}
    s0 = ShardingPolicy(mesh, stage=0, logical_specs=logical)
    assert s0.param_pspecs(params)["tok"] == PartitionSpec(None, None)
    assert s0.param_pspecs(params)["even"] == PartitionSpec("tensor", None)
    # ZeRO still finds the free dim it can shard
    s3 = ShardingPolicy(mesh, stage=3, logical_specs=logical)
    assert s3.param_pspecs(params)["tok"] == PartitionSpec(None, "fsdp")
    placed = jax.device_put(jnp.zeros((50257, 64)), s3.param_shardings(params)["tok"])
    assert placed.addressable_shards[0].data.shape == (50257, 32)
