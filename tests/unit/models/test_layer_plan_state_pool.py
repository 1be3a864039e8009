"""A layer plan whose cache has a third pool: delta-rule layers keep a
recurrent state and a convolution tail a row, with no time axis, beside the
keys and values of the full-attention layers. The pool's format behind
``kv_cache.py``, the plan's checks, the row reset that rides the admission's
row flip, and every place that refuses a plan saying so with a state pool
too. (The mathematics against the reference: ``tests/benchmark/
test_bench_qwen3_next.py``; the rule itself: ``tests/unit/ops/
test_gated_delta.py``.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from deepspeed_tpu import comm
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.inference.decoding import compile_row_update_fn
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache

KINDS = (LayerKind("gdn", mixer="gdn", ffn="moe", ffn_size=32),
         LayerKind("full", kv_heads=1, rope_theta=1e7, ffn="moe", ffn_size=32))


def config(**over):
    base = dict(vocab_size=97, hidden_size=64, num_layers=8, num_heads=4, head_size=32, rope_dim=8,
                pos_embedding="rope", norm_type="rmsnorm", norm_eps=1e-6, activation="silu_glu",
                tie_embeddings=False, use_bias=False, dtype="float32", attn_impl="pallas",
                max_seq_len=128, layer_kinds=KINDS, layer_plan=(0, 0, 0, 1) * 2,
                moe_num_experts=16, moe_top_k=4, moe_experts_held=(4, 8), moe_score="softmax",
                moe_shared_size=32, attn_out_gate=True, qk_norm=True, norm_one_plus=True,
                gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16, gdn_value_dim=16, gdn_conv=4)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def model():
    return TransformerModel(config())


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def test_parameters_of_both_kinds_and_what_the_flags_add(model, params):
    gdn, full = params["layers"]["gdn"], params["layers"]["full"]
    assert {k: v.shape for k, v in gdn["gdn"].items()} == {
        "wqkvz": (6, 64, 2 * 32 + 64 + 64), "wba": (6, 64, 8), "conv": (6, 128, 4),
        "a_log": (6, 4), "dt_bias": (6, 4), "norm": (6, 16), "wo": (6, 64, 64)}
    assert set(full["attn"]) == {"wq", "wq_gate", "wk", "wv", "wo", "q_norm", "k_norm"}
    assert full["attn"]["wq_gate"].shape == (2, 64, 4 * 32) and full["attn"]["q_norm"].shape == (2, 32)
    assert set(full["mlp"]) == {"gate", "wg", "wi", "wo", "shared_wg", "shared_wi", "shared_wo",
                                "shared_gate"}           # softmax scores: no selection bias
    assert "attn" not in gdn and float(jnp.abs(gdn["ln1"]["scale"]).mean()) < 0.2   # (1 + w): w near 0
    assert model.num_params() == sum(x.size for x in jax.tree.leaves(params))
    names = jax.tree.leaves(model.logical_specs(params), is_leaf=lambda x: isinstance(x, tuple))
    assert [len(n) for n in names] == [leaf.ndim for leaf in jax.tree.leaves(params)]


def test_a_run_that_is_part_of_its_kinds_stack_reads_its_layers_in_place():
    runs = layer_plan.runs(config())
    assert [(r.kind.name, r.kind_start, r.n, r.pool_start) for r in runs] == [
        ("gdn", 0, 3, 0), ("full", 0, 1, 0), ("gdn", 3, 3, 3), ("full", 1, 1, 1)]


@pytest.mark.parametrize("bad,why", [
    (dict(layer_plan=(0,) * 8), "full-attention layer"),
    (dict(gdn_value_heads=3), "state pool"),
    (dict(gdn_key_dim=0), "state pool"),
    (dict(gdn_conv=1), "state pool"),
    (dict(moe_score="tanh"), "moe_score"),
    (dict(layer_kinds=(dataclasses.replace(KINDS[0], mixer="mamba"), KINDS[1])), "mixer"),
])
def test_a_plan_the_program_cannot_run_is_refused(bad, why):
    with pytest.raises(ValueError, match=why):
        config(**bad)


def test_the_state_pool_has_no_time_axis_and_lives_behind_the_cache_module():
    cfg = config()
    assert [s.name for s in kv_cache.specs(cfg)] == ["full"]
    assert kv_cache.state_spec(cfg) == kv_cache.StateSpec(6, 4, 16, 16, 3, 128)
    assert kv_cache.state_spec(TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                                                 num_heads=4)) is None
    cache = tf.init_cache(cfg, 3, 128)
    assert set(cache) == {"full", "state"} and cache["full"]["k"].shape == (2, 3, 1, 128, 32)
    assert cache["state"]["s"].shape == (6, 3, 4, 16, 16) and cache["state"]["s"].dtype == jnp.float32
    assert cache["state"]["conv"].shape == (6, 3, 3, 128)
    assert kv_cache.alloc_len(cfg, cache) == 128
    state_bytes = 6 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert kv_cache.pool_bytes(cfg, cache) == {"full": 2 * 2 * 3 * 128 * 32 * 4, "state": state_bytes}
    by_pool = kv_cache.read_bytes_by_pool(cfg, 40)
    assert by_pool == {"full": 2 * 40 * (32 + 32) * 4, "state": state_bytes // 3}
    assert kv_cache.read_bytes_by_pool(cfg, 80)["state"] == by_pool["state"]     # no read bucket
    grown = jax.jit(lambda c: kv_cache.grow(cfg, c, 256))(cache)
    assert grown["full"]["k"].shape[3] == 256 and grown["state"]["s"].shape == (6, 3, 4, 16, 16)
    specs = kv_cache.partition_spec(cfg, None, ("data", "fsdp"))
    assert specs["state"]["s"] == PartitionSpec(None, ("data", "fsdp"), None, None, None)
    assert specs["state"]["conv"] == PartitionSpec(None, ("data", "fsdp"), None, None)
    assert not kv_cache.rows_write_by_blocks(cfg, cache, None)


def test_the_row_flip_of_a_state_pool_zeroes_the_admitted_row_and_no_other():
    cfg = config()
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=jax.devices()[:1])
    state = jax.tree.map(lambda a: jnp.ones_like(a), tf.init_cache(cfg, 3, 64)["state"])
    flip = compile_row_update_fn(mesh, cfg, 3, donate=False)
    last, done, new = flip(jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32), 1, 7, 0, state)
    assert last.tolist() == [0, 7, 0] and done.tolist() == [1, 0, 1]
    for leaf in jax.tree.leaves(new):
        assert float(jnp.abs(leaf[:, 1]).max()) == 0.0 and float(leaf[:, 0].min()) == 1.0 == float(leaf[:, 2].min())
    one_kind = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4)
    assert len(compile_row_update_fn(mesh, one_kind, 3, donate=False)(
        jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32), 1, 7, 0)) == 2   # as it was: no pool rides it
    comm.destroy()


def test_the_tick_reports_what_the_state_pool_did_and_a_plan_without_one_reports_as_before(model, params):
    cfg = model.cfg
    assert layer_plan.stats_len(cfg) == 7
    plain = dataclasses.replace(cfg, layer_kinds=(KINDS[1],), layer_plan=(0,) * 8)
    assert layer_plan.stats_len(plain) == 5
    cache = tf.init_cache(cfg, 3, 64)
    pos = jnp.asarray([5, 64, 9], jnp.int32)              # row 1 is parked
    before = jax.tree.map(lambda a: a + 1.0, cache["state"])
    _, after, stats = layer_plan.forward_plan_cached(params, cfg, jnp.zeros(3, jnp.int32), pos,
                                                     dict(cache, state=before))
    assert stats.shape == (7,) and stats[-2:].tolist() == [0, 2]
    for a, b in zip(jax.tree.leaves(after["state"]), jax.tree.leaves(before)):
        assert np.array_equal(a[:, 1], b[:, 1]) and not np.array_equal(a[:, 0], b[:, 0])


def test_what_a_plan_with_a_state_pool_cannot_do_yet_says_so(model, params):
    cache = tf.init_cache(model.cfg, 2, 64)
    with pytest.raises(NotImplementedError, match="chunk by chunk"):
        tf.forward_with_cache(params, model.cfg, jnp.zeros((2, 8), jnp.int32), cache, 0)
    kw = dict(config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
              params=params, max_slots=2, cache_len=64)
    with pytest.raises(NotImplementedError, match="roll a state pool's recurrent state back"):
        ContinuousBatchingEngine(model, tokens_per_tick=2, **kw)
    with pytest.raises(NotImplementedError, match="roll a state pool's recurrent state back"):
        ContinuousBatchingEngine(model, **dict(kw, config=dict(
            kw["config"], speculative={"enabled": True, "pool": True, "mode": "ngram"})))
    with pytest.raises(NotImplementedError, match="recurrent state would need a snapshot"):
        ContinuousBatchingEngine(model, **kw).register_prefix(np.arange(5))
    assert kv_cache.shard_width(None, model.cfg) == 1     # pools of a plan are never split over `tensor`
