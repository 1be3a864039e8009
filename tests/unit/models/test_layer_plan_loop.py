"""A layer plan walked several times over the same weights (``loop_steps``)
with a norm before and after each sublayer (``norm_position`` ``"sandwich"``):
the plan's checks, the pool of passes x layers layer-steps and which of them
a pass touches, the one layer body of the lowered tick, a two-kind plan that
loops, the counters and scopes a looped engine gains, and that a plan walked
once is the program it was. (The looped model against its plain reference:
``tests/benchmark/test_bench_ouro.py``.)"""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, models_ouro
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.telemetry import hlo_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
TOY_DIR = os.path.join(ROOT, "tests", "benchmark", "toy", "configs")


def toy(name, **over):
    with open(os.path.join(TOY_DIR, name + ".json")) as fh:
        return dict(json.load(fh), **over)


TOY = toy("toy-ouro", dtype="float32")
L, T = TOY["model"]["num_hidden_layers"], TOY["model"]["total_ut_steps"]
VOCAB, LENGTH = TOY["model"]["vocab_size"], 64


@pytest.fixture(scope="module")
def model():
    return models_ouro.build_model(TOY, max_seq_len=LENGTH, remat=False, attn_impl="pallas")


@pytest.fixture(scope="module")
def params(model):
    return models_ouro.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 3.0)


def test_the_plan_the_builder_makes_its_pool_and_its_parameters(model, params):
    cfg = model.cfg
    assert (cfg.loop_steps, cfg.norm_position, cfg.num_layers) == (T, "sandwich", L) == (3, "sandwich", 2)
    assert [r.n for r in layer_plan.runs(cfg)] == [L]                     # one kind: a plan of one run
    (spec,) = kv_cache.specs(cfg)
    assert (spec.name, spec.layers, spec.kv_heads) == ("full", T * L, 4)
    assert jax.tree.leaves(kv_cache.init(cfg, 2, LENGTH))[0].shape == (T * L, 2, 4, LENGTH, 32)
    assert kv_cache.read_bytes_by_pool(cfg, 16) == {"full": T * L * 16 * 4 * (32 + 32) * 4}
    block = params["layers"][models_ouro.KIND]
    assert {"ln1", "ln1_post", "ln2", "ln2_post"} <= set(block) and block["ln1_post"]["scale"].shape == (L, 128)
    assert params["exit_gate"]["w"].shape == (128,) and float(params["exit_gate"]["b"]) == 0.0
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params)) == TOY["num_params"]


@pytest.mark.parametrize("bad,why", [
    (dict(loop_steps=0), "at least once"),
    (dict(norm_position="post"), "pre-norm or sandwich-norm"),
    (dict(layer_kinds=(LayerKind(name="m", mixer="ssm"), LayerKind(name="a", kv_heads=4)),
          layer_plan=(0, 1), ssm_heads=4, ssm_head_dim=32, ssm_state=16), "keyed pools only"),
    (dict(layer_kinds=None, layer_plan=None), "a layer plan's"),
])
def test_a_loop_the_program_cannot_run_is_refused_and_says_why(model, bad, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(model.cfg, **bad)


def test_the_builder_refuses_an_exit_threshold_under_one_and_says_what_is_missing():
    early = dict(TOY, model=dict(TOY["model"], early_exit_threshold=0.9))
    with pytest.raises(ValueError, match="skips.*no keys and values|rule the configuration does not"):
        models_ouro.build_model(early, max_seq_len=LENGTH, remat=False, attn_impl="xla")


def test_pass_t_of_layer_l_touches_slot_t_L_plus_l_and_no_other(model, params, monkeypatch):
    """One tick on a pool of sevens, a row at position 5 beside a parked one:
    every one of the T x L layer-steps wrote that row's position 5 in ITS
    slot of both leaves and nothing else moved; with every pass on pass 1's
    slots (the planted fault) only the first L slots are written."""
    cfg = model.cfg
    assert int(layer_plan._pass_slot(L, 2, 1)) == 2 * L + 1
    sevens = jax.tree.map(lambda a: a + 7.0, kv_cache.init(cfg, 2, LENGTH))
    tick = lambda: layer_plan.forward_plan_cached(
        params, cfg, jnp.asarray([3, 0], jnp.int32), jnp.asarray([5, LENGTH], jnp.int32), sevens)[1]
    for leaf in jax.tree.leaves(tick()):
        moved = np.asarray(leaf != 7.0)
        assert moved[:, 0, :, 5].all() and not moved[:, 1].any()
        assert not np.delete(moved[:, 0], 5, axis=2).any()
        assert len({leaf[s, 0, :, 5].tobytes() for s in range(T * L)}) == T * L   # each its own keys
    monkeypatch.setattr(layer_plan, "_pass_slot", lambda per_pass, step, pool_index: pool_index)
    for leaf in jax.tree.leaves(tick()):
        moved = np.asarray(leaf != 7.0)
        assert moved[:L, 0, :, 5].all() and not moved[L:].any()


def lowered(cfg, chunk=None, slots=4, length=128, read_len=64):
    abstract = jax.eval_shape(TransformerModel(cfg).init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, slots, length))
    row, scalar = jax.ShapeDtypeStruct((slots,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)
    wide = jax.ShapeDtypeStruct((chunk or 1,), jnp.int32)
    ride = layer_plan.Chunk(wide, wide, scalar, scalar) if chunk else None
    return jax.jit(lambda p, t, ps, ca, ch: layer_plan.forward_plan_cached(
        p, cfg, t, ps, ca, read_len=read_len, chunk=ch)).lower(abstract, row, row, cache, ride).as_text()


@pytest.mark.parametrize("chunk", [None, 32], ids=["plain", "fused"])
def test_the_lowered_looped_tick_holds_one_layer_body(model, chunk):
    """The passes are a scan around the walk's scan: one ``while`` more than
    the same plan walked once, and not one product more in the layer body
    (the looped tick's head does not norm again: its last pass did)."""
    once = dataclasses.replace(model.cfg, loop_steps=1)
    looped, plain = lowered(model.cfg, chunk), lowered(once, chunk)
    count = lambda text, op: text.count("stablehlo." + op)
    assert count(looped, "while") == count(plain, "while") + 1
    assert count(looped, "dot_general") == count(plain, "dot_general")
    assert count(looped, "rsqrt") == count(plain, "rsqrt")    # the final norm moved into the loop


# sha256 of jit(forward_plan_cached).lower(...).as_text() of the toy MiMo and toy Qwen3-Next ticks
# (4 slots of 128, read 64; plain, and with a 32-token chunk), recorded on the parent of the PR
# that brought loop_steps and the sandwich norm (409d684): walked once and pre-norm, a plan's
# tick is the old program, to the text — since PR 46 but for the pin on its q / k / v products'
# results (``tf._heads_product``, a custom call a product): with the plain product in its place
# the text is still that parent's, and with it the tick gains three calls an attention body.
# Recorded anew in PR 52, which MEANT to change them: the expert layers' way back sums over a
# leading assignment axis (``held_experts._combine``); with the parent's three lines in its place
# all six were 409d684's still (checked once, by hand).
PARENTS_TICKS = {
    ("toy-mimo-v2", None): "e18d93c0a052c08232b6c95ae7e5b9857cc182cb5490e81a1729e0e1af3d646e",
    ("toy-mimo-v2", 32): "b3f117b725b2367614d3204014f86a70fa2791a98caf4ba4c25b47e050be7c2b",
    ("toy-qwen3-next", None): "d15550f82d5a77106aec8b9fffcdfe2edf0c322570e497b656e80479bc4db89c",
    ("toy-qwen3-next", 32): "f1a42d6797165421c5fcec473174581f7a31667672cd573a32c09fc2a0da3885",
    ("toy-glm4-moe-lite", None): "025b72557deb507e16a5bdff40c9356bfde415abe7fc815647ca113d2efb0cee",
    ("toy-granitemoehybrid", 32): "37b1863729183d2a8ac513df50315903c82b4c03fd2ebb02c3842583c53ac575",
}


@pytest.mark.parametrize("name,chunk", sorted(PARENTS_TICKS, key=str))
def test_a_plan_walked_once_with_pre_norm_is_the_parents_program(name, chunk, monkeypatch):
    config = toy(name)
    cfg = compare.builder_of(config).build_model(config, max_seq_len=128, remat=False,
                                                 attn_impl="pallas").cfg
    assert (cfg.loop_steps, cfg.norm_position) == (1, "pre")
    assert sum(s.layers for s in kv_cache.specs(cfg)) == sum(k.pool != "state" for k in cfg.plan)
    assert sum(r.n for r in layer_plan.runs(cfg)) == cfg.num_layers
    bodies = sum(r.kind.mixer == "attention" for r in layer_plan.runs(cfg))   # a run is one traced body
    assert lowered(cfg, chunk).count("@LayoutConstraint") == 3 * bodies
    monkeypatch.setattr(tf, "_heads_product", tf._linear)
    assert hashlib.sha256(lowered(cfg, chunk).encode()).hexdigest() == PARENTS_TICKS[name, chunk]


def test_a_two_kind_plan_loops_too_each_pool_with_its_own_layers_a_pass():
    """Window, window, full walked twice: the ring holds 2 x 2 layer-steps and
    the full pool 2 x 1, and prefill by a chunk then decoding through both
    agree with the whole-sequence forward of the same plan."""
    kinds = (LayerKind(name="w", kv_heads=2, window=8), LayerKind(name="f", kv_heads=4))
    cfg = TransformerConfig(
        vocab_size=211, hidden_size=64, num_layers=3, num_heads=4, head_size=16, ffn_hidden_size=96,
        pos_embedding="rope", norm_type="rmsnorm", norm_position="sandwich", activation="silu_glu",
        tie_embeddings=False, use_bias=False, layer_kinds=kinds, layer_plan=(0, 0, 1), loop_steps=2,
        max_seq_len=32)
    assert {s.name: (s.layers, s.ring) for s in kv_cache.specs(cfg)} == {"window": (4, 8), "full": (2, None)}
    params = TransformerModel(cfg).init(jax.random.PRNGKey(1))
    toks = np.random.RandomState(0).randint(0, 211, 22).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(layer_plan.forward_plan(params, cfg, toks[None])[0][0])
        cache = kv_cache.init(cfg, 2, 32)
        at = np.full(16, 32, np.int32)
        at[:13] = np.arange(13)
        chunk = layer_plan.Chunk(jnp.asarray(np.pad(toks[:13], (0, 3))), jnp.asarray(at),
                                 jnp.int32(1), jnp.int32(12))
        parked = jnp.full((2,), 32, jnp.int32)
        out, cache, _ = layer_plan.forward_plan_cached(params, cfg, jnp.zeros(2, jnp.int32), parked,
                                                       cache, chunk=chunk)
        assert np.abs(np.asarray(out[1]) - want[12]).max() < 2e-4
        for p in range(13, 22):
            out, cache, _ = layer_plan.forward_plan_cached(
                params, cfg, jnp.asarray([0, toks[p]], jnp.int32), parked.at[1].set(p), cache)
            assert np.abs(np.asarray(out[1]) - want[p]).max() < 2e-4, p


def test_a_looped_engine_counts_its_passes_and_what_its_rows_read(model, params):
    eng = ContinuousBatchingEngine(
        model, config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
        params=params, max_slots=2, cache_len=LENGTH, prefill_chunk=32)
    rs = np.random.RandomState(1)
    for n in (9, 20, 14):                                 # the third waits for a slot
        eng.submit(rs.randint(0, VOCAB, n).astype(np.int32), max_new_tokens=6)
    while eng.has_work():
        eng.step()
    stats = eng.tick_stats()
    assert stats["loop_passes"] == T * stats["ticks"] > 0
    assert stats["loop_kv_positions_read"] >= stats["loop_kv_positions_live"] > 0
    assert stats["loop_kv_positions_read"] % 2 == 0       # both slots to the bucket, live or not
    assert stats["kv_pool_bytes"] == stats["kv_pool_bytes_full"] == T * L * 2 * 4 * LENGTH * 64 * 4
    assert "moe_ticks" not in stats and "ssm_step_rows" not in stats


def test_the_scope_table_of_a_looped_tick_shows_the_two_new_norms(model):
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tf.init_cache(model.cfg, 2, LENGTH))
    row = jax.ShapeDtypeStruct((2,), jnp.int32)
    compiled = jax.jit(lambda p, t, ps, ca: layer_plan.forward_plan_cached(
        p, model.cfg, t, ps, ca)).lower(abstract, row, row, cache).compile()
    seen = {hlo_scopes.model_scope(path) for path in hlo_scopes.scope_table(compiled).values()}
    assert {hlo_scopes.Scope.NORM_POST, hlo_scopes.Scope.LOOP_NORM, hlo_scopes.Scope.NORM,
            hlo_scopes.Scope.MLP, hlo_scopes.Scope.ATTN_FULL, hlo_scopes.Scope.LM_HEAD} <= seen
