"""Layers of several kinds in one stack: the plan, its parameters stacked by
kind, the cache of two sizes, and the serving tick (decode rows and one
prefill chunk as one flat list of tokens) against the model's own forward
over whole sequences, through rings that wrap and rows at different depths."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache

KINDS = (LayerKind("dense_full", kv_heads=1, rope_theta=1e7, ffn="dense", ffn_size=96),
         LayerKind("moe_window", kv_heads=2, window=8, rope_theta=1e4, sink=True, ffn="moe",
                   ffn_size=32),
         LayerKind("moe_full", kv_heads=1, rope_theta=1e7, ffn="moe", ffn_size=32))
PLAN = (0, 1, 1, 1, 2)
VOCAB = 97


def config(**over):
    base = dict(vocab_size=VOCAB, hidden_size=64, num_layers=5, num_heads=4, head_size=24,
                v_head_size=16, rope_dim=8, attn_value_scale=0.707, pos_embedding="rope",
                norm_type="rmsnorm", activation="silu_glu", tie_embeddings=False, use_bias=False,
                dtype="float32", attn_impl="pallas", max_seq_len=128, layer_kinds=KINDS,
                layer_plan=PLAN, moe_num_experts=16, moe_top_k=4, moe_experts_held=(4, 8))
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def model():
    return TransformerModel(config())


@pytest.fixture(scope="module")
def params(model):
    p = model.init(jax.random.PRNGKey(0))
    for kind in p["layers"].values():  # sharper attention: the context decides the token
        kind["attn"]["wq"] = kind["attn"]["wq"] * 3.0
    return p


def test_parameters_are_stacked_by_kind_with_each_kinds_own_shapes(model, params):
    layers = params["layers"]
    assert set(layers) == {"dense_full", "moe_window", "moe_full"}
    assert layers["moe_window"]["attn"]["wk"].shape == (3, 64, 2 * 24)   # 2 kv heads of width 24
    assert layers["moe_window"]["attn"]["wv"].shape == (3, 64, 2 * 16)   # ... values of width 16
    assert layers["moe_full"]["attn"]["wk"].shape == (1, 64, 1 * 24)
    assert layers["dense_full"]["attn"]["wq"].shape == (1, 64, 4 * 24)   # 96 != hidden 64
    assert layers["dense_full"]["attn"]["wo"].shape == (1, 4 * 16, 64)
    assert "sink" in layers["moe_window"]["attn"] and "sink" not in layers["moe_full"]["attn"]
    assert layers["moe_full"]["mlp"]["gate"].shape == (1, 64, 16)        # routes over all 16
    assert layers["moe_full"]["mlp"]["wg"].shape == (1, 8, 64, 32)       # holds 8
    assert layers["dense_full"]["mlp"]["wg"].shape == (1, 64, 96)
    assert model.num_params() == sum(x.size for x in jax.tree.leaves(params))


def test_logical_specs_name_every_leaf(model, params):
    specs = model.logical_specs(params)
    flat = jax.tree_util.tree_leaves_with_path(params)
    names = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat) == len(names)
    for (_, leaf), spec in zip(flat, names):
        assert len(spec) == leaf.ndim, (spec, leaf.shape)


def test_plan_is_walked_as_runs_of_equal_layers():
    runs = layer_plan.runs(config())
    assert [(r.kind.name, r.kind_start, r.n, r.pool_start) for r in runs] == [
        ("dense_full", 0, 1, 0), ("moe_window", 0, 3, 0), ("moe_full", 0, 1, 1)]
    alternating = config(num_layers=6, layer_plan=(0, 1, 2, 1, 1, 2))
    assert [(r.kind.name, r.kind_start, r.n, r.pool_start) for r in layer_plan.runs(alternating)] == [
        ("dense_full", 0, 1, 0), ("moe_window", 0, 1, 0), ("moe_full", 0, 1, 1),
        ("moe_window", 1, 2, 1), ("moe_full", 1, 1, 2)]


@pytest.mark.parametrize("bad,why", [
    (dict(layer_plan=(0, 1, 1)), "each of num_layers"),
    (dict(layer_plan=(0, 1, 1, 1, 5)), "outside layer_kinds"),
    (dict(layer_plan=(1, 1, 1, 1, 1)), "full-attention layer"),
    (dict(pos_embedding="learned"), "rotary"),
    (dict(moe_num_experts=0), "routes"),
    (dict(layer_kinds=KINDS[:2] + (dataclasses.replace(KINDS[2], kv_heads=2),)), "differ"),
])
def test_a_plan_the_program_cannot_run_is_refused(bad, why):
    with pytest.raises(ValueError, match=why):
        config(**bad)


def test_cache_is_one_pool_a_reach_heads_before_time():
    cache = tf.init_cache(config(), 3, 128)
    assert {k: v["k"].shape for k, v in cache.items()} == {
        "full": (2, 3, 1, 128, 24), "window": (3, 3, 2, 8, 24)}   # a ring of `window` positions
    assert cache["full"]["v"].shape[-1] == 16 and cache["window"]["v"].shape[-1] == 16
    assert kv_cache.alloc_len(config(), cache) == 128


def test_kv_read_bytes_count_the_row_in_full_layers_and_the_window_in_window_layers():
    by_pool = layer_plan.kv_read_bytes_by_pool(config(), 100)
    assert by_pool == {"full": 2 * 100 * 1 * (24 + 16) * 4, "window": 3 * 8 * 2 * (24 + 16) * 4}
    assert kv_cache.read_bytes_per_row(config(), 100) == sum(by_pool.values())
    assert layer_plan.kv_read_bytes_by_pool(config(), 4)["window"] == 3 * 4 * 2 * 40 * 4


def serve(model, params, prompts, new=12, slots=3, chunk=32, floor=16, cache_len=128):
    eng = ContinuousBatchingEngine(
        model, config={"dtype": model.cfg.dtype, "mesh": {"shape": {"data": 1, "tensor": 1}}},
        params=params, max_slots=slots, cache_len=cache_len, prefill_chunk=chunk)
    eng._chunk_floor = floor
    rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while eng.has_work():
        eng.step()
    return eng, [np.asarray(eng.result(r)) for r in rids]


def gaps(model, params, prompt, out):
    """How far each emitted token's logit sits below the top one in the
    model's forward over the whole sequence."""
    toks = np.zeros((1, 128), np.int32)
    toks[0, :len(out)] = out
    logits = np.asarray(model.apply(params, jnp.asarray(toks)))[0, len(prompt) - 1:len(out) - 1]
    emitted = out[len(prompt):]
    return logits.max(-1) - logits[np.arange(len(emitted)), emitted]


@pytest.mark.parametrize("chunk,floor", [(32, 16), (64, 64)], ids=["chunk32", "chunk64"])
def test_tick_matches_the_forward_through_wrapped_rings_at_different_depths(model, params, chunk,
                                                                           floor):
    rs = np.random.RandomState(0)
    # five prompts over three slots: rows admitted while others decode, prompts far longer
    # than window + chunk (the ring of 8 wraps many times), last chunks of every width
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in (5, 37, 70, 23, 50)]
    with jax.default_matmul_precision("highest"):
        eng, outs = serve(model, params, prompts, chunk=chunk, floor=floor)
        for p, out in zip(prompts, outs):
            assert (out[:len(p)] == p).all() and len(out) == len(p) + 12
            assert gaps(model, params, p, out).max() < 1e-4
    stats = eng.tick_stats()
    assert stats["moe_assignments"] > stats["moe_held_assignments"] > 0   # 8 of 16 held
    assert stats["moe_experts_hit"] > 0 and stats["moe_ticks"] == stats["ticks"]
    assert stats["kv_pool_bytes_full"] == 2 * 3 * 1 * 128 * 40 * 4
    assert stats["kv_pool_bytes_window"] == 3 * 3 * 2 * 8 * 40 * 4
    assert stats["prefill_chunk_tokens"] == sum(len(p) for p in prompts)
    assert stats["prefill_pairs_full"] == sum(n * (n + 1) // 2 for n in map(len, prompts))
    assert eng.kv_cache_bytes() == stats["kv_pool_bytes_full"] + stats["kv_pool_bytes_window"]


def test_tick_stats_count_the_tiles_the_chunk_kernel_walked(model, params):
    from deepspeed_tpu.ops.pallas.flash_attention import chunk_tiles

    # one chunk's sums over the plan: two full layers of one key-value head read to the bucket,
    # three window layers of two over the ring's tail joined to the chunk (the offset an int)
    W, size, first = 32, 128, 32
    full = chunk_tiles(W, 4, 1, size, 24, 16, first, itemsize=4)
    ring = chunk_tiles(W, 4, 2, 8 + W, 24, 16, 8, 0, 8, True, 4)
    assert full[0] > 0 and ring[0] > 0
    assert layer_plan.chunk_attention_tiles(model.cfg, W, size, first) == tuple(
        2 * f + 3 * r for f, r in zip(full, ring))

    eng = ContinuousBatchingEngine(
        model, config={"dtype": model.cfg.dtype, "mesh": {"shape": {"data": 1, "tensor": 1}}},
        params=params, max_slots=3, cache_len=128, prefill_chunk=32)
    names = ("prefill_tiles_visited", "prefill_tiles_masked", "prefill_kv_tile_fetches")
    assert [eng.tick_stats()[n] for n in names] == [0, 0, 0]
    calls, walk = [], eng._chunk_tiles

    def recorded(*args):
        calls.append((args[1:], walk(*args)))
        return calls[-1][1]
    eng._chunk_tiles = recorded
    eng.submit(np.arange(70, dtype=np.int32) % VOCAB, max_new_tokens=8)
    plain_steps = 0
    while eng.has_work():
        before, n = eng.tick_stats(), len(calls)
        eng.step()
        after = eng.tick_stats()
        grew = [after[k] - before[k] for k in names]
        assert grew == [sum(c[1][i] for c in calls[n:]) for i in range(3)]   # the host walk's sums ...
        plain_steps += len(calls) == n and after["ticks"] > before["ticks"]
        assert any(grew) == (len(calls) > n)                                  # ... and nothing on a plain tick
    stats = eng.tick_stats()
    assert plain_steps > 0 and stats["fused_prefill_ticks"] == len(calls) == 3
    assert [args[2] for args, _ in calls] == [0, 32, 64]                      # each chunk's first position    assert stats["prefill_tiles_visited"] >= stats["prefill_tiles_masked"] > 0
    from deepspeed_tpu.serving.loadgen import format_summary, host_overhead

    said = host_overhead(stats)
    assert said["prefill_tiles_per_chunk"] == round(stats["prefill_tiles_visited"] / 3, 1)
    assert said["prefill_kv_tile_fetches_per_chunk"] == round(stats["prefill_kv_tile_fetches"] / 3, 1)
    printed = format_summary(dict(
        requests=1, outcomes={"finished": 1}, wall_s=1.0, throughput_tok_s=8.0, goodput_tok_s=8.0,
        shed_rate=0.0, host=said))
    assert "prefill chunks " in printed and "K/V tile fetches/chunk" in printed


def test_a_stale_ring_from_the_slots_last_request_is_never_attended(model, params):
    rs = np.random.RandomState(1)
    long_first = [rs.randint(0, VOCAB, 90).astype(np.int32), rs.randint(0, VOCAB, 3).astype(np.int32)]
    with jax.default_matmul_precision("highest"):
        _, outs = serve(model, params, long_first, slots=1)   # the short one inherits the slot
        assert gaps(model, params, long_first[1], outs[1]).max() < 1e-4


@pytest.mark.parametrize("what", ["sink", "value_scale", "partial_rotary", "window", "bias"])
def test_each_piece_of_the_mathematics_moves_the_output(model, params, what):
    toks = jnp.asarray(np.random.RandomState(2).randint(0, VOCAB, (1, 40)), jnp.int32)
    base = model.apply(params, toks)
    p = jax.tree.map(lambda a: a, params)
    cfg = model.cfg
    if what == "sink":
        p["layers"]["moe_window"]["attn"]["sink"] = p["layers"]["moe_window"]["attn"]["sink"] - 30.0
    elif what == "bias":
        mlp = p["layers"]["moe_window"]["mlp"]
        mlp["gate_bias"] = mlp["gate_bias"].at[:, 5].add(10.0)
    elif what == "value_scale":
        cfg = dataclasses.replace(cfg, attn_value_scale=None)
    elif what == "partial_rotary":
        cfg = dataclasses.replace(cfg, rope_dim=None)
    else:
        kinds = (KINDS[0], dataclasses.replace(KINDS[1], window=16), KINDS[2])
        cfg = dataclasses.replace(cfg, layer_kinds=kinds)
    other = TransformerModel(cfg).apply(p, toks)
    assert float(jnp.abs(other - base).max()) > 1e-3


def test_training_forward_has_a_loss_and_a_gradient_for_every_leaf():
    model = TransformerModel(config(attn_impl="xla"))
    params = model.init(jax.random.PRNGKey(3))
    batch = {"input_ids": jnp.asarray(np.random.RandomState(3).randint(0, VOCAB, (2, 24)), jnp.int32)}
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)   # one program, not one an operation
    assert np.isfinite(float(loss)) and abs(float(loss) - np.log(VOCAB)) < 1.0
    dead = [jax.tree_util.keystr(k) for k, g in jax.tree_util.tree_leaves_with_path(grads)
            if not float(jnp.abs(g).sum()) > 0]
    assert dead == ["['layers']['moe_full']['mlp']['gate_bias']",
                    "['layers']['moe_window']['mlp']['gate_bias']"]  # the bias enters no weight


def test_what_a_plan_model_cannot_do_yet_says_so(model, params):
    cache = tf.init_cache(model.cfg, 2, 64)
    with pytest.raises(NotImplementedError, match="chunk by chunk"):
        tf.forward_with_cache(params, model.cfg, jnp.zeros((2, 8), jnp.int32), cache, 0)
    kw = dict(config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
              params=params, max_slots=2, cache_len=64)
    with pytest.raises(NotImplementedError, match="single-token ticks"):
        ContinuousBatchingEngine(model, tokens_per_tick=2, **kw)
    with pytest.raises(NotImplementedError, match="splice"):
        ContinuousBatchingEngine(model, **kw).register_prefix(np.arange(5))


def test_a_model_of_one_kind_is_left_as_it_was():
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4)
    assert cfg.plan is None and cfg.head_dim == 8 == cfg.v_head_dim
    params = tf.init(jax.random.PRNGKey(0), cfg)
    assert set(params["layers"]) == {"attn", "mlp", "ln1", "ln2"}      # one stack, one scan
    assert params["layers"]["attn"]["wq"].shape == (2, 32, 32)
    cache = tf.init_cache(cfg, 2, 16)
    assert set(cache) == {"k", "v"} and cache["k"].shape == (2, 2, 16, 4, 8)
    assert kv_cache.alloc_len(cfg, cache) == 16
    text = jax.jit(lambda p, t: tf.forward(p, cfg, t)[0]).lower(
        params, jnp.zeros((1, 8), jnp.int32)).as_text()
    assert text.count("stablehlo.while") == 1
