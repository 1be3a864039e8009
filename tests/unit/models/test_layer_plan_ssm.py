"""A layer plan with state-space (Mamba-2) layers and attention layers that
have no positions: the plan's checks, the state pool's shape read off the
kind's mixer, the scalar multipliers, and the program against the plain
reference (``benchmark/reference/granitemoehybrid.py``) in float32 on seeded
weights - the whole forward, each kind of layer, the serving tick's logits
through prefill chunks of several widths and rows at several depths - with
three faults planted in the program, each of which fails the same
comparison. (The scan itself: ``tests/unit/ops/test_ssd.py``; the reference's
pieces, the costs and the toy cell: ``tests/benchmark/
test_bench_granitemoehybrid.py``.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import models_granitemoehybrid
from benchmark.reference import granitemoehybrid as reference
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.pallas import ssd
from deepspeed_tpu.ops.transformer import kv_cache

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
with open(os.path.join(ROOT, "tests", "benchmark", "toy", "configs", "toy-granitemoehybrid.json")) as fh:
    TOY = dict(json.load(fh), dtype="float32")
ARCH = reference.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]
LENGTH = 128
SPREAD = 3e-4                 # of the toy's logits: / 16, a 64-wide model, the tied embedding / 32
MATCH, MISS = 2e-3 * SPREAD, 5e-2 * SPREAD


@pytest.fixture(scope="module")
def model():
    return models_granitemoehybrid.build_model(TOY, max_seq_len=LENGTH, remat=False, attn_impl="pallas")


@pytest.fixture(scope="module")
def params(model):
    return models_granitemoehybrid.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 12.0)


def logits(params, tokens, arch=ARCH):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits_at(params, tokens, at, arch))


def test_the_plan_the_builder_makes_and_its_parameters(model, params):
    cfg = model.cfg
    assert [k.name for k in cfg.plan] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert [(k.mixer, k.pool) for k in cfg.layer_kinds] == [("ssm", "state"), ("attention", "full")]
    assert (cfg.pos_embedding, cfg.embed_scale, cfg.residual_scale, cfg.logit_scale, cfg.attn_scale) == (
        "none", 12.0, 0.22, 1 / 16, 0.0625)
    assert {k: v.shape for k, v in params["layers"]["mamba"]["ssm"].items()} == {
        "win": (9, 64, 128 + 192 + 8), "conv": (9, 192, 4), "conv_bias": (9, 192), "a_log": (9, 8),
        "dt_bias": (9, 8), "d": (9, 8), "norm": (9, 128), "wo": (9, 128, 64)}
    assert set(params["layers"]["attention"]["attn"]) == {"wq", "wk", "wv", "wo"}
    assert "lm_head" not in params                       # tied
    assert model.num_params() == sum(x.size for x in jax.tree.leaves(params))
    names = jax.tree.leaves(model.logical_specs(params), is_leaf=lambda x: isinstance(x, tuple))
    assert [len(n) for n in names] == [leaf.ndim for leaf in jax.tree.leaves(params)]
    assert [(r.kind.name, r.kind_start, r.n, r.pool_start) for r in layer_plan.runs(cfg)] == [
        ("mamba", 0, 5, 0), ("attention", 0, 1, 0), ("mamba", 5, 4, 5)]


GDN = LayerKind("gdn", mixer="gdn", ffn="moe", ffn_size=32)


@pytest.mark.parametrize("bad,why", [
    (dict(ssm_heads=0), "state-space kinds"),
    (dict(ssm_groups=2), "groups of whole stored tiles"),   # 8 heads of 16 are ONE stored tile
    (dict(ssm_conv=1), "a convolution"),
    (dict(pos_embedding="learned"), "rotary positions or none at all"),
    (dict(layer_plan=(0,) * 10), "full-attention layer"),
    (dict(layer_kinds="with-gdn"), "delta-rule layers or state-space layers, not both"),
])
def test_a_plan_the_program_cannot_run_is_refused_and_says_what_a_plan_takes(model, bad, why):
    if bad.get("layer_kinds") == "with-gdn":
        bad = dict(layer_kinds=model.cfg.layer_kinds + (GDN,), gdn_key_heads=2, gdn_value_heads=4,
                   gdn_key_dim=16, gdn_value_dim=16)
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(model.cfg, **bad)


def test_the_state_pools_shape_is_read_off_the_kinds_mixer(model):
    cfg = model.cfg
    assert [s.name for s in kv_cache.specs(cfg)] == ["full"]
    # 8 heads of 16 fill one 128-lane tile: (state width 32, 8 x 16) a tile, the tail 3 x (128 + 2 x 32)
    assert kv_cache.state_spec(cfg) == kv_cache.StateSpec(9, 1, 32, 128, 3, 192)
    real = dataclasses.replace(cfg, ssm_heads=128, ssm_head_dim=64, ssm_state=128, hidden_size=4096)
    assert kv_cache.state_spec(real) == kv_cache.StateSpec(9, 64, 128, 128, 3, 8448)
    cache = tf.init_cache(cfg, 3, LENGTH)
    assert set(cache) == {"full", "state"} and cache["full"]["k"].shape == (1, 3, 1, LENGTH, 16)
    assert cache["state"]["s"].shape == (9, 3, 1, 32, 128) and cache["state"]["s"].dtype == jnp.float32
    assert cache["state"]["conv"].shape == (9, 3, 3, 192)
    assert kv_cache.state_bytes_per_row(cfg) == 9 * (8 * 16 * 32 * 4 + 3 * 192 * 4)
    assert layer_plan.stats_len(cfg) == 7
    assert layer_plan.state_counters(cfg) == ("ssm_chunk_tokens", "ssm_step_rows")
    gdn = TransformerConfig(
        vocab_size=97, hidden_size=64, num_layers=2, num_heads=4, head_size=16, pos_embedding="rope",
        norm_type="rmsnorm", activation="silu_glu", use_bias=False, tie_embeddings=False,
        layer_kinds=(GDN, LayerKind("full", ffn="moe", ffn_size=32)), layer_plan=(0, 1),
        moe_num_experts=4, moe_top_k=2, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=16,
        gdn_value_dim=16)
    assert layer_plan.state_counters(gdn) == ("gdn_chunk_tokens", "gdn_step_rows")   # as they were


def test_reference_forward_matches_the_model_in_float32(model, params):
    tokens = np.random.RandomState(0).randint(0, VOCAB, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    ref = logits(params, tokens)
    assert np.allclose(ref, theirs, atol=MATCH) and float(np.abs(ref).max()) > 2 * SPREAD


@pytest.mark.parametrize("kind", ["mamba", "attention", "experts"])
def test_each_kind_of_layer_matches_the_reference(model, params, kind):
    cfg, rs = model.cfg, np.random.RandomState(1)
    h = jnp.asarray(rs.randn(37, cfg.hidden_size), jnp.float32)
    f32 = lambda tree: jax.tree.map(lambda a: a[0].astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        if kind == "mamba":
            w = f32(params["layers"]["mamba"]["ssm"])
            mine = layer_plan._ssm_plain(h, w, cfg, 1, 37)
            theirs = reference._mamba(h, w, ARCH, reference._as_is)
        elif kind == "experts":
            w = f32(params["layers"]["attention"]["mlp"])
            mine, stats = layer_plan._ffn(h, w, cfg.layer_kinds[1], cfg, None, grad=True)
            theirs = reference._experts(h, w, ARCH, reference._as_is)
            assert int(stats[0]) == 37 * 4 and 0 < int(stats[1]) < 37 * 4
        else:   # a model of one attention layer
            one = TransformerModel(dataclasses.replace(cfg, num_layers=1, layer_plan=(1,)))
            p = dict(params, layers={"attention": params["layers"]["attention"]})
            tokens = rs.randint(0, VOCAB, (1, 37)).astype(np.int32)
            mine = one.apply(p, tokens) * 512.0            # logits of a unit scale again
            theirs = logits(p, tokens, ARCH._replace(layer_types=("attention",))) * 512.0
    assert np.allclose(mine, theirs, atol=2e-4) and float(jnp.abs(mine).max()) > 1e-2


# -- the serving tick's logits: chunks of several widths, rows at several depths --------

def drive(cfg, params, prompts, width, new=5, hold=None):
    """The tick by hand: the prompts admitted one after another, each in chunks of ``width``
    that ride beside the rows already decoding, then ``new`` plain ticks. Returns, a row, its
    tokens (prompt + greedy) and [(position, the tick's logits there)]."""
    B = len(prompts)
    cache = tf.init_cache(cfg, B, LENGTH)
    pos, last = np.full(B, LENGTH, np.int32), np.zeros(B, np.int32)
    seqs, seen = [list(p) for p in prompts], [[] for _ in prompts]
    sound = layer_plan._hold_dt
    if hold is not None:
        layer_plan._hold_dt = lambda dt, valid: sound(dt, hold(valid, B))
    try:
        tick = jax.jit(lambda cache, last, pos, chunk: layer_plan.forward_plan_cached(
            params, cfg, last, pos, cache, chunk=chunk))

        def run(chunk):
            nonlocal cache
            out, cache, stats = tick(cache, jnp.asarray(last), jnp.asarray(pos), chunk)
            for row in np.flatnonzero(pos < LENGTH):
                seen[row].append((int(pos[row]), np.asarray(out[row])))
                last[row] = int(np.argmax(out[row]))
                seqs[row].append(int(last[row]))
                pos[row] += 1
            return np.asarray(out), np.asarray(stats)

        with jax.default_matmul_precision("highest"):
            for row, prompt in enumerate(prompts):
                cache = dict(cache, state=kv_cache.reset_row(cache["state"], row))
                for start in range(0, len(prompt), width):
                    n = min(width, len(prompt) - start)
                    toks, at = np.zeros(width, np.int32), np.full(width, LENGTH, np.int32)
                    toks[:n], at[:n] = prompt[start:start + n], np.arange(start, start + n)
                    live = int((pos < LENGTH).sum())
                    out, stats = run(layer_plan.Chunk(jnp.asarray(toks), jnp.asarray(at),
                                                      jnp.int32(row), jnp.int32(n - 1)))
                    assert stats[-2:].tolist() == [n, live]     # the scan's real tokens, rows stepped
                seen[row].append((len(prompt) - 1, out[row]))    # the chunk's sampled column
                last[row], pos[row] = int(np.argmax(out[row])), len(prompt)
                seqs[row].append(int(last[row]))
            for _ in range(new):
                run(None)
    finally:
        layer_plan._hold_dt = sound
    return seqs, seen


def worst_gap(params, seqs, seen, arch=ARCH):
    """Largest |tick logit - reference logit| over every position a tick gave logits for."""
    worst = 0.0
    for seq, marks in zip(seqs, seen):
        toks = np.zeros((1, LENGTH), np.int32)
        toks[0, :len(seq)] = seq
        ref = logits(params, toks, arch)[0]
        worst = max([worst] + [float(np.abs(ref[p] - got).max()) for p, got in marks])
    return worst


PROMPTS = [np.random.RandomState(2).randint(0, VOCAB, n).astype(np.int32) for n in (37, 90, 5)]


@pytest.mark.parametrize("width", [8, 32, 96], ids=["chunk8", "chunk32", "chunk96"])
def test_tick_logits_match_the_reference_through_chunks_and_rows_at_other_depths(model, params,
                                                                                 width):
    seqs, seen = drive(model.cfg, params, PROMPTS, width)
    assert [len(m) for m in seen] == [len(s) - len(p) for s, p in zip(seqs, PROMPTS)]
    assert worst_gap(params, seqs, seen) < MATCH
    if width == 8:   # the convolution's tail crosses every chunk boundary: without it, no match
        lost = ARCH._replace(without=("conv_tail",), tail_every=8)
        assert worst_gap(params, seqs, seen, lost) > MISS


@pytest.mark.parametrize("fault", ["pads_step", "parked_rows_step"])
def test_a_fault_planted_in_the_ticks_hold_fails_the_same_comparison(model, params, fault):
    hold = {"pads_step": lambda valid, B: valid.at[B:].set(True),
            "parked_rows_step": lambda valid, B: valid.at[:B].set(True)}[fault]
    seqs, seen = drive(model.cfg, params, PROMPTS, 32, hold=hold)
    assert worst_gap(params, seqs, seen) > MISS


def serve(model, params, prompts, new=10, slots=1, chunk=32):
    eng = ContinuousBatchingEngine(
        model, config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
        params=params, max_slots=slots, cache_len=LENGTH, prefill_chunk=chunk)
    eng._chunk_floor = 16
    rids = [eng.submit(p, max_new_tokens=new) for p in prompts]
    while eng.has_work():
        eng.step()
    return eng, [np.asarray(eng.result(r)) for r in rids]


def stream_gaps(params, prompt, out):
    """How far each emitted token's logit sits below the reference's top one."""
    toks = np.zeros((1, LENGTH), np.int32)
    toks[0, :len(out)] = out
    ref = logits(params, toks)[0, len(prompt) - 1:len(out) - 1]
    emitted = out[len(prompt):]
    return ref.max(-1) - ref[np.arange(len(emitted)), emitted]


def test_a_slot_reused_without_its_reset_fails_and_with_it_passes(model, params, monkeypatch):
    rs = np.random.RandomState(3)
    two = [rs.randint(0, VOCAB, 80).astype(np.int32), rs.randint(0, VOCAB, 6).astype(np.int32)]
    with jax.default_matmul_precision("highest"):
        eng, outs = serve(model, params, two)            # the short one inherits the slot
        assert all(stream_gaps(params, p, o).max() < MATCH for p, o in zip(two, outs))
        stats = eng.tick_stats()
        assert stats["ssm_chunk_tokens"] == 86 == stats["prefill_chunk_tokens"]
        assert stats["ssm_step_rows"] == 2 * 9           # a request's first token is its chunk's
        assert "gdn_step_rows" not in stats
        assert stats["state_pool_bytes"] == stats["kv_pool_bytes_state"] == sum(
            a.nbytes for a in jax.tree.leaves(tf.init_cache(model.cfg, 1, LENGTH)["state"]))
        monkeypatch.setattr(kv_cache, "reset_row", lambda state, slot: state)
        _, outs = serve(model, params, two)
        assert stream_gaps(params, two[1], outs[1]).max() > MISS


def test_the_tick_leaves_a_parked_rows_state_and_tail_bit_for_bit(model, params):
    cfg = model.cfg
    cache = tf.init_cache(cfg, 3, 64)
    pos = jnp.asarray([5, 64, 9], jnp.int32)              # row 1 is parked
    before = jax.tree.map(lambda a: a + 1.0, cache["state"])
    _, after, stats = jax.jit(lambda cache: layer_plan.forward_plan_cached(   # one program, not one an operation
        params, cfg, jnp.zeros(3, jnp.int32), pos, cache))(dict(cache, state=before))
    assert stats.shape == (7,) and stats[-2:].tolist() == [0, 2]
    for a, b in zip(jax.tree.leaves(after["state"]), jax.tree.leaves(before)):
        assert np.array_equal(a[:, 1], b[:, 1]) and not np.array_equal(a[:, 0], b[:, 0])
    g = ssd.heads_per_tile(cfg.ssm_head_dim, cfg.ssm_heads)
    assert ssd.from_pool(after["state"]["s"], g).shape == (9, 3, 8, 16, 32)
