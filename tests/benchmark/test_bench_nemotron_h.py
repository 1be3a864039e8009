"""Nemotron 3 Super's family (a layer is ONE sublayer: a Mamba-2 mixer with
groups of ``B`` and ``C``, attention with no positions, or an expert layer
whose un-gated squared-ReLU experts work in a latent, a sigmoid top-k with a
selection bias and a routed scale, of which a chip holds a share; an untied
head): the program against the reference (the uncached forward, and chunks
then rows through the pools, float32 and bfloat16), each piece of the
reference failing when left out, the shares of a deployment adding up to the
uncut layer, the parameter counts from the published keys, its cost functions
against hand counts, its configuration file against the published
``config.json``, its variant tool on the toy cell, and the ticks of the plans
the benchmark already served lowering to the parent's text. Its toy cell
(``toy/cells/toy-nemotron-reasoning.json``) runs end to end, traced and
untraced, with every other toy cell (``test_bench_runners_cpu.py`` finds it by
its file). Everything of this family is a file of its own."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_nemotron_h as costs, harness, models_nemotron_h, readers
from benchmark.reference import nemotron_h
from deepspeed_tpu.models import layer_plan, transformer as tf
from deepspeed_tpu.models.transformer import TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache

sys.path.insert(0, os.path.join(bench_toy.ROOT, "tools"))
import nemotron_cell_variant  # noqa: E402

TOY_BF16 = harness.load_json(os.path.join(bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-nemotron-h.json"))
TOY = dict(TOY_BF16, dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs",
                                      "nemotron-3-super-120b-a12b.json"))
CELL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "cells",
                                      "serve-nemotron-3-super-reasoning-batch.json"))
ARCH = nemotron_h.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]
LENGTH = 128
MATCH, MISS = 2e-4, 0.02      # of logits whose spread is ~0.5 at the toy's width


def build(config):
    return models_nemotron_h.build_model(config, max_seq_len=LENGTH, remat=False, attn_impl="pallas")


@pytest.fixture(scope="module")
def model():
    return build(TOY)


@pytest.fixture(scope="module")
def params(model):
    return models_nemotron_h.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 3.0)


def logits(params, tokens, arch=ARCH):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(nemotron_h.logits_at(params, tokens, at, arch))


def test_the_configuration_names_its_reference_and_builder(model):
    assert compare.reference_of(TOY) is nemotron_h and compare.builder_of(TOY) is models_nemotron_h
    assert all(isinstance(TOY["model"][k], int) for k in models_nemotron_h.REQUIRED_SIZES)
    assert ARCH.pattern == "MEM*E" and (ARCH.held_first, ARCH.held_count, ARCH.top_k) == (4, 8, 4)
    assert (ARCH.ssm_groups, ARCH.routed_scale, ARCH.norm_eps) == (2, 5.0, 1e-20)
    cfg = model.cfg
    assert cfg.moe_num_experts == 16 and cfg.held_experts == (4, 8) and cfg.moe_routed_scale == 5.0
    assert not cfg.moe_shared_gated and cfg.moe_score == "sigmoid" and cfg.activation == "relu2"
    assert cfg.pos_embedding == "none" and not cfg.tie_embeddings and cfg.ssm_groups == 2
    assert [k.name for k in cfg.plan] == ["mamba", "experts", "mamba", "attention", "experts"]
    assert [nemotron_h.KINDS[c] for c in ARCH.pattern] == [k.name for k in cfg.plan]
    assert cfg.layer_kinds[2].ffn_latent == 32 and cfg.layer_kinds[2].pool is None
    assert nemotron_h.__name__.startswith("benchmark.") and not any(
        "deepspeed_tpu" in line for line in open(nemotron_h.__file__) if line.startswith(("import", "from")))


# -- the program against the reference ---------------------------------------------------

@pytest.fixture(scope="module")
def sound(model, params):
    """(tokens, the program's logits), which the whole reference matches."""
    tokens = np.random.RandomState(4).randint(0, VOCAB, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=MATCH)
    assert theirs.std() > 0.2                                # ... of logits that say something
    return tokens, theirs


@pytest.mark.parametrize("left_out", [
    "decay", "skip", "conv", "conv_bias", "z_gate", "gate_before_norm", "group_norm", "groups", "nope",
    "shared", "latent_up", "routed_scale", "select_bias", "relu2"])
def test_each_piece_of_the_mathematics_fails_when_left_out(params, sound, left_out):
    """The program's logits against a reference that lacks one piece (or has
    ONE norm over the whole inner width, group 0's B and C for every head,
    rotary positions turned on, a routed scale of 1): the comparison that
    passes must fail."""
    tokens, theirs = sound
    wrong = logits(params, tokens, ARCH._replace(without=(left_out,)))
    assert not np.abs(wrong - theirs).max() < MISS      # (a reference that blew up is not a match)


@pytest.mark.parametrize("dtype,tol,share", [("float32", MATCH, 1.0), ("bfloat16", 0.2, 0.6)])
def test_chunks_then_rows_through_the_pools_agree_with_the_references_full_forward(dtype, tol, share):
    """Prefill in chunks of 32 and decoding through both pools (the timed
    path's programs), every emitted position's logits against the
    reference's full forward over the same tokens. float32: every position
    within 2e-4. bfloat16, logits of spread ~1: a position's widest gap is
    0.02-0.07 where both sides chose the same experts, and about a logit where
    an expert at the 4th place flipped (half the experts are held here, and a
    routed expert carries 5 / 4 of a weight; a flip in the first expert layer
    moves the second's choices too): 0.6 of the positions within 0.2 (0.72
    read) and the median under 0.1 (the cell's margin is set from chip
    readings, where a quarter is held and the 22nd place carries 5 / 22)."""
    config = dict(TOY_BF16, dtype=dtype)
    model = build(config)
    cfg = model.cfg
    params = models_nemotron_h.sharpen(model.init(jax.random.PRNGKey(1)), config, 3.0)
    if dtype == "bfloat16":
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    tokens = np.random.RandomState(7).randint(0, VOCAB, (2, 80)).astype(np.int32)
    want = logits(params, tokens, nemotron_h.arch(config))
    B, W = 2, 32
    cache = tf.init_cache(cfg, B, LENGTH)
    step = jax.jit(lambda p, t, ps, ca, ch: layer_plan.forward_plan_cached(p, cfg, t, ps, ca, chunk=ch))
    parked = jnp.full((B,), LENGTH, jnp.int32)
    gaps = []
    for r in range(B):
        for first in (0, W):
            ch = layer_plan.Chunk(jnp.asarray(tokens[r, first:first + W]),
                                  first + jnp.arange(W, dtype=jnp.int32), jnp.int32(r), jnp.int32(W - 1))
            lg, cache, _ = step(params, jnp.zeros((B,), jnp.int32), parked, cache, ch)
            gaps.append(np.abs(np.asarray(lg[r], np.float32) - want[r, first + W - 1]).max())
    pos = jnp.full((B,), 2 * W, jnp.int32)
    for t in range(2 * W, 80):
        lg, cache, _ = step(params, jnp.asarray(tokens[:, t]), pos, cache, None)
        gaps.extend(np.abs(np.asarray(lg, np.float32) - want[:, t]).max(-1))
        pos = pos + 1
    gaps = np.asarray(gaps)
    assert len(gaps) == 4 + 2 * 16 and (gaps < tol).mean() >= share and np.median(gaps) < tol / 2, gaps


def test_serving_comparison_passes_greedy_streams_and_fails_wrong_ones(params):
    rs = np.random.RandomState(8)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in (9, 14, 20, 27)]
    streams = []
    for p in prompts:  # greedy decoding by the reference itself: the right answer
        seq = list(p)
        for _ in range(6):
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            seq.append(int(np.argmax(logits(params, toks)[0, len(seq) - 1])))
        streams.append(np.array(seq[len(p):], np.int32))
    tol = dict(margin=MISS, share_within=0.99, control_share=0.0, distinct_per_request=1)
    verdict = lambda s: compare.serve_verdict(nemotron_h, params, prompts, s, ARCH, 5, tol,
                                              width=64, new_max=6)
    ok, fields = verdict(streams)
    assert ok and fields["share_within_margin"] == 1.0 and fields["worst_gap"] == 0.0, fields
    assert fields["control_share_outside_margin"]["prompt_permuted"] > 0.2, fields
    ok, fields = verdict([rs.randint(0, VOCAB, 6).astype(np.int32) for _ in prompts])
    assert not ok and fields["share_within_margin"] < 0.5


def test_float8_operands_move_the_reference_by_more_than_float32_rounding(params):
    tokens = np.random.RandomState(9).randint(0, VOCAB, (1, 32)).astype(np.int32)
    at = np.arange(32, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        low = np.asarray(nemotron_h.logits_at(params, tokens, at, ARCH, operand=compare.fp8))
    assert np.abs(low - logits(params, tokens)).max() > MISS


# -- the shares of a deployment ----------------------------------------------------------

@pytest.mark.parametrize("chips", [4, 2, 8])
def test_the_shares_add_up_to_the_uncut_reference_layer(chips):
    """What every chip of a deployment computes for an expert layer of a
    512-like shape (32 experts in a 16-wide latent, a count 2, 4 and 8 divide;
    the program's layer told each share in turn, through ``layer_plan._ffn``)
    adds up to the reference's layer with every expert held: the shared
    expert, which every chip computes alike, counted once, and the latent
    up-projection, which is linear, taken once over the sum of the shares'
    latent sums (a chip's own output minus the shared expert's is its share
    THROUGH the up-projection: they add)."""
    from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig

    rs = np.random.RandomState(6)
    E, D, Lt, F, Fs, k = 32, 64, 16, 24, 40, 6
    count = E // chips
    h = jnp.asarray(rs.randn(29, D), jnp.float32)
    draw = lambda *shape, scale=0.2: jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
    whole = {"gate": draw(D, E, scale=0.3), "gate_bias": draw(E, scale=0.05),
             "latent_down": draw(D, Lt), "latent_up": draw(Lt, D), "wi": draw(E, Lt, F),
             "wo": draw(E, F, Lt), "shared_wi": draw(D, Fs), "shared_wo": draw(Fs, D)}
    stack = lambda m: dict(m, wi=m["wi"][None], wo=m["wo"][None])     # the reference reads a stack
    every = ARCH._replace(top_k=k, held_first=0, held_count=E)
    ref = lambda m, ar: nemotron_h._experts(h, stack(m), 0, ar, nemotron_h._as_is)
    uncut = ref(whole, every)
    shared = uncut - ref(whole, every._replace(without=("shared",)))
    assert float(jnp.abs(shared).max()) > 0.05 and float(jnp.abs(uncut - shared).max()) > 0.05
    total = shared
    for first in range(0, E, count):
        kind = LayerKind(name="e", mixer="none", ffn="moe", ffn_size=F, ffn_latent=Lt)
        cfg = TransformerConfig(
            vocab_size=8, hidden_size=D, num_layers=2, num_heads=4, pos_embedding="none",
            norm_type="rmsnorm", activation="relu2", use_bias=False,
            layer_kinds=(kind, LayerKind(name="a", kv_heads=1, ffn="none")), layer_plan=(0, 1),
            moe_num_experts=E, moe_top_k=k, moe_experts_held=(first, count), moe_routed_scale=5.0,
            moe_norm_eps=1e-20, moe_shared_size=Fs, moe_shared_gated=False)
        mine = dict(whole, wi=whole["wi"][first:first + count], wo=whole["wo"][first:first + count])
        out, stats = layer_plan._ffn(h, mine, kind, cfg, None, grad=False)
        part = out - shared
        ref_part = ref(mine, every._replace(held_first=first, held_count=count, without=("shared",)))
        assert np.allclose(part, ref_part, atol=5e-5)   # the reference is given the same share
        assert int(stats[0]) == 29 * k and int(stats[3]) == 1
        total = total + part
    assert np.allclose(total, uncut, atol=2e-4)


# -- counts from the published keys ------------------------------------------------------

def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of published configurations is not on this machine")
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    return next(r for r in rows if r["source_url"] == REAL["source"])


def test_configuration_file_holds_the_published_config_but_for_what_it_lists_as_reduced():
    entry = catalog_entry()
    assert entry["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
    for key, value in entry["config"].items():
        assert REAL[key] == REAL["model"][key], key          # one set of values, in both places
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    changed = {k for k, v in entry["config"].items() if REAL[k] != v}
    assert changed == set(REAL["reduced"]) == {"num_hidden_layers", "hybrid_override_pattern",
                                               "n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert REAL["published"] == {k: entry["config"][k] for k in REAL["reduced"]}
    # no width among them, and every published width in the file
    m = REAL["model"]
    assert (m["hidden_size"], m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"], m["n_groups"],
            m["conv_kernel"]) == (4096, 128, 64, 128, 8, 4)
    assert (m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]) == (32, 2, 128)
    assert (REAL["deployment"]["experts_published"], m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["moe_latent_size"], m["moe_intermediate_size"], m["moe_shared_expert_intermediate_size"]) == (
                512, 22, 5, 1024, 2688, 5376)
    dep = REAL["deployment"]
    assert dep["held_experts"] == {"first": 128, "count": 128} and m["n_routed_experts"] == 128
    assert dep["chips_sharing_a_layers_experts"] * 128 == 512 == REAL["published"]["n_routed_experts"]
    assert dep["vocabulary_split"] * m["vocab_size"] == dep["vocab_size_published"] == 131072
    assert dep["num_hidden_layers_published"] == 88 == dep["pipeline_stages"] * m["num_hidden_layers"]
    published = REAL["published"]["hybrid_override_pattern"]
    assert published.startswith(m["hybrid_override_pattern"]) and len(published) == 88
    assert [published.count(c) for c in "M*E"] == [40, 8, 40]
    assert [m["hybrid_override_pattern"].count(c) for c in "M*E"] == [5, 1, 5]      # 40 : 8 : 40


def test_parameter_counts_of_the_cut_and_of_the_whole_model():
    s = costs.shapes(REAL)
    assert costs.ssm_params(s) == 109_640_064 and costs.attention_params(s) == 35_655_680
    assert costs.expert_layer_params(s) == 54_530_560 and costs.expert_params(s) == 5_505_024
    assert costs.num_params(REAL) == REAL["num_params"] == 4_648_163_712
    # the program's own count, from its parameter shapes at the published widths
    model = models_nemotron_h.build_model(REAL, max_seq_len=4096, remat=False, attn_impl="pallas")
    assert model.cfg.num_params() == 4_648_163_712
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 4_648_163_712
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes))
    # the whole model from the published keys: 40 M + 8 * + 40 E of 512 experts, 131,072 rows twice
    whole = costs.num_params(REAL, experts=512, vocab=131072,
                             pattern=REAL["published"]["hybrid_override_pattern"])
    assert whole == (40 * 109_640_064 + 8 * 35_655_680 + 40 * (54_530_560 + 512 * 5_505_024)
                     + 2 * 131072 * 4096 + 4096)
    assert round(whole / 1e9, 2) == 120.67


def test_memory_of_the_cell_is_what_its_sizing_says():
    model = models_nemotron_h.build_model(REAL, max_seq_len=4096, remat=False, attn_impl="pallas")
    cfg, s = model.cfg, CELL["serve_latent_moe"]
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, s["slots"], s["cache_len"]))
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert cache["state"]["s"].shape == (5, s["slots"], 64, 128, 128) and cache["state"]["s"].dtype == jnp.float32
    assert nbytes(cache["state"]["s"]) == 5 * s["slots"] * 128 * 64 * 128 * 4
    assert nbytes(cache["state"]["conv"]) == 5 * s["slots"] * 3 * 10240 * 2
    assert nbytes(cache["full"]) == 1 * s["slots"] * 2 * s["cache_len"] * 256 * 2
    assert kv_cache.state_bytes_per_row(cfg) == costs.state_bytes_per_row(costs.shapes(REAL))


# -- the cost functions against hand counts ----------------------------------------------

OBS = dict(mean_live_rows=128.0, mean_live_kv_tokens=200_000.0, moe_experts_hit_per_tick=630.0,
           moe_held_assignments_per_tick=3500.0, ssm_step_rows_per_tick=120.0, ssm_chunk_tokens=400.0,
           chunk_tokens=400.0, chunk_pairs_full=250_000.0, chunk_keys_full=900.0)


def test_cost_functions_against_hand_counts():
    s = costs.shapes(REAL)
    assert (s["n_ssm"], s["n_attn"], s["n_moe"], s["G"], s["Lt"]) == (5, 1, 5, 8, 1024)
    assert costs.conv_channels(s) == 8192 + 2 * 8 * 128 == 10240
    always = 5 * 109_640_064 + 35_655_680 + 5 * 54_530_560 + 32768 * 4096 + 4096
    assert costs.always_read_params(s) == always
    assert costs.kv_bytes_per_position(s) == 1 * 2 * 2 * 128 * 2 == 1024          # 1 KB a token
    assert costs.state_elements(s) == 128 * 64 * 128 == 1_048_576
    row = 5 * (1_048_576 * 4 + 3 * 10240 * 2)
    assert costs.state_bytes_per_row(s) == row and costs.state_bytes_tick(REAL, OBS) == 2 * 120 * row
    tick = costs.decode_tick(REAL, CELL, OBS)
    assert tick["bytes"] == (always + 630 * 5_505_024) * 2 + 200_000 * 1024 + 2 * 120 * row
    assert tick["flops"] == 2 * (always * 128 + 3500 * 5_505_024) + 5 * 120 * 5 * 1_048_576
    gm = costs.grouped_matmul_tick(REAL, CELL, OBS)
    assert gm["flops"] == 2 * 3500 * 5_505_024
    assert gm["bytes"] == 630 * 5_505_024 * 2 + 3500 * (2 * 1024 + 2 * 2688) * 2
    step = costs.ssd_step_tick(REAL, CELL, OBS)
    assert step == {"flops": 5 * 120 * 5 * 1_048_576, "bytes": 2 * 120 * 5 * 1_048_576 * 4}
    chunk = costs.ssd_chunk(REAL, CELL, OBS)
    assert chunk["flops"] == 5 * 128 * 400 * (2 * 256 * 64 + 4 * 128 * 64)
    assert chunk["bytes"] == 5 * (400 * (2 * 8192 + 8 * (2 * 128 + 256) + 2 * 128) + 2 * 1_048_576) * 4
    flash = costs.flash_chunk(REAL, CELL, OBS)
    assert flash["flops"] == 2 * 1 * 250_000 * 32 * 256
    assert flash["bytes"] == 1 * (400 * 32 + 900 * 2) * 256 * 2
    # the issue's arithmetic of a full tick: every held expert of every layer hit, 128 rows stepped
    full = costs.decode_tick(REAL, CELL, dict(OBS, moe_experts_hit_per_tick=640.0, ssm_step_rows_per_tick=128.0,
                                              mean_live_kv_tokens=128 * 2048.0))
    assert 14.0e9 < full["bytes"] < 15.2e9


def test_metric_files_read_the_runners_observations_and_nothing_on_a_parent():
    names = ["tick_weight_roofline", "moe_experts_roofline", "ssd_step_roofline", "ssd_chunk_roofline",
             "state_bytes_share_pct", "moe_load_imbalance", "moe_held_share_pct", "moe_buffer_fill_pct"]
    manifest = harness.load_json(os.path.join(bench_toy.ROOT, "BENCHMARK.json"))
    dirs = [os.path.join(bench_toy.ROOT, "benchmark")]
    for name in names:
        metric = readers.load_metric(dirs, name + ".latent_moe")
        entry = next(m for m in manifest["per_layer"] if m["name"] == metric["name"])
        assert entry["workloads"] == ["serve-nemotron-3-super-reasoning-batch"]
        assert all(entry[k] == metric[k] for k in ("unit", "better", "source", "layer", "moves"))
        if metric["reader"]["reduction"] == "roofline":
            assert metric["reader"]["cost_module"] == "benchmark.costs_nemotron_h"
            assert callable(getattr(costs, metric["reader"]["cost"])) and name.endswith("_roofline")
    # a program without the counters (the parent): the runner reads nothing, the line leaves them out
    from benchmark.runners import serve_latent_moe

    class Bare(serve_latent_moe.Runner):
        def __init__(self):
            self.records, self.live_rows, self.live_kv = [], [], []
            self.ctx = dict(config=REAL, cell={})

    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = Bare()._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert obs["moe_buffer_fill_pct"] is None and obs["ssm_step_rows_per_tick"] is None
    assert obs["moe_expert_layers_per_tick"] is None and "state_bytes_share_pct" not in obs
    ctx = readers.Context(obs=obs, config=REAL, cell={}, peaks=None, chips=1)
    assert readers.evaluate({"reduction": "value", "key": "moe_buffer_fill_pct"}, ctx) is None
    import dataclasses
    fields = dataclasses.fields
    try:
        models_nemotron_h.dataclasses.fields = lambda cls: [f for f in fields(cls) if f.name != "ffn_latent"]
        with pytest.raises(ImportError, match="one sublayer"):
            models_nemotron_h.build_model(REAL, max_seq_len=128, remat=False, attn_impl="xla")
    finally:
        models_nemotron_h.dataclasses.fields = fields


# -- the variant tool on the toy cell ---------------------------------------------------

@pytest.fixture
def environment(tmp_path):
    saved = {k: os.environ.get(k) for k in ("JAX_COMPILATION_CACHE_DIR", "TMPDIR")}
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    os.environ["TMPDIR"] = str(tmp_path)
    leaked = {k: os.environ.pop(k) for k in ("DSTPU_COORDINATOR", "DSTPU_NUM_PROCESSES",
                                             "DSTPU_PROCESS_ID") if k in os.environ}
    yield
    os.environ.update(leaked)
    for k, v in saved.items():
        os.environ.pop(k, None) if v is None else os.environ.__setitem__(k, v)


def test_a_reference_with_one_norm_for_all_groups_is_refused_by_the_toy_cells_comparison(environment, capsys):
    sound = nemotron_h.arch
    line = nemotron_cell_variant.main(
        ["--variant", "no_group_norm", "--workload", "toy-nemotron-reasoning", "--seed", str(2 ** 31 + 7),
         "--seconds", "1.0"], manifest=bench_toy.manifest_path(), require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 1
    assert nemotron_h.arch is sound
    out = capsys.readouterr().out
    assert '"variant": "no_group_norm"' in out
    said = next(json.loads(l) for l in out.splitlines() if l.startswith('{"phase": "observations"'))
    # the new counters, among the runner's observations
    assert said["ssm_chunk_tokens"] > 0 and said["ssm_step_rows_per_tick"] > 0
    assert said["state_pool_gb"] > 0 and 0 < said["state_bytes_share_pct"] < 100
    assert said["moe_expert_layers_per_tick"] == 2.0 and 0 < said["moe_buffer_fill_pct"] <= 100
    assert 40 < said["moe_held_share_pct"] < 60


def test_the_variant_tools_swaps_are_undone_when_the_run_ends():
    sound = (kv_cache.reset_row, layer_plan._hold_dt, layer_plan._group_mean_square)
    y = jnp.arange(16.0).reshape(1, 16)
    cfg = build(TOY).cfg
    by_group = layer_plan._group_mean_square(jnp.tile(y, (1, 16)), cfg)
    with nemotron_cell_variant.whole_norm(TOY):
        whole = layer_plan._group_mean_square(jnp.tile(y, (1, 16)), cfg)
    assert whole.shape == (1, 1) and by_group.shape == (1, 256)
    with nemotron_cell_variant.pads_step(TOY):
        assert float(layer_plan._hold_dt(jnp.ones((2, 1)), jnp.zeros(2, bool)).sum()) == 2.0
    assert (kv_cache.reset_row, layer_plan._hold_dt, layer_plan._group_mean_square) == sound
    assert set(nemotron_cell_variant.VARIANTS) >= {
        "no_reset", "whole_norm", "no_group_norm", "no_groups", "no_latent_up", "no_routed_scale",
        "no_nope", "fp8"}


# -- the ticks of the plans served before are the parent's, to the text -------------------

# sha256 of jit(forward_plan_cached).lower(...).as_text() of the toy ticks of the five plan families
# the benchmark served before this family (4 slots of 128, read 64; plain, and with a 32-token chunk).
# First recorded on the parent of the PR that brought layers of one sublayer, groups of B and C, the
# latent and the squared ReLU (0b8964a): with every layer whole, one group and SwiGLU the tick is the
# old program, to the text. RECORDED ANEW by PR 56 (benchmark) on its own tree, whose program is commit
# 88700f3's (= PR 54's, 67dbef5): PR 52 (the expert layers' way back) MEANT to change these programs
# and, as a program PR, could not edit this file. A PR that MEANS to change them records them anew,
# and says so; a program PR leaves the cases red for the next `benchmark` PR.
PARENTS_TICKS = {
    ("toy-granitemoehybrid", None): "679fbb448927c196", ("toy-granitemoehybrid", 32): "24f9d24d540000b0",
    ("toy-mimo-v2", None): "d84e8ab00dd44882", ("toy-mimo-v2", 32): "b27a54f14fea9e25",
    ("toy-qwen3-next", None): "9d8f4101c41a17d2", ("toy-qwen3-next", 32): "3a325d7a3a6ea57e",
    ("toy-glm4-moe-lite", None): "025b72557deb507e", ("toy-glm4-moe-lite", 32): "2bab620e675237b2",
}


@pytest.mark.parametrize("name,chunk", sorted(PARENTS_TICKS, key=str))
def test_the_served_plans_ticks_lower_to_the_parents_text(name, chunk):
    config = harness.load_json(os.path.join(bench_toy.ROOT, bench_toy.TOY_DIR, "configs", name + ".json"))
    cfg = compare.builder_of(config).build_model(config, max_seq_len=128, remat=False,
                                                 attn_impl="pallas").cfg
    assert cfg.ssm_groups == 1 and cfg.activation == "silu_glu" and not layer_plan.counts_rows(cfg)
    params = jax.eval_shape(TransformerModel(cfg).init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: tf.init_cache(cfg, 4, 128))
    row, scalar = jax.ShapeDtypeStruct((4,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)
    wide = jax.ShapeDtypeStruct((chunk or 1,), jnp.int32)
    ride = layer_plan.Chunk(wide, wide, scalar, scalar) if chunk else None
    text = jax.jit(lambda p, t, ps, ca, ch: layer_plan.forward_plan_cached(
        p, cfg, t, ps, ca, read_len=64, chunk=ch)).lower(params, row, row, cache, ride).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_TICKS[name, chunk]
