"""Granite 4.0-H's family (Mamba-2 layers whose state lives beside keys and
values in one cache, nine to every attention layer that has no positions,
top-k-then-softmax expert layers with an ungated shared expert of which a
chip holds a share, four scalar multipliers): each piece of its reference
failing when left out, the shares of a deployment adding up to the uncut
layer, the two orders of its routing one function, its cost functions
against hand counts, its configuration file against the published
``config.json``, and its variant tool on the toy cell. (The program against
the reference - the whole forward, each kind of layer, the serving tick
through chunks and rows, faults planted in the program:
``tests/unit/models/test_layer_plan_ssm.py``.) Its toy cell
(``toy/cells/toy-granite-longdoc.json``) runs end to end, traced and
untraced, with every other toy cell (``test_bench_runners_cpu.py`` finds it
by its file). Everything of this family is a file of its own."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_granitemoehybrid as costs, harness, models_granitemoehybrid
from benchmark.reference import granitemoehybrid
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.ops.transformer import kv_cache

sys.path.insert(0, os.path.join(bench_toy.ROOT, "tools"))
import granite_cell_variant  # noqa: E402

TOY = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-granitemoehybrid.json")), dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs",
                                      "granite-4.0-h-small.json"))
ARCH = granitemoehybrid.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]
LENGTH = 128
SPREAD = 3e-4                 # of the toy's logits: / 16, a 64-wide model, the tied embedding / 32
MATCH, MISS = 2e-3 * SPREAD, 0.2 * SPREAD


@pytest.fixture(scope="module")
def model():
    return models_granitemoehybrid.build_model(TOY, max_seq_len=LENGTH, remat=False, attn_impl="pallas")


@pytest.fixture(scope="module")
def params(model):
    return models_granitemoehybrid.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 12.0)


def logits(params, tokens, arch=ARCH):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(granitemoehybrid.logits_at(params, tokens, at, arch))


def test_the_configuration_names_its_reference_and_builder(model):
    assert compare.reference_of(TOY) is granitemoehybrid
    assert compare.builder_of(TOY) is models_granitemoehybrid
    assert all(isinstance(TOY["model"][k], int) for k in models_granitemoehybrid.REQUIRED_SIZES)
    assert ARCH.layer_types == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert (ARCH.held_first, ARCH.held_count, ARCH.top_k) == (12, 12, 4)
    assert (ARCH.embedding_multiplier, ARCH.residual_multiplier, ARCH.attention_multiplier,
            ARCH.logits_scaling) == (12.0, 0.22, 0.0625, 16.0)
    assert model.cfg.moe_num_experts == 24 and model.cfg.held_experts == (12, 12)
    assert not model.cfg.moe_shared_gated and model.cfg.moe_score == "softmax"


# -- each piece of the mathematics ------------------------------------------------------

@pytest.fixture(scope="module")
def sound(model, params):
    """(tokens, the program's logits), which the whole reference matches."""
    tokens = np.random.RandomState(4).randint(0, VOCAB, (1, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=MATCH)
    return tokens, theirs


@pytest.mark.parametrize("left_out", [
    "decay", "softplus", "dt_bias", "skip", "conv", "conv_bias", "z_gate", "gate_before_norm",
    "embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling", "nope",
    "shared"])
def test_each_piece_of_the_mathematics_fails_when_left_out(params, sound, left_out):
    """The program's logits against a reference that lacks one piece (or has
    the norm before the gate, rotary positions turned on, 1/sqrt(width) for
    the attention multiplier): the comparison that passes must fail."""
    tokens, theirs = sound
    wrong = logits(params, tokens, ARCH._replace(without=(left_out,)))
    assert not np.abs(wrong - theirs).max() < MISS      # (a reference that blew up is not a match)


def test_the_convolutions_tail_across_a_boundary_is_part_of_the_function(params):
    tokens = np.random.RandomState(5).randint(0, VOCAB, (1, 40)).astype(np.int32)
    whole = logits(params, tokens)
    cut = logits(params, tokens, ARCH._replace(without=("conv_tail",), tail_every=8))
    assert np.allclose(cut[0, :8], whole[0, :8], atol=MATCH)     # before the first boundary: the same
    assert np.abs(cut[0, 8:] - whole[0, 8:]).max() > MISS


@pytest.mark.parametrize("experts,top_k", [(24, 4), (72, 10)])
def test_top_k_then_softmax_is_softmax_then_top_k_renormalised(experts, top_k):
    """The published router takes the k largest logits and a softmax over
    those; the program's ``route(score="softmax")`` takes a softmax over all
    the experts, the k largest, divided by their sum: one function (the
    configuration's ``assumed.routing``), at the toy's shape and at 72 / 10."""
    from deepspeed_tpu.moe import held_experts as he

    rs = np.random.RandomState(experts)
    h = jnp.asarray(rs.randn(50, 64), jnp.float32)
    gate = jnp.asarray(rs.randn(64, experts), jnp.float32)
    chosen, weights = he.route(h, gate, None, top_k, "softmax")
    top, want = jax.lax.top_k(h @ gate, top_k)
    order = np.argsort(np.asarray(chosen), axis=1)
    assert np.array_equal(np.take_along_axis(np.asarray(chosen), order, 1), np.sort(np.asarray(want), 1))
    want_w = np.take_along_axis(np.asarray(jax.nn.softmax(top, axis=-1)), np.argsort(np.asarray(want), 1), 1)
    assert np.allclose(np.take_along_axis(np.asarray(weights), order, 1), want_w, atol=1e-6)
    arch = ARCH._replace(top_k=top_k, held_first=0, held_count=experts)
    draw = lambda *shape: jnp.asarray(rs.randn(*shape) * 0.2, jnp.float32)
    layer = {"gate": gate, "wg": draw(experts, 64, 8), "wi": draw(experts, 64, 8),
             "wo": draw(experts, 8, 64), "shared_wg": draw(64, 8), "shared_wi": draw(64, 8),
             "shared_wo": draw(8, 64)}
    both = [granitemoehybrid._experts(h, layer, arch._replace(without=w), granitemoehybrid._as_is)
            for w in ((), ("topk_first",))]
    assert np.allclose(both[0], both[1], atol=1e-5) and float(jnp.abs(both[0]).max()) > 0.05


@pytest.mark.parametrize("chips", [8, 4, 2])
def test_the_shares_add_up_to_the_uncut_reference_layer(chips):
    """What every chip of a deployment computes for an expert layer of a
    72-like shape (24 experts, a count 2, 4 and 8 divide; the program's layer
    told each share in turn) adds up, the shared expert that every chip
    computes alike counted once, to the reference's layer with every expert
    held."""
    from deepspeed_tpu.moe import held_experts as he

    rs = np.random.RandomState(6)
    E, D, F = 24, 64, 32
    count = E // chips
    h = jnp.asarray(rs.randn(29, D), jnp.float32)
    draw = lambda *shape, scale=0.2: jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
    whole = {"gate": draw(D, E, scale=0.3), "wg": draw(E, D, F), "wi": draw(E, D, F),
             "wo": draw(E, F, D), "shared_wg": draw(D, 2 * F), "shared_wi": draw(D, 2 * F),
             "shared_wo": draw(2 * F, D)}
    every = ARCH._replace(held_first=0, held_count=E)
    uncut = granitemoehybrid._experts(h, whole, every, granitemoehybrid._as_is)
    shared = uncut - granitemoehybrid._experts(h, whole, every._replace(without=("shared",)),
                                               granitemoehybrid._as_is)
    assert float(jnp.abs(shared).max()) > 0.05
    chosen, weights = he.route(h, whole["gate"], None, ARCH.top_k, "softmax")
    total = shared
    for first in range(0, E, count):
        mine = {n: whole[n][first:first + count] for n in ("wg", "wi", "wo")}
        part, _ = he.held_experts_ffn(h, chosen, weights, mine, first, count, tm=8)
        ref_part = granitemoehybrid._experts(
            h, dict(whole, **mine),
            ARCH._replace(held_first=first, held_count=count, without=("shared",)),
            granitemoehybrid._as_is)
        assert np.allclose(part, ref_part, atol=5e-5)   # the reference is given the same share
        total = total + part
    assert np.allclose(total, uncut, atol=1e-4)


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
    verdict = lambda s: compare.serve_verdict(granitemoehybrid, params, prompts, s, ARCH, 5, tol,
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
        low = np.asarray(granitemoehybrid.logits_at(params, tokens, at, ARCH, operand=compare.fp8))
    assert np.abs(low - logits(params, tokens)).max() > MISS


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


def test_a_reference_without_the_decay_is_refused_by_the_toy_cells_comparison(environment, capsys):
    sound = granitemoehybrid.arch
    line = granite_cell_variant.main(
        ["--variant", "no_decay", "--workload", "toy-granite-longdoc", "--seed", str(2 ** 31 + 7),
         "--seconds", "1.0"], manifest=bench_toy.manifest_path(), require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 1
    assert granitemoehybrid.arch is sound
    out = capsys.readouterr().out
    assert '"variant": "no_decay"' in out
    said = next(json.loads(l) for l in out.splitlines() if l.startswith('{"phase": "observations"'))
    # the new counters, among the runner's observations, and none of the other mixer's
    assert said["ssm_chunk_tokens"] > 0 and said["ssm_step_rows_per_tick"] > 0
    assert said["state_pool_gb"] > 0 and 0 < said["state_bytes_share_pct"] < 100
    assert "gdn_chunk_tokens" not in said and 40 < said["moe_held_share_pct"] < 60


def test_the_variant_tools_swaps_are_undone_when_the_run_ends():
    sound = (kv_cache.reset_row, layer_plan._hold_dt)
    state = {"s": jnp.ones((2, 3, 1, 4, 8))}
    with granite_cell_variant.no_reset(TOY):
        assert kv_cache.reset_row(state, 1) is state
    with granite_cell_variant.pads_step(TOY):
        assert float(layer_plan._hold_dt(jnp.ones((2, 1)), jnp.zeros(2, bool)).sum()) == 2.0
    assert (kv_cache.reset_row, layer_plan._hold_dt) == sound
    assert float(kv_cache.reset_row(state, 1)["s"][:, 1].sum()) == 0.0
    assert set(granite_cell_variant.VARIANTS) >= {"no_reset", "pads_step", "no_decay", "no_skip",
                                                  "no_nope", "no_residual_multiplier", "fp8"}


# -- the published configuration, cut to one chip ---------------------------------------

def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of published configurations is not on this machine")
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    return next(r for r in rows if r["source_url"] == REAL["source"])


def test_configuration_file_holds_the_published_config_but_for_what_it_lists_as_reduced():
    entry = catalog_entry()
    assert entry["name"] == REAL["name"] == "granite-4.0-h-small"
    for key, value in entry["config"].items():
        assert REAL[key] == REAL["model"][key], key          # one set of values, in both places
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    changed = {k for k, v in entry["config"].items() if REAL[k] != v}
    assert changed == set(REAL["reduced"]) == {"num_hidden_layers", "layer_types",
                                               "num_local_experts", "vocab_size"}
    assert REAL["layer_types"] == entry["config"]["layer_types"][:10]   # its first whole period
    widths = ("hidden_size", "intermediate_size", "shared_intermediate_size", "mamba_d_head",
              "mamba_d_state", "mamba_n_heads", "mamba_expand", "mamba_d_conv", "mamba_n_groups",
              "num_attention_heads", "num_key_value_heads", "num_experts_per_tok")
    assert not set(widths) & set(REAL["reduced"])


def test_the_cut_keeps_the_guides_floors_and_states_the_deployment():
    m, dep = REAL["model"], REAL["deployment"]
    assert m["num_hidden_layers"] == 10 == len(m["layer_types"])          # one whole period
    assert m["layer_types"].count("mamba") == 9 and m["layer_types"][5] == "attention"
    assert m["num_local_experts"] == dep["held_experts"]["count"] == 36 >= 8
    assert dep["experts_published"] == 72 == 36 * dep["chips_sharing_a_layers_experts"]
    assert dep["held_experts"]["first"] == 36
    assert m["vocab_size"] * dep["vocabulary_split"] == dep["vocab_size_published"] == 100352
    assert m["vocab_size"] * 8 >= dep["vocab_size_published"]
    assert dep["num_hidden_layers_published"] == 40 and m["num_experts_per_tok"] == 10
    assert set(REAL["assumed"]) >= {"weights", "intermediate_size", "in_proj_layout", "mamba2",
                                    "gated_norm", "state_dtype", "conv", "attention", "routing",
                                    "multipliers", "no_equation", "depth"}
    tol = REAL["compare"]["serve_ssm"]
    assert tol["controls_held"] == ["prompt_permuted"] and 0 < tol["margin"] < 0.25


def test_the_program_built_from_the_file_has_the_issues_parameter_count_and_pools():
    model = models_granitemoehybrid.build_model(REAL, max_seq_len=16896, remat=False,
                                                attn_impl="pallas")
    cfg = model.cfg
    assert cfg.num_params() == REAL["num_params"] == 4_757_211_776
    assert cfg.head_dim == 128 == cfg.v_head_dim and cfg.pos_embedding == "none"
    assert [k.name for k in cfg.plan] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert layer_plan.pool_shapes(cfg) == {"full": (1, 8, 0)}
    assert kv_cache.state_spec(cfg) == (9, 64, 128, 128, 3, 8448)   # 64 pairs of heads, transposed
    cache = jax.eval_shape(lambda: kv_cache.init(cfg, 32, 16896))
    sizes = {pool: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(sub))
             for pool, sub in cache.items()}
    assert sizes == {"full": 32 * 8 * 16896 * 256 * 2,
                     "state": 9 * 32 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)}
    assert cache["state"]["s"].dtype == jnp.float32 and cache["state"]["conv"].dtype == jnp.bfloat16
    assert kv_cache.read_bytes_by_pool(cfg, 4096) == {
        "full": 4096 * 8 * 256 * 2, "state": sizes["state"] // 32}


# -- operations and bytes, against hand counts ------------------------------------------

def test_cost_shapes_and_parameter_counts_by_hand():
    s = costs.shapes(REAL)
    assert (s["n_attn"], s["n_ssm"], s["L"], s["hd"]) == (1, 9, 10, 128)
    assert costs.conv_channels(s) == 8448
    assert costs.ssm_params(s) == 102_286_976
    assert costs.attention_params(s) == 41_943_040
    assert costs.expert_params(s) == 3 * 4096 * 768 == 9_437_184
    layer = 294_912 + 4096 * 3072 + 1536 * 4096 + 8192
    assert layer == 19_177_472
    always = 9 * 102_286_976 + 41_943_040 + 10 * layer + 4096 * 50176
    assert costs.always_read_params(s) == always
    # with the held experts and the final norm (the embedding IS the head): the issue's count
    assert always + 10 * 36 * 9_437_184 + 4096 == 4_757_211_776
    assert costs.kv_bytes_per_position(s) == 8 * 256 * 2 == 4096
    assert costs.state_bytes_per_row(s) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)


def test_tick_costs_by_hand():
    obs = dict(mean_live_rows=30.0, mean_live_kv_tokens=270_000.0, moe_experts_hit_per_tick=355.0,
               moe_held_assignments_per_tick=1500.0, ssm_step_rows_per_tick=29.0)
    s = costs.shapes(REAL)
    always, row = costs.always_read_params(s), costs.state_bytes_per_row(s)
    cost = costs.decode_tick(REAL, {}, obs)
    assert cost["bytes"] == (always + 355 * 9_437_184) * 2 + 270_000 * 4096 + 2 * 29 * row
    assert cost["flops"] == 2 * (always * 30 + 1500 * 9_437_184) + 5 * 29 * 9 * 128 * 64 * 128
    assert costs.state_bytes_tick(REAL, obs) == 2 * 29 * row
    step = costs.ssd_step_tick(REAL, {}, obs)
    assert step == {"flops": 5 * 29 * 9 * 128 * 64 * 128, "bytes": 2 * 29 * 9 * 128 * 64 * 128 * 4}
    gm = costs.grouped_matmul_tick(REAL, {}, obs)
    assert gm["flops"] == 2 * 1500 * 9_437_184
    assert gm["bytes"] == 355 * 9_437_184 * 2 + 1500 * (2 * 4096 + 3 * 768) * 2


def test_chunk_costs_by_hand():
    obs = dict(chunk_tokens=1000.0, chunk_pairs_full=1000 * 4096 + 1000 * 1001 / 2,
               chunk_keys_full=5096.0, ssm_chunk_tokens=1000.0)
    fl = costs.flash_chunk(REAL, {}, obs)
    assert fl["flops"] == 2 * (1000 * 4096 + 1000 * 1001 / 2) * 32 * 256
    assert fl["bytes"] == (1000 * 32 + 5096 * 8) * 256 * 2
    scan = costs.ssd_chunk(REAL, {}, obs)
    assert scan["flops"] == 9 * 128 * 1000 * (2 * 256 * 64 + 4 * 128 * 64)
    assert scan["bytes"] == 9 * (1000 * (2 * 8192 + 2 * 128 + 256 + 2 * 128) + 2 * 128 * 64 * 128) * 4


def test_a_program_without_the_counters_gives_no_reading():
    """The parent of the PR that brought the counters: its tick_stats() lacks
    them, the runner's observations carry None, which a reader returns as
    nothing; and its layer plan has no such mixer, which the builder reports
    as the harness's "the program is not here"."""
    import dataclasses

    from benchmark import readers
    from benchmark.runners import serve_ssm
    from deepspeed_tpu.models import transformer as tf

    class Bare(serve_ssm.Runner):
        def __init__(self):
            self.records, self.live_rows, self.live_kv = [], [], []
            self.ctx = dict(config=REAL, cell={})

    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = Bare()._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert obs["ssm_chunk_tokens"] is None and obs["ssm_step_rows_per_tick"] is None
    assert obs["state_pool_gb"] is None and "state_bytes_share_pct" not in obs
    for name in ("ssd_chunk_roofline.ssm", "ssd_step_roofline.ssm", "state_bytes_share_pct.ssm"):
        metric = readers.load_metric([os.path.join(bench_toy.ROOT, "benchmark")], name)
        ctx = readers.Context(obs=obs, config=REAL, cell={}, peaks={"flops_per_s": 1.0,
                              "hbm_bytes_per_s": 1.0}, chips=1)
        assert readers.evaluate(metric["reader"], ctx) is None

    class Parent:   # a TransformerConfig without the state-space sizes
        __dataclass_fields__ = {k: v for k, v in tf.TransformerConfig.__dataclass_fields__.items()
                                if not k.startswith("ssm_")}

    real, tf.TransformerConfig = tf.TransformerConfig, Parent
    try:
        assert "ssm_heads" not in {f.name for f in dataclasses.fields(Parent)}
        with pytest.raises(ImportError, match="no state-space mixer"):
            models_granitemoehybrid.build_model(REAL, max_seq_len=64, remat=False, attn_impl="pallas")
    finally:
        tf.TransformerConfig = real
