"""MiMo-V2's family (window and full attention layers of different shapes in
one stack, a dense first layer, sigmoid top-k expert layers of which a chip
holds a share): its plain reference against the program's model in float32,
each piece of its mathematics failing when left out, the shares of a
deployment adding up to the uncut layer, its cost functions against hand
counts, and its configuration file against the published ``config.json``.
Its toy cell (``toy/cells/toy-mimo-longdoc.json``) runs end to end with
every other toy cell (``test_bench_runners_cpu.py`` finds it by its file).
Everything of this family is a file of its own."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_mimo_v2, harness, models_mimo_v2
from benchmark.reference import mimo_v2

TOY = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-mimo-v2.json")), dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs", "mimo-v2.5.json"))
ARCH = mimo_v2.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]


@pytest.fixture(scope="module")
def model():
    return models_mimo_v2.build_model(TOY, max_seq_len=64, remat=False, attn_impl="xla")


@pytest.fixture(scope="module")
def params(model):
    return models_mimo_v2.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 3.0)


def logits(params, tokens, arch=ARCH):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(mimo_v2.logits_at(params, tokens, at, arch))


def test_the_configuration_names_its_reference_and_builder(model):
    assert compare.reference_of(TOY) is mimo_v2 and compare.builder_of(TOY) is models_mimo_v2
    assert all(isinstance(TOY["model"][k], int) for k in models_mimo_v2.REQUIRED_SIZES)
    assert ARCH.rotary == 8 and ARCH.kv_heads == (1, 2) and ARCH.sink == (False, True)
    assert (ARCH.held_first, ARCH.held_count, ARCH.top_k) == (8, 4, 4)
    cfg = model.cfg
    assert [k.name for k in cfg.plan] == ["dense_full", "moe_window", "moe_window", "moe_window",
                                          "moe_full"]
    assert cfg.head_dim == 24 and cfg.v_head_dim == 16 and cfg.rope_dim == 8
    assert cfg.moe_num_experts == 16 and cfg.held_experts == (8, 4)
    window = next(k for k in cfg.layer_kinds if k.name == "moe_window")
    assert (window.window, window.kv_heads, window.rope_theta, window.sink) == (8, 2, 1e4, True)


def test_reference_forward_matches_the_model_in_float32(model, params):
    tokens = np.random.RandomState(0).randint(0, VOCAB, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=2e-4)


def test_reference_loss_and_grads_match_the_models(model):
    params = model.init(jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, VOCAB, (4, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads = mimo_v2.loss_and_grads(params, tokens, ARCH, rows_per_pass=2)
        want, want_g = jax.value_and_grad(lambda p: model.loss(p, {"input_ids": tokens}))(params)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_g)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-5), jax.tree_util.keystr(path)


def test_reference_trains_and_its_faults_leave_the_tolerances(model):
    tokens = np.random.RandomState(2).randint(0, VOCAB, (4, 24)).astype(np.int32)
    opt = dict(lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.01)
    key = jax.random.PRNGKey(2)
    run = lambda f: compare.train_reference(mimo_v2, model.init, key, tokens, ARCH, 3, opt,
                                            jax.devices()[:1], rows_per_pass=2, fault=f)
    good, bad = run(None), run(mimo_v2.FAULTS[0])
    tol = dict(loss_abs=0.005, grad_norm_rel=0.01, min_fall=0.01)
    assert compare.train_verdict(good["losses"], good["grad_norms"][0], good, tol)[0]
    assert not compare.train_verdict(good["losses"], good["grad_norms"][0], bad, tol)[0]


@pytest.mark.parametrize("left_out", ["sink", "partial_rotary", "value_scale", "window",
                                      "selection_bias", "kv_heads_by_kind", "theta_by_kind"])
def test_each_piece_of_the_mathematics_fails_when_left_out(model, params, left_out):
    """The program's logits against a reference that lacks one piece: the
    comparison that passes above must fail."""
    tokens = np.random.RandomState(3).randint(0, VOCAB, (1, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    p = params
    if left_out == "selection_bias":
        # a bias large enough to decide choices: the program must follow it, not the scores
        p = jax.tree.map(lambda a: a, params)
        for kind in ("moe_window", "moe_full"):
            p["layers"][kind]["mlp"]["gate_bias"] = p["layers"][kind]["mlp"]["gate_bias"].at[:, 9].add(5.0)
        with jax.default_matmul_precision("highest"):
            theirs = np.asarray(model.apply(p, tokens), np.float32)
        assert np.allclose(logits(p, tokens), theirs, atol=2e-4)
        zero = jax.tree.map(lambda a: a, p)
        for kind in ("moe_window", "moe_full"):
            zero["layers"][kind]["mlp"]["gate_bias"] = jnp.zeros_like(zero["layers"][kind]["mlp"]["gate_bias"])
        assert np.abs(logits(zero, tokens) - theirs).max() > 1e-3
        return
    if left_out == "kv_heads_by_kind":  # one head count for both kinds cannot even read the tree
        with pytest.raises(Exception):
            logits(params, tokens, ARCH._replace(kv_heads=(2, 2)))
        return
    wrong = {"sink": ARCH._replace(sink=(False, False)),
             "partial_rotary": ARCH._replace(rotary=ARCH.head_dim),
             "value_scale": ARCH._replace(value_scale=1.0),
             "window": ARCH._replace(window=10 ** 6),
             "theta_by_kind": ARCH._replace(theta=(1e7, 1e7))}[left_out]
    assert np.abs(logits(p, tokens, wrong) - theirs).max() > 1e-3


def test_widths_192_and_128_in_miniature_a_value_width_of_its_own(model, params):
    window = params["layers"]["moe_window"]["attn"]
    assert window["wk"].shape[-1] == 2 * 24 and window["wv"].shape[-1] == 2 * 16
    assert window["wo"].shape[-2] == 4 * 16 and window["wq"].shape[-1] == 4 * 24 != 64
    equal = dict(TOY, model=dict(TOY["model"], v_head_dim=24, swa_v_head_dim=24))
    other = models_mimo_v2.build_model(equal, max_seq_len=64, remat=False, attn_impl="xla")
    assert other.init(jax.random.PRNGKey(0))["layers"]["moe_window"]["attn"]["wv"].shape[-1] == 48
    with pytest.raises(Exception):  # the reference at equal widths cannot read this tree
        logits(params, np.zeros((1, 8), np.int32), mimo_v2.arch(equal))


def test_the_shares_add_up_to_the_uncut_reference_layer(model, params):
    """What every chip of the toy deployment computes for an expert layer (the
    program's held-experts layer told each share in turn) adds up to the
    reference's layer with every expert held."""
    from deepspeed_tpu.moe import held_experts as he

    rs = np.random.RandomState(4)
    E, count, D, F = 16, 4, 64, 32
    h = jnp.asarray(rs.randn(29, D), jnp.float32)
    whole = {"gate": jnp.asarray(rs.randn(D, E) * 0.3, jnp.float32),
             "gate_bias": jnp.asarray(rs.randn(E) * 0.01, jnp.float32),
             "wg": jnp.asarray(rs.randn(E, D, F) * 0.2, jnp.float32),
             "wi": jnp.asarray(rs.randn(E, D, F) * 0.2, jnp.float32),
             "wo": jnp.asarray(rs.randn(E, F, D) * 0.2, jnp.float32)}
    uncut = mimo_v2._experts(h, whole, ARCH._replace(held_first=0, held_count=E), mimo_v2._as_is)
    chosen, weights = he.route(h, whole["gate"], whole["gate_bias"], ARCH.top_k)
    total = jnp.zeros_like(h)
    for first in range(0, E, count):
        mine = {n: whole[n][first:first + count] for n in ("wg", "wi", "wo")}
        part, _ = he.held_experts_ffn(h, chosen, weights, mine, first, count, tm=8)
        ref_part = mimo_v2._experts(h, dict(whole, **mine), ARCH._replace(held_first=first), mimo_v2._as_is)
        assert np.allclose(part, ref_part, atol=5e-5)   # the reference is given the same share
        total = total + part
    assert np.allclose(total, uncut, atol=1e-4)


def test_serving_comparison_passes_greedy_streams_and_fails_wrong_ones(params):
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in (9, 14, 20, 27)]
    streams = []
    for p in prompts:  # greedy decoding by the reference itself: the right answer
        seq = list(p)
        for _ in range(6):
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            seq.append(int(np.argmax(logits(params, toks)[0, len(seq) - 1])))
        streams.append(np.array(seq[len(p):], np.int32))
    tol = dict(margin=0.25, share_within=0.99, control_share=0.0, distinct_per_request=1)
    verdict = lambda s: compare.serve_verdict(mimo_v2, params, prompts, s, ARCH, 5, tol,
                                              width=64, new_max=6)
    ok, fields = verdict(streams)
    assert ok and fields["share_within_margin"] == 1.0 and fields["worst_gap"] == 0.0, fields
    assert fields["control_share_outside_margin"]["prompt_permuted"] > 0.2, fields
    ok, fields = verdict([rs.randint(0, VOCAB, 6).astype(np.int32) for _ in prompts])
    assert not ok and fields["share_within_margin"] < 0.5


def test_float8_operands_move_the_reference_by_more_than_float32_rounding(params):
    tokens = np.random.RandomState(6).randint(0, VOCAB, (1, 32)).astype(np.int32)
    at = np.arange(32, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        low = np.asarray(mimo_v2.logits_at(params, tokens, at, ARCH, operand=compare.fp8))
    assert np.abs(low - logits(params, tokens)).max() > 0.05


# -- the published configuration, cut to one chip -----------------------

def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of published configurations is not on this machine")
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    return next(r for r in rows if r["source_url"] == REAL["source"])


def test_configuration_file_holds_the_published_config_but_for_what_it_lists_as_reduced():
    entry = catalog_entry()
    for key, value in entry["config"].items():
        assert REAL[key] == REAL["model"][key], key          # one set of values, in both places
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    changed = {k for k, v in entry["config"].items() if REAL[k] != v}
    assert changed == set(REAL["reduced"])
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim", "v_head_dim",
              "swa_head_dim", "swa_v_head_dim", "num_experts_per_tok", "sliding_window")
    assert not set(widths) & set(REAL["reduced"])


def test_the_cut_keeps_the_guides_floors_and_states_the_deployment():
    m, dep = REAL["model"], REAL["deployment"]
    assert m["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]  # the dense layer, then 5 : 1 whole
    assert m["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1] and m["num_hidden_layers"] == 7
    assert m["n_routed_experts"] == dep["held_experts"]["count"] == 16 >= 8
    assert dep["experts_published"] == 256 == 16 * dep["chips_sharing_a_layers_experts"]
    assert m["vocab_size"] * dep["vocabulary_split"] == dep["vocab_size_published"] == 152576
    assert set(REAL["assumed"]) >= {"weights", "attention_value_scale", "sink", "qk_norm", "routing"}


def test_the_program_built_from_the_file_has_the_issues_parameter_count():
    model = models_mimo_v2.build_model(REAL, max_seq_len=16896, remat=False, attn_impl="pallas")
    cfg = model.cfg
    assert cfg.num_params() == 3_429_955_392
    assert cfg.head_dim == 192 and cfg.v_head_dim == 128 and cfg.rope_dim == 64
    assert [k.name for k in cfg.plan] == ["dense_full"] + ["moe_window"] * 5 + ["moe_full"]
    from deepspeed_tpu.models.layer_plan import kv_read_bytes_by_pool, pool_shapes

    assert pool_shapes(cfg) == {"full": (2, 4, 0), "window": (5, 8, 128)}
    assert kv_read_bytes_by_pool(cfg, 16896) == {"full": 5120 * 16896, "window": 5 * 8 * 320 * 2 * 128}


# -- operations and bytes, against hand counts --------------------------

def test_cost_shapes_and_parameter_counts_by_hand():
    s = costs_mimo_v2.shapes(REAL)
    assert (s["n_full"], s["n_window"], s["n_dense"], s["n_expert"]) == (2, 5, 1, 6)
    assert costs_mimo_v2.attention_params(s, 0) == 4096 * (64 * 192 + 4 * 320) + 64 * 128 * 4096 == 89_128_960
    assert costs_mimo_v2.attention_params(s, 1) == 4096 * (64 * 192 + 8 * 320) + 64 * 128 * 4096 == 94_371_840
    assert costs_mimo_v2.expert_params(s) == 3 * 4096 * 2048 == 25_165_824
    always = 2 * 89_128_960 + 5 * 94_371_840 + 3 * 4096 * 16384 + 6 * 4096 * 256 + 4096 * 19072
    assert costs_mimo_v2.always_read_params(s) == always
    assert costs_mimo_v2.kv_bytes_per_position(s, 0) == 5120 == costs_mimo_v2.kv_bytes_per_position(s, 1) // 5 * 1


def test_decode_tick_cost_by_hand():
    obs = dict(mean_live_rows=30.0, mean_live_kv_tokens=270_000.0, moe_experts_hit_per_tick=60.0,
               moe_held_assignments_per_tick=90.0)
    cost = costs_mimo_v2.decode_tick(REAL, {}, obs)
    always = costs_mimo_v2.always_read_params(costs_mimo_v2.shapes(REAL))
    assert cost["bytes"] == (always + 60 * 25_165_824) * 2 + 270_000 * 5120 + 30 * 128 * 25_600
    assert cost["flops"] == 2 * (always * 30 + 90 * 25_165_824)


def test_grouped_matmul_and_flash_chunk_costs_by_hand():
    obs = dict(moe_experts_hit_per_tick=96.0, moe_held_assignments_per_tick=3000.0,
               chunk_tokens=1024.0, chunk_pairs_full=1024 * 4096 + 1024 * 1025 / 2,
               chunk_pairs_window=1024 * 128.0, chunk_keys_full=5120.0)
    gm = costs_mimo_v2.grouped_matmul_tick(REAL, {}, obs)
    assert gm["flops"] == 2 * 3000 * 25_165_824
    assert gm["bytes"] == 96 * 25_165_824 * 2 + 3000 * (2 * 4096 + 3 * 2048) * 2
    fl = costs_mimo_v2.flash_chunk(REAL, {}, obs)
    pairs = 2 * (1024 * 4096 + 1024 * 1025 / 2) + 5 * 1024 * 128
    assert fl["flops"] == 2 * pairs * 64 * 320
    assert fl["bytes"] == (7 * 1024 * 64 * 320 + (2 * 5120 * 4 + 5 * 1152 * 8) * 320) * 2


def test_a_program_without_the_counters_gives_no_reading():
    """The parent of the PR that brought the counters: its tick_stats() lacks
    them, and the wrapped runner's observations carry None, which a reader
    returns as nothing."""
    from benchmark import readers
    from benchmark.runners import serve_routed

    class Bare(serve_routed.Runner):
        def __init__(self):
            self.records, self.live_rows, self.live_kv = [], [], []

    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = Bare()._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert obs["moe_load_imbalance"] is None and obs["chunk_tokens"] is None
    ctx = readers.Context(obs=obs, config=REAL, cell={}, peaks=None, chips=1)
    assert readers.evaluate({"reduction": "value", "key": "moe_load_imbalance"}, ctx) is None
