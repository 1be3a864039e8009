"""LFM2-24B-A2B's family on the TRAINING path (gated short convolutions
beside grouped-query attention, sigmoid top-k routing with a selection bias
over a held share of the experts, a tied head): the program against the plain
reference (``benchmark/reference/lfm2_moe.py``) in float32 on seeded weights -
the loss and the gradient leaf by leaf with either attention and with or
without remat, the convolution against direct sums, the routing piece by
piece, the shares of the experts adding up to the uncut layer (outputs and
input-gradients), the routing counters out of the engine, each planted fault
failing, the cost functions against hand counts and the configuration file
against the published ``config.json``. Its toy cell
(``toy/cells/toy-lfm2-train.json``) runs end to end with every other toy cell
(``test_bench_runners_cpu.py`` finds it by its file); here it runs with every
control. Everything of this family is a file of its own."""

import dataclasses
import io
import itertools
import json
import os
import re
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_lfm2_moe as costs, harness, models_lfm2_moe
from benchmark.reference import lfm2_moe as ref
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.moe import held_experts

TOY = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-lfm2-moe.json")), dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs", "lfm2-24b-a2b.json"))
CELL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "cells",
                                      "train-lfm2-24b-a2b-8k-1chip.json"))
ARCH = ref.arch(TOY)
VOCAB, SEQ = TOY["model"]["vocab_size"], 64
MATCH, MISS = 2e-4, 0.02     # float32 rounding of a gradient leaf, and a piece got wrong


def build(attn_impl="xla", remat=False, config=TOY):
    return models_lfm2_moe.build_model(config, max_seq_len=SEQ, remat=remat, attn_impl=attn_impl)


@pytest.fixture(scope="module")
def params():
    return build().init(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, VOCAB, (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def reference_grads(params, tokens):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(lambda p: ref.loss_and_grads(p, jnp.asarray(tokens), ARCH, 1))(params)
    return float(loss), jax.tree.map(float, compare.leaf_readings(grads))


def test_the_configuration_names_its_reference_and_builder():
    assert compare.reference_of(TOY) is ref and compare.builder_of(TOY) is models_lfm2_moe
    assert all(isinstance(TOY["model"][k], int) for k in models_lfm2_moe.REQUIRED_SIZES)
    assert ARCH == (4, 2, 1e6, 1e-5, ("conv", "full_attention", "conv", "conv", "conv"), 1, 2, 1.0,
                    1e-6, 0, 4, ())
    assert [ref.kind_of(ARCH, i) for i in range(5)] == [
        ("conv_dense", 0), ("attn_moe", 0), ("conv_moe", 0), ("conv_moe", 1), ("conv_moe", 2)]
    assert "deepspeed_tpu" not in open(ref.__file__).read()   # nothing of the program
    cfg = build().cfg
    assert [k.name for k in cfg.plan] == models_lfm2_moe.kind_names(TOY["model"])
    assert cfg.tie_embeddings and cfg.qk_norm and cfg.moe_norm_eps == 1e-6 and cfg.conv_taps == 3
    assert cfg.moe_num_experts == 8 and cfg.held_experts == (0, 4)


# -- the whole model: loss and gradient ---------------------------------------------------

@pytest.mark.parametrize("attn_impl,remat", [("xla", False), ("xla", True), ("pallas", False),
                                             ("pallas", True)])
def test_loss_and_gradient_match_the_reference_leaf_by_leaf(params, tokens, reference_grads,
                                                            attn_impl, remat):
    model = build(attn_impl, remat)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, {"input_ids": tokens})))(params)
    want_loss, want = reference_grads
    assert float(loss) == pytest.approx(want_loss, abs=1e-5)
    norm_gap, proj_gap, gaps = compare.worst_leaf_gaps(
        jax.tree.map(float, compare.leaf_readings(grads)), want)
    assert norm_gap < MATCH and proj_gap < MATCH, gaps
    # the selection bias reaches the loss through top_k's indices alone: no gradient at all
    for kind in ("attn_moe", "conv_moe"):
        assert not np.asarray(grads["layers"][kind]["mlp"]["gate_bias"]).any()
        assert want[f"['layers']['{kind}']['mlp']['gate_bias']"] == (0.0, 0.0)


@pytest.mark.parametrize("wrong", ["swap_bc", "taps_reversed", "qk_norm", "bias", "bias_in_weights"])
def test_each_piece_got_wrong_leaves_the_gradient(params, tokens, reference_grads, wrong):
    """The reference with the two gates exchanged, the taps in the other
    order, no norm on the heads, the selection bias left out of the choice,
    or the weights taken from score + bias: the comparison above must fail."""
    with jax.default_matmul_precision("highest"):
        _, grads = jax.jit(lambda p: ref.loss_and_grads(
            p, jnp.asarray(tokens), ARCH._replace(without=(wrong,)), 1))(params)
    norm_gap, proj_gap, _ = compare.worst_leaf_gaps(
        jax.tree.map(float, compare.leaf_readings(grads)), reference_grads[1])
    assert max(norm_gap, proj_gap) > MISS


def test_the_reference_in_blocks_is_the_reference_whole(params, tokens, monkeypatch):
    """Queries in blocks and tokens in blocks change memory, not values."""
    at = np.tile(np.arange(SEQ, dtype=np.int32), (2, 1))
    whole = np.asarray(ref.logits_at(params, tokens, at, ARCH))
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 32)
    assert np.abs(np.asarray(ref.logits_at(params, tokens, at, ARCH)) - whole).max() < 1e-5


# -- the convolution mixer alone ------------------------------------------------------------

def direct_conv_mixer(h, w):
    """The equations by loops over positions and taps, in float64."""
    h, win, taps, wo = (np.asarray(a, np.float64) for a in (h, w["win"], w["conv"], w["wo"]))
    S, D = h.shape
    K = taps.shape[1]
    b, c, u = np.split(h @ win, 3, axis=1)
    v = b * u
    z = np.zeros((S, D))
    for t in range(S):
        for j in range(K):
            if t - (K - 1) + j >= 0:        # nothing before the sequence's first position
                z[t] += taps[:, j] * v[t - (K - 1) + j]
    return (c * z) @ wo


def test_the_convolution_mixer_is_the_direct_sum(params):
    w = jax.tree.map(lambda a: a[1], params["layers"]["conv_moe"]["conv"])
    h = np.random.RandomState(1).standard_normal((2 * 24, 64)).astype(np.float32)
    want = np.stack([direct_conv_mixer(h[:24], w), direct_conv_mixer(h[24:], w)]).reshape(48, 64)
    scale = np.abs(want).max()
    cfg = build().cfg
    with jax.default_matmul_precision("highest"):
        mine = np.asarray(layer_plan._conv_plain(jnp.asarray(h), w, cfg, 2, 24))
        theirs = np.concatenate([np.asarray(ref.conv_mixer(jnp.asarray(h[i:i + 24]), w, ARCH))
                                 for i in (0, 24)])
        assert np.abs(mine - want).max() < 1e-4 * scale and np.abs(theirs - want).max() < 1e-4 * scale
        assert np.abs(mine[:2] - want[:2]).max() < 1e-4 * scale   # the first K - 1 positions
        for wrong in ("swap_bc", "taps_reversed"):
            bad = np.asarray(ref.conv_mixer(jnp.asarray(h[:24]), w, ARCH._replace(without=(wrong,))))
            assert np.abs(bad - want[:24]).max() > 0.1 * scale
    # a row does not see the row before it: the second row alone gives the same
    with jax.default_matmul_precision("highest"):
        alone = np.asarray(layer_plan._conv_plain(jnp.asarray(h[24:]), w, cfg, 1, 24))
    assert np.abs(alone - mine[24:]).max() < 1e-6 * scale


# -- the routing ----------------------------------------------------------------------------

def test_routing_bias_chooses_and_enters_nothing_else(params):
    m = jax.tree.map(lambda a: a[0], params["layers"]["attn_moe"]["mlp"])
    m = dict(m, gate_bias=jnp.asarray([0.3, -0.3, 0.2, -0.2, 0.1, -0.1, 0.0, 0.25], jnp.float32))
    h = jnp.asarray(np.random.RandomState(2).standard_normal((64, 64)), jnp.float32)
    route = lambda bias, eps=1e-6: held_experts.route(h, m["gate"], bias, 2, "sigmoid", norm_eps=eps)
    chosen, weights = route(m["gate_bias"])
    plain, _ = route(jnp.zeros(8))
    assert (np.sort(chosen, 1) != np.sort(plain, 1)).any()       # the bias changes the choice
    scores = np.asarray(jax.nn.sigmoid(h @ m["gate"]))
    picked = np.take_along_axis(scores, np.asarray(chosen), 1)
    want = picked / (picked.sum(1, keepdims=True) + 1e-6)          # ... and not the weights
    assert np.abs(np.asarray(weights) - want).max() < 1e-6
    # the published 1e-6: with it the weights of a token sum to a little under one
    assert np.abs(np.asarray(route(m["gate_bias"], 0.0)[1]).sum(1) - 1).max() < 1e-6
    assert (1 - want.sum(1)).min() > 2e-7 and np.abs(np.asarray(route(m["gate_bias"], 0.1)[1])
                                                      - picked / (picked.sum(1, keepdims=True) + 0.1)).max() < 1e-6
    # the reference says the same, expert by expert, and its planted faults do not
    dense = np.asarray(ref.routing(h, m, ARCH))
    mine = np.zeros_like(dense)
    np.put_along_axis(mine, np.asarray(chosen), np.asarray(weights), 1)
    assert np.abs(dense - mine).max() < 1e-6
    for wrong in ("bias", "bias_in_weights"):
        assert np.abs(np.asarray(ref.routing(h, m, ARCH._replace(without=(wrong,)))) - mine).max() > 0.05
    # a choice fed in takes the place of the reference's own, and of nothing else
    assert np.array_equal(ref.routing(h, m, ARCH, chosen=chosen), dense)
    assert np.array_equal(ref.routing(h, m, ARCH, chosen=plain),
                          ref.routing(h, m, ARCH._replace(without=("bias",))))


def test_a_choice_fed_to_the_reference_is_the_one_a_bias_would_force(params, tokens, monkeypatch):
    """Through the whole model, a block of tokens at a time: every token sent
    to experts 0 and 5 by a fed choice, or by a selection bias that leaves no
    other choice (the bias chooses and enters nothing else): the same loss
    and the same gradient."""
    monkeypatch.setattr(ref, "TOKEN_BLOCK", 16)
    forced = jax.tree.map(lambda a: a, params)
    for kind in ("attn_moe", "conv_moe"):
        bias = forced["layers"][kind]["mlp"]["gate_bias"]
        forced["layers"][kind]["mlp"] = dict(forced["layers"][kind]["mlp"],
                                             gate_bias=bias.at[:, 0].set(9.0).at[:, 5].set(8.0))
    fed = jnp.broadcast_to(jnp.asarray([0, 5], jnp.int32), (len(tokens), 4, SEQ, 2))
    with jax.default_matmul_precision("highest"):
        by_bias = jax.jit(jax.value_and_grad(lambda p: ref.loss_sum(p, jnp.asarray(tokens), ARCH)))(forced)
        by_feed = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_sum(p, jnp.asarray(tokens), ARCH, chosen=fed)))(params)
        own = jax.jit(lambda p: ref.loss_sum(p, jnp.asarray(tokens), ARCH))(params)
    assert float(by_bias[0]) == float(by_feed[0]) != float(own)
    for a, b in zip(jax.tree.leaves(by_bias[1]), jax.tree.leaves(by_feed[1])):
        assert np.array_equal(a, b)


# -- the share ties to the model ------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(params):
    """8 experts as two shares of 4: the shares' expert-layer outputs, and
    their gradients with respect to the layer's input, add up to what the
    reference gives for the layer whole."""
    rs = np.random.RandomState(5)
    D, F = 64, 32
    draw = lambda *shape, scale=1.0: jnp.asarray(rs.standard_normal(shape) * scale, jnp.float32)
    full = dict(gate=draw(D, 8, scale=0.3), gate_bias=draw(8, scale=0.05),
                wg=draw(8, D, F, scale=D ** -0.5), wi=draw(8, D, F, scale=D ** -0.5),
                wo=draw(8, F, D, scale=F ** -0.5))
    h, cot = draw(96, D), draw(96, D)
    cfg = build().cfg
    kind = cfg.layer_kinds[1]
    assert kind.ffn == "moe"

    def share(first):
        c = dataclasses.replace(cfg, moe_experts_held=(first, 4))
        mlp = dict(full, **{n: full[n][first:first + 4] for n in ("wg", "wi", "wo")})
        out = lambda x: layer_plan._ffn(x, mlp, kind, c, None, grad=True)[0]
        return out(h), jax.grad(lambda x: (out(x) * cot).sum())(h)

    whole = lambda x: ref.experts(x, full, ARCH._replace(held_first=0, held_count=8))
    with jax.default_matmul_precision("highest"):
        (out_a, grad_a), (out_b, grad_b) = share(0), share(4)
        want, want_grad = whole(h), jax.grad(lambda x: (whole(x) * cot).sum())(h)
    assert np.abs(np.asarray(out_a)).max() > 0.05 and np.abs(np.asarray(out_b)).max() > 0.05
    assert np.abs(np.asarray(out_a + out_b - want)).max() < 1e-5
    assert np.abs(np.asarray(grad_a + grad_b - want_grad)).max() < 1e-4
    assert np.abs(np.asarray(out_a - want)).max() > 0.05          # one share alone is not the layer


# -- the counters ---------------------------------------------------------------------------

def test_forward_counters_count_the_assignments(params, tokens):
    model = build()
    counted = model.loss_with_counters
    loss, counters = jax.jit(lambda p: counted(p, {"input_ids": tokens}))(params)
    made, held, most, layers, hit = (int(c) for c in counters)
    assert float(loss) == pytest.approx(float(model.loss(params, {"input_ids": tokens})), abs=1e-6)
    assert layers == 4 and made == tokens.size * 2 * layers      # tokens x top-k x expert layers
    assert 0 < held <= made and held / made == pytest.approx(0.5, abs=0.1)   # 4 of 8 held
    assert held / (layers * 4) <= most <= tokens.size and hit == 16
    assert len(model.counter_names) == 5


def test_engine_sums_the_counters_over_micro_steps(tokens):
    import deepspeed_tpu
    from deepspeed_tpu import comm

    comm.destroy()
    model = build("pallas", True)
    config = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 0}, "mesh": {"fsdp": 1},
              "steps_per_print": 10 ** 9, "seed": 1}
    mesh = comm.build_mesh(config["mesh"], devices=jax.devices()[:1])   # one chip, as the cell
    engine = deepspeed_tpu.initialize(model=model, config=config, mesh=mesh)[0]
    assert engine.moe_stats() == dict.fromkeys(model.counter_names, 0)
    other = np.random.RandomState(9).randint(0, VOCAB, tokens.shape).astype(np.int32)
    feed = itertools.cycle([{"input_ids": tokens}, {"input_ids": other}])
    count = jax.jit(lambda p, t: model.loss_with_counters(p, {"input_ids": t})[1])
    want = np.zeros(5, np.int64)
    for _ in range(2):                       # two optimizer steps of two micro-steps
        for batch in (tokens, other):        # the step's weights, before it donates them
            want += np.asarray(count(engine.params, batch))
        engine.train_batch(feed)
    stats = engine.moe_stats()
    assert [stats[n] for n in model.counter_names] == want.tolist()
    assert stats["moe_assignments"] == 4 * tokens.size * 2 * 4
    comm.destroy()


# -- the toy cell through the harness, with every control -----------------------------------

def test_the_toy_cell_is_correct_and_every_control_fails():
    saved = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            line = harness.run_cell(bench_toy.manifest_path(), "toy-lfm2-train", 2 ** 31 + 7, 1.0,
                                    False, require_tpu=False,
                                    overrides=["cell.train.controls=true"])
    finally:
        if saved is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = saved
    said = next(json.loads(l) for l in out.getvalue().splitlines()
                if l.startswith('{"phase": "compare"'))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1, said
    assert said["controls_passed_the_check"] == {
        "grads_scaled": False, "shard_left_out": False, "double_update": False,
        "lower_precision": False}
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    obs = next(json.loads(l) for l in out.getvalue().splitlines()
               if l.startswith('{"phase": "observations"'))
    assert obs["moe_assignments_per_micro_step"] == 2 * SEQ * 2 * 4
    assert 40 < obs["moe_held_share_pct"] < 60 and obs["moe_load_imbalance"] >= 1


# -- the committed configuration and its costs ----------------------------------------------

def test_the_committed_configuration_is_the_published_one_cut_as_it_says():
    m = REAL["model"]
    published = dict(conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=11776,
                     max_position_embeddings=128000, model_type="lfm2_moe",
                     moe_intermediate_size=1536, norm_eps=1e-5, norm_topk_prob=True,
                     num_attention_heads=32, num_experts_per_tok=4, num_key_value_heads=8,
                     rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
                     routed_scaling_factor=1, use_expert_bias=True)
    for key, value in published.items():
        assert m[key] == value and REAL[key] == value, key
    cut = dict(num_hidden_layers=5, layer_types=["conv", "full_attention", "conv", "conv", "conv"],
               num_dense_layers=1, num_experts=8, vocab_size=8192)
    assert sorted(REAL["reduced"]) == sorted(cut) and all(m[k] == v == REAL[k] for k, v in cut.items())
    dep = REAL["deployment"]
    assert dep["experts_published"] == 64 and dep["held_experts"] == {"first": 0, "count": 8}
    assert dep["chips_sharing_a_layer"] == 8 and dep["vocab_size_published"] == 8 * m["vocab_size"]
    assert {"head", "rotary", "in_proj_order", "selection_bias"} <= set(REAL["assumed"])
    model = models_lfm2_moe.build_model(REAL, max_seq_len=8192, remat=True, attn_impl="pallas")
    assert model.num_params() == 469_285_248
    assert [k.name for k in model.cfg.plan] == ["conv_dense", "attn_moe", "conv_moe", "conv_moe",
                                                "conv_moe"]
    tol = REAL["compare"]["train_routed"]
    assert tol["micro_batches"] == "distinct" and {"loss_abs", "min_fall", "grad_norm_rel"} <= set(tol)
    # every leaf's norm is held, the experts' own weights included; the projection leaves them out
    assert tol["grad_leaf_norm_rel"] <= 0.01 and tol["grad_leaf_proj_rel"] <= 0.3
    assert [leaf for leaf in ("['layers']['conv_moe']['mlp']['wi']", "['layers']['attn_moe']['mlp']['gate']",
                              "['layers']['conv_dense']['mlp']['wi']")
            if re.search(tol["proj_not_held"], leaf)] == ["['layers']['conv_moe']['mlp']['wi']"]
    t = CELL["train"]
    assert CELL["runner"] == "train_routed" and set(CELL["train_routed"]) == {"why"}
    assert t["seq"] == 8192 and t["micro_batch_per_chip"] * t["gradient_accumulation_steps"] == 8
    assert t["attn_impl"] == "pallas" and t["remat"] and t["zero_stage"] == 0


def test_costs_are_the_hand_counts():
    D, S = 2048, 8192
    conv = 2 * (3 * D * D + D * D)
    attn = 2 * (2 * D * D + 2 * D * 512) + 4 * D * (S / 2)
    experts = 0.5 * 6 * D * 1536 + 2 * D * 64          # 4 x 8 / 64 assignments a token, the router
    forward = (conv + 6 * D * 11776) + (attn + experts) + 3 * (conv + experts) + 2 * D * 8192
    assert costs.forward_flops_per_token(REAL, S) == forward == pytest.approx(405.8e6, rel=1e-3)
    assert costs.train_flops_per_token(REAL, S) == 3 * forward
    flash = costs.flash_train_micro_step(REAL, CELL, {})
    pairs = 2 * 32 * (S * S / 2) * 64                    # a micro-batch of 2, 32 heads of 64, causal
    assert flash["flops"] == (2 + 3 + 4) * 2 * pairs
    assert flash["bytes"] == 2 * S * 64 * 2 * ((2 + 3 + 2) * 32 + (2 + 2 + 4) * 8)
    # four expert layers x (3 forward + 3 again under remat + 6 gradients), 8,192 expected rows
    ragged = costs.ragged_products_micro_step(REAL, CELL, {})
    assert ragged["flops"] == 4 * 12 * 2 * (2 * S * 0.5) * D * 1536
    assert ragged["bytes"] == 4 * 12 * 2 * (8 * D * 1536 + (2 * S * 0.5) * (D + 1536))
    # the yardstick does not move with a run's routing: no counter is read
    assert costs.flash_train_micro_step(REAL, CELL, {"moe_held_share_pct": 3.0}) == flash
