"""Qwen3-Next's family (Gated DeltaNet layers whose recurrent state lives
beside keys and values in one cache, three to every gated full-attention
layer, softmax top-k expert layers with a shared expert of which a chip holds
a share): its plain reference against the program's model in float32 — the
whole forward, each kind of layer, and the serving tick's logits through
prefill chunks of several widths and rows at several depths —, each piece of
its mathematics failing when left out, faults planted in the program
failing, the shares of a deployment adding up to the uncut layer, its cost
functions against hand counts, and its configuration file against the
published ``config.json``. Its toy cell
(``toy/cells/toy-qwen3-next-longdoc.json``) runs end to end, traced and
untraced, with every other toy cell (``test_bench_runners_cpu.py`` finds it
by its file). Everything of this family is a file of its own."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_qwen3_next, harness, models_qwen3_next
from benchmark.reference import qwen3_next
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache

sys.path.insert(0, os.path.join(bench_toy.ROOT, "tools"))
import qwen3_next_cell_variant  # noqa: E402

TOY = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-qwen3-next.json")), dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs",
                                      "qwen3-next-80b-a3b.json"))
ARCH = qwen3_next.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]
LENGTH = 128


@pytest.fixture(scope="module")
def model():
    return models_qwen3_next.build_model(TOY, max_seq_len=LENGTH, remat=False, attn_impl="pallas")


@pytest.fixture(scope="module")
def params(model):
    return models_qwen3_next.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 3.0)


def logits(params, tokens, arch=ARCH):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(qwen3_next.logits_at(params, tokens, at, arch))


def test_the_configuration_names_its_reference_and_builder(model):
    assert compare.reference_of(TOY) is qwen3_next and compare.builder_of(TOY) is models_qwen3_next
    assert all(isinstance(TOY["model"][k], int) for k in models_qwen3_next.REQUIRED_SIZES)
    assert ARCH.rotary == 8 and ARCH.full_layers == (0, 0, 0, 1, 0, 0, 0, 1)
    assert (ARCH.held_first, ARCH.held_count, ARCH.top_k) == (8, 4, 4)
    cfg = model.cfg
    assert [k.name for k in cfg.plan] == ["gdn", "gdn", "gdn", "full"] * 2
    assert [k.pool for k in cfg.layer_kinds] == ["state", "full"]
    assert cfg.head_dim == 32 and cfg.rope_dim == 8 and cfg.moe_score == "softmax"
    assert cfg.moe_num_experts == 16 and cfg.held_experts == (8, 4) and cfg.moe_shared_size == 32
    assert cfg.attn_out_gate and cfg.qk_norm and cfg.norm_one_plus
    assert model.num_params() == sum(x.size for x in jax.tree.leaves(model.init(jax.random.PRNGKey(1))))


def test_reference_forward_matches_the_model_in_float32(model, params):
    tokens = np.random.RandomState(0).randint(0, VOCAB, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=2e-4)


@pytest.mark.parametrize("kind", ["gdn", "full", "experts"])
def test_each_kind_of_layer_matches_the_reference(model, params, kind):
    cfg, rs = model.cfg, np.random.RandomState(1)
    h = jnp.asarray(rs.randn(37, cfg.hidden_size), jnp.float32)
    f32 = lambda tree: jax.tree.map(lambda a: a[0].astype(jnp.float32), tree)
    with jax.default_matmul_precision("highest"):
        if kind == "gdn":
            w = f32(params["layers"]["gdn"]["gdn"])
            mine = layer_plan._gdn_plain(h, w, cfg, 1, 37)
            theirs = qwen3_next._delta_net(h, w, ARCH, qwen3_next._as_is)
        elif kind == "experts":
            w = f32(params["layers"]["full"]["mlp"])
            mine, stats = layer_plan._ffn(h, w, cfg.layer_kinds[1], cfg, None, grad=True)
            theirs = qwen3_next._experts(h, w, ARCH, qwen3_next._as_is)
            assert int(stats[0]) == 37 * 4 and 0 < int(stats[1]) < 37 * 4
        else:   # a model of one full-attention layer
            one = TransformerModel(dataclasses.replace(cfg, num_layers=1, layer_plan=(1,)))
            p = dict(params, layers={"full": jax.tree.map(lambda a: a[:1], params["layers"]["full"])})
            tokens = rs.randint(0, VOCAB, (1, 37)).astype(np.int32)
            mine = one.apply(p, tokens)
            theirs = logits(p, tokens, ARCH._replace(full_layers=(1,)))
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
    sound = layer_plan._hold
    if hold is not None:
        layer_plan._hold = lambda g, beta, valid: sound(g, beta, hold(valid, B))
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
        layer_plan._hold = sound
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


@pytest.mark.parametrize("width", [8, 32, 96], ids=["chunk8", "chunk32", "chunk96-two-sub-chunks"])
def test_tick_logits_match_the_reference_through_chunks_and_rows_at_other_depths(model, params,
                                                                                 width):
    seqs, seen = drive(model.cfg, params, PROMPTS, width)
    assert [len(m) for m in seen] == [len(s) - len(p) for s, p in zip(seqs, PROMPTS)]
    assert worst_gap(params, seqs, seen) < 2e-3
    if width == 8:   # the convolution's tail crosses every chunk boundary: without it, no match
        lost = ARCH._replace(without=("conv_tail",), tail_every=8)
        assert worst_gap(params, seqs, seen, lost) > 0.05


@pytest.mark.parametrize("fault", ["pads_step", "parked_rows_step"])
def test_a_fault_planted_in_the_ticks_hold_fails_the_same_comparison(model, params, fault):
    hold = {"pads_step": lambda valid, B: valid.at[B:].set(True),
            "parked_rows_step": lambda valid, B: valid.at[:B].set(True)}[fault]
    seqs, seen = drive(model.cfg, params, PROMPTS, 32, hold=hold)
    assert worst_gap(params, seqs, seen) > 0.05


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


def test_a_slot_reused_without_its_reset_fails_and_with_it_passes(model, params):
    rs = np.random.RandomState(3)
    two = [rs.randint(0, VOCAB, 80).astype(np.int32), rs.randint(0, VOCAB, 6).astype(np.int32)]
    with jax.default_matmul_precision("highest"):
        eng, outs = serve(model, params, two)            # the short one inherits the slot
        assert all(stream_gaps(params, p, o).max() < 1e-3 for p, o in zip(two, outs))
        stats = eng.tick_stats()
        assert stats["gdn_chunk_tokens"] == 86 == stats["prefill_chunk_tokens"]
        assert stats["gdn_step_rows"] == 2 * 9           # a request's first token is its chunk's
        assert stats["state_pool_bytes"] == stats["kv_pool_bytes_state"] == sum(
            a.nbytes for a in jax.tree.leaves(tf.init_cache(model.cfg, 1, LENGTH)["state"]))
        with qwen3_next_cell_variant.no_reset(TOY):
            _, outs = serve(model, params, two)
        assert stream_gaps(params, two[1], outs[1]).max() > 0.05
    assert kv_cache.reset_row.__module__ == kv_cache.__name__   # the variant is gone


# -- each piece of the mathematics ------------------------------------------------------

@pytest.mark.parametrize("left_out", ["decay", "beta", "conv", "l2norm", "z_gate", "attn_gate",
                                      "qk_norm", "partial_rotary", "shared", "shared_gate"])
def test_each_piece_of_the_mathematics_fails_when_left_out(model, params, left_out):
    """The program's logits against a reference that lacks one piece: the
    comparison that passes above must fail."""
    tokens = np.random.RandomState(4).randint(0, VOCAB, (1, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(model.apply(params, tokens), np.float32)
    assert np.allclose(logits(params, tokens), theirs, atol=2e-4)
    wrong = logits(params, tokens, ARCH._replace(without=(left_out,)))
    assert not np.abs(wrong - theirs).max() < 0.02      # (a reference that blew up is not a match)


def test_softmax_then_top_k_is_top_k_then_softmax_once_the_weights_are_normalised(model, params):
    """``norm_topk_prob``: the chosen probabilities divided by their sum ARE
    the softmax over the chosen logits, so the two orders the issue tells
    apart are one function (the configuration's ``assumed.routing``); what is
    not that function is another score: the sigmoid router of the other
    family chooses and weighs differently."""
    from deepspeed_tpu.moe import held_experts as he

    tokens = np.random.RandomState(5).randint(0, VOCAB, (1, 40)).astype(np.int32)
    both = [logits(params, tokens, ARCH._replace(without=w)) for w in ((), ("softmax_first",))]
    assert np.allclose(both[0], both[1], atol=1e-4)
    rs = np.random.RandomState(5)
    h, gate = jnp.asarray(rs.randn(50, 64), jnp.float32), jnp.asarray(rs.randn(64, 16), jnp.float32)
    soft, soft_w = he.route(h, gate, None, 4, "softmax")
    sig, sig_w = he.route(h, gate, jnp.zeros(16), 4, "sigmoid")
    assert np.array_equal(np.sort(soft, 1), np.sort(sig, 1))       # monotone: the same choice
    assert np.abs(np.sort(soft_w, 1) - np.sort(sig_w, 1)).max() > 0.05   # ... weighed differently


@pytest.mark.parametrize("chips", [8, 4, 2])
def test_the_shares_add_up_to_the_uncut_reference_layer(chips):
    """What every chip of a deployment computes for an expert layer (the
    program's layer told each share in turn) adds up, the shared expert that
    every chip computes alike counted once, to the reference's layer with
    every expert held."""
    from deepspeed_tpu.moe import held_experts as he

    rs = np.random.RandomState(6)
    E, D, F = 16, 64, 32
    count = E // chips
    h = jnp.asarray(rs.randn(29, D), jnp.float32)
    draw = lambda *shape, scale=0.2: jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
    whole = {"gate": draw(D, E, scale=0.3), "wg": draw(E, D, F), "wi": draw(E, D, F),
             "wo": draw(E, F, D), "shared_wg": draw(D, F), "shared_wi": draw(D, F),
             "shared_wo": draw(F, D), "shared_gate": draw(D, 1)}
    every = ARCH._replace(held_first=0, held_count=E)
    uncut = qwen3_next._experts(h, whole, every, qwen3_next._as_is)
    shared = uncut - qwen3_next._experts(h, whole, every._replace(without=("shared",)),
                                         qwen3_next._as_is)
    assert float(jnp.abs(shared).max()) > 0.05
    chosen, weights = he.route(h, whole["gate"], None, ARCH.top_k, "softmax")
    total = shared
    for first in range(0, E, count):
        mine = {n: whole[n][first:first + count] for n in ("wg", "wi", "wo")}
        part, _ = he.held_experts_ffn(h, chosen, weights, mine, first, count, tm=8)
        ref_part = qwen3_next._experts(
            h, dict(whole, **mine),
            ARCH._replace(held_first=first, held_count=count, without=("shared",)), qwen3_next._as_is)
        assert np.allclose(part, ref_part, atol=5e-5)   # the reference is given the same share
        total = total + part
    assert np.allclose(total, uncut, atol=1e-4)


def test_every_token_routed_to_one_held_expert_still_computes(model, params):
    cfg = model.cfg
    w = jax.tree.map(lambda a: a[0].astype(jnp.float32), params["layers"]["full"]["mlp"])
    w["gate"] = w["gate"].at[:, 9].set(0.0) + 0.0
    h = jnp.abs(jnp.asarray(np.random.RandomState(7).randn(33, cfg.hidden_size), jnp.float32))
    w["gate"] = w["gate"].at[:, 9].set(5.0)              # h > 0: expert 9 (held) wins every token
    with jax.default_matmul_precision("highest"):
        mine, stats = layer_plan._ffn(h, w, cfg.layer_kinds[1], cfg, None, grad=False)
        theirs = qwen3_next._experts(h, w, ARCH, qwen3_next._as_is)
    assert int(stats[2]) == 33 and np.allclose(mine, theirs, atol=2e-4)


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
    tol = dict(margin=0.25, share_within=0.99, control_share=0.0, distinct_per_request=1)
    verdict = lambda s: compare.serve_verdict(qwen3_next, params, prompts, s, ARCH, 5, tol,
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
        low = np.asarray(qwen3_next.logits_at(params, tokens, at, ARCH, operand=compare.fp8))
    assert np.abs(low - logits(params, tokens)).max() > 0.05


# -- the toy cell through the harness, and the variant tool -----------------------------

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
    sound = qwen3_next.arch
    line = qwen3_next_cell_variant.main(
        ["--variant", "no_decay", "--workload", "toy-qwen3-next-longdoc", "--seed", str(2 ** 31 + 7),
         "--seconds", "1.0"], manifest=bench_toy.manifest_path(), require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 1
    assert qwen3_next.arch is sound
    out = capsys.readouterr().out
    assert '"variant": "no_decay"' in out
    said = next(json.loads(l) for l in out.splitlines() if l.startswith('{"phase": "observations"'))
    # the new counters, among the runner's observations
    assert said["gdn_chunk_tokens"] > 0 and said["gdn_step_rows_per_tick"] > 0
    assert said["state_pool_gb"] > 0 and 0 < said["state_bytes_share_pct"] < 100


@pytest.mark.parametrize("tol, ok", [
    (dict(controls_held=["prompt_permuted"]), True),                       # no limit on the gaps
    (dict(controls_held=["prompt_permuted"], gap_p99_max=0.11), False),    # 0.2 is over it
    (dict(controls_held=["prompt_permuted"], gap_p99_max=0.3), True),
    (dict(gap_p99_max=0.3), False),                                        # absent: both controls held
], ids=["no-gap-limit", "gap-p99-over-its-limit", "gap-p99-under-its-limit", "both-controls-held"])
def test_the_hybrid_runner_holds_the_gaps_99th_percentile_and_the_named_controls(monkeypatch, tol, ok):
    from benchmark.runners import serve_hybrid, serve_routed

    fields = dict(share_within_margin=1.0, share_within_required=0.9, gap_p99=0.2,
                  control_share_outside_margin={"prompt_permuted": 0.9, "prompt_one_position_early": 0.0},
                  control_share_required=0.05, distinct_tokens=20, distinct_required=18,
                  finished_with_wrong_token_count=0)
    monkeypatch.setattr(serve_routed.Runner, "finish", lambda self: dict(ok=None, fields=dict(fields)))
    runner = object.__new__(serve_hybrid.Runner)
    runner.tol = tol
    verdict = runner.finish()
    assert verdict["ok"] is ok
    assert verdict["fields"]["gap_p99_max"] == tol.get("gap_p99_max")
    assert set(verdict["fields"]["controls_held"]) <= set(fields["control_share_outside_margin"])


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
    for key, value in entry["config"].items():
        assert REAL[key] == REAL["model"][key], key          # one set of values, in both places
        if key not in REAL["reduced"]:
            assert REAL[key] == value, key
    changed = {k for k, v in entry["config"].items() if REAL[k] != v}
    assert changed == set(REAL["reduced"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    widths = ("hidden_size", "moe_intermediate_size", "shared_expert_intermediate_size", "head_dim",
              "linear_key_head_dim", "linear_value_head_dim", "num_experts_per_tok",
              "linear_conv_kernel_dim", "linear_num_key_heads", "linear_num_value_heads")
    assert not set(widths) & set(REAL["reduced"])


def test_the_cut_keeps_the_guides_floors_and_states_the_deployment():
    m, dep = REAL["model"], REAL["deployment"]
    assert m["num_hidden_layers"] == 12 == 3 * m["full_attention_interval"]   # three whole periods
    assert m["num_experts"] == dep["held_experts"]["count"] == 64 >= 8
    assert dep["experts_published"] == 512 == 64 * dep["chips_sharing_a_layers_experts"]
    assert m["vocab_size"] * dep["vocabulary_split"] == dep["vocab_size_published"] == 151936
    assert dep["num_hidden_layers_published"] == 48 and m["num_experts_per_tok"] == 10
    assert set(REAL["assumed"]) >= {"weights", "norm", "qkvz_layout", "gated_delta_rule",
                                    "state_dtype", "conv", "attention", "routing", "depth"}


def test_the_program_built_from_the_file_has_the_issues_parameter_count_and_pools():
    model = models_qwen3_next.build_model(REAL, max_seq_len=16896, remat=False, attn_impl="pallas")
    cfg = model.cfg
    assert cfg.num_params() == 2_929_374_400
    assert cfg.head_dim == 256 == cfg.v_head_dim and cfg.rope_dim == 64
    assert [k.name for k in cfg.plan] == ["gdn", "gdn", "gdn", "full"] * 3
    assert layer_plan.pool_shapes(cfg) == {"full": (3, 2, 0)}
    state = kv_cache.state_spec(cfg)
    assert state == (9, 32, 128, 128, 3, 8192)
    cache = jax.eval_shape(lambda: kv_cache.init(cfg, 32, 16896))
    sizes = {pool: sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(sub))
             for pool, sub in cache.items()}
    assert sizes == {"full": 3 * 32 * 2 * 16896 * 512 * 2,
                     "state": 9 * 32 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)}
    assert cache["state"]["s"].dtype == jnp.float32 and cache["state"]["conv"].dtype == jnp.bfloat16
    assert kv_cache.read_bytes_by_pool(cfg, 4096) == {
        "full": 3 * 4096 * 2 * 512 * 2, "state": sizes["state"] // 32}


# -- operations and bytes, against hand counts ------------------------------------------

def test_cost_shapes_and_parameter_counts_by_hand():
    s = costs_qwen3_next.shapes(REAL)
    assert (s["n_full"], s["n_gdn"], s["L"]) == (3, 9, 12)
    assert costs_qwen3_next.gdn_params(s) == 33_718_464
    assert costs_qwen3_next.attention_params(s) == 27_263_488
    assert costs_qwen3_next.expert_params(s) == 3 * 2048 * 512 == 3_145_728
    always = 9 * 33_718_464 + 3 * 27_263_488 + 12 * 4_200_448 + 2048 * 18992
    assert costs_qwen3_next.always_read_params(s) == always
    # with the held experts, the embedding and the final norm: the issue's parameter count
    assert always + 12 * 64 * 3_145_728 + 18992 * 2048 + 2048 == 2_929_374_400
    assert costs_qwen3_next.kv_bytes_per_position(s) == 3 * 2 * 512 * 2 == 6144
    assert costs_qwen3_next.state_bytes_per_row(s) == 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)


def test_tick_costs_by_hand():
    obs = dict(mean_live_rows=30.0, mean_live_kv_tokens=270_000.0, moe_experts_hit_per_tick=400.0,
               moe_held_assignments_per_tick=480.0, gdn_step_rows_per_tick=29.0)
    s = costs_qwen3_next.shapes(REAL)
    always, row = costs_qwen3_next.always_read_params(s), costs_qwen3_next.state_bytes_per_row(s)
    cost = costs_qwen3_next.decode_tick(REAL, {}, obs)
    assert cost["bytes"] == (always + 400 * 3_145_728) * 2 + 270_000 * 6144 + 2 * 29 * row
    assert cost["flops"] == 2 * (always * 30 + 480 * 3_145_728) + 6 * 29 * 9 * 32 * 128 * 128
    assert costs_qwen3_next.state_bytes_tick(REAL, obs) == 2 * 29 * row
    step = costs_qwen3_next.gdn_step_tick(REAL, {}, obs)
    assert step == {"flops": 6 * 29 * 9 * 32 * 128 * 128, "bytes": 2 * 29 * 9 * 32 * 128 * 128 * 4}
    gm = costs_qwen3_next.grouped_matmul_tick(REAL, {}, obs)
    assert gm["flops"] == 2 * 480 * 3_145_728
    assert gm["bytes"] == 400 * 3_145_728 * 2 + 480 * (2 * 2048 + 3 * 512) * 2


def test_chunk_costs_by_hand():
    obs = dict(chunk_tokens=1000.0, chunk_pairs_full=1000 * 4096 + 1000 * 1001 / 2,
               chunk_keys_full=5096.0, gdn_chunk_tokens=1000.0)
    fl = costs_qwen3_next.flash_chunk(REAL, {}, obs)
    assert fl["flops"] == 2 * 3 * (1000 * 4096 + 1000 * 1001 / 2) * 16 * 512
    assert fl["bytes"] == 3 * (1000 * 16 + 5096 * 2) * 512 * 2
    scan = costs_qwen3_next.gdn_chunk(REAL, {}, obs)
    assert scan["flops"] == 9 * 32 * 1000 * (6 * 128 * 128 + 2 * 64 * 128)
    assert scan["bytes"] == 9 * 32 * (1000 * (3 * 128 + 2 * 128 + 64) + 2 * 128 * 128) * 4


def test_a_program_without_the_counters_gives_no_reading():
    """The parent of the PR that brought the counters: its tick_stats() lacks
    them, the wrapped runner's observations carry None, which a reader
    returns as nothing; and its layer plan has no such mixer, which the
    builder reports as the harness's "the program is not here"."""
    from benchmark import readers
    from benchmark.runners import serve_hybrid

    class Bare(serve_hybrid.Runner):
        def __init__(self):
            self.records, self.live_rows, self.live_kv = [], [], []
            self.ctx = dict(config=REAL, cell={})

    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = Bare()._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert obs["gdn_chunk_tokens"] is None and obs["gdn_step_rows_per_tick"] is None
    assert obs["state_pool_gb"] is None and "state_bytes_share_pct" not in obs
    ctx = readers.Context(obs=obs, config=REAL, cell={}, peaks=None, chips=1)
    assert readers.evaluate({"reduction": "value", "key": "state_bytes_share_pct"}, ctx) is None
    fields = dataclasses.fields
    try:
        models_qwen3_next.dataclasses.fields = lambda cls: [f for f in fields(cls) if f.name != "mixer"]
        with pytest.raises(ImportError, match="gated-delta-rule"):
            models_qwen3_next.build_model(REAL, max_seq_len=128, remat=False, attn_impl="xla")
    finally:
        models_qwen3_next.dataclasses.fields = fields
