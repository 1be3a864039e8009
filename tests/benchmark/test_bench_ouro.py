"""Ouro's family (one kind of layer walked several times over the same
weights, a norm before and after each sublayer and after every pass, a cache
of passes x layers layer-steps, an exit gate): the program against the plain
reference (``benchmark/reference/ouro.py``) in float32 on seeded weights -
the whole forward and the exit gate's distribution, the serving tick's logits
through prefill chunks and rows admitted at other ticks, a slot reused - each
piece of the reference failing when left out, each fault planted in the
program failing the same comparison, the tool's variants refused by the toy
cell's tolerances, the cost functions against hand counts, and the
configuration file against the published ``config.json``. Its toy cell
(``toy/cells/toy-ouro-chat.json``) runs end to end, traced and untraced, with
every other toy cell (``test_bench_runners_cpu.py`` finds it by its file).
Everything of this family is a file of its own."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, costs_ouro as costs, harness, models_ouro
from benchmark.reference import ouro
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.ops.transformer import kv_cache

sys.path.insert(0, os.path.join(bench_toy.ROOT, "tools"))
import ouro_cell_variant  # noqa: E402

TOY = dict(harness.load_json(os.path.join(
    bench_toy.ROOT, bench_toy.TOY_DIR, "configs", "toy-ouro.json")), dtype="float32")
REAL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "configs", "ouro-2.6b.json"))
CELL = harness.load_json(os.path.join(bench_toy.ROOT, "benchmark", "cells",
                                      "serve-ouro-2.6b-chat-batch.json"))
ARCH = ouro.arch(TOY)
VOCAB = TOY["model"]["vocab_size"]
LENGTH = 128
MATCH, MISS = 5e-4, 0.05      # of logits whose spread is ~1: float32 rounding, and a piece left out


@pytest.fixture(scope="module")
def model():
    return models_ouro.build_model(TOY, max_seq_len=LENGTH, remat=False, attn_impl="pallas")


@pytest.fixture(scope="module")
def params(model):
    return models_ouro.sharpen(model.init(jax.random.PRNGKey(0)), TOY, 3.0)


def logits(params, tokens, arch=ARCH):
    at = np.tile(np.arange(tokens.shape[1], dtype=np.int32), (tokens.shape[0], 1))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ouro.logits_at(params, tokens, at, arch))


def test_the_configuration_names_its_reference_and_builder(model):
    assert compare.reference_of(TOY) is ouro and compare.builder_of(TOY) is models_ouro
    assert all(isinstance(TOY["model"][k], int) for k in models_ouro.REQUIRED_SIZES)
    assert ARCH == (4, 4, 32, 1e6, 1e-6, 3, ())            # passes, layers (2) and heads all differ
    assert "deepspeed_tpu" not in open(ouro.__file__).read().replace("deepspeed_tpu/", "")


# -- the plain forward ------------------------------------------------------------------

@pytest.fixture(scope="module")
def sound(model, params):
    """(tokens, the program's logits), which the whole reference matches."""
    tokens = np.random.RandomState(4).randint(0, VOCAB, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        theirs, _, pdf = layer_plan.forward_plan(params, model.cfg, tokens, return_exit=True)
        assert np.allclose(np.asarray(model.apply(params, tokens)), np.asarray(theirs))
        want_pdf = np.asarray(ouro.exit_pdf(params, tokens, ARCH))
    assert np.abs(logits(params, tokens) - np.asarray(theirs)).max() < MATCH
    # the exit gate: the same distribution over the passes, a distribution, and not a flat one
    assert pdf.shape == (2, 40, 3) and np.abs(np.asarray(pdf) - want_pdf).max() < 1e-5
    assert np.allclose(want_pdf.sum(-1), 1.0, atol=1e-6) and want_pdf.std() > 0.05
    return tokens, np.asarray(theirs, np.float32)


@pytest.mark.parametrize("left_out", ["last_pass", "loop_norm", "post_norm", "theta"])
def test_each_piece_of_the_mathematics_fails_when_left_out(params, sound, left_out):
    """The program's logits against a reference with three passes for four,
    without the norm between passes, without the norms on the sublayers'
    outputs, with rotary base 1e4: the comparison that passes must fail."""
    tokens, theirs = sound
    wrong = logits(params, tokens, ARCH._replace(without=(left_out,)))
    assert not np.abs(wrong - theirs).max() < MISS


# -- the serving tick -------------------------------------------------------------------

def drive(cfg, params, prompts, width, new=5):
    """The tick by hand: the prompts admitted one after another, each in chunks of ``width``
    that ride beside the rows already decoding, then ``new`` plain ticks. Returns, a row, its
    tokens (prompt + greedy) and [(position, the tick's logits there)]."""
    B = len(prompts)
    cache = tf.init_cache(cfg, B, LENGTH)
    pos, last = np.full(B, LENGTH, np.int32), np.zeros(B, np.int32)
    seqs, seen = [list(p) for p in prompts], [[] for _ in prompts]
    tick = jax.jit(lambda cache, last, pos, chunk: layer_plan.forward_plan_cached(
        params, cfg, last, pos, cache, read_len=64, chunk=chunk))

    def run(chunk):
        nonlocal cache
        out, cache, _ = tick(cache, jnp.asarray(last), jnp.asarray(pos), chunk)
        for row in np.flatnonzero(pos < LENGTH):
            seen[row].append((int(pos[row]), np.asarray(out[row])))
            last[row] = int(np.argmax(out[row]))
            seqs[row].append(int(last[row]))
            pos[row] += 1
        return np.asarray(out)

    with jax.default_matmul_precision("highest"):
        for row, prompt in enumerate(prompts):
            for start in range(0, len(prompt), width):
                n = min(width, len(prompt) - start)
                toks, at = np.zeros(width, np.int32), np.full(width, LENGTH, np.int32)
                toks[:n], at[:n] = prompt[start:start + n], np.arange(start, start + n)
                out = run(layer_plan.Chunk(jnp.asarray(toks), jnp.asarray(at), jnp.int32(row),
                                           jnp.int32(n - 1)))
            seen[row].append((len(prompt) - 1, out[row]))    # the chunk's sampled column
            last[row], pos[row] = int(np.argmax(out[row])), len(prompt)
            seqs[row].append(int(last[row]))
        for _ in range(new):
            run(None)
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


PROMPTS = [np.random.RandomState(2).randint(0, VOCAB, n).astype(np.int32) for n in (37, 50, 5)]


@pytest.mark.parametrize("width", [16, 32], ids=["chunk16", "chunk32"])
def test_tick_logits_match_the_reference_through_chunks_and_rows_at_other_depths(model, params, width):
    seqs, seen = drive(model.cfg, params, PROMPTS, width)
    assert [len(m) for m in seen] == [len(s) - len(p) for s, p in zip(seqs, PROMPTS)]
    assert worst_gap(params, seqs, seen) < MATCH


@pytest.mark.parametrize("fault", ["shared_cache", "previous_pass_cache"])
def test_a_fault_planted_in_the_passes_caches_fails_the_same_comparison(model, params, fault):
    """Every pass on pass 1's layer-caches, or pass t on pass t - 1's:
    prefill and decoding agree with each other, and not with the reference."""
    with ouro_cell_variant.FAULTS[fault](TOY):
        seqs, seen = drive(model.cfg, params, PROMPTS, 32)
    assert worst_gap(params, seqs, seen) > MISS
    assert int(layer_plan._pass_slot(2, 1, 1)) == 3          # the swap is undone


def stream_gaps(params, prompt, out):
    """How far each emitted token's logit sits below the reference's top one."""
    toks = np.zeros((1, LENGTH), np.int32)
    toks[0, :len(out)] = out
    ref = logits(params, toks)[0, len(prompt) - 1:len(out) - 1]
    emitted = out[len(prompt):]
    return ref.max(-1) - ref[np.arange(len(emitted)), emitted]


def test_requests_through_the_engine_match_the_reference_and_a_slot_is_reused(model, params):
    """Three requests through two slots of the continuous-batching engine:
    the second is admitted while the first decodes, the third inherits a
    slot whose 6 layer-steps still hold the last request's keys."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in (60, 9, 23)]
    with jax.default_matmul_precision("highest"):
        eng = ContinuousBatchingEngine(
            model, config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
            params=params, max_slots=2, cache_len=LENGTH, prefill_chunk=32)
        eng._chunk_floor = 16
        rids = [eng.submit(p, max_new_tokens=8) for p in prompts]
        while eng.has_work():
            eng.step()
        outs = [np.asarray(eng.result(r)) for r in rids]
        assert all(len(o) == len(p) + 8 for o, p in zip(outs, prompts))
        assert all(stream_gaps(params, p, o).max() < MATCH for p, o in zip(prompts, outs))
    stats = eng.tick_stats()
    assert stats["prefill_chunk_tokens"] == 60 + 9 + 23 and stats["loop_passes"] == 3 * stats["ticks"]


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
    verdict = lambda s: compare.serve_verdict(ouro, params, prompts, s, ARCH, 5, tol, width=64, new_max=6)
    ok, fields = verdict(streams)
    assert ok and fields["share_within_margin"] == 1.0 and fields["worst_gap"] == 0.0, fields
    assert fields["control_share_outside_margin"]["prompt_permuted"] > 0.2, fields
    ok, fields = verdict([rs.randint(0, VOCAB, 6).astype(np.int32) for _ in prompts])
    assert not ok and fields["share_within_margin"] < 0.5


def test_float8_operands_move_the_reference_by_more_than_float32_rounding(params):
    tokens = np.random.RandomState(9).randint(0, VOCAB, (1, 32)).astype(np.int32)
    at = np.arange(32, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        low = np.asarray(ouro.logits_at(params, tokens, at, ARCH, operand=compare.fp8))
    assert np.abs(low - logits(params, tokens)).max() > MISS


def test_the_reference_trains_through_gpt2s_passes():
    model = models_ouro.build_model(TOY, max_seq_len=32, remat=False, attn_impl="xla")
    params = model.init(jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (4, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads = ouro.loss_and_grads(params, tokens, ARCH, rows_per_pass=2)
        ours = jax.nn.log_softmax(layer_plan.forward_plan(params, model.cfg, tokens)[0][:, :-1])
    want = -jnp.take_along_axis(ours, tokens[:, 1:, None], axis=2).mean()
    assert abs(float(loss) - float(want)) < 1e-4 and abs(float(loss) - np.log(VOCAB)) < 1.0
    norms = {k: float(jnp.linalg.norm(v)) for k, v in jax.tree_util.tree_leaves_with_path(grads)}
    gate = [n for k, n in norms.items() if "exit_gate" in jax.tree_util.keystr(k)]
    assert gate == [0.0, 0.0] and sum(n > 0 for n in norms.values()) == len(norms) - 2


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


@pytest.mark.parametrize("variant", ["shared_cache", "no_last_pass", "fp8"])
def test_a_planted_fault_and_float8_are_refused_by_the_toy_cells_tolerances(environment, capsys, variant):
    """A fault planted in the program, one planted in the reference and the
    float8 control, through the tool and the toy cell as the chip runs them
    (the tool's other variants swap the same two hooks: the comparisons above
    hold each of them; all seven were read on the toy cell and at full size,
    PERF.md section 6)."""
    assert set(ouro_cell_variant.VARIANTS) == {"shared_cache", "previous_pass_cache", "no_last_pass",
                                               "no_loop_norm", "no_post_norm", "no_theta", "fp8"}
    sound = (ouro.arch, ouro.logits_at, layer_plan._pass_slot)
    line = ouro_cell_variant.main(
        ["--variant", variant, "--workload", "toy-ouro-chat", "--seed", str(2 ** 31 + 7),
         "--seconds", "1.0"], manifest=bench_toy.manifest_path(), require_tpu=False)
    assert line["correct"] is False and line["failed"] == 0 and line["attempted"] >= 1
    assert (ouro.arch, ouro.logits_at, layer_plan._pass_slot) == sound
    out = capsys.readouterr().out
    assert f'"variant": "{variant}"' in out
    said = next(json.loads(l) for l in out.splitlines() if l.startswith('{"phase": "observations"'))
    # the looped counters, among the runner's observations, and no other family's
    assert said["loop_passes_per_tick"] == 3.0 and said["loop_kv_overread"] > 1.0
    assert said["kv_pool_gb"] > 0 and 0 < said["loop_kv_bytes_share_pct"] < 100
    assert said["moe_ticks"] is None and "ssm_chunk_tokens" not in said


# -- the published configuration, uncut -------------------------------------------------

def catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of published configurations is not on this machine")
    with open(path) as fh:
        rows = [json.loads(line) for line in fh]
    return next(r for r in rows if r["source_url"] == REAL["source"])


def test_configuration_file_holds_the_published_config_with_nothing_reduced():
    entry = catalog_entry()
    assert entry["name"] == "Ouro-2.6B" and REAL["name"] == "ouro-2.6b" and REAL["reduced"] == []
    for key, value in entry["config"].items():
        assert REAL[key] == REAL["model"][key] == value, key    # one set of values, in both places
    assert set(REAL["assumed"]) >= {"weights", "norm_places", "loop", "cache", "attention", "rotary",
                                    "exit_gate", "mlp", "head", "no_equation"}
    tol = REAL["compare"]["serve_looped"]
    assert tol["controls_held"] == ["prompt_permuted"] and 0 < tol["gap_p99_max"] and tol["why"]


def test_the_program_built_from_the_file_has_the_issues_parameter_count_and_pool():
    s = CELL["serve_looped"]
    model = models_ouro.build_model(REAL, max_seq_len=s["cache_len"], remat=False, attn_impl="pallas")
    cfg = model.cfg
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    built = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert built == cfg.num_params() == REAL["num_params"] == 2_667_974_657
    assert 48 * 51_388_416 + 2 * 100_663_296 + 2_048 + 2_049 == built
    assert {a.dtype for a in jax.tree.leaves(tree)} == {jnp.dtype(jnp.bfloat16)}
    assert (cfg.loop_steps, cfg.norm_position, cfg.head_dim, cfg.kv_heads) == (4, "sandwich", 128, 16)
    assert layer_plan.pool_shapes(cfg) == {"full": (192, 16, 0)}
    cache = jax.eval_shape(lambda: kv_cache.init(cfg, s["slots"], s["cache_len"]))
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert nbytes == s["slots"] * s["cache_len"] * 1_572_864               # 1.5 MiB a position
    assert kv_cache.read_bytes_by_pool(cfg, 1) == {"full": 1_572_864}
    assert (s["slots"], s["engine"]["prefill_chunk"]) == (16, 256)


# -- operations and bytes, against hand counts ------------------------------------------

def test_costs_by_hand():
    s = costs.shapes(REAL)
    assert costs.layer_params(s) == 51_388_416 and costs.layer_steps(s) == 192
    read = 4 * 48 * 51_388_416 + 4 * 2048 + 2048 * 49152
    assert costs.tick_read_params(s) == read
    assert costs.kv_bytes_per_position(s) == 1_572_864
    obs = dict(mean_live_rows=15.0, mean_live_kv_tokens=3000.0, chunk_tokens=80.0,
               chunk_pairs_full=80 * 81 / 2, chunk_keys_full=80.0)
    tick = costs.decode_tick(REAL, {}, obs)
    assert tick["bytes"] == read * 2 + 3000 * 1_572_864
    assert tick["flops"] == 2 * read * 15 + 4 * 192 * 16 * 128 * 3000
    assert costs.kv_bytes_tick(REAL, obs) == 3000 * 1_572_864
    fl = costs.flash_chunk(REAL, {}, obs)
    assert fl["flops"] == 2 * 192 * (80 * 81 / 2) * 16 * 256
    assert fl["bytes"] == 192 * (80 * 16 + 80 * 16) * 256 * 2


def test_a_program_without_the_counters_gives_no_reading():
    """The parent of the PR that brought the loop: its tick_stats() lacks the
    counters, the runner's observations carry None, which a reader returns as
    nothing; and its configuration has no ``loop_steps``, which the builder
    reports as the harness's "the program is not here"."""
    import dataclasses

    from benchmark import readers
    from benchmark.runners import serve_looped

    class Bare(serve_looped.Runner):
        def __init__(self):
            self.records, self.live_rows, self.live_kv = [], [], []
            self.ctx = dict(config=REAL, cell={})

    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = Bare()._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert obs["loop_passes_per_tick"] is None and obs["loop_kv_overread"] is None
    assert obs["kv_pool_gb"] is None and "loop_kv_bytes_share_pct" not in obs
    for name in ("tick_weight_roofline.looped", "flash_roofline.looped",
                 "loop_kv_bytes_share_pct.looped", "loop_kv_overread.looped"):
        metric = readers.load_metric([os.path.join(bench_toy.ROOT, "benchmark")], name)
        ctx = readers.Context(obs=obs, config=REAL, cell={}, peaks={"flops_per_s": 1.0,
                              "hbm_bytes_per_s": 1.0}, chips=1)
        assert readers.evaluate(metric["reader"], ctx) is None

    class Parent:   # a TransformerConfig without the loop
        __dataclass_fields__ = {k: v for k, v in tf.TransformerConfig.__dataclass_fields__.items()
                                if k != "loop_steps"}

    real, tf.TransformerConfig = tf.TransformerConfig, Parent
    try:
        assert "loop_steps" not in {f.name for f in dataclasses.fields(Parent)}
        with pytest.raises(ImportError, match="no loop_steps"):
            models_ouro.build_model(REAL, max_seq_len=64, remat=False, attn_impl="pallas")
    finally:
        tf.TransformerConfig = real
