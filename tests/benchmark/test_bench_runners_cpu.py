"""Each runner end to end on the CPU through the harness's Python entry
point, on the toy cell files kept beside this file: traced and untraced, the
ZeRO-3 cell on four virtual devices. Control flow and correctness only: a CPU
run gives no device number, and its result never says ``tpu``.

Also the comparisons' power: a wrong trainer and wrong token streams FAIL."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_toy
from benchmark import compare, harness, models
from benchmark.reference import gpt2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = bench_toy.manifest_path()
RUNS = [(cell, trace) for cell in bench_toy.cells() for trace in (0, 1)]  # every toy cell file


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    # the harness places the compile cache unless JAX_COMPILATION_CACHE_DIR is set: keep the
    # session's own directory (conftest.py), never the checkout's .jax_cache
    saved = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    os.environ["TMPDIR"] = str(tmp_path_factory.mktemp("traces"))
    # launcher tests that ran earlier on this xdist worker can leave a rendezvous in the
    # environment (PERF.md section 7), and `initialize` would then try to join eight processes
    leaked = {k: os.environ.pop(k) for k in ("DSTPU_COORDINATOR", "DSTPU_NUM_PROCESSES",
                                             "DSTPU_PROCESS_ID") if k in os.environ}
    cache = {}

    def get(workload, trace, overrides=()):
        key = (workload, trace, tuple(overrides))
        if key not in cache:
            cache[key] = harness.run_cell(TOY, workload, 2 ** 31 + 7, 1.5, bool(trace),
                                          require_tpu=False, overrides=list(overrides))
        return cache[key]

    yield get
    os.environ.update(leaked)
    os.environ.pop("TMPDIR", None)
    if saved is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = saved


@pytest.mark.parametrize("workload,trace", RUNS)
def test_runner_gives_the_contracts_object(results, workload, trace):
    line = results(workload, trace)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"} | (
        {"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert "tpu" not in json.dumps(line).lower()
    device = line["device"]
    assert device["platform"] == "cpu" and device["count"] == bench_toy.cells()[workload]["chips"]
    manifest = harness.load_json(TOY)
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    mine = {m["name"]: m for m in group if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) <= set(mine)
    for name, metric in line["metrics"].items():
        assert metric["unit"] == mine[name]["unit"] and np.isfinite(metric["value"])
    if trace:
        assert device["busy_s"] > 0 and device["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
        assert "compile_s" in line["metrics"]
        # no peak is known for a CPU: a roofline or an MFU is left out, never made up
        assert not any("roofline" in n or "mfu" in n for n in line["metrics"])
    else:
        assert set(line["metrics"]) == set(mine) and line["metrics"]["setup_s"]["value"] > 0


def test_zero3_cell_reports_its_collectives(results):
    metrics = results("toy-train-zero3", 1)["metrics"]
    assert metrics["collective_ms.train"]["value"] > 0
    assert 0 <= metrics["collective_exposed.train"]["value"] <= 100


# the open loop's own readings (PR 56): how long this process once did not run, and, from the program's
# host ledger, which the plain serve runner reads for every serving cell, the share of ticks the host paced
OPEN_LOOP_READINGS = {"generator_late_max_ms.chat": (0.0, 1e4), "host_bound_tick_pct.chat": (0.0, 100.0)}


@pytest.mark.parametrize("name", sorted(OPEN_LOOP_READINGS))
def test_an_open_loop_line_holds_the_freezes_counter_and_the_host_paced_share(results, name):
    low, high = OPEN_LOOP_READINGS[name]
    assert low <= results("toy-chat", 1)["metrics"][name]["value"] <= high
    # the chat cell's metrics list the chat cell alone: a closed loop's line holds none of them
    assert name not in results("toy-batch", 1)["metrics"]


def _compare_line(capsys):
    return next(json.loads(l) for l in capsys.readouterr().out.splitlines()
                if l.startswith('{"phase": "compare"'))


def test_a_wrong_trainer_fails_the_training_comparison(results, capsys):
    line = results("toy-train", 0, ['cell.train.controls=["grads_scaled", "shard_left_out", '
                                    '"double_update"]'])
    assert line["correct"] is True  # the engine passes AND every control failed
    assert _compare_line(capsys)["controls_passed_the_check"] == {
        "grads_scaled": False, "shard_left_out": False, "double_update": False}


def test_the_reference_in_the_precision_below_fails_the_leaf_comparison(results, capsys):
    """The control that a later PR would be tempted by: the float32 reference with fp8 matmul
    operands, in the program's place, at the toy cell that stands for the four-chip one. The
    program's bfloat16 passes the same limits; every wrong trainer fails them too."""
    line = results("toy-train-zero3", 0, ["cell.train.controls=true"])
    assert line["correct"] is True
    said = _compare_line(capsys)
    assert said["controls_passed_the_check"] == {
        "grads_scaled": False, "shard_left_out": False, "double_update": False,
        "lower_precision": False}
    limit = said["grad_leaf_proj_rel_tolerance"]
    assert said["grad_leaf_proj_rel_diff"] < limit / 2
    assert said["controls"]["lower_precision"]["grad_leaf_proj_rel_diff"] > 1.5 * limit
    assert said["grad_norm_rel_tolerance"] is None  # the norms do not tell the two apart: not held


# -- the timed path broken underneath: the rest of a run must say so ------------

def _run_broken(workload):
    return harness.run_cell(TOY, workload, 2 ** 31 + 9, 1.0, False, require_tpu=False)


def test_a_step_that_leaves_the_parameters_unchanged_is_not_correct(results, monkeypatch, capsys):
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam

    real = FusedAdam.update

    def no_update(self, grads, state, params, lr=None):
        updates, state = real(self, grads, state, params, lr)
        return jax.tree.map(jnp.zeros_like, updates), state

    results("toy-train", 0)  # the fixture's environment (compile cache, TMPDIR) is in place
    monkeypatch.setattr(FusedAdam, "update", no_update)
    line = _run_broken("toy-train")
    assert line["correct"] is False and line["attempted"] >= 1
    said = json.loads(capsys.readouterr().err.strip().splitlines()[-1])  # standard error's last line
    assert said["phase"] == "compare" and said["correct"] is False
    assert said["max_loss_diff"] > said["loss_abs_tolerance"]


@pytest.mark.parametrize("feed,correct", [("distinct", False), ("same", True)])
def test_a_step_that_counts_one_micro_batch_twice_is_seen_on_distinct_rows(results, monkeypatch,
                                                                            feed, correct):
    """An accumulation that takes its first micro-batch for every micro-step (one counted
    twice, one dropped): not correct where the compared steps feed rows that all differ, and
    invisible where one micro-batch is fed at every micro-step."""
    import itertools

    from deepspeed_tpu.runtime.engine import TpuEngine

    real = TpuEngine.train_batch
    results("toy-train-zero3", 0)
    monkeypatch.setattr(TpuEngine, "train_batch",
                        lambda self, data_iter=None: real(self, itertools.repeat(next(data_iter))))
    line = harness.run_cell(TOY, "toy-train-zero3", 2 ** 31 + 9, 1.0, False, require_tpu=False,
                            overrides=[f"cell.train.compare.micro_batches={feed}"])
    assert line["correct"] is correct


def test_a_token_altered_where_it_is_produced_is_not_correct(results, monkeypatch):
    from deepspeed_tpu.serving import ServingEngine

    real = ServingEngine.reap

    def reap(self):
        done = real(self)
        for request in done.values():
            if len(request.tokens):  # the last token: no later position is scored on it
                request.tokens[-1] = (int(request.tokens[-1]) + 1) % 503
        return done

    results("toy-chat", 0)
    monkeypatch.setattr(ServingEngine, "reap", reap)
    line = _run_broken("toy-chat")
    assert line["correct"] is False and line["failed"] == 0


def test_the_reference_trains_once_the_engine_is_gone(results, capsys):
    results("toy-train", 0, ["cell.note=a run of its own, so that its lines are printed here"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith('{"phase"')]
    compare_line = next(l for l in lines if l["phase"] == "compare")
    # state, moments and accumulators are freed before the float32 reference takes the chip: the
    # peak the harness read after the window is the engine's, and setup_s holds no reference
    assert compare_line["live_array_bytes_after_engine_release"] < 1024
    assert compare_line["reference_s"] > 0


def test_the_command_line_has_no_way_to_run_another_cell_under_a_cells_name(capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "toy-train", "--seed", "1", "--seconds", "1",
                      "--set", "cell.train.controls=true"], 0.0)
    assert e.value.code == 2 and "unrecognized arguments" in capsys.readouterr().err


def test_correct_does_not_read_trace_or_seconds():
    import inspect

    from benchmark.runners import serve, train

    for fn in (compare.train_verdict, compare.serve_verdict, compare.train_reference):
        assert not {"trace", "seconds"} & set(inspect.signature(fn).parameters)
    for module in (train, serve):
        assert "trace" not in inspect.signature(module.Runner.finish).parameters
        assert "trace" not in inspect.signature(module.Runner.setup).parameters


def test_refuses_the_cpu_and_too_few_chips_and_unknown_cells(results):
    with pytest.raises(harness.BenchmarkError, match="not a TPU"):
        harness.run_cell(TOY, "toy-train", 1, 1.0, False)
    with pytest.raises(harness.BenchmarkError, match="no workload"):
        harness.run_cell(TOY, "no-such-cell", 1, 1.0, False, require_tpu=False)


def test_overrides_reach_the_files():
    target = {"traffic": {"arrivals": {"rate_per_s": 1.0}}, "cell": {}}
    harness.apply_overrides(target, ["traffic.arrivals.rate_per_s=2.5", "cell.train.controls=true",
                                     "cell.note=plain"])
    assert target == {"traffic": {"arrivals": {"rate_per_s": 2.5}},
                      "cell": {"train": {"controls": True}, "note": "plain"}}


# -- the reference against itself and against wrong outputs -------------------

@pytest.fixture(scope="module")
def toy_model():
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

    return TransformerModel(TransformerConfig(vocab_size=211, hidden_size=64, num_layers=2,
                                              num_heads=4, max_seq_len=64))


def test_reference_forward_matches_the_model_in_float32(toy_model):
    params = toy_model.init(jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, 211, (2, 32)).astype(np.int32)
    at = np.tile(np.arange(32, dtype=np.int32), (2, 1))
    ours = gpt2.logits_at(params, tokens, at, 4)
    theirs = toy_model.apply(params, tokens)
    assert np.allclose(np.asarray(ours), np.asarray(theirs, np.float32), atol=2e-4)


def test_reference_loss_and_grads_match_the_models(toy_model):
    params = toy_model.init(jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 211, (4, 32)), jnp.int32)
    loss, grads = gpt2.loss_and_grads(params, tokens, 4, rows_per_pass=2)
    want, want_g = jax.value_and_grad(lambda p: toy_model.loss(p, {"input_ids": tokens}))(params)
    assert float(loss) == pytest.approx(float(want), abs=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_g)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("fault", gpt2.FAULTS)
def test_each_training_fault_leaves_the_tolerances(toy_model, fault):
    tokens = np.random.RandomState(2).randint(0, 211, (4, 32)).astype(np.int32)
    opt = dict(lr=1e-2, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.01)
    key = jax.random.PRNGKey(2)
    run = lambda f: compare.train_reference(gpt2, toy_model.init, key, tokens, 4, 3, opt,
                                            jax.devices()[:1], rows_per_pass=2, fault=f)
    good, bad = run(None), run(fault)
    tol = dict(loss_abs=0.005, grad_norm_rel=0.01, min_fall=0.01)
    assert compare.train_verdict(good["losses"], good["grad_norms"][0], good, tol)[0]
    ok, fields = compare.train_verdict(good["losses"], good["grad_norms"][0], bad, tol)
    assert not ok, fields
    assert good["checksum"] == bad["checksum"]  # same start: only the trainer differs


def test_fp8_rounds_the_value_and_passes_the_gradient_through():
    x = jnp.asarray(np.random.RandomState(4).randn(64, 32), jnp.float32)
    y = compare.fp8(x)
    err = np.abs(np.asarray(y - x)) / np.abs(np.asarray(x))
    assert float(jnp.max(jnp.abs(y))) == pytest.approx(float(jnp.max(jnp.abs(x))), rel=1e-6)
    assert 0.01 < np.median(err) < 2 ** -4 + 1e-6  # three bits of mantissa, no finer and no coarser
    assert len(np.unique(np.asarray(y))) < 2 * 2 ** 7 + 1
    grad = jax.grad(lambda a: jnp.sum(compare.fp8(a) * x))(x)
    assert np.array_equal(np.asarray(grad), np.asarray(x))


def test_leaf_gaps_are_measured_against_the_leaf_or_the_median_leaf():
    tree = {"a": jnp.full((4, 8), 2.0), "b": jnp.full((8,), 1e-6), "c": jnp.full((3,), 1.0)}
    read = jax.device_get(jax.jit(compare.leaf_readings)(tree))
    assert set(read) == {"['a']", "['b']", "['c']"} and float(read["['a']"][0]) == pytest.approx(
        2.0 * np.sqrt(32))
    assert abs(float(read["['a']"][1])) <= 2.0 * 32 and float(read["['a']"][1]) % 4.0 == 0.0
    theirs = {k: (float(n), float(p)) for k, (n, p) in read.items()}
    b = theirs["['b']"]
    mine = dict(theirs, **{"['b']": (3 * b[0], b[1])})  # an all but zero leaf, tripled
    norm_gap, proj_gap, gaps = compare.worst_leaf_gaps(mine, theirs)
    assert norm_gap == pytest.approx(2 * b[0] / np.sqrt(3.0)) and proj_gap == 0.0  # against the median leaf
    half = {k: (n / 2, p / 2) for k, (n, p) in theirs.items()}
    assert compare.worst_leaf_gaps(theirs, theirs)[:2] == (0.0, 0.0)
    assert compare.worst_leaf_gaps(half, theirs)[0] == pytest.approx(0.5)
    with pytest.raises(ValueError):
        compare.worst_leaf_gaps({"['a']": (1.0, 1.0)}, theirs)


def test_adamw_matches_the_programs_optimizer():
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam

    rs = np.random.RandomState(3)
    p = {"a": jnp.asarray(rs.randn(5, 3), jnp.float32), "b": jnp.asarray(rs.randn(7), jnp.float32)}
    g = jax.tree.map(lambda x: 0.1 * x + 0.01, p)
    opt = FusedAdam(lr=1e-3, weight_decay=0.01, adam_w_mode=True)
    state = opt.init(p)
    ours_p, m, v = p, state.exp_avg, state.exp_avg_sq
    for step in (1, 2):
        updates, state = opt.update(g, state, p)
        p = jax.tree.map(jnp.add, p, updates)
        ours_p, m, v = gpt2.adamw(ours_p, g, m, v, float(step), 1e-3, 0.9, 0.999, 1e-8, 0.01)
    for a, b in zip(jax.tree.leaves(ours_p), jax.tree.leaves(p)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_serving_comparison_passes_greedy_streams_and_fails_wrong_ones(toy_model):
    config = {"model": {"n_layer": 2, "n_head": 4}}
    assert gpt2.arch(config) == 4
    params = compare.seed_params(toy_model, 5, lambda p: models.sharpen(p, config, 3.0))
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 211, n).astype(np.int32) for n in (9, 14, 20, 27)]
    streams = []
    for p in prompts:  # greedy decoding by the reference itself: the right answer
        seq = list(p)
        for _ in range(6):
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            logits = gpt2.logits_at(params, toks, np.array([[len(seq) - 1]], np.int32), 4)
            seq.append(int(np.argmax(np.asarray(logits)[0, 0])))
        streams.append(np.array(seq[len(p):], np.int32))
    tol = dict(margin=0.25, share_within=0.99, control_share=0.2, distinct_per_request=1)  # 24 toy tokens
    ok, fields = compare.serve_verdict(gpt2, params, prompts, streams, 4, 5, tol, width=64, new_max=6)
    assert ok and fields["share_within_margin"] == 1.0 and fields["worst_gap"] == 0.0, fields
    wrong = [rs.randint(0, 211, 6).astype(np.int32) for _ in prompts]
    ok, fields = compare.serve_verdict(gpt2, params, prompts, wrong, 4, 5, tol, width=64, new_max=6)
    assert not ok and fields["share_within_margin"] < 0.5
    shifted = [np.roll(s, 1) for s in streams]  # the right tokens, one position off
    assert not compare.serve_verdict(gpt2, params, prompts, shifted, 4, 5, tol, width=64, new_max=6)[0]


def test_warm_plan_reaches_every_tick_program_the_lengths_can():
    from deepspeed_tpu.inference.decoding import read_bucket

    from benchmark.runners.serve import warm_plan

    for prompt_range, new_max in (((16, 512), 192), ((128, 768), 256)):
        plan = list(warm_plan(prompt_range, 1024, 128))
        reached = set()
        for anchor, new, shorts in plan:
            assert anchor + new <= 1024 and new >= 1
            for extent in range(anchor + 1, anchor + new + 1):
                reached.add(read_bucket(extent, 1024, 128))
        wanted = {read_bucket(e, 1024, 128)
                  for e in range(prompt_range[0] + 1, min(1024, prompt_range[1] + new_max) + 1)}
        assert wanted <= reached
        widths = {read_bucket(n, 128, 16) for _, _, shorts in plan for n in shorts}
        assert widths == {16, 32, 64, 128}
