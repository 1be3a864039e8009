"""The program's host ledger as the serving runner reads it (PR 53's
``runners/serve_ledger.py``; since PR 56 ``serve.ledger_observations``, read by the
plain ``serve`` runner for every serving cell, and ``serve_ledger`` is that runner
under its old name) on the toy chat cell, through the harness's Python entry point
on the CPU: which observations it adds, that the line's metrics are the cell's, and
that the ledger's rows sum to the window, never a device number. The same runner on
a ``tick_stats()`` without the ledger's keys (an older program) adds nothing it can
not read."""

import os

import jax
import pytest

import bench_toy
from benchmark import harness
from benchmark.runners import serve_ledger

TOY = bench_toy.manifest_path()
CELL = "toy-chat"
PER_STEP = ["step_schedule_ms", "tick_admit_ms", "tick_attribute_ms", "step_emit_ms",
            "step_between_ms", "step_other_ms"]
SHARES = ["empty_share_pct", "starved_share_pct", "host_bound_tick_pct"]
PARENTS = ["compile_s", "tick_dispatch_ms.chat", "tick_block_ms.chat", "queue_wait_p95_ms.chat",
           "prefill_wait_p95_ms.chat", "prefill_p95_ms.chat", "fused_tick_block_ms.chat",
           "plain_tick_block_ms.chat", "fused_tick_share_pct.chat", "prefill_q_depth_mean.chat",
           "peak_hbm_gb.chat"]   # tick_device_ms.chat reads a TPU's program events: not on the CPU
LEDGER_KEYS = ("empty_ms", "between_steps_ms", "schedule_ms", "attribute_ms", "emit_ms",
               "step_other_ms", "admit_ms", "starved_ms", "inflight_empty_ms",
               "ticks_ready_at_retire")


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    saved = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    os.environ["TMPDIR"] = str(tmp_path_factory.mktemp("traces"))
    cache = {}

    def get(stripped):
        if stripped not in cache:
            from deepspeed_tpu.serving import ServingEngine

            sound, seen = ServingEngine.tick_stats, []

            def tick_stats(self):
                s = sound(self)
                return {k: v for k, v in s.items() if k not in LEDGER_KEYS} if stripped else s

            measure = serve_ledger.Runner._measure

            def noting(self, *args):
                result = measure(self, *args)
                seen.append(dict(result["obs"]))
                return result

            ServingEngine.tick_stats, serve_ledger.Runner._measure = tick_stats, noting
            try:
                line = harness.run_cell(TOY, CELL, 2 ** 31 + 53, 1.5, True, require_tpu=False,
                                        overrides=["cell.runner=serve_ledger"])
            finally:
                ServingEngine.tick_stats, serve_ledger.Runner._measure = sound, measure
            cache[stripped] = line, seen[0]
        return cache[stripped]

    yield get
    os.environ.pop("TMPDIR", None)
    if saved is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = saved


@pytest.mark.parametrize("name", PARENTS)
def test_a_traced_line_holds_the_parent_runners_metrics(lines, name):
    line, _ = lines(False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][name]["value"] >= 0


@pytest.mark.parametrize("name", PER_STEP)
def test_the_ledgers_rows_are_observed_as_ms_a_step(lines, name):
    _, obs = lines(False)
    assert 0 <= obs[name] < 1e3


@pytest.mark.parametrize("share", SHARES)
def test_the_shares_are_observed_as_shares(lines, share):
    _, obs = lines(False)
    assert 0 <= obs[share] <= 100


def test_the_rows_sum_to_the_window(lines):
    line, obs = lines(False)
    assert abs(obs["ledger_residual_pct"]) < 1
    assert obs["starved_share_pct"] <= 100 - obs["empty_share_pct"] + 1
    # the admission loop lies inside the dispatch, and there are at least as many steps as ticks
    assert obs["tick_admit_ms"] <= line["metrics"]["tick_dispatch_ms.chat"]["value"]


def test_a_program_without_the_ledgers_keys_gives_the_parents_line(lines):
    line, obs = lines(True)
    assert line["correct"] is True and line["failed"] == 0
    assert set(PARENTS) <= set(line["metrics"])
    assert all(obs[name] is None for name in PER_STEP + SHARES + ["ledger_residual_pct"])


def test_the_old_name_is_the_plain_runner():
    from benchmark.runners import serve

    assert serve_ledger.Runner is serve.Runner
    assert serve_ledger.ledger_observations is serve.ledger_observations
    assert serve_ledger.ROWS == serve.ROWS and len(serve.ROWS) == 8
