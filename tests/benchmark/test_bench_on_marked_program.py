"""The benchmark as the parent left it, on a program that now marks itself
(PR 24: lifecycle timestamps, per-kind tick counters, ``dstpu:`` host spans,
named scopes). PR 24 adds no reader to the benchmark: a line must hold what
the parent's line held, computed from the same things, and nothing of the
program's new marks may leak into it. Toy cells on the CPU: which metrics a
line holds, never a device number."""

import os

import jax
import pytest

import bench_toy
from benchmark import harness
from benchmark.reduce import reductions as R
from benchmark.reduce import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOY = bench_toy.manifest_path()
RECORDED = os.path.join(ROOT, "benchmark", "reduce", "recorded_1chip_toy_train.json.gz")

PARENT_LINE = [  # (cell, a per-layer metric the parent's traced line held there)
    ("toy-chat", "compile_s"), ("toy-chat", "tick_dispatch_ms.chat"),
    ("toy-chat", "tick_block_ms.chat"), ("toy-chat", "queue_wait_p95_ms.chat"),
    ("toy-chat", "peak_hbm_gb.chat"),
    ("toy-batch", "compile_s"), ("toy-batch", "slot_use.batch"),
    ("toy-batch", "peak_hbm_gb.batch"),
]


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    saved = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = jax.config.jax_compilation_cache_dir
    os.environ["TMPDIR"] = str(tmp_path_factory.mktemp("traces"))
    cache = {}

    def get(workload, trace):
        if (workload, trace) not in cache:
            cache[workload, trace] = harness.run_cell(
                TOY, workload, 2 ** 31 + 24, 1.5, bool(trace), require_tpu=False)
        return cache[workload, trace]

    yield get
    os.environ.pop("TMPDIR", None)
    if saved is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = saved


@pytest.mark.parametrize("cell,name", PARENT_LINE)
def test_traced_line_holds_what_the_parents_line_held(lines, cell, name):
    line = lines(cell, 1)
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"][name]["value"] >= 0


@pytest.mark.parametrize("cell", ["toy-chat", "toy-batch"])
def test_a_line_holds_no_metric_the_manifest_does_not_list(lines, cell):
    listed = {m["name"] for m in harness.load_json(TOY)["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert set(lines(cell, 1)["metrics"]) <= listed


def test_untraced_lines_hold_end_to_end_metrics_only(lines):
    line = lines("toy-chat", 0)
    assert set(line["metrics"]) == {"ttft_p50_ms", "gap_p95_ms", "setup_s"}


def test_idle_gaps_are_charged_to_the_benchmarks_own_spans_only(lines):
    """The program writes ``dstpu:`` annotations into the same xplane; the
    benchmark's reader keeps to ``bench:`` (its prefix is not PR 24's to widen)."""
    for cell in ("toy-chat", "toy-batch"):
        for name, _ in lines(cell, 1)["breakdown"]["idle_gaps"]:
            assert name == "(no span)" or name.startswith("bench:"), name


# -- the reductions the benchmark had read the recorded trace as before ---------

PARENT_VALUES = {  # computed with the parent commit's benchmark/ (PR 23) on the same file
    "busy_s": 0.02319366, "micro_ms_per_call": 3.4886275, "micro_ms": 20.931765,
    "apply_ms_per_call": 0.7692873333333333, "flash_op_ms": 2.296225, "fusion_op_ms": 19.123875,
}


@pytest.mark.parametrize("name", sorted(PARENT_VALUES))
def test_existing_reductions_read_the_recorded_trace_as_the_parent_did(name):
    tr = xplane.load(RECORDED)
    got = {
        "busy_s": lambda: R.busy_s(tr),
        "micro_ms_per_call": lambda: R.module_ms_per_call(tr, "^jit_micro_fn"),
        "micro_ms": lambda: R.module_ms(tr, "^jit_micro_fn"),
        "apply_ms_per_call": lambda: R.module_ms_per_call(tr, "^jit_apply_fn"),
        "flash_op_ms": lambda: R.op_ms(tr, "^custom-call:tpu_custom_call "),
        "fusion_op_ms": lambda: R.op_ms(tr, "^fusion "),
    }[name]()
    assert got == pytest.approx(PARENT_VALUES[name], rel=1e-12)


def test_existing_rankings_read_the_recorded_trace_as_the_parent_did():
    tr = xplane.load(RECORDED)
    assert R.collective_ms(tr) == (None, None) and R.span_window(tr) is None
    top = R.top_ops(tr, 3)
    assert [n for n, _ in top] == ["fusion fusion.231", "fusion fusion.186",
                                   "fusion exponential_reduce_fusion"]
    assert [s for _, s in top] == pytest.approx([0.00568566, 0.003774068, 0.0019796])
    assert R.idle_gaps(tr, 3) == [["(no span)", pytest.approx(0.00926247)]]
