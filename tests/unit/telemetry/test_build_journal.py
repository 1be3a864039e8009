"""The build journal (telemetry/compile_log.py): with the hub OFF every
program an engine builds leaves one entry with its first dispatch split
into trace / lower / backend; the caches hold bare jitted callables after
that dispatch; phases tile the time; and the number of programs a toy
serving warm-up builds is pinned, so a PR that adds a program to a warm-up
fails here, on the CPU, before it costs ``setup_s`` on the chip."""

import collections
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.telemetry import compile_log, read_trace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools"))
import ds_trace_report  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "mini_trace.jsonl")
JIT_TYPE = type(jax.jit(lambda x: x))
ONE_DEVICE = {"mesh": {"shape": {"data": 1, "tensor": 1}}}


@pytest.fixture(scope="module")
def toy():
    comm.destroy()
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=64, dtype="float32")
    model = TransformerModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _batcher(toy, telemetry=None, config=(), **kw):
    model, params = toy
    config = dict({"dtype": "float32"}, **ONE_DEVICE, **dict(config))
    if telemetry:
        config["telemetry"] = telemetry
    kw.setdefault("max_slots", 4)
    kw.setdefault("cache_len", 64)
    return ContinuousBatchingEngine(model, params=params, config=config, **kw)


def _serve(cb, prompts=(5, 20), new=6):
    for n in prompts:
        cb.submit(np.arange(1, n + 1, dtype=np.int32), max_new_tokens=new)
    while cb.has_work():
        cb.step()
    cb.finished()


def _since(t0):
    return [e for e in compile_log.journal() if e["t"] >= t0]


def _sound(entry):
    assert entry["wall_ms"] > 0
    assert entry["wall_ms"] >= entry["trace_ms"] + entry["lower_ms"] + entry["backend_ms"] - 1e-3
    assert entry["other_ms"] >= 0 and entry["backend_ms"] >= entry["load_ms"] >= 0
    assert entry["trace_ms"] > 0 and entry["lower_ms"] > 0 and entry["backend_ms"] > 0
    assert "hbm" not in entry  # the CPU keeps no memory_stats: nothing is made up


# -- serving, hub off ------------------------------------------------------
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
def test_serving_with_the_hub_off_journals_every_program_once(toy, fused):
    t0 = time.monotonic()
    cb = _batcher(toy, fused_prefill=fused)
    assert not cb.telemetry.enabled
    _serve(cb)
    entries = _since(t0)
    for e in entries:
        _sound(e)
        assert e["phase"] == "running" and e["tick"] >= 1
    by_family = collections.Counter(e["family"] for e in entries)
    pool = cb._pools[0]
    # one entry a distinct tick program, one for set_row, one a prefill program
    assert by_family["pool_tick"] == len(pool.tick_fns) >= 1
    assert len({e["key"] for e in entries if e["family"] == "pool_tick"}) == len(pool.tick_fns)
    assert by_family["row_update"] == 1
    if not fused:
        assert by_family["prefill_bucket"] >= 1 and by_family["insert_bucket"] >= 1
    stats = cb.tick_stats()
    assert stats["programs_built"] == len(entries)
    assert stats["program_build_ms"] == pytest.approx(sum(e["wall_ms"] for e in entries), abs=0.01)


def test_after_its_first_dispatch_the_table_holds_the_bare_program(toy):
    cb = _batcher(toy)
    pool = cb._pools[0]
    armed = cb._tick_fn(pool, None, chunk=None)
    assert type(armed) is not JIT_TYPE and armed.lower is not None   # AOT surfaces forwarded
    _serve(cb)
    assert pool.tick_fns and all(type(fn) is JIT_TYPE for fn in pool.tick_fns.values())
    for family in getattr(cb, "_fn_cache", {}).values():
        for value in family.values():
            assert type(value[0] if isinstance(value, tuple) else value) is JIT_TYPE
    t0, built = time.monotonic(), cb.tick_stats()["programs_built"]
    cb.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=50)
    ticks = 0
    while cb.has_work():
        cb.step()
        ticks += 1
    assert ticks >= 50
    assert _since(t0) == [] and cb.tick_stats()["programs_built"] == built


def test_a_rebuilt_engines_second_build_of_a_key_is_a_recompile(toy):
    # per PROCESS: a pool of 7 x 56 is a tick key no other test of this worker builds
    # (``row_update (7,)`` may well have been built before: it is not asserted new)
    t0 = time.monotonic()
    _serve(_batcher(toy, max_slots=7, cache_len=56))
    first = {(e["family"], e["key"]): e["recompile"] for e in _since(t0)}
    ticks = [ident for ident in first if ident[0] == "pool_tick"]
    assert ticks and not any(first[ident] for ident in ticks)
    t1 = time.monotonic()
    _serve(_batcher(toy, max_slots=7, cache_len=56))
    again = {(e["family"], e["key"]): e["recompile"] for e in _since(t1)}
    assert set(again) == set(first) and all(again.values())


# -- training --------------------------------------------------------------
@pytest.fixture(scope="module")
def trained():
    comm.destroy()
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                            max_seq_len=32, dtype="float32")
    t0 = time.monotonic()
    engine = deepspeed_tpu.initialize(model=TransformerModel(cfg), config={
        "train_micro_batch_size_per_gpu": 8, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})[0]
    sds = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    micro_args = (sds(engine.params), sds(engine.grad_acc),
                  {"input_ids": jax.ShapeDtypeStruct((8, 32), jnp.int32)},
                  sds(jax.random.PRNGKey(0)), scalar, scalar)
    armed_text = engine._micro_fn.lower(*micro_args).as_text()
    armed_type = type(engine._micro_jits[None])
    batch = {"input_ids": np.random.RandomState(0).randint(0, 128, (8, 32)).astype(np.int32)}
    feed = iter(lambda: batch, None)
    for _ in range(3):
        engine.train_batch(feed)
    yield dict(engine=engine, entries=_since(t0), armed_text=armed_text, armed_type=armed_type,
               settled_text=engine._micro_fn.lower(*micro_args).as_text())
    comm.destroy()


def test_training_journals_micro_and_apply_once_with_the_global_step(trained):
    entries = [e for e in trained["entries"] if e["family"].startswith("train_")]
    assert sorted(e["family"] for e in entries) == ["train_apply", "train_micro"]
    for e in entries:
        _sound(e)
        assert e["tick"] == 0          # both first dispatched inside global step 0
    assert trained["engine"].global_steps == 3


def test_a_steady_micro_step_runs_the_bare_program(trained):
    engine = trained["engine"]
    assert trained["armed_type"] is not JIT_TYPE
    assert type(engine._micro_jits[None]) is JIT_TYPE and type(engine._micro_fn) is JIT_TYPE
    assert type(engine._apply_fn) is JIT_TYPE


# -- the programs are the programs they were ---------------------------------
def test_the_micro_step_lowers_to_the_same_text_armed_and_settled(trained):
    assert trained["armed_text"] == trained["settled_text"]


def test_the_tick_lowers_to_the_same_text_with_and_without_the_journal(toy):
    cb = _batcher(toy)
    pool = cb._pools[0]
    args = cb._tick_arg_structs(pool, None)
    armed = cb._tick_fn(pool, None, chunk=None).lower(*args).as_text()
    bare = compile_pool_tick_fn(
        cb.mesh, cb.cfg, cb._eng.param_shardings, pool.n_slots, pool.length,
        cb.tokens_per_tick, cb.temperature, cb.top_k, cb.top_p,
        eos_token_id=cb.eos_token_id, read_len=None, chunk=None,
        donate=cb.donate_cache)[0]
    assert type(bare) is JIT_TYPE
    assert bare.lower(*args).as_text() == armed


# -- the hub, when on ----------------------------------------------------------
def test_with_the_hub_on_the_compile_event_carries_the_old_fields_and_the_new(toy, tmp_path):
    trace = tmp_path / "hub.jsonl"
    cb = _batcher(toy, telemetry={"enabled": True, "trace_file": str(trace)})
    _serve(cb)
    cb.telemetry.close()
    events = [e for e in read_trace(str(trace)) if e.get("kind") == "compile_event"]
    assert {e["family"] for e in events} >= {"pool_tick", "row_update"}
    for e in events:
        assert isinstance(e["key"], str) and e["recompile"] in (True, False)
        assert e["compile_ms"] >= e["trace_ms"] + e["lower_ms"] + e["backend_ms"] - 1e-3
        assert e["other_ms"] >= 0 and e["cache_hit"] in (True, False)
        assert e["phase"] == "running" and e["tick"] >= 1
    dump = cb.telemetry.registry.dump()
    assert dump["counters"]["compile_event_total{family=pool_tick}"] == len(cb._pools[0].tick_fns)
    for stage in ("trace", "lower", "backend", "other"):
        assert dump["histograms"][f"build_stage_ms{{family=pool_tick,stage={stage}}}"]["count"] \
            == dump["histograms"]["compile_ms{family=pool_tick}"]["count"]
    table = ds_trace_report.compile_table(events)
    pool_tick = table["families"]["pool_tick"]
    assert pool_tick["compile_ms"] >= pool_tick["trace_ms"] + pool_tick["lower_ms"] > 0
    assert "trace_ms" in ds_trace_report.format_compile_table(table)


def test_the_report_reads_a_trace_written_before_the_split():
    events, _ = ds_trace_report.load_events(FIXTURE)
    table = ds_trace_report.compile_table(events)
    assert table["families"]["pool_tick"] == {"count": 2, "compile_ms": 815.5, "recompiles": 1}
    text = ds_trace_report.format_compile_table(table)
    assert "compile_ms" in text and "trace_ms" not in text


# -- phases and the unwrapped rest -----------------------------------------
def test_mark_orders_phases_and_their_seconds_sum_to_the_span():
    t0 = time.monotonic()
    with compile_log.phase("test_place"):
        time.sleep(0.02)
        with compile_log.phase("test_pools"):
            time.sleep(0.02)
        time.sleep(0.01)
    time.sleep(0.02)                      # the caller's own time ...
    with compile_log.phase("test_precompile"):  # ... because a phase follows it
        time.sleep(0.02)
    time.sleep(0.01)
    since = time.monotonic()
    s = compile_log.summary(since_t=since)
    names = list(s["phases"])   # in order of first appearance; these three are this test's own
    assert names.index("test_place") < names.index("test_pools") < names.index("test_precompile")
    assert "caller" in names and names[-1] == "running"
    assert s["phases"]["running"]["seconds"] >= 0.01   # after the last phase: not the caller's
    assert s["phases"]["test_place"]["seconds"] >= 0.03
    assert s["phases"]["test_pools"]["seconds"] >= 0.02
    assert s["phases"]["test_precompile"]["seconds"] >= 0.02
    assert sum(row["seconds"] for row in s["phases"].values()) == pytest.approx(s["span_s"], abs=1e-6)
    assert s["remainder_s"] == pytest.approx(0.0, abs=1e-6)
    assert s["since_t"] == since and s["span_s"] >= since - t0
    if "before_import_s" in s:
        assert s["phases"]["before_import"]["seconds"] == pytest.approx(s["before_import_s"])
    assert "hbm" not in s and s["peak_raisers"] == []   # the CPU keeps no memory_stats


def test_builds_with_no_entry_open_land_in_unwrapped_and_after_since_t_apart():
    with compile_log.phase("params_place"):
        jax.jit(lambda x: jnp.cos(x) * 3.25)(jnp.ones((7, 3)))      # the caller's own jit
    since = time.monotonic()
    s = compile_log.summary(since_t=since)
    row = s["phases"]["params_place"]
    assert row["unwrapped_s"] > 0 and row["rest_s"] == pytest.approx(
        row["seconds"] - row["wall_s"] - row["unwrapped_s"])
    built = compile_log.record_build(jax.jit(lambda x: jnp.sin(x) * 1.75), "toy_family", (7, 3),
                                     tick=lambda: 41)
    built(jnp.ones((7, 3)))
    after = compile_log.summary(since_t=since)["built_after"]
    assert [(b["family"], b["key"], b["tick"]) for b in after][-1] == ("toy_family", "(7, 3)", 41)
    assert after[-1]["at_s"] >= 0 and after[-1]["wall_ms"] > 0
    assert "toy_family" not in compile_log.summary(since_t=since)["families"]
    assert "toy_family" in compile_log.summary()["families"]


# -- the pinned count --------------------------------------------------------
# the programs a toy serving warm-up builds (precompile, then one request a
# prompt bucket), by family: a PR that changes a number here changes what a
# set-up compiles on the chip — say so in PERF.md, and measure setup_s
TIGHT = {"kv_tight_read": True, "kv_read_floor": 16}
WARM_UP_PROGRAMS = {
    "fused": (dict(), {"pool_tick": 2, "row_update": 1}),
    "fused_tight_read": (dict(config=TIGHT), {"pool_tick": 6, "row_update": 1}),
    "unfused": (dict(fused_prefill=False),
                {"pool_tick": 1, "row_update": 1, "prefill_bucket": 3, "insert_bucket": 3}),
    "unfused_tight_read": (dict(fused_prefill=False, config=TIGHT),
                           {"pool_tick": 3, "row_update": 1, "prefill_bucket": 3,
                            "insert_bucket": 3}),
    "burst": (dict(tokens_per_tick=4),
              {"pool_tick": 1, "row_update": 1, "prefill_bucket": 3, "insert_bucket": 3}),
}


@pytest.mark.parametrize("name", sorted(WARM_UP_PROGRAMS))
def test_the_toy_serving_warm_up_builds_a_pinned_number_of_programs(toy, name):
    kw, pinned = WARM_UP_PROGRAMS[name]
    t0 = time.monotonic()
    cb = _batcher(toy, prefill_chunk=32, **kw)
    cb.precompile_tick_programs()
    _serve(cb, prompts=(5, 20, 40), new=4)
    entries = _since(t0)
    assert dict(collections.Counter(e["family"] for e in entries)) == pinned
    # every tick program is built by the precompile, none by the requests
    assert [e["family"] for e in entries if e["phase"] == "precompile"] \
        == ["pool_tick"] * pinned["pool_tick"]
    assert cb.tick_stats()["programs_built"] == len(entries)


# -- the tool ----------------------------------------------------------------
def test_cell_journal_runs_a_toy_cell_through_the_unedited_harness(tmp_path):
    import json
    import subprocess

    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    sys.path.insert(0, os.path.join(repo, "tests", "benchmark"))
    import bench_toy

    out = tmp_path / "journal.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "cell_journal.py"), "--rehearse",
         "--manifest", bench_toy.manifest_path(), "--workload", "toy-chat",
         "--seed", str(2 ** 31 + 11), "--seconds", "1.5", "--out", str(out)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["hub"] == 0
    s = line["journal"]
    # the window's opening was noted: the span ends where setup_s ends, to the process's start
    assert s["span_s"] == pytest.approx(line["metrics"]["setup_s"]["value"], abs=1.0)
    assert {"caller", "params_place", "pools", "running"} <= set(s["phases"])
    assert sum(row["seconds"] for row in s["phases"].values()) == pytest.approx(s["span_s"], abs=1e-3)
    assert s["families"]["pool_tick"]["programs"] >= 2 and s["families"]["row_update"]["programs"] == 1
    assert s["phases"]["caller"]["unwrapped_s"] > 0          # seeding the weights: the caller's own jits
    assert "== set-up (build journal) ==" in proc.stderr
    saved = json.loads(out.read_text())
    assert len(saved["journal"]) >= 3 and saved["summary"]["since_t"] == s["since_t"]
