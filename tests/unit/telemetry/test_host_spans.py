"""Program spans in the profiler's own trace (ISSUE 24, parts C and E):
``dstpu:`` ``TraceAnnotation``s from inside the serving and training loops,
the ``clock_sync`` marker that ties the JSONL spans to the xplane's axis, and
the one entry point that starts every capture. CPU profiler captures of toy
engines: what is recorded and where, never a time."""

import collections
import glob
import importlib.util
import json
import os

import numpy as np
import pytest

import jax

from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.telemetry import Telemetry, TelemetryConfig
from deepspeed_tpu.telemetry.timeline import CLOCK_SYNC_PREFIX

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

SERVE_SPANS = ("dstpu:serve.schedule", "dstpu:tick.admit", "dstpu:tick.dispatch.fused",
               "dstpu:tick.dispatch.plain", "dstpu:tick.retire")
TRAIN_SPANS = ("dstpu:train.next_batch", "dstpu:train.micro_dispatch",
               "dstpu:train.apply_dispatch", "dstpu:train.loss_fetch")


def host_events(logdir):
    """{name: [(start_ns, dur_ns)]} of the capture's ``dstpu:`` annotations."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    out = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dstpu:"):
                    out[e.name].append((int(e.start_ns), int(e.duration_ns)))
    return out, path


def _toy_model():
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128, dtype="float32")
    return TransformerModel(cfg)


def _serve_some(srv, n=3):
    rs = np.random.RandomState(0)
    for size in (20, 5, 9)[:n]:
        srv.submit(rs.randint(0, 128, (size,)).astype(np.int32), max_new_tokens=5)
    while srv.has_work():
        srv.step()
    srv.reap()


@pytest.fixture(scope="module")
def serve_capture(tmp_path_factory):
    """One serving run under a capture started through the hub, with the
    hub live: the xplane, the JSONL, and an uncaptured run before it."""
    comm.destroy()
    tmp = tmp_path_factory.mktemp("serve_capture")
    model = _toy_model()
    params = model.init(jax.random.PRNGKey(0))
    trace_file = str(tmp / "trace.jsonl")
    cb = ContinuousBatchingEngine(
        model, params=params, max_slots=3, cache_len=64, prefill_chunk=16,
        config={"dtype": "float32", "kv_read_floor": 16,
                "telemetry": {"enabled": True, "trace_file": trace_file}})
    srv = ServingEngine(cb)
    _serve_some(srv)                       # uncaptured: warms the programs, must leave no span
    logdir = str(tmp / "xplane")
    cb.telemetry.start_capture(logdir)
    _serve_some(srv)
    cb.telemetry.stop_capture()
    srv.close()
    events, path = host_events(logdir)
    with open(trace_file) as fh:
        jsonl = [json.loads(line) for line in fh]
    return dict(events=events, xplane=path, logdir=logdir, trace_file=trace_file, jsonl=jsonl)


def test_serving_loop_spans_are_in_the_xplane(serve_capture):
    events = serve_capture["events"]
    for name in SERVE_SPANS:
        assert events[name], f"{name} missing; have {sorted(events)}"
    # one dispatch span a pool a step, one retire a retired tick
    dispatched = len(events["dstpu:tick.dispatch.fused"]) + len(events["dstpu:tick.dispatch.plain"])
    assert dispatched == len(events["dstpu:tick.admit"]) == len(events["dstpu:serve.schedule"])
    # prompts of 20, 5 and 9 tokens in 16-wide chunks: 2 + 1 + 1 fused dispatches
    assert len(events["dstpu:tick.dispatch.fused"]) == 4
    assert 0 < len(events["dstpu:tick.retire"]) <= dispatched


def test_only_the_captured_run_left_spans(serve_capture):
    """The run before ``start_capture`` dispatched as many ticks and left
    nothing: without a profiler session an annotation records nothing."""
    events = serve_capture["events"]
    (sync,) = [n for n in events if n.startswith(CLOCK_SYNC_PREFIX)]
    t_sync = events[sync][0][0]
    for name in SERVE_SPANS:
        assert all(start >= t_sync for start, _ in events[name])


def test_clock_sync_pairs_the_xplane_with_the_jsonl(serve_capture):
    events, jsonl = serve_capture["events"], serve_capture["jsonl"]
    syncs = [n for n in events if n.startswith(CLOCK_SYNC_PREFIX)]
    assert len(syncs) == 1 and events[syncs[0]][0][1] < 1_000_000  # zero-length, to the clock's grain
    reading = int(syncs[0][len(CLOCK_SYNC_PREFIX):])
    windows = [e for e in jsonl if e["kind"] == "profile_window"]
    assert [w["event"] for w in windows] == ["start", "stop"]
    assert windows[0]["monotonic_ns"] == reading
    assert windows[1]["monotonic_ns"] > reading
    assert windows[0]["logdir"] == serve_capture["logdir"]


def test_timeline_tool_places_jsonl_spans_on_the_xplane_axis(serve_capture):
    spec = importlib.util.spec_from_file_location(
        "_tl_cli_xplane", os.path.join(REPO, "tools", "ds_trace_timeline.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    tm = cli.load_timeline_module()
    rep = cli.xplane_report(tm, list(tm.iter_events(serve_capture["trace_file"])),
                            serve_capture["logdir"])
    assert rep["profile_window_matches"] and rep["host_spans"] > 0 and rep["placed_spans"] > 0
    # a decode window of the captured run lies between the capture's first and last tick span
    events = serve_capture["events"]
    ticks = [s for name in SERVE_SPANS for s, _ in events[name]]
    ends = [s + d for name in SERVE_SPANS for s, d in events[name]]
    (sync,) = [n for n in events if n.startswith(CLOCK_SYNC_PREFIX)]
    t_sync = events[sync][0][0]
    captured = [(n, s, d) for n, s, d in rep["placed"] if s >= t_sync]
    assert captured and any(n.startswith("prefill_wait ") for n, _, _ in captured)
    slack = 5_000_000  # the hub's spans close on time.monotonic just outside the annotation
    for _, s, d in captured:
        assert min(ticks) - slack <= s and s + d <= max(ends) + slack
    assert cli.main([serve_capture["trace_file"], "--xplane", serve_capture["logdir"]]) == 0


@pytest.mark.parametrize("wider, blamed, covered", [
    ([], "dstpu:tick.dispatch.plain", 0.7),
    # a request-long JSONL span covers every gap of its lifetime whole and says
    # nothing (what the chip capture of PR 24 showed): the narrowest span that
    # covers nearly as much is what the host was doing
    ([("decode_window r0/7", 0, 30_000_000), ("dstpu:probe.pause", 10_100_000, 4_900_000)],
     "dstpu:probe.pause", 0.98),
    # ... but a wider span that covers much more than any narrow one takes it
    ([("decode_window r0/7", 0, 30_000_000)], "decode_window r0/7", 1.0),
])
def test_blame_idle_gaps_picks_the_narrowest_span_that_covers_most(wider, blamed, covered):
    from deepspeed_tpu.telemetry.timeline import blame_idle_gaps, clock_offset_ns

    busy = [(0, 10_000_000), (15_000_000, 20_000_000), (20_500_000, 30_000_000)]
    host = [("dstpu:tick.retire", 9_000_000, 2_000_000),
            ("dstpu:tick.dispatch.plain", 11_000_000, 3_500_000)]
    (gap,) = blame_idle_gaps(busy, host + wider)   # the 0.5 ms gap is under the 1 ms floor
    assert gap["gap_ms"] == 5.0 and gap["span"] == blamed
    assert gap["covered"] == pytest.approx(covered)
    assert blame_idle_gaps(busy, [])[0]["span"] == "(no span)"
    assert clock_offset_ns([(CLOCK_SYNC_PREFIX + "1000", 5000, 0)]) == 4000
    assert clock_offset_ns(host) is None


def test_training_loop_spans_and_the_one_capture_entry_point(tmp_path, monkeypatch):
    import deepspeed_tpu

    comm.destroy()
    started = []
    orig = Telemetry.start_capture
    monkeypatch.setattr(Telemetry, "start_capture",
                        lambda self, logdir: (started.append(logdir), orig(self, logdir))[1])
    engine = deepspeed_tpu.initialize(model=_toy_model(), config={
        "train_micro_batch_size_per_gpu": 8, "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}, "steps_per_print": 10 ** 9})[0]
    batch = {"input_ids": np.random.RandomState(0).randint(0, 128, (8, 32)).astype(np.int32)}
    engine.train_batch(iter([batch] * 2))           # uncaptured
    logdir = str(tmp_path / "xplane")
    engine.start_profile(logdir)                    # -> Telemetry.start_capture, hub off
    engine.train_batch(iter([batch] * 2))
    engine.stop_profile()
    assert started == [logdir] and not engine.telemetry._profiling
    events, _ = host_events(logdir)
    for name in TRAIN_SPANS:
        assert events[name], f"{name} missing; have {sorted(events)}"
    assert len(events["dstpu:train.next_batch"]) == len(events["dstpu:train.micro_dispatch"]) == 2
    assert len(events["dstpu:train.apply_dispatch"]) == 1
    assert len([n for n in events if n.startswith(CLOCK_SYNC_PREFIX)]) == 1
    engine.stop_profile()                           # idempotent


def test_an_untraced_run_opens_no_file(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    comm.destroy()
    model = _toy_model()
    cb = ContinuousBatchingEngine(model, params=model.init(jax.random.PRNGKey(0)),
                                  max_slots=3, cache_len=64, prefill_chunk=16,
                                  config={"dtype": "float32", "kv_read_floor": 16})
    srv = ServingEngine(cb)
    _serve_some(srv)
    srv.close()
    assert not cb.telemetry.enabled and not cb.telemetry._profiling
    assert os.listdir(tmp_path) == []


def test_accelerator_ranges_ride_the_same_helper(tmp_path):
    from deepspeed_tpu.accelerator.real_accelerator import get_accelerator

    acc = get_accelerator()
    tele = Telemetry(TelemetryConfig())
    tele.start_capture(str(tmp_path / "xp"))
    acc.range_push("outer")
    acc.range_push("inner")
    acc.range_pop()
    acc.range_pop()
    with acc.range("scoped"):
        pass
    tele.close()                                    # close() stops a capture left open
    events, _ = host_events(str(tmp_path / "xp"))
    assert {"dstpu:outer", "dstpu:inner", "dstpu:scoped"} <= set(events)
    (o,), (i,) = events["dstpu:outer"], events["dstpu:inner"]
    assert o[0] <= i[0] and i[0] + i[1] <= o[0] + o[1]  # LIFO nesting


def test_maybe_capture_goes_through_the_same_entry_point(tmp_path, monkeypatch):
    calls = []

    def start(self, logdir):
        calls.append(("start", logdir))
        self._profiling = True

    def stop(self):
        calls.append(("stop",))
        self._profiling = False

    monkeypatch.setattr(Telemetry, "start_capture", start)
    monkeypatch.setattr(Telemetry, "stop_capture", stop)
    tele = Telemetry(TelemetryConfig(enabled=True, trace_file="", profile_start_step=2,
                                     profile_num_steps=1, profile_dir=str(tmp_path)))
    for step in range(1, 6):
        tele.maybe_capture(step)
    assert calls == [("start", str(tmp_path)), ("stop",)]
