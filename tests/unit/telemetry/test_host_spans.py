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
# since PR 53 a step() is tiled: the whole call, and inside it scheduling, the tick's four
# phases and the fan-out; an emptiness's two ends are zero-length markers
STEP_SPAN, INNER_SPANS = "dstpu:serve.step", SERVE_SPANS + ("dstpu:tick.attribute", "dstpu:serve.emit")
MARKS = ("dstpu:serve.emptied", "dstpu:serve.refilled")
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


def test_the_spans_tile_a_step_and_markers_end_an_emptiness(serve_capture):
    events = serve_capture["events"]
    steps = sorted(events[STEP_SPAN])
    assert len(steps) == len(events["dstpu:serve.schedule"]) == len(events["dstpu:serve.emit"])
    assert len(events["dstpu:tick.attribute"]) == len(events["dstpu:tick.retire"])
    inner = sorted((s, s + d) for name in INNER_SPANS for s, d in events[name])
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))           # one after the other
    covered = 0
    for s0, d in steps:                                                  # each inside ONE step
        mine = [(a, b) for a, b in inner if s0 <= a and b <= s0 + d]
        covered += sum(b - a for a, b in mine)
        assert mine[0][0] - s0 < 0.2 * d + 50_000 and len(mine) >= 2
    assert sum(len(events[name]) for name in INNER_SPANS) == len(inner) == sum(
        1 for a, b in inner if any(s0 <= a and b <= s0 + d for s0, d in steps))
    assert covered >= 0.8 * sum(d for _, d in steps)                     # little of a step is unnamed
    # three requests at once, served until none is held: one emptiness ended, one began
    (refilled,), (emptied,) = events[MARKS[1]], events[MARKS[0]]
    # (the step that leaves the server empty says so as its last act)
    assert refilled[0] < steps[0][0] and inner[-1][1] <= emptied[0] <= steps[-1][0] + steps[-1][1]
    assert refilled[1] < 1_000_000 and emptied[1] < 1_000_000


def test_idle_by_phase_reads_the_capture_and_the_tool_prints_it(serve_capture, capsys):
    spec = importlib.util.spec_from_file_location(
        "_tl_cli_by_phase", os.path.join(REPO, "tools", "ds_trace_timeline.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    tm = cli.load_timeline_module()
    rep = cli.by_phase_report(tm, serve_capture["logdir"])
    assert rep["host_spans"] > 0 and 0 < rep["idle_s"] <= rep["extent_s"]
    assert sum(rep["idle_by_phase"].values()) == pytest.approx(rep["idle_s"])
    assert set(rep["idle_by_phase"]) <= set(tm.PHASE_OF_SPAN.values()) | {
        tm.EMPTY, tm.BETWEEN_STEPS, tm.NO_SPAN} | {n for n in rep["idle_by_phase"]
                                                   if n.startswith(("build.", "setup."))}
    assert cli.main(["--xplane", serve_capture["logdir"], "--by-phase"]) == 0   # no JSONL needed
    assert "device idle by host phase" in capsys.readouterr().out


MS = 1_000_000
HAND_BUSY = [(0, 10 * MS), (15 * MS, 20 * MS), (40 * MS, 41 * MS)]
HAND_HOST = [("dstpu:serve.step", 9 * MS, 8 * MS), ("dstpu:tick.retire", 9 * MS, 2 * MS),
             ("dstpu:tick.attribute", 11 * MS, 1 * MS), ("dstpu:serve.emit", 12 * MS, 4 * MS),
             ("dstpu:serve.emptied", 17 * MS, 100), ("dstpu:serve.refilled", 30 * MS, 90),
             ("dstpu:serve.step", 31 * MS, 10 * MS), ("dstpu:tick.dispatch.plain", 32 * MS, 9 * MS),
             ("dstpu:build.pool_tick", 33 * MS, 5 * MS)]


@pytest.mark.parametrize("case, host, window, want", [
    # a gap split over the phases that overlap it: the fetch's tail, the attribution, the fan-out
    ("a gap split", HAND_HOST[:4], (0, 20 * MS), {"block": 0.001, "attribute": 0.001, "emit": 0.003}),
    # an emptiness between its markers; outside every step after it; the innermost span where
    # they nest (a build inside a dispatch inside a step)
    ("empty, between, nesting", HAND_HOST, None,
     {"block": 0.001, "attribute": 0.001, "emit": 0.003, "empty": 0.010, "between_steps": 0.001,
      "step_other": 0.001, "dispatch": 0.001 + 0.002, "build.pool_tick": 0.005}),
    # a gap no span touches, in a trace that holds no step at all
    ("no span", [("dstpu:train.next_batch", 10 * MS, 2 * MS)], (0, 20 * MS),
     {"train.next_batch": 0.002, "(no span)": 0.003}),
    # a window that opens in an emptiness (its first marker is a refill) and closes in one
    ("open ends", [("dstpu:serve.refilled", 12 * MS, 50), ("dstpu:serve.step", 12 * MS, 4 * MS),
                   ("dstpu:serve.emptied", 21 * MS, 50)], (0, 50 * MS),
     {"empty": 0.002 + 0.019 + 0.009, "step_other": 0.003, "between_steps": 0.001}),
])
def test_idle_by_phase_splits_each_gap_and_its_rows_sum_to_the_idle_time(case, host, window, want):
    from deepspeed_tpu.telemetry.timeline import idle_by_phase

    got = idle_by_phase(HAND_BUSY, host, window=window)
    assert got == pytest.approx(want), case
    lo, hi = window or (0, 41 * MS)
    busy = sum(min(e, hi) - max(s, lo) for s, e in HAND_BUSY if s < hi and e > lo)
    assert sum(got.values()) == pytest.approx((hi - lo - busy) / 1e9)
    assert idle_by_phase([], host) == {} and idle_by_phase([(0, 5)], host) == {}


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
