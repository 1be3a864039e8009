"""The ``prefill_wait`` span kind (admit -> first prefill dispatch) from the
serving layer to the blame tables — jax-free (FakeEngine; part of
tools/ci_jaxfree_tests.py). The wait in the batcher's prefill queue used to
be a ``gap`` between the ``admission`` span and the first ``prefill_chunk``;
it now has a name, a category (``queue``) and two fields on the
``inference_request`` event."""

import importlib.util
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(REPO, "tests", "unit", "serving"))
from fake_engine import FakeEngine  # noqa: E402

from deepspeed_tpu.serving.engine import ServingEngine  # noqa: E402
from deepspeed_tpu.telemetry.registry import MetricsRegistry  # noqa: E402
from deepspeed_tpu.telemetry.spans import SpanEmitter  # noqa: E402
from deepspeed_tpu.telemetry.timeline import (  # noqa: E402
    SPAN_CATEGORY,
    SPAN_KINDS,
    build_timelines,
    slo_blame,
)


class FakeClock:
    def __init__(self):
        self.t = 50.0

    def __call__(self):
        return self.t


class Hub:
    def __init__(self):
        self.enabled = True
        self.registry = MetricsRegistry()
        self.events = []

    def emit(self, kind, payload, **kw):
        self.events.append(dict(payload, kind=kind))

    def close(self):
        pass


def _serve(wait_ticks, deadline_ms=None, n=2):
    clock, hub = FakeClock(), Hub()
    eng = FakeEngine(clock=clock)
    eng._eng.telemetry = hub
    eng.prefill_wait_ticks = wait_ticks
    srv = ServingEngine(eng, clock=clock)
    adms = [srv.submit(np.arange(6, dtype=np.int32), max_new_tokens=3,
                       deadline_ms=deadline_ms) for _ in range(n)]
    reqs = [srv.request(a.rid) for a in adms]
    ticks = 0
    while srv.has_work():
        srv.step()
        clock.t += 0.1
        ticks += 1
        assert ticks < 100
    return hub, reqs


def test_kind_is_registered_as_queue_time():
    assert "prefill_wait" in SPAN_KINDS
    assert SPAN_CATEGORY["prefill_wait"] == "queue"
    sid = SpanEmitter(Hub()).emit("prefill_wait", "r0", 1.0, 2.0)
    assert sid is not None  # the write side validates against the same table


def test_span_runs_from_admit_to_first_prefill_dispatch():
    hub, reqs = _serve(wait_ticks=4)
    spans = [e for e in hub.events if e["kind"] == "span" and e["span"] == "prefill_wait"]
    assert len(spans) == len(reqs)
    for span, req in zip(spans, reqs):
        assert span["t0"] == req.admit_t and span["t1"] == req.prefill_start_t
        assert span["dur_ms"] == pytest.approx(400.0)  # four ticks of 0.1 s
    timelines = build_timelines(hub.events)
    assert len(timelines) == len(reqs)
    for tl in timelines.values():
        assert not tl.orphans
        kinds = [s.kind for s in tl.spans]
        assert kinds.index("admission") < kinds.index("prefill_wait") < kinds.index("prefill_chunk")
        wait = next(s for s in tl.spans if s.kind == "prefill_wait")
        admission = next(s for s in tl.spans if s.kind == "admission")
        assert wait.parent_id == admission.span_id


def test_blame_names_the_prefill_queue_not_a_gap():
    hub, _ = _serve(wait_ticks=6)
    for tl in build_timelines(hub.events).values():
        path = tl.critical_path()
        # the six ticks in the queue are charged by name; what is left as a gap
        # is the fake's zero-length decode windows (its clock stands still in a tick)
        assert path["prefill_wait"] == pytest.approx(600.0)
        assert path.get("gap", 0.0) <= tl.duration_ms - 600.0 + 1e-6
        assert tl.dominant_kind() == "prefill_wait"
        assert tl.attribution()["queue"] >= 600.0


def test_request_event_carries_the_parts_of_ttft():
    hub, reqs = _serve(wait_ticks=3)
    events = [e for e in hub.events if e["kind"] == "inference_request"]
    assert len(events) == len(reqs)
    for ev in events:
        assert ev["prefill_wait_ms"] == pytest.approx(300.0)
        assert ev["queue_ms"] + ev["prefill_wait_ms"] + ev["prefill_ms"] == \
            pytest.approx(ev["ttft_ms"], abs=0.01)
        assert ev["prefill_ms"] >= 0


def test_slo_blame_puts_a_missed_deadline_down_to_the_prefill_queue():
    hub, _ = _serve(wait_ticks=8, deadline_ms=500.0)
    rows = slo_blame(hub.events)
    assert rows and all(r["dominant"] == "prefill_wait" for r in rows)
    assert all(r["attribution"]["queue"] >= 800.0 for r in rows)


def test_no_wait_leaves_a_zero_length_span_and_no_blame():
    hub, _ = _serve(wait_ticks=0)
    spans = [e for e in hub.events if e["kind"] == "span" and e["span"] == "prefill_wait"]
    assert spans and all(s["dur_ms"] == 0.0 for s in spans)
    for tl in build_timelines(hub.events).values():
        assert tl.dominant_kind() != "prefill_wait"


def test_timeline_cli_summary_names_it(tmp_path, capsys):
    import json

    hub, _ = _serve(wait_ticks=6)
    trace = tmp_path / "t.jsonl"
    trace.write_text("".join(json.dumps(e) + "\n" for e in hub.events))
    spec = importlib.util.spec_from_file_location(
        "_tl_cli", os.path.join(REPO, "tools", "ds_trace_timeline.py"))
    cli = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cli)
    assert cli.main([str(trace)]) == 0
    out = capsys.readouterr().out
    assert out.count("prefill_wait") == 2  # the dominant kind of both traces
