"""Where a request's first token waits (ISSUE 24, part A and B): the
lifecycle marks ``submit_t <= admit_t <= prefill_start_t <= first_token_t``
on the serving engine's ONE injected clock, on every admission path and
across a recovery re-admission; and the tick-kind counters of
``tick_stats()``. Real engines at toy size on the CPU."""

import numpy as np
import pytest

import jax

from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.serving import (
    Fault,
    FaultInjector,
    FaultPlan,
    RecoveryConfig,
    ServingEngine,
)

PROMPT_NS = (5, 9, 40, 3, 17)   # 40 spans three 16-token chunks
MAX_NEW = (6, 8, 4, 5, 7)


class ReadClock:
    """Advances on every read: two marks can only be equal if ONE read
    wrote both, and their order is the order the program read them in."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.001
        return self.t


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128, dtype="float32")
    model = TransformerModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _prompts():
    rs = np.random.RandomState(3)
    return [rs.randint(0, 128, (n,)).astype(np.int32) for n in PROMPT_NS]


def _build(setup, path, depth=1):
    model, params = setup
    cfg = {"dtype": "float32", "kv_read_floor": 16}
    if path == "spec":
        cfg["speculative"] = {"enabled": True, "pool": True, "mode": "ngram",
                              "num_draft_tokens": 2}
    return ContinuousBatchingEngine(
        model, params=params, config=cfg, max_slots=3, cache_len=64,
        pipeline_depth=depth, fused_prefill=(path != "separate"),
        prefill_chunk=16)


def _drive(srv, max_ticks=400):
    n = 0
    while srv.has_work():
        assert n < max_ticks, "serving did not drain"
        srv.step()
        n += 1
    return srv.reap()


def _assert_identity(req):
    marks = (req.submit_t, req.admit_t, req.prefill_start_t, req.first_token_t)
    assert None not in marks, marks
    queue = req.admit_t - req.submit_t
    wait = req.prefill_start_t - req.admit_t
    prefill = req.first_token_t - req.prefill_start_t
    assert queue >= 0 and wait >= 0 and prefill >= 0, (queue, wait, prefill)
    assert queue + wait + prefill == pytest.approx(
        req.first_token_t - req.submit_t, abs=1e-9)
    assert req.queue_ms() + req.prefill_wait_ms() + req.prefill_ms() == \
        pytest.approx(req.ttft_ms(), abs=1e-6)


@pytest.mark.parametrize("path", ["fused", "separate", "prefix", "spec"])
def test_lifecycle_identity_on_each_admission_path(setup, path):
    clock = ReadClock()
    srv = ServingEngine(_build(setup, path), clock=clock)
    kw = {}
    if path == "prefix":
        kw["prefix_id"] = srv.register_prefix(np.arange(12, dtype=np.int32))
    adms = [srv.submit(p, max_new_tokens=m, **kw)
            for p, m in zip(_prompts(), MAX_NEW)]
    done = _drive(srv)
    assert len(done) == len(adms)
    for a in adms:
        req = done[a.rid]
        assert req.state == "finished" and len(req.tokens) == req.max_new_tokens
        _assert_identity(req)
        # a strictly advancing clock: the first prefill dispatch is read
        # after the handover and before the token's retire
        assert req.admit_t < req.prefill_start_t < req.first_token_t


def test_fused_prefill_queue_serves_one_request_at_a_time(setup):
    """Three requests handed over before a step enter the pool's prefill queue
    together; one chunk rides each tick, so their first prefill dispatches
    are in FIFO order, a 3-chunk prompt holds the queue for three ticks, and
    the wait is what ``prefill_wait`` measures."""
    srv = ServingEngine(_build(setup, "fused"), clock=ReadClock())
    prompts = _prompts()
    order = [2, 0, 1]                      # the 40-token prompt first
    adms = [srv.submit(prompts[i], max_new_tokens=4) for i in order]
    done = _drive(srv)
    first, second, third = (done[a.rid] for a in adms)
    assert first.prefill_start_t < second.prefill_start_t < third.prefill_start_t
    assert second.prefill_wait_ms() > first.prefill_wait_ms()
    # 3 ticks behind the long prompt, then 2 and 1 left waiting
    assert srv.tick_stats()["prefill_q_depth_sum"] >= 3 + 3 + 3 + 2 + 1


@pytest.mark.parametrize("depth", [0, 1])
def test_tick_kind_counters_add_up(setup, depth):
    cb = _build(setup, "fused", depth=depth)
    srv = ServingEngine(cb, clock=ReadClock())
    for p, m in zip(_prompts(), MAX_NEW):
        srv.submit(p, max_new_tokens=m)
    _drive(srv)
    s = srv.tick_stats()
    assert s["plain_ticks"] + s["fused_prefill_ticks"] == s["ticks"]
    assert s["plain_ticks"] > 0 and s["fused_prefill_ticks"] > 0
    assert s["block_ms_plain"] + s["block_ms_fused"] == pytest.approx(s["block_ms"], rel=1e-9)
    assert s["block_ms_plain"] > 0 and s["block_ms_fused"] > 0
    # one chunk of <= 16 tokens a tick, and one rode only where a request waited
    assert s["fused_prefill_ticks"] == sum(-(-n // 16) for n in PROMPT_NS)
    assert s["fused_prefill_ticks"] <= s["prefill_q_depth_sum"] <= 3 * s["steps"]


def test_separate_admission_has_no_prefill_queue(setup):
    srv = ServingEngine(_build(setup, "separate"), clock=ReadClock())
    for p, m in zip(_prompts(), MAX_NEW):
        srv.submit(p, max_new_tokens=m)
    _drive(srv)
    s = srv.tick_stats()
    assert s["fused_prefill_ticks"] == 0 and s["plain_ticks"] == s["ticks"]
    assert s["block_ms_fused"] == 0.0
    assert s["prefill_q_depth_sum"] == 0   # prefilled at admission: nothing waits for ticks


@pytest.mark.parametrize("fused", [True, False])
def test_recovery_readmission_gets_new_marks_only_where_no_token_was_felt(setup, fused):
    """An engine preempted mid-run is rebuilt and every running request is
    re-admitted (``prompt + emitted``). A request that had streamed its first
    token keeps the marks its client felt; one that had not gets the rebuilt
    engine's first prefill dispatch as ``prefill_start_t``. ``submit_t`` and
    ``admit_t`` are never rewritten, so the identity holds and every term
    stays >= 0 either way."""
    path = "fused" if fused else "separate"
    clock = ReadClock()
    cb = _build(setup, path)
    cb.fault_hook = FaultInjector(FaultPlan([Fault(tick=3, kind="preempt")]))
    srv = ServingEngine(cb, clock=clock,
                        engine_factory=lambda mesh_shape=None: _build(setup, path),
                        recovery=RecoveryConfig(backoff_s=0.0), sleep=lambda s: None)
    # fused: the short prompt streams a token by tick 2, the long one is mid-prefill
    # and the third has not started when the engine is lost
    order = [0, 2, 1]
    adms = [srv.submit(_prompts()[i], max_new_tokens=6) for i in order]
    before = {}
    for _ in range(3):                     # ticks 0..2 run; tick 3 is the preemption
        srv.step()
    for a in adms:
        r = srv.request(a.rid)
        before[a.rid] = (r.admit_t, r.prefill_start_t, r.first_token_t)
    t_fault = clock.t
    done = _drive(srv)
    assert srv.recovery_stats()["rebuilds"] == 1
    felt = unfelt = 0
    for a in adms:
        req = done[a.rid]
        assert req.state == "finished" and req.recoveries == 1
        _assert_identity(req)
        admit0, start0, first0 = before[a.rid]
        assert req.admit_t == admit0
        if first0 is not None:             # its client had a token: nothing moves
            felt += 1
            assert (req.prefill_start_t, req.first_token_t) == (start0, first0)
        else:                              # re-marked by the rebuilt engine
            unfelt += 1
            assert req.prefill_start_t > t_fault and req.first_token_t > req.prefill_start_t
    assert felt and (unfelt or not fused)


@pytest.mark.parametrize("path", ["fused", "separate"])
def test_batcher_calls_on_prefill_start_once_per_request(setup, path):
    """The batcher's side of the mark, without a serving layer: the callback
    handed to ``submit`` fires once per request (a 40-token prompt rides three
    chunks), in the order the prefill work was dispatched, and a request
    submitted without one costs nothing."""
    cb = _build(setup, path)
    calls = []
    prompts = _prompts()[:3]
    rids = [cb.submit(p, max_new_tokens=3, on_prefill_start=lambda i=i: calls.append(i))
            for i, p in enumerate(prompts)]
    silent = cb.submit(_prompts()[3], max_new_tokens=3)
    while cb.has_work():
        cb.step()
    assert calls == [0, 1, 2]              # once each, FIFO, never per tick
    assert sorted(cb.finished()) == sorted(rids + [silent])


# -- the program's own reader of the marks: the load generator's summary ------

def test_loadgen_reports_ttfts_parts_and_the_tick_kinds(setup):
    """``ds_loadgen`` is where the program itself reports the marks: every
    finished record carries TTFT's three parts, the summary their
    percentiles, and its ``host`` section the two kinds of tick apart."""
    from deepspeed_tpu.serving import loadgen

    srv = ServingEngine(_build(setup, "fused"), clock=ReadClock())
    clock = ReadClock()
    workload = [{"prompt_tokens": n, "max_new_tokens": m} for n, m in zip(PROMPT_NS, MAX_NEW)]
    records, wall_s = loadgen.run_load(srv, workload, [0.0] * len(workload),
                                       clock=clock, sleep=lambda s: None)
    for rec in records:
        assert rec["state"] == "finished"
        assert rec["queue_ms"] + rec["prefill_wait_ms"] + rec["prefill_ms"] == \
            pytest.approx(rec["ttft_ms"], abs=1e-6)
    stats = srv.tick_stats()
    summary = loadgen.summarize(records, wall_s, tick_stats=stats)
    assert summary["prefill_wait_ms"]["p99"] >= summary["prefill_wait_ms"]["p50"] >= 0
    assert summary["prefill_ms"]["p50"] > 0
    host = summary["host"]
    assert host["fused_tick_share"] == pytest.approx(
        stats["fused_prefill_ticks"] / stats["ticks"], abs=1e-4)
    # the kinds' block times, weighted by their ticks, are the one mean
    share = stats["fused_prefill_ticks"] / stats["ticks"]
    mean = share * host["tick_block_ms_fused"] + (1 - share) * host["tick_block_ms_plain"]
    assert mean == pytest.approx(stats["block_ms"] / stats["ticks"], rel=1e-3)
    assert host["prefill_q_depth_mean"] == pytest.approx(
        stats["prefill_q_depth_sum"] / stats["steps"], abs=1e-4)
    text = loadgen.format_summary(summary)
    assert "prefill wait" in text and "tick kinds     fused" in text
    assert host["block_write_share"] == 0.0 and "block writes 0.0%" in text   # toy rows: the window


def test_loadgen_host_columns_without_the_kind_counters():
    """A ``tick_stats()`` that lacks the per-kind keys (another engine, an
    older snapshot) gives the columns it always gave and no others."""
    from deepspeed_tpu.serving import loadgen

    old = {"steps": 4, "ticks": 4, "dispatch_ms": 8.0, "block_ms": 40.0,
           "fused_prefill_ticks": 2, "pipeline_depth": 1}
    host = loadgen.host_overhead(old)
    assert host["tick_block_ms_mean"] == 10.0
    assert not {"fused_tick_share", "tick_block_ms_fused", "tick_block_ms_plain",
                "prefill_q_depth_mean"} & set(host)
    new = dict(old, plain_ticks=2, block_ms_fused=30.0, block_ms_plain=10.0,
               prefill_q_depth_sum=6)
    host = loadgen.host_overhead(new)
    assert (host["fused_tick_share"], host["tick_block_ms_fused"],
            host["tick_block_ms_plain"], host["prefill_q_depth_mean"]) == (0.5, 15.0, 5.0, 1.5)
    idle = loadgen.host_overhead(dict(new, steps=0, fused_prefill_ticks=0, plain_ticks=0))
    assert idle["fused_tick_share"] is None and idle["tick_block_ms_fused"] is None
