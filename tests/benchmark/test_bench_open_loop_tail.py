"""What ``runners/serve.py``'s ``_measure`` makes of an open loop's records (the
median and the 95th percentile of the wait for the first token, the gaps, what a
freeze of the machine does to each, a request that never answered, the host
ledger's readings) on hand-made records, and the committed chat schedule: how
many requests fall in the pre-roll and in the window, whatever the ``--seed``.
No engine runs here."""

import json
import os

import numpy as np
import pytest

from benchmark import trafficgen
from benchmark.runners import serve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SECONDS = 40.0


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


CHAT = load("benchmark", "traffic", "chat_open_loop.json")
RUN_SECONDS = load("BENCHMARK.json")["run_seconds"]


# -- the committed schedule ------------------------------------------------------

def counts(seed):
    dues = np.array([r.due_s for r in trafficgen.open_loop(CHAT, seed, float(RUN_SECONDS), 50257)])
    window = dues[dues >= 0]
    quarters = np.histogram(window, bins=4, range=(0.0, float(RUN_SECONDS)))[0]
    return int((dues < 0).sum()), int(window.size), [int(n) for n in quarters]


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 56])
def test_the_committed_rate_puts_the_same_requests_in_the_window_for_every_seed(seed):
    preroll, window, quarters = counts(seed)
    arr = CHAT["arrivals"]
    assert preroll + window == int(np.ceil((arr["preroll_s"] + RUN_SECONDS) * arr["rate_per_s"]))
    assert (preroll, window, quarters) == counts(1)
    # the file's own account of it (its `schedule` names both counts)
    assert f"{preroll:,} of them fall in the {arr['preroll_s']:g} s pre-roll" in CHAT["schedule"]
    assert f"{window:,} in the {RUN_SECONDS} s window" in CHAT["schedule"]


def test_the_window_holds_enough_requests_beyond_its_95th_percentile():
    _, window, quarters = counts(3)
    assert window >= 200                      # ten and more beyond the 95th percentile
    assert min(quarters) >= 200               # ... and no quarter of the window stands empty


# -- what _measure makes of a window's records ---------------------------------------

class Bare(serve.Runner):
    def __init__(self):
        self.records, self.live_rows, self.live_kv = [], [], []


def request(due_s, new=4):
    return trafficgen.Request(0, due_s, np.zeros(8, np.int32), new)


def answered(due_s, ttft_s, t_open=100.0, new=4):
    rec = serve.Record(request(due_s, new), t_open + due_s)
    rec.submitted = rec.admit = rec.due
    rec.times = [rec.due + ttft_s + 0.003 * k for k in range(new)]
    rec.state, rec.tokens = "finished", [1] * new
    return rec


STATS = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}


def measure(records, t_end=150.0, stats1=None):
    runner = Bare()
    runner.records = records
    return runner._measure(False, SECONDS, 100.0, 140.0, t_end, 1.0, STATS,
                           dict(STATS, ticks=3) if stats1 is None else stats1)


def test_measure_reports_the_median_the_tail_and_the_gaps():
    records = [answered(d, 0.020 + 0.001 * (i % 7)) for i, d in enumerate(np.arange(0.0, 40.0, 0.05))]
    records += [answered(-3.0, 0.5), answered(-0.1, 0.5)]     # the pre-roll's are not measured
    result = measure(records)
    obs, e2e = result["obs"], result["end_to_end"]
    assert result["attempted"] == 800 and result["failed"] == 0
    # the harness picks what the manifest lists: both statistics of the wait are on offer
    assert set(e2e) == {"setup_s", "serve_tokens_per_s", "ttft_p50_ms", "ttft_p95_ms", "gap_p95_ms"}
    assert e2e["ttft_p95_ms"] == pytest.approx(26.0, abs=0.01)
    assert e2e["ttft_p50_ms"] == obs["ttft_p50_ms"] == pytest.approx(23.0, abs=0.01)
    assert e2e["gap_p95_ms"] == pytest.approx(3.0) and obs["gaps_measured"] == 800 * 3
    # (every request that ENDED in the window counts there: the pre-roll's last one did)
    assert obs["requests_shed"] == 0 and obs["requests_ended_in_window"] == 801


def frozen(records, at, length, t_open=100.0):
    """The machine stands still for ``length`` seconds from ``at``: a request due inside the freeze
    is submitted at its end and waits from its due time, those behind it wait for the queue to
    drain; a token due inside the freeze comes at its end."""
    end = t_open + at + length
    clear = end + 2.0 * length      # the queue behind it drains over twice its length (u / (1 - u) at two thirds)
    for rec in records:
        if t_open + at <= rec.due < end:
            rec.submitted = rec.admit = end
            rec.times = [t + (end - rec.due) for t in rec.times]
        elif end <= rec.due < clear:
            rec.times = [t + length * (clear - rec.due) / (clear - end) for t in rec.times]
        else:
            rec.times = [end if t_open + at <= t < end else t for t in rec.times]
    return records


def spread_out(n=2560):
    rs = np.random.RandomState(0)
    # the chat cell's shape at 64 requests/s: median 14 ms, 95th percentile ~31, 97th ~36
    return [answered(d, 0.010 + 0.004 * np.exp(rs.randn()), new=8) for d in np.sort(rs.uniform(0.0, 40.0, n))]


@pytest.mark.parametrize("freezes", [1, 3])
def test_a_freeze_of_the_machine_moves_the_tail_and_neither_the_median_nor_the_gaps(freezes):
    """105-113 ms, none to three times in a run (PR 56): the 95th percentile takes the frozen
    arrivals into its top twentieth, the median and the gaps' 95th percentile do not see them;
    the generator's lateness is the freeze's length."""
    clean = measure(spread_out())
    records = spread_out()
    for k in range(freezes):
        frozen(records, 7.0 + 9.0 * k, 0.110)
    hit = measure(records)
    e0, e1 = clean["end_to_end"], hit["end_to_end"]
    assert e1["ttft_p50_ms"] == pytest.approx(e0["ttft_p50_ms"], rel=0.01)
    assert e1["gap_p95_ms"] == pytest.approx(e0["gap_p95_ms"], rel=0.01)
    assert e1["ttft_p95_ms"] > e0["ttft_p95_ms"] * (1.0 + 0.015 * freezes)
    assert clean["obs"]["generator_late_max_ms"] == 0.0
    assert 90.0 < hit["obs"]["generator_late_max_ms"] <= 110.0
    assert hit["failed"] == 0 and hit["attempted"] == 2560


def test_the_most_rows_a_step_answered_stand_beside_their_mean():
    runner = Bare()
    runner.records = [answered(d, 0.020) for d in np.arange(0.0, 40.0, 0.5)]
    runner.live_rows = [3, 11, 12, 29, 10]
    obs = runner._measure(False, SECONDS, 100.0, 140.0, 150.0, 1.0, STATS, dict(STATS, ticks=5))["obs"]
    assert obs["max_live_rows"] == 29 and obs["mean_live_rows"] == pytest.approx(13.0)
    assert measure(runner.records)["obs"]["max_live_rows"] is None      # no step answered in the window


def test_a_request_that_never_answered_counts_to_the_drains_end():
    records = [answered(d, 0.020) for d in np.arange(0.0, 40.0, 0.5)]       # 80
    for d in np.arange(21.0, 29.0):
        lost = serve.Record(request(d), 100.0 + d)
        lost.submitted = lost.due
        records.append(lost)
    result = measure(records, t_end=190.0)
    # 8 of 88 never answered: the 95th percentile lies among them, each waited until the drain ended
    assert (190.0 - 100.0 - 28.0) * 1e3 <= result["end_to_end"]["ttft_p95_ms"] <= (190.0 - 100.0 - 21.0) * 1e3
    assert result["end_to_end"]["ttft_p50_ms"] == pytest.approx(20.0)
    assert result["failed"] == 8 and result["attempted"] == 88


def test_the_ledgers_observations_stand_beside_the_generators_lateness():
    ledger = dict(STATS, steps=0, empty_ms=0.0, starved_ms=0.0, ticks_ready_at_retire=0)
    after = dict(ledger, ticks=100, steps=120, empty_ms=10_000.0, starved_ms=400.0,
                 ticks_ready_at_retire=25)
    runner = Bare()
    runner.records = [answered(d, 0.020) for d in np.arange(0.0, 40.0, 0.5)]
    obs = runner._measure(False, SECONDS, 100.0, 140.0, 150.0, 1.0, ledger, after)["obs"]
    assert obs["empty_share_pct"] == pytest.approx(25.0)
    assert obs["starved_share_pct"] == pytest.approx(1.0)
    assert obs["host_bound_tick_pct"] == pytest.approx(25.0)
    assert obs["generator_late_max_ms"] == 0.0
    # a program without the ledger's keys gives no reading, and the rest of the line stands
    bare = measure(runner.records)["obs"]
    assert bare["empty_share_pct"] is None and bare["host_bound_tick_pct"] is None
    assert bare["ttft_p50_ms"] == pytest.approx(20.0)
