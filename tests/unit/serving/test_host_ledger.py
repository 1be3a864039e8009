"""The serving loop's host ledger (PR 53): every millisecond of a server's wall
time in exactly one row of ``tick_stats()``, always on. The rows under an
injected clock on the FakeEngine, to the microsecond; the identity on a real
toy engine; emptiness across submit / reap / cancel / a recovery rebuild;
``starved_ms`` with and without a tick pipeline; the ticks whose result was
on hand; the fleet's sums; ``host_overhead``'s columns with and without it."""

import functools
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from fake_engine import FakeEngine  # noqa: E402

from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.serving.loadgen import (LEDGER_ROWS, format_summary, host_overhead,
                                           ledger_delta)
from deepspeed_tpu.serving.recovery import RecoveryConfig
from deepspeed_tpu.serving.router import FleetRouter

MS = 1e-3
TICK = (1.0 * MS, 3.0 * MS, 0.5 * MS)      # the fake tick's dispatch, block, attribution


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def wall_ms(stats):
    return sum(stats[key] for key in LEDGER_ROWS)


def fake_server(clock, schedule_s=0.2 * MS, token_s=0.1 * MS, **kw):
    """A server over the FakeEngine whose every phase costs a known time on
    ``clock``: the tick by ``TICK``, scheduling ``schedule_s`` a step, the
    fan-out ``token_s`` a token (an ``on_token`` callback of the test's)."""
    eng = FakeEngine(clock=clock)
    eng.tick_cost, eng.advance = TICK, clock.advance
    srv = ServingEngine(eng, clock=clock, **kw)
    schedule = srv._schedule
    srv._schedule = lambda now: (clock.advance(schedule_s), schedule(now))[1]
    srv.on_token = lambda rid, tok: clock.advance(token_s)
    return srv


def test_every_row_to_the_microsecond_under_an_injected_clock():
    clock = FakeClock()
    srv = fake_server(clock)
    t_built = clock()
    clock.advance(10 * MS)                                   # empty: nothing submitted yet
    a = srv.submit(np.arange(1, 6), max_new_tokens=2, on_token=srv.on_token)
    clock.advance(2 * MS)                                    # the caller's, a request held
    assert srv.step() == {a.rid: [srv.request(a.rid).tokens[0]]}
    clock.advance(1 * MS)
    srv.step()                                               # the second token: finished, empty
    assert not srv.has_work()
    srv.reap()
    clock.advance(7 * MS)                                    # empty again; a reap changes nothing
    b = srv.submit(np.arange(1, 4), max_new_tokens=1, on_token=srv.on_token)
    c = srv.submit(np.arange(1, 4), max_new_tokens=1, on_token=srv.on_token)
    assert b and c
    clock.advance(0.5 * MS)
    srv.step()                                               # both finish in one tick
    clock.advance(4 * MS)
    s = srv.tick_stats()                                     # the open emptiness counts to this read
    want = dict(empty_ms=10 + 7 + 4, between_steps_ms=2 + 1 + 0.5, schedule_ms=3 * 0.2,
                dispatch_ms=3 * 1.0, block_ms=3 * 3.0, attribute_ms=3 * 0.5,
                emit_ms=4 * 0.1, step_other_ms=0.0)
    for key, ms in want.items():
        assert s[key] == pytest.approx(ms, abs=1e-3), key
    assert wall_ms(s) == pytest.approx((clock() - t_built) * 1e3, abs=1e-6)
    # the fake chip runs only while the host is blocked on it: a request was held and
    # nothing ran for the rest of the steps and of the caller's time
    assert s["starved_ms"] == pytest.approx(wall_ms(s) - s["empty_ms"] - s["block_ms"], abs=1e-3)
    assert s["steps"] == s["ticks"] == 3


def test_a_step_entered_while_empty_counts_in_its_own_rows():
    clock = FakeClock()
    srv = fake_server(clock)
    clock.advance(3 * MS)
    srv.step()                                               # nothing held: no tick, only scheduling
    clock.advance(2 * MS)
    s = srv.tick_stats()
    assert s["empty_ms"] == pytest.approx(5.0, abs=1e-3) and s["between_steps_ms"] == 0
    assert s["schedule_ms"] == pytest.approx(0.2, abs=1e-3) and s["dispatch_ms"] == 0
    assert wall_ms(s) == pytest.approx(5.2, abs=1e-3)


def test_emptiness_ends_at_the_submit_and_starts_where_the_last_request_leaves():
    clock = FakeClock()
    srv = fake_server(clock)
    clock.advance(5 * MS)
    a = srv.submit(np.arange(1, 6), max_new_tokens=8)
    clock.advance(1 * MS)
    srv.step()
    clock.advance(1 * MS)
    assert srv.cancel(a.rid)                                 # the one request goes OUTSIDE a step
    clock.advance(6 * MS)
    srv.drain()
    assert not srv.submit(np.arange(1, 6), max_new_tokens=8)  # shed: nothing is held by it
    srv.resume()
    clock.advance(1 * MS)
    s = srv.tick_stats()
    assert s["empty_ms"] == pytest.approx(5 + 6 + 1, abs=1e-3)
    assert s["between_steps_ms"] == pytest.approx(1 + 1, abs=1e-3)
    srv.reap()
    clock.advance(2 * MS)
    assert srv.tick_stats()["empty_ms"] == pytest.approx(14, abs=1e-3)


def test_a_rebuild_is_a_steps_own_time_and_emptiness_goes_on_across_it():
    clock = FakeClock()
    engines = []

    def factory(mesh_shape=None):
        clock.advance(50 * MS)                               # a rebuild that costs wall time
        eng = FakeEngine(clock=clock)
        eng.tick_cost, eng.advance = TICK, clock.advance
        engines.append(eng)
        return eng

    srv = fake_server(clock, schedule_s=0.0, engine_factory=factory,
                      recovery=RecoveryConfig(backoff_s=0.0), sleep=lambda s: None)
    t_built = clock()
    clock.advance(4 * MS)
    a = srv.submit(np.arange(1, 6), max_new_tokens=3)
    srv.step()
    srv._cb.poison_next_step = True
    clock.advance(1 * MS)
    srv.step()                                               # poisoned -> rebuilt inside the step
    assert srv.recovery_stats()["rebuilds"] == 1 and srv._cb is engines[0]
    before = srv.tick_stats()
    assert before["step_other_ms"] == pytest.approx(50, abs=1e-3)
    assert before["empty_ms"] == pytest.approx(4, abs=1e-3)
    # nothing ran on either engine while the rebuild went on: the chip starved, and
    # the count goes on where the lost engine's stood
    # (all the time since the server was built, less its emptiness and the one tick's run)
    assert before["starved_ms"] == pytest.approx((clock() - t_built) * 1e3 - 4 - 3.0, abs=1e-3)
    # the lost engine's own rows went with it, as all its counters: the rows that are left
    assert wall_ms(before) == pytest.approx(4 + 1 + 50, abs=1e-3)
    while srv.has_work():
        clock.advance(1 * MS)
        srv.step()
    assert srv.reap()[a.rid].state == "finished"
    clock.advance(9 * MS)
    after = srv.tick_stats()
    assert after["empty_ms"] == pytest.approx(4 + 9, abs=1e-3)
    assert after["starved_ms"] > before["starved_ms"]
    assert after["step_other_ms"] == pytest.approx(50, abs=1e-3)


def test_the_fleet_sums_the_ledgers_of_its_replicas():
    clock = FakeClock()

    def factory(replica_id):
        return fake_server(clock)

    router = FleetRouter(factory, replicas=2, clock=clock)
    for n in (3, 4, 5):
        assert router.submit(np.arange(1, 6), max_new_tokens=n)
    while router.has_work():
        router.step()
        clock.advance(2 * MS)
    fleet = router.tick_stats()
    each = [rep.serving.tick_stats() for rep in router._replicas.values()]
    for key in LEDGER_ROWS + ("starved_ms", "inflight_empty_ms", "ticks_ready_at_retire"):
        assert fleet[key] == pytest.approx(sum(s[key] for s in each), abs=1e-6), key
    assert wall_ms(fleet) == pytest.approx(sum(wall_ms(s) for s in each), abs=1e-6)


def test_host_overhead_prints_the_ledger_only_where_the_keys_are():
    clock = FakeClock()
    srv = fake_server(clock)
    srv.submit(np.arange(1, 6), max_new_tokens=4)
    while srv.has_work():
        clock.advance(1 * MS)
        srv.step()
    clock.advance(30 * MS)
    stats = srv.tick_stats()
    host = host_overhead(stats)
    assert sum(host["ledger_shares"].values()) == pytest.approx(1.0, abs=1e-3)
    assert host["ledger_shares"]["empty"] == pytest.approx(30 / wall_ms(stats), abs=1e-3)
    assert host["ledger_wall_ms"] == pytest.approx(wall_ms(stats), abs=1e-3)
    summary = dict(outcomes={}, requests=0, wall_s=1.0, throughput_tok_s=0, goodput_tok_s=0,
                   shed_rate=0.0, host=host)
    assert "host ledger    empty " in format_summary(summary)
    delta = ledger_delta(stats, dict(stats, empty_ms=stats["empty_ms"] - 5.0))
    assert delta["empty_ms"] == pytest.approx(5.0) and delta["wall_ms"] == pytest.approx(5.0)
    # a bare batcher's snapshot, or an older program's: the columns there were, and no more
    old = {k: v for k, v in stats.items() if k not in LEDGER_ROWS[:2] + ("starved_ms",)}
    assert ledger_delta(old) is None
    bare = host_overhead(old)
    assert set(host) - set(bare) == {"ledger_wall_ms", "ledger_shares", "starved_share",
                                    "host_bound_tick_share"}
    assert "host ledger" not in format_summary(dict(summary, host=bare))


# -- a real toy engine ----------------------------------------------------------

class Reading:
    """``time.monotonic`` that remembers its last reading: ``tick_stats()``
    reads the clock last, for the stretch open outside ``step()``."""

    def __call__(self):
        self.last = time.monotonic()
        return self.last


@pytest.fixture(scope="module")
def toy():
    from deepspeed_tpu import comm
    from serving_toys import SMALL, built

    comm.destroy()
    model, params = built(SMALL)

    @functools.cache      # one server a depth: every test leaves it holding nothing
    def build(depth):
        from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine

        cb = ContinuousBatchingEngine(model, params=params, max_slots=3, cache_len=64,
                                      prefill_chunk=16, pipeline_depth=depth,
                                      config={"dtype": "float32", "kv_read_floor": 16})
        clock = Reading()
        return ServingEngine(cb, clock=clock), clock

    return build


def read(srv, clock):
    stats = srv.tick_stats()
    return stats, clock.last


def test_the_rows_sum_to_the_wall_time_on_a_real_engine_over_an_open_loop(toy):
    from serving_toys import prompts

    srv, clock = toy(1)
    s0, t0 = read(srv, clock)
    script = [(0.03, 9, 5), (0.0, 20, 4), (0.05, 5, 6)]      # (idle before it, prompt, new tokens)
    for (idle, n, new), prompt in zip(script, prompts([n for _, n, _ in script])):
        time.sleep(idle)                                     # an idle stretch, then a burst
        assert srv.submit(prompt, max_new_tokens=new)
        while srv.has_work():
            srv.step()
            time.sleep(0.001)                                # the caller's own time
        srv.reap()
    time.sleep(0.02)
    s1, t1 = read(srv, clock)
    led = ledger_delta(s1, s0)
    wall = (t1 - t0) * 1e3
    assert abs(led["wall_ms"] - wall) < 1e-3 * wall          # the identity, to 0.1 %
    assert led["empty_ms"] >= (0.03 + 0.05 + 0.02) * 1e3
    assert led["between_steps_ms"] >= led["steps"] - 3       # a millisecond a step but each burst's last
    assert all(led[key] > 0 for key in ("schedule_ms", "dispatch_ms", "block_ms", "attribute_ms",
                                        "emit_ms", "admit_ms"))
    assert led["admit_ms"] < led["dispatch_ms"]
    assert 0 <= led["starved_ms"] <= wall - led["empty_ms"]
    assert 0 <= led["ticks_ready_at_retire"] <= led["ticks"]


@pytest.mark.parametrize("depth", [1, 0])
def test_the_chip_starves_between_ticks_only_without_a_pipeline(toy, depth):
    from serving_toys import prompts

    srv, clock = toy(depth)
    assert srv.submit(prompts([7])[0], max_new_tokens=12)
    for _ in range(3):
        srv.step()
    before = srv.tick_stats()
    for _ in range(5):                                       # the row decodes throughout
        srv.step()
        time.sleep(0.001)
    after = srv.tick_stats()
    assert srv.has_work() and after["empty_ms"] == before["empty_ms"]
    starved = after["starved_ms"] - before["starved_ms"]
    if depth:
        assert starved == 0 and bool(srv._cb._inflight)      # a tick was always in flight
    else:
        assert starved >= 5.0 and not srv._cb._inflight      # from each fetch to the next dispatch
    while srv.has_work():
        srv.step()


class Fetched:
    """A tick's packed result whose readiness the test decides."""

    def __init__(self, packed, ready):
        self.packed, self.ready = packed, ready

    def is_ready(self):
        return self.ready

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.packed)


@pytest.mark.parametrize("ready", [True, False])
def test_a_tick_whose_result_was_on_hand_before_the_fetch_is_counted(toy, ready):
    from serving_toys import prompts

    srv, clock = toy(1)
    assert srv.submit(prompts([7])[0], max_new_tokens=6)
    srv.step()                                               # tick 1 dispatched, in flight
    (rec,) = srv._cb._inflight[0].values()
    rec.packed = Fetched(rec.packed, ready)
    before = srv.tick_stats()["ticks_ready_at_retire"]
    srv.step()                                               # tick 2 dispatched, tick 1 retired
    assert srv.tick_stats()["ticks_ready_at_retire"] - before == int(ready)
    while srv.has_work():
        srv.step()


# sha256 of the lowered text of the toy engine's plain tick, its fused-prefill tick (a 16-token
# chunk) and its row update (3 slots of 64, read 32; the tests' eight CPU devices), recorded on the parent of the PR that brought
# the ledger (8d78cc0): counters and spans are the host's, and nothing of them reaches a program
PARENTS_PROGRAMS = {
    "plain": "52d5fa81a79696dbac4c0e46faf65d5909fa46cd96d4b31fd7866e0ea1fda4fe",
    "fused": "5baa6e90188b5dd15b0588d9854ff85e982c674f04b1ced2fcd160281b515cd2",
    "set_row": "b8b2d236e4929872e12f2805df3ffeb0f9de91a2f2b7db42982c7d960a7874ca",
}


@pytest.mark.parametrize("program", sorted(PARENTS_PROGRAMS))
def test_the_ticks_and_the_row_update_lower_to_the_parents_text(toy, program):
    import hashlib

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn, compile_row_update_fn

    cb = toy(1)[0]._cb
    pool = cb._pools[0]
    if program == "set_row":
        row = jax.ShapeDtypeStruct((pool.n_slots,), jnp.int32)
        text = compile_row_update_fn(cb.mesh, cb.cfg, pool.n_slots, donate=cb.donate_cache).lower(
            row, row, 0, 0, 0).as_text()
    else:
        chunk = 16 if program == "fused" else None
        fn = compile_pool_tick_fn(cb.mesh, cb.cfg, cb._eng.param_shardings, pool.n_slots, pool.length,
                                  1, cb.temperature, cb.top_k, cb.top_p, eos_token_id=cb.eos_token_id,
                                  read_len=32, chunk=chunk, donate=cb.donate_cache)[0]
        text = fn.lower(*cb._tick_arg_structs(pool, chunk)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_PROGRAMS[program]
