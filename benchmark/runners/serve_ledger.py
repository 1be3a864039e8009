"""Serving runner that also reads the program's host ledger: ``runners/serve.py``'s
runner (the same loop, clocks, warm-up and reference comparison), plus what
``ServingEngine.tick_stats()`` says of where the server's wall time went between
the window's two reads (the program's docs/telemetry.md, "The serving loop's
ledger"), as observations a ``"reduction": "value"`` metric file can read.

No committed cell names this runner yet (PR 53: a configuration's file names the
comparison of every runner its cells use, and a program PR edits no such file;
PERF.md section 7 has the edit). ``tests/benchmark/test_bench_serve_ledger.py`` runs it
on the toy chat cell; ``tools/cell_journal.py --ledger 1`` reads the same rows in
any committed cell meanwhile.

``step_schedule_ms``      ms a ``step()`` in expiry, policy order and handovers
``tick_admit_ms``         ms a ``step()`` in the batcher's admission loop (inside
                          ``tick_dispatch_ms``)
``tick_attribute_ms``     ms a ``step()`` in the host's work on fetched results
``step_emit_ms``          ms a ``step()`` from the tick's return to its own: fan-out to
                          requests, finishing, gauges
``step_between_ms``       ms a ``step()`` outside it while a request is held: the caller's
                          time (here this runner's own loop)
``step_other_ms``         ms a ``step()`` of its wall that no row above names
``empty_share_pct``       % of the window outside ``step()`` with no request held at all
``starved_share_pct``     % of the window in which a request was held and no tick was in
                          flight: what the host costs the chip
``host_bound_tick_pct``   % of the ticks whose result was on hand before the host came to
                          fetch it: the host, not the device, set that tick's pace (one or
                          more after every stall of the host's process, none otherwise)
``ledger_residual_pct``   the rows' sum against the window's wall on this runner's clock,
                          % of the window: the identity's error

A cell's group and a configuration's ``compare`` group named after this module
(``serve_ledger``) are read where they are there, ``serve``'s where not: the model,
the weights and the reference are the same. A program whose ``tick_stats()`` lacks a
key gives no reading for what is made of it.
"""

from benchmark.runners import serve

NAME = "serve_ledger"

# the rows whose sum is the server's wall time between two reads
ROWS = ("empty_ms", "between_steps_ms", "schedule_ms", "dispatch_ms", "block_ms", "attribute_ms",
        "emit_ms", "step_other_ms")


def ledger_observations(stats0, stats1, window_s):
    delta = lambda key: (stats1[key] - stats0[key] if key in stats0 and key in stats1 else None)
    per = lambda total, count: total / count if total is not None and count else None
    share = lambda ms: 100.0 * ms / (window_s * 1e3) if ms is not None else None
    steps, ticks = delta("steps"), delta("ticks")
    ready = delta("ticks_ready_at_retire")
    rows = [delta(key) for key in ROWS]
    return dict(
        empty_share_pct=share(delta("empty_ms")),
        starved_share_pct=share(delta("starved_ms")),
        host_bound_tick_pct=100.0 * ready / ticks if ready is not None and ticks else None,
        step_schedule_ms=per(delta("schedule_ms"), steps),
        tick_admit_ms=per(delta("admit_ms"), steps),
        tick_attribute_ms=per(delta("attribute_ms"), steps),
        step_emit_ms=per(delta("emit_ms"), steps),
        step_between_ms=per(delta("between_steps_ms"), steps),
        step_other_ms=per(delta("step_other_ms"), steps),
        ledger_residual_pct=(share(sum(rows) - window_s * 1e3)
                             if all(ms is not None for ms in rows) else None))


class Runner(serve.Runner):
    def __init__(self, ctx):
        own = lambda groups: dict(groups, serve=groups[NAME]) if NAME in groups else groups
        config = dict(ctx["config"], compare=own(ctx["config"]["compare"]))
        super().__init__(dict(ctx, cell=own(ctx["cell"]), config=config))

    def _measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        result = super()._measure(closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1)
        result["obs"].update(ledger_observations(stats0, stats1, result["window_s"]))
        return result
