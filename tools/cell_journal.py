"""Run one benchmark cell through the harness, as the driver runs it, and
print what the program's build journal (``telemetry/compile_log.py``) says
of it afterwards: the set-up by phase and by program family, the phases and
builds that raised the allocator's lifetime peak, the steady floor, and the
programs built after the measured window opened. The harness is not edited
and does not know: the window's opening instant (``since_t``) is noted by a
``bench:window`` span swapped into the runner's module for the run.

    python3 tools/cell_journal.py --workload serve-gpt2-medium-chat --seed 7 --seconds 40
    ... --hub 1      the same cell with the telemetry hub on (what the hub costs)
    ... --ledger 1   a serving cell: where the server's wall time went over the window

``--ledger 1`` prints the host ledger's delta over the measured window
(``ServingEngine.tick_stats()``, docs/telemetry.md "The serving loop's ledger")
as shares of the window and as ms a step, with ``starved`` and the ticks whose
result was ready before the host asked; with ``--trace 1`` also the device-idle
seconds of the harness's own trace split over the host's phases
(``timeline.idle_by_phase``) beside the counters, and the trace's count of tick
programs run in the window beside the counters' ticks (a trace the profiler cut
short holds fewer). Every serving runner inherits ``serve.Runner``'s loop, whose
``_measure`` is handed the two reads: the swap notes them there.

The result line is the harness's own, with ``journal`` (``summary()``) beside
it; the tables go to standard error and, with ``--out``, the whole summary
and the journal's entries to a JSON file. ``--rehearse`` drops the demand for
a TPU (control flow on the CPU; no number from it is a device number)."""

import argparse
import contextlib
import json
import os
import sys
import time

from cell_variant import T_PROCESS_START, swapped  # also puts the repo root on sys.path


@contextlib.contextmanager
def noting_window(harness, noted):
    """The runners' ``span`` notes ``time.monotonic()`` as ``bench:window``
    opens (``serve`` and ``train`` hold the loops every runner inherits), and
    marks the window as a phase of the CALLER's: the journal then holds a
    memory reading as it opens and one as it closes, so a peak raised by
    the steady ticks or steps shows as ``phase:window``."""
    from benchmark.runners import serve, train
    from deepspeed_tpu.telemetry import compile_log

    @contextlib.contextmanager
    def window():
        noted.setdefault("since_t", time.monotonic())
        compile_log.mark("window")
        try:
            with harness.span("window"):
                yield
        finally:
            compile_log.mark(compile_log.RUNNING)

    def span(name):
        return window() if name == "window" else harness.span(name)

    with swapped(serve, "span", span), swapped(train, "span", span):
        yield


@contextlib.contextmanager
def noting_ledger(noted):
    """``serve.Runner._measure`` notes the two ``tick_stats()`` reads it is
    handed and the window they bracket; the harness's trace reader notes the
    program's own ``dstpu:`` host spans and the device's busy intervals of the
    capture it reads (the benchmark's ``Trace`` keeps ``bench:`` spans only, and
    the capture's files are gone when the harness returns)."""
    from benchmark.reduce import xplane
    from benchmark.runners import serve
    from ds_trace_timeline import read_xplane

    measure, read = serve.Runner._measure, xplane.read_xplane

    def noting_measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1):
        result = measure(self, closed, seconds, t_open, t_close, t_end, setup_s, stats0, stats1)
        # (a traced line holds no end-to-end metric: what the window read under the profiler)
        noted.update(stats0=stats0, stats1=stats1, window_s=t_close - t_open,
                     end_to_end=result["end_to_end"])
        return result

    def noting_read(path):
        trace = read(path)
        noted["trace"] = trace
        noted["host"], noted["busy"] = read_xplane(path)
        return trace

    with swapped(serve.Runner, "_measure", noting_measure), \
            swapped(xplane, "read_xplane", noting_read):
        yield


def ledger_report(noted, line):
    """The window's ledger as a dict: rows in ms, as shares of the window and
    ms a step; with a trace, ``idle_by_phase`` and the harness's idle share."""
    from deepspeed_tpu.serving.loadgen import LEDGER_ROWS, ledger_delta
    from deepspeed_tpu.telemetry.timeline import idle_by_phase

    if "stats1" not in noted:
        return {"error": "no serving window was measured (a training cell?)"}
    led = ledger_delta(noted["stats1"], noted["stats0"])
    if led is None:
        return {"error": "tick_stats() holds no host ledger"}
    window_ms = noted["window_s"] * 1000.0
    steps = max(1, led["steps"])
    rep = {"window_s": noted["window_s"], "steps": led["steps"], "ticks": led["ticks"],
           "end_to_end": noted["end_to_end"],
           "rows_ms": {key: led[key] for key in LEDGER_ROWS},
           "share_of_window": {key: led[key] / window_ms for key in LEDGER_ROWS},
           "ms_per_step": {key: led[key] / steps for key in LEDGER_ROWS + ("admit_ms",)},
           "residual_pct": 100.0 * (led["wall_ms"] - window_ms) / window_ms,
           "starved_ms": led["starved_ms"],
           "starved_share": led["starved_ms"] / window_ms,
           "ticks_ready_at_retire": led["ticks_ready_at_retire"],
           "host_bound_tick_share": led["ticks_ready_at_retire"] / max(1, led["ticks"])}
    since = lambda key: noted["stats1"].get(key, 0) - noted["stats0"].get(key, 0)
    by_blocks, slots = since("block_write_ticks"), since("capacity_tokens") / max(1, led["ticks"])
    # the rows' write by blocks: the live rows whose blocks the kernel moved, of the rows those ticks held
    rep["block_write"] = {"ticks": by_blocks, "rows": since("block_write_rows"), "bytes": since("block_write_bytes"),
                          "share_of_rows": since("block_write_rows") / max(1.0, by_blocks * slots)}
    if "host" in noted:
        from benchmark.reduce import reductions as R

        window = R.span_window(noted["trace"], "bench:window")
        phases = idle_by_phase(noted["busy"], noted["host"], window=window)
        extent = (window[1] - window[0]) / 1e9
        device = line["device"]
        programs = sum(len([e for e in R.clip(dev.modules, window) if e[0].startswith("jit_run(")])
                       for dev in noted["trace"].devices.values())
        rep["trace"] = {
            "window_s": extent, "idle_by_phase_s": dict(sorted(phases.items(), key=lambda kv: -kv[1])),
            "idle_by_phase_total_share": sum(phases.values()) / extent,
            "harness_idle_share": 1.0 - device["busy_s"] / device["window_s"],
            "counters_empty_plus_starved_share": (led["empty_ms"] + led["starved_ms"]) / window_ms,
            "tick_programs_in_trace": programs}
    return rep


def format_ledger(rep):
    if "error" in rep:
        return f"== host ledger: {rep['error']} ==\n"
    lines = [f"== host ledger over the window: {rep['window_s']:.3f} s, {rep['steps']} steps, "
             f"{rep['ticks']} ticks (rows sum to the window {rep['residual_pct']:+.4f} %) =="]
    for key, share in rep["share_of_window"].items():
        lines.append(f"  {key:<18} {share:>7.2%} of the window   {rep['ms_per_step'][key]:>9.4f} ms a step")
    lines.append(f"  (admit_ms, inside dispatch_ms: {rep['ms_per_step']['admit_ms']:.4f} ms a step)")
    lines.append("  the window's end-to-end readings: "
                 + "  ".join(f"{k} {v:.4f}" for k, v in rep["end_to_end"].items() if v is not None))
    lines.append(f"  starved {rep['starved_share']:.3%} of the window ({rep['starved_ms']:.1f} ms)   "
                 f"ticks ready at retire {rep['ticks_ready_at_retire']} = "
                 f"{rep['host_bound_tick_share']:.2%} of ticks")
    bw = rep["block_write"]
    lines.append(f"  rows' write by blocks: {bw['ticks']} ticks, {bw['rows']} live rows' blocks = "
                 f"{bw['share_of_rows']:.2%} of those ticks' rows, {bw['bytes'] / 1e6:.1f} MB fetched and stored")
    tr = rep.get("trace")
    if tr:
        lines.append(f"== the trace's {tr['window_s']:.3f} s window: idle share {tr['harness_idle_share']:.2%} "
                     f"by the harness (1 - busy_s / window_s), {tr['idle_by_phase_total_share']:.2%} by "
                     f"idle_by_phase, empty + starved {tr['counters_empty_plus_starved_share']:.2%} by the "
                     f"counters; {tr['tick_programs_in_trace']} jit_run programs in the trace's window ==")
        idle = sum(tr["idle_by_phase_s"].values()) or 1.0
        for phase, seconds in tr["idle_by_phase_s"].items():
            lines.append(f"  {phase:<18} {seconds:>9.4f} s  {seconds / idle:>7.2%} of idle")
    return "\n".join(lines) + "\n"


@contextlib.contextmanager
def hub_on(trace_file):
    """Every engine the runner builds gets a ``telemetry`` block: the entry
    points take their config as a dict, so the swap adds the block there."""
    import deepspeed_tpu
    from deepspeed_tpu import inference

    block = {"enabled": True, "trace_file": trace_file}

    def with_block(fn):
        def call(*args, config=None, **kw):
            return fn(*args, config=dict(config or {}, telemetry=block), **kw)
        return call

    with contextlib.ExitStack() as stack:
        stack.enter_context(swapped(deepspeed_tpu, "init_inference",
                                    with_block(deepspeed_tpu.init_inference)))
        stack.enter_context(swapped(deepspeed_tpu, "initialize",
                                    with_block(deepspeed_tpu.initialize)))
        stack.enter_context(swapped(inference, "ContinuousBatchingEngine",
                                    with_block(inference.ContinuousBatchingEngine)))
        yield


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--hub", type=int, choices=(0, 1), default=0)
    ap.add_argument("--ledger", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--manifest", default=None, help="default: the checkout's BENCHMARK.json")
    ap.add_argument("--set", action="append", default=[], metavar="PATH=VALUE")
    ap.add_argument("--out", default=None, metavar="FILE.json")
    args = ap.parse_args(argv)

    from benchmark import harness
    from deepspeed_tpu.serving.loadgen import format_setup
    from deepspeed_tpu.telemetry import compile_log

    manifest = args.manifest or os.path.join(harness.ROOT, "BENCHMARK.json")
    noted = {}
    with contextlib.ExitStack() as stack:
        stack.enter_context(noting_window(harness, noted))
        if args.ledger:
            stack.enter_context(noting_ledger(noted))
        if args.hub:
            tmp = os.environ.get("TMPDIR") or "/tmp"
            stack.enter_context(hub_on(os.path.join(tmp, f"cell_journal_{os.getpid()}.jsonl")))
        line = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                                bool(args.trace), require_tpu=not args.rehearse,
                                overrides=args.set, t_process_start=T_PROCESS_START)
    summary = compile_log.summary(since_t=noted.get("since_t"))
    sys.stderr.write(format_setup(summary))
    ledger = ledger_report(noted, line) if args.ledger else None
    if ledger is not None:
        sys.stderr.write(format_ledger(ledger))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(dict(line=line, hub=args.hub, summary=summary, ledger=ledger,
                           journal=compile_log.journal()), fh, indent=1, default=float)
    print(json.dumps(dict(line, hub=args.hub, journal=summary, **({"ledger": ledger} if ledger else {})),
                     default=float), flush=True)
    return line


if __name__ == "__main__":
    main()
