"""Run one benchmark cell through the harness, as the driver runs it, and
print what the program's build journal (``telemetry/compile_log.py``) says
of it afterwards: the set-up by phase and by program family, the phases and
builds that raised the allocator's lifetime peak, the steady floor, and the
programs built after the measured window opened. The harness is not edited
and does not know: the window's opening instant (``since_t``) is noted by a
``bench:window`` span swapped into the runner's module for the run.

    python3 tools/cell_journal.py --workload serve-gpt2-medium-chat --seed 7 --seconds 40
    ... --hub 1      the same cell with the telemetry hub on (what the hub costs)

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
        if args.hub:
            tmp = os.environ.get("TMPDIR") or "/tmp"
            stack.enter_context(hub_on(os.path.join(tmp, f"cell_journal_{os.getpid()}.jsonl")))
        line = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                                bool(args.trace), require_tpu=not args.rehearse,
                                overrides=args.set, t_process_start=T_PROCESS_START)
    summary = compile_log.summary(since_t=noted.get("since_t"))
    sys.stderr.write(format_setup(summary))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(dict(line=line, hub=args.hub, summary=summary,
                           journal=compile_log.journal()), fh, indent=1, default=float)
    print(json.dumps(dict(line, hub=args.hub, journal=summary), default=float), flush=True)
    return line


if __name__ == "__main__":
    main()
