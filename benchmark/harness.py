"""The harness: one cell, one seed, one window -> the contract's JSON object.

``run_cell`` is the Python entry point (tests call it with toy cell files
and ``require_tpu=False``; it names the platform it ran on in ``device``).
``run.py`` is the command line and has no way round the platform check.

Whatever belongs to one configuration, cell, traffic mix or per-layer metric
is a file found by its name in the manifest (``BENCHMARK.json``):
``configs[].file``, ``cells/<workload>.json``, ``traffic/<traffic>.json``,
``layer_metrics/<metric>.json`` under the manifest's ``paths``; a cell's
``runner`` names a module of ``benchmark/runners``.
"""

import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchmarkError(Exception):
    """A fault of the benchmark or its environment (no TPU, a missing file, a
    reducer that found nothing): exit non-zero, print no result."""


def span(name):
    """A host span in the profiler's own trace (``bench:<name>``): what the
    idle-gap attribution reads. Costs a no-op when no trace is on."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


def emit(**fields):
    """An earlier, free-form line of output."""
    print(json.dumps(fields, default=float), flush=True)


class CompileLog:
    """Counts JAX's own compile events: executables requested (each a
    persistent-cache hit or a compilation), cache hits, seconds spent."""

    def __init__(self):
        import jax

        self.programs, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.programs, self.seconds, self.cache_hits)

    def since(self, mark):
        return (self.programs - mark[0], self.seconds - mark[1], self.cache_hits - mark[2])


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def find_file(dirs, *parts):
    for d in dirs:
        path = os.path.join(d, *parts)
        if os.path.exists(path):
            return path
    raise BenchmarkError(f"no {os.path.join(*parts)} under {dirs}")


def apply_overrides(target: dict, overrides):
    """``["traffic.arrivals.rate_per_s=12", ...]`` -> set in ``target``
    (``{"cell":..., "traffic":..., "config":...}``). For sweeps and tests through
    ``run_cell``; the command line has no such option, so a cell named there
    is the cell as committed."""
    for item in overrides or ():
        key, _, raw = item.partition("=")
        try:
            value = json.loads(raw)
        except json.JSONDecodeError:
            value = raw
        node = target
        *path, last = key.split(".")
        for part in path:
            node = node.setdefault(part, {})
        node[last] = value


@contextlib.contextmanager
def traced(enabled, tmp_root):
    """``jax.profiler`` over the body when ``enabled``; yields a dict that
    holds the ``Trace`` afterwards. The trace's files live in a temporary
    directory that is gone when this returns."""
    out = {"trace": None}
    if not enabled:
        yield out
        return
    import jax

    from benchmark.reduce import xplane

    tmp = tempfile.mkdtemp(prefix="bench_trace_", dir=tmp_root)
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no Python frames: they slow the host loop under test
        options.host_tracer_level = 2    # TraceAnnotation spans
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out["trace"] = xplane.read_xplane(xplane.find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def device_fields(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices), memory_peak_bytes=int(max(peaks)))


def run_cell(manifest_path, workload, seed, seconds, trace, *, require_tpu=True,
             overrides=None, t_process_start=None):
    """Run one cell; return the contract's result object (a dict)."""
    t_start = time.perf_counter() if t_process_start is None else t_process_start
    base = ROOT  # a manifest's paths are relative to the checkout's root
    manifest = load_json(manifest_path)
    dirs = [os.path.join(base, p) for p in manifest["paths"]]
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise BenchmarkError(f"no workload {workload!r} in {manifest_path}")
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    files = {
        "cell": load_json(find_file(dirs, "cells", workload + ".json")),
        "traffic": load_json(find_file(dirs, "traffic", entry["traffic"] + ".json")),
        "config": load_json(os.path.join(base, config_entry["file"])),
    }
    apply_overrides(files, overrides)
    cell, traffic, config = files["cell"], files["traffic"], files["config"]
    chips = int(entry["chips"])

    import jax

    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    log = CompileLog()
    all_devices = jax.devices()
    platform = all_devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchmarkError(f"JAX found platform {platform!r} ({all_devices[0].device_kind}), "
                             f"not a TPU: nothing to measure here")
    if len(all_devices) < chips:
        raise BenchmarkError(f"the cell asks for {chips} chip(s), JAX sees {len(all_devices)}")
    devices = all_devices[:chips]
    peaks_table = load_json(find_file(dirs, "peaks.json"))
    kind = devices[0].device_kind
    if kind not in peaks_table:
        if require_tpu:
            raise BenchmarkError(f"device kind {kind!r} is not in peaks.json")
        peaks = None  # a rehearsal off the chip: no roofline, no MFU
    else:
        peaks = peaks_table[kind]

    from benchmark import readers

    runner_mod = importlib.import_module("benchmark.runners." + cell["runner"])
    ctx = dict(cell=cell, traffic=traffic, config=config, seed=int(seed), devices=devices,
               all_devices=all_devices, chips=chips, emit=emit)
    runner = runner_mod.Runner(ctx)
    emit(phase="start", workload=workload, seed=int(seed), seconds=seconds, trace=int(trace),
         platform=platform, kind=kind, devices=len(all_devices), compile_cache_dir=cache_dir)

    mark = log.mark()
    runner.setup()                       # engine, warm-up
    setup_programs, compile_s, cache_hits = log.since(mark)
    window_len = min(seconds, cell.get("trace_seconds", 8)) if trace else seconds
    tmp_root = os.environ.get("TMPDIR") or tempfile.gettempdir()
    mark = log.mark()
    with traced(trace, tmp_root) as holder:
        result = runner.window(window_len, t_start)   # pre-roll, then the measured window
    compiled_in_window = log.since(mark)[0]
    device = device_fields(devices)      # the engine's peak: no reference has run yet
    verdict = runner.finish()            # engine released, then the float32 reference: `correct`
    emit(phase="compare", **verdict["fields"])

    obs = dict(result["obs"], compile_s=compile_s, programs_in_setup=setup_programs,
               cache_hits_in_setup=cache_hits, programs_compiled_in_window=compiled_in_window,
               peak_hbm_gb=device["memory_peak_bytes"] / 1e9)
    emit(phase="observations", **obs)
    if compiled_in_window:
        emit(phase="warning", what=f"{compiled_in_window} program(s) compiled inside the window")

    metrics = {}
    if not trace:
        for m in manifest["end_to_end"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            if m["name"] not in result["end_to_end"]:
                raise BenchmarkError(f"runner did not report {m['name']}")
            metrics[m["name"]] = {"value": float(result["end_to_end"][m["name"]]),
                                  "unit": m["unit"]}
    else:
        tr = holder["trace"]
        from benchmark.reduce import reductions as R

        window = R.span_window(tr, "bench:window")
        ctx_r = readers.Context(obs=obs, config=config, cell=cell, peaks=peaks, chips=chips,
                                trace=tr, window=window)
        for m in manifest["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            value = readers.evaluate(readers.load_metric(dirs, m["name"])["reader"], ctx_r)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        busy = R.busy_s(tr, window)
        if busy is None:
            raise BenchmarkError("the trace holds no device op: the reducer found no device "
                                 "plane (a fault of the benchmark, not of the outputs)")
        device["busy_s"] = busy
        device["window_s"] = ((window[1] - window[0]) / 1e9 if window is not None
                              else result["window_s"])
    line = dict(correct=bool(verdict["ok"]), attempted=int(result["attempted"]),
                failed=int(result["failed"]), metrics=metrics, device=device)
    if trace:
        line["breakdown"] = dict(device_ops=R.top_ops(tr, 10, window),
                                 idle_gaps=R.idle_gaps(tr, 10, window))
    # each number compared beside its limit, as the last line of standard error too: where a
    # run is not correct, the end of that stream is what the driver's record keeps
    print(json.dumps(dict(phase="compare", correct=line["correct"], **verdict["fields"]),
                     default=float), file=sys.stderr, flush=True)
    return line


def main(argv, t_process_start):
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the attached TPU.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run_cell(os.path.join(ROOT, "BENCHMARK.json"), args.workload, args.seed,
                        args.seconds, bool(args.trace), t_process_start=t_process_start)
    except BenchmarkError as e:
        sys.exit(f"benchmark: {e}")
    except ImportError as e:  # a directory that holds the benchmark alone: no program to measure
        sys.exit(f"benchmark: the program is not here ({e})")
    print(json.dumps(line), flush=True)
