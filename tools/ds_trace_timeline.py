#!/usr/bin/env python
"""Reconstruct per-request span timelines from a telemetry JSONL trace
and export them as Chrome trace-event JSON for Perfetto.

The span layer (``docs/telemetry.md``, "Request tracing") writes one
``kind: "span"`` line per closed span into the same trace every other
telemetry event rides. This CLI groups them by ``trace_id``, stitches
the parent/child tree (a ``migration`` span bridges replica tags, so a
request that moved replicas reconstructs as ONE timeline), reports
orphans — spans whose ``parent_id`` the file cannot back — and writes a
``--perfetto`` JSON artifact loadable in https://ui.perfetto.dev or
chrome://tracing: one process lane per replica, one thread lane per
trace_id.

Usage:
    python tools/ds_trace_timeline.py runs/trace.jsonl
    python tools/ds_trace_timeline.py runs/trace.jsonl --perfetto out.json
    python tools/ds_trace_timeline.py runs/trace.jsonl --trace r0/5 --json
    python tools/ds_trace_timeline.py runs/trace.jsonl --strict  # orphans -> exit 1
    python tools/ds_trace_timeline.py runs/trace.jsonl --xplane runs/xla_trace
    python tools/ds_trace_timeline.py --xplane runs/xla_trace --by-phase

``--xplane`` takes the ``jax.profiler`` capture (its ``.xplane.pb`` or the
directory it was written under) that ``Telemetry.start_capture`` made
beside this JSONL: the capture's ``dstpu:clock_sync monotonic_ns=<n>``
annotation and the JSONL's ``profile_window`` event hold the same clock
reading, so every JSONL span is placed on the profiler's axis, and each
device-idle gap over 1 ms is printed with the span (the program's
``dstpu:`` host spans or a placed JSONL span) to blame: the narrowest of
those that cover nearly as much of the gap as any does.
``--by-phase`` SPLITS the capture's device-idle seconds over what the host
was doing (``timeline.idle_by_phase``): the serving loop's ``dstpu:`` spans
under the names of ``tick_stats()``'s host ledger, ``empty`` between a
``dstpu:serve.emptied`` and a ``dstpu:serve.refilled`` marker,
``between_steps`` outside every ``dstpu:serve.step``. The spans are in the
xplane, so it needs no JSONL and no ``clock_sync``.
Reading an xplane needs jax (``jax.profiler.ProfileData``); nothing else
here does.

Deliberately stdlib-only (``telemetry/timeline.py`` is loaded by file
path, no package import): runs anywhere, including laptops holding
traces scp'd off a pod — same portability contract as
``ds_trace_report.py``.
"""

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TIMELINE_PY = os.path.join(REPO, "deepspeed_tpu", "telemetry", "timeline.py")
_ALIAS = "_ds_trace_timeline_mod"


def load_timeline_module():
    """The stdlib-only read-side module, loaded by file path so this
    tool never imports ``deepspeed_tpu`` (whose __init__ pulls in jax)."""
    if _ALIAS in sys.modules:
        return sys.modules[_ALIAS]
    spec = importlib.util.spec_from_file_location(_ALIAS, _TIMELINE_PY)
    module = importlib.util.module_from_spec(spec)
    sys.modules[_ALIAS] = module
    spec.loader.exec_module(module)
    return module


def _fmt_ms(v):
    return f"{v:,.3f}".rstrip("0").rstrip(".")


def timeline_row(tl):
    """One machine-readable summary row per reconstructed timeline."""
    return {
        "trace_id": tl.trace_id,
        "spans": len(tl.spans),
        "orphans": len(tl.orphans),
        "duration_ms": round(tl.duration_ms, 3),
        "replicas": tl.replicas,
        "migrated": any(s.kind == "migration" for s in tl.spans),
        "dominant": tl.dominant_kind(),
        "attribution": {k: round(v, 3)
                        for k, v in sorted(tl.attribution().items())},
    }


def format_summary(timelines, skipped_spans):
    tls = sorted(timelines.values(), key=lambda t: -t.duration_ms)
    n_spans = sum(len(t.spans) for t in tls)
    n_orphans = sum(len(t.orphans) for t in tls)
    migrated = sum(1 for t in tls if any(s.kind == "migration"
                                         for s in t.spans))
    lines = [f"== timelines ({len(tls)} traces, {n_spans} spans, "
             f"{n_orphans} orphans, {migrated} migrated) =="]
    if skipped_spans:
        lines.append(f"   ({skipped_spans} non-span events ignored)")
    head = (f"{'trace_id':<20} {'spans':>6} {'dur_ms':>12} "
            f"{'dominant':>18}  replicas")
    lines.append(head)
    lines.append("-" * len(head))
    for tl in tls:
        reps = "->".join(str(r) for r in tl.replicas) or "-"
        mark = " ORPHANS" if tl.orphans else ""
        lines.append(f"{tl.trace_id:<20} {len(tl.spans):>6} "
                     f"{_fmt_ms(tl.duration_ms):>12} "
                     f"{tl.dominant_kind() or '-':>18}  {reps}{mark}")
    return "\n".join(lines) + "\n"


def format_one(tl):
    """The drill-down view: the span tree of one trace_id, indented by
    causal depth, timestamps relative to the timeline start."""
    lines = [f"== trace {tl.trace_id} — {_fmt_ms(tl.duration_ms)} ms, "
             f"{len(tl.spans)} spans, replicas "
             f"{'->'.join(str(r) for r in tl.replicas) or '-'} =="]
    origin = tl.t_start
    for s in tl.spans:
        pad = "  " * tl.depth(s)
        rep = f" @{s.replica}" if s.replica is not None else ""
        orphan = "  [ORPHAN: parent missing]" if s in tl.orphans else ""
        extras = " ".join(f"{k}={v}" for k, v in sorted(s.attrs.items()))
        lines.append(f"  {(s.t0 - origin) * 1000.0:>10.3f} ms "
                     f"{pad}{s.kind} ({_fmt_ms(s.dur_ms)} ms){rep}"
                     + (f"  {extras}" if extras else "") + orphan)
    path = tl.critical_path()
    lines.append("  critical path: "
                 + "   ".join(f"{k} {_fmt_ms(v)} ms"
                              for k, v in sorted(path.items(),
                                                 key=lambda kv: -kv[1])))
    attr = tl.attribution()
    lines.append("  attribution:   "
                 + "   ".join(f"{k} {_fmt_ms(v)} ms"
                              for k, v in sorted(attr.items(),
                                                 key=lambda kv: -kv[1])))
    return "\n".join(lines) + "\n"


def read_xplane(path):
    """(host events, device busy intervals) of a ``jax.profiler`` capture:
    the host plane's ``dstpu:`` annotations as (name, start_ns, dur_ns),
    and every device op's (start_ns, end_ns) — a TPU's ``XLA Ops`` lines,
    or XLA:CPU's worker threads in a rehearsal."""
    import glob

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    import warnings

    host, busy = [], []
    with warnings.catch_warnings():  # an event's stats warn once per read on this jaxlib
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            on_tpu = plane.name.startswith("/device:TPU:")
            if not on_tpu and plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                if on_tpu:
                    if line.name == "XLA Ops":
                        busy += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                                 for e in line.events]
                elif line.name.startswith("tf_"):
                    busy += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                             for e in line.events if "hlo_module" in dict(e.stats)]
                else:
                    host += [(e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events if e.name.startswith("dstpu:")]
    host.sort(key=lambda e: e[1])
    return host, busy


def xplane_report(tm, events, xplane_path):
    """The ``--xplane`` view as a dict: the clock offset, how it was
    checked against the JSONL, the placed spans, the blamed idle gaps."""
    host, busy = read_xplane(xplane_path)
    offset = tm.clock_offset_ns(host)
    if offset is None:
        raise ValueError("the capture holds no dstpu:clock_sync annotation "
                         "(not started through Telemetry.start_capture?)")
    sync = next(int(n[len(tm.CLOCK_SYNC_PREFIX):]) for n, _, _ in host
                if n.startswith(tm.CLOCK_SYNC_PREFIX))
    windows = [e for e in events if e.get("kind") == "profile_window"
               and e.get("event") == "start"]
    placed = tm.place_on_xplane(tm.spans_of(events), offset)
    program = [e for e in host if not e[0].startswith(tm.CLOCK_SYNC_PREFIX)]
    gaps = tm.blame_idle_gaps(busy, program + placed)
    return {
        "clock_offset_ns": offset,
        "clock_sync_monotonic_ns": sync,
        "profile_window_matches": any(w.get("monotonic_ns") == sync for w in windows),
        "host_spans": len(program),
        "placed_spans": len(placed),
        "placed": placed,
        "idle_gaps": gaps,
    }


def by_phase_report(tm, xplane_path):
    """The ``--by-phase`` view as a dict: the capture's idle seconds split
    over the host's phases, beside the busy and the whole extent."""
    host, busy = read_xplane(xplane_path)
    phases = tm.idle_by_phase(busy, host)
    extent = (max(e for _, e in busy) - min(s for s, _ in busy)) / 1e9 if busy else 0.0
    return {"extent_s": extent, "idle_s": sum(phases.values()), "host_spans": len(host),
            "idle_by_phase": dict(sorted(phases.items(), key=lambda kv: -kv[1]))}


def format_by_phase(rep):
    idle, extent = rep["idle_s"], rep["extent_s"]
    lines = [f"== device idle by host phase: {idle:.4f} s idle of {extent:.4f} s "
             f"({idle / extent:.1%}), {rep['host_spans']} dstpu: host spans =="
             if extent else "== no device op in the capture =="]
    for phase, seconds in rep["idle_by_phase"].items():
        lines.append(f"  {phase:<16} {seconds:>10.4f} s  {seconds / idle:>6.1%} of idle  "
                     f"{seconds / extent:>6.1%} of the extent")
    return "\n".join(lines) + "\n"


def format_xplane(rep):
    lines = [f"== on the profiler's axis: offset {rep['clock_offset_ns']} ns "
             f"(clock_sync monotonic_ns={rep['clock_sync_monotonic_ns']}; "
             f"profile_window event {'matches' if rep['profile_window_matches'] else 'NOT FOUND'}), "
             f"{rep['host_spans']} dstpu: host spans, {rep['placed_spans']} JSONL spans placed =="]
    if not rep["idle_gaps"]:
        lines.append("no device-idle gap over 1 ms")
    for g in rep["idle_gaps"]:
        lines.append(f"  idle {_fmt_ms(g['gap_ms']):>10} ms  at {g['start_ns']} ns  "
                     f"{g['span']} (covers {g['covered']:.0%})")
    return "\n".join(lines) + "\n"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="per-request span timelines + Perfetto export from a "
                    "deepspeed_tpu telemetry JSONL trace")
    ap.add_argument("trace", nargs="?", default=None,
                    help="path to the JSONL trace file (not needed by --by-phase)")
    ap.add_argument("--trace-id", dest="trace_id", default=None,
                    metavar="TID",
                    help="drill into one trace_id (e.g. 'r0/5' or "
                         "'step:12'): full span tree + critical path")
    ap.add_argument("--perfetto", metavar="OUT", default=None,
                    help="write Chrome trace-event JSON here (load in "
                         "https://ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit summary rows as JSON instead of tables")
    ap.add_argument("--xplane", metavar="PB_OR_DIR", default=None,
                    help="the jax.profiler capture made beside this trace: "
                         "place the JSONL spans on its axis and blame each "
                         "device-idle gap over 1 ms (needs jax)")
    ap.add_argument("--by-phase", action="store_true", dest="by_phase",
                    help="with --xplane: split the capture's device-idle "
                         "seconds over the host's phases (the serving "
                         "ledger's rows); needs no JSONL trace")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 if any timeline has orphan spans (CI "
                         "round-trip gate)")
    args = ap.parse_args(argv)

    tm = load_timeline_module()
    if args.by_phase:
        if args.xplane is None:
            ap.error("--by-phase reads a profiler capture: give --xplane")
        try:
            rep = by_phase_report(tm, args.xplane)
        except OSError as e:
            print(f"error: --xplane: {e}", file=sys.stderr)
            return 2
        if args.as_json:
            print(json.dumps({"by_phase": rep}, indent=2))
        else:
            sys.stdout.write(format_by_phase(rep))
        if args.trace is None:
            return 0
    elif args.trace is None:
        ap.error("a JSONL trace is needed (only --xplane DIR --by-phase goes without)")
    try:
        events = list(tm.iter_events(args.trace))
    except OSError as e:
        print(f"error: cannot read {args.trace}: {e}", file=sys.stderr)
        return 2
    timelines = tm.build_timelines(events)
    if not timelines:
        print(f"no span events in {args.trace} (is request tracing "
              f"enabled? see docs/telemetry.md)", file=sys.stderr)
        return 1

    if args.trace_id is not None:
        tl = timelines.get(args.trace_id)
        if tl is None:
            print(f"error: no trace_id {args.trace_id!r} in the trace "
                  f"(have: {', '.join(sorted(timelines))})", file=sys.stderr)
            return 2
        if args.as_json:
            print(json.dumps(timeline_row(tl), indent=2, sort_keys=True))
        else:
            sys.stdout.write(format_one(tl))
    else:
        rows = [timeline_row(tl) for tl in timelines.values()]
        if args.as_json:
            rows.sort(key=lambda r: -r["duration_ms"])
            print(json.dumps({"timelines": rows}, indent=2, sort_keys=True))
        else:
            n_span_events = sum(1 for e in events if e.get("kind") == "span")
            sys.stdout.write(format_summary(
                timelines, len(events) - n_span_events))

    if args.xplane is not None:
        try:
            rep = xplane_report(tm, events, args.xplane)
        except (OSError, ValueError) as e:
            print(f"error: --xplane: {e}", file=sys.stderr)
            return 2
        if args.as_json:
            print(json.dumps({"xplane": rep}, indent=2, sort_keys=True))
        else:
            sys.stdout.write(format_xplane(rep))

    if args.perfetto is not None:
        doc = tm.to_chrome_trace(timelines)
        problems = tm.validate_chrome_trace(doc)
        if problems:
            for p in problems:
                print(f"error: export failed lint: {p}", file=sys.stderr)
            return 2
        with open(args.perfetto, "w") as fh:
            json.dump(doc, fh)
        n = sum(1 for ev in doc["traceEvents"] if ev.get("ph") == "X")
        print(f"wrote {n} span events to {args.perfetto} "
              f"(open in https://ui.perfetto.dev)", file=sys.stderr)

    orphans = sum(len(tl.orphans) for tl in timelines.values())
    if args.strict and orphans:
        print(f"error: {orphans} orphan span(s) — causality the trace "
              f"cannot back", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
