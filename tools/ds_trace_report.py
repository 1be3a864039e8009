#!/usr/bin/env python
"""Render per-metric p50/p95/max tables from a telemetry JSONL trace.

The trace is what the engines write with the ``telemetry`` config block
enabled (``docs/telemetry.md``): one JSON event per line, each carrying
``"schema": 1`` and a ``"kind"`` discriminator ("train_step",
"inference_request", "comm_summary", ...). This CLI aggregates every
numeric field per kind — nested dicts flatten to dotted names
(``comm_bytes.all_reduce``) — and prints count/mean/p50/p95/max tables.

Usage:
    python tools/ds_trace_report.py runs/trace.jsonl
    python tools/ds_trace_report.py runs/trace.jsonl --kind train_step
    python tools/ds_trace_report.py runs/trace.jsonl --json   # machine-readable

Deliberately stdlib-only (no jax/numpy import): runs anywhere, including
laptops holding traces scp'd off a pod.
"""

import argparse
import importlib.util
import json
import os
import re
import sys

SUPPORTED_SCHEMA = 1
# bookkeeping fields that aren't latencies/rates — excluded from tables
# unless --all-fields asks for them; t0/t1 are span-event monotonic
# endpoints (dur_ms is the metric, the endpoints are bookkeeping)
_SKIP_FIELDS = {"schema", "ts", "request", "step", "micro_steps", "samples",
                "t0", "t1"}

_TIMELINE_PY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "deepspeed_tpu", "telemetry", "timeline.py")


def _load_timeline():
    """``telemetry/timeline.py`` loaded by file path — the module is
    stdlib-only and self-contained, so the package (which imports jax)
    never loads. Powers --request/--slowest/--blame."""
    alias = "_ds_trace_report_timeline"
    if alias in sys.modules:
        return sys.modules[alias]
    spec = importlib.util.spec_from_file_location(alias, _TIMELINE_PY)
    module = importlib.util.module_from_spec(spec)
    sys.modules[alias] = module
    spec.loader.exec_module(module)
    return module


def percentile(sorted_vals, q):
    """Linear-interpolated percentile over an ALREADY SORTED list."""
    if not sorted_vals:
        return 0.0
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    rank = (q / 100.0) * (len(sorted_vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = rank - lo
    return float(sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac)


def flatten_numeric(event, prefix=""):
    """Yield (dotted_name, float) for every numeric field, recursing into
    nested dicts (comm_bytes, comm_summary ops...). Bools excluded."""
    for key, value in event.items():
        name = f"{prefix}{key}"
        if isinstance(value, bool):
            continue
        if isinstance(value, (int, float)):
            yield name, float(value)
        elif isinstance(value, dict):
            yield from flatten_numeric(value, prefix=f"{name}.")


def load_events(path):
    """(events, skipped_lines): parsed event dicts + malformed-line count
    (a crashed writer may leave a torn last line)."""
    events, skipped = [], 0
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                skipped += 1
                continue
            if isinstance(ev, dict):
                events.append(ev)
            else:
                skipped += 1
    return events, skipped


def aggregate(events, kinds=None, all_fields=False):
    """{kind: {field: {count, mean, p50, p95, max}}} over numeric fields."""
    by_kind = {}
    for ev in events:
        kind = ev.get("kind", "?")
        if kinds and kind not in kinds:
            continue
        fields = by_kind.setdefault(kind, {})
        for name, value in flatten_numeric(ev):
            if not all_fields and name in _SKIP_FIELDS:
                continue
            fields.setdefault(name, []).append(value)
    report = {}
    for kind, fields in by_kind.items():
        report[kind] = {}
        for name, vals in sorted(fields.items()):
            vals.sort()
            report[kind][name] = {
                "count": len(vals),
                "mean": sum(vals) / len(vals),
                "p50": percentile(vals, 50.0),
                "p95": percentile(vals, 95.0),
                "max": vals[-1],
            }
    return report


def decode_table(events):
    """Per-path decode/serving summary over ``inference_request`` events:
    {path: {count, ttft_ms_p50/p95, tok_s_p50/p95, kv_bytes_read_p50/p95,
    kv_bytes_per_token_mean, cache_utilization_mean}}. The kv_* fields come
    from the cache-geometry telemetry (int8 KV / tight-read overhaul); rows
    omit stats their events don't carry (e.g. the fused path has no TTFT)."""
    by_path = {}
    for ev in events:
        if ev.get("kind") != "inference_request":
            continue
        by_path.setdefault(ev.get("path", "?"), []).append(ev)
    out = {}
    for path, evs in sorted(by_path.items()):
        row = {"count": len(evs)}
        for field, label in (("ttft_ms", "ttft_ms"),
                             ("decode_tokens_per_sec", "tok_s"),
                             ("kv_bytes_read", "kv_bytes_read")):
            vals = sorted(float(e[field]) for e in evs
                          if isinstance(e.get(field), (int, float))
                          and not isinstance(e.get(field), bool))
            if vals:
                row[f"{label}_p50"] = percentile(vals, 50.0)
                row[f"{label}_p95"] = percentile(vals, 95.0)
        for field in ("kv_bytes_per_token", "cache_utilization"):
            vals = [float(e[field]) for e in evs
                    if isinstance(e.get(field), (int, float))
                    and not isinstance(e.get(field), bool)]
            if vals:
                row[f"{field}_mean"] = sum(vals) / len(vals)
        # speculative acceptance (requests served by spec pool ticks):
        # pooled drafted/accepted totals — "accepted" means emitted to
        # the client (quota-clipped), so this is the effective rate
        drafted = sum(int(e["spec_drafted"]) for e in evs
                      if isinstance(e.get("spec_drafted"), int)
                      and not isinstance(e.get("spec_drafted"), bool))
        if drafted:
            accepted = sum(int(e.get("spec_accepted", 0)) for e in evs)
            row["spec_acceptance"] = accepted / drafted
        out[path] = row
    return out


def format_decode_table(table):
    if not table:
        return ""
    cols = ("count", "ttft_ms_p50", "ttft_ms_p95", "tok_s_p50", "tok_s_p95",
            "kv_bytes_read_p50", "kv_bytes_read_p95", "kv_bytes_per_token_mean",
            "cache_utilization_mean", "spec_acceptance")
    present = [c for c in cols if any(c in row for row in table.values())]
    name_w = max(len("path"), max(len(p) for p in table))
    col_w = max(12, max(len(c) for c in present) + 2)
    lines = ["== decode summary (inference_request by path) =="]
    header = "path".ljust(name_w) + "".join(c.rjust(col_w) for c in present)
    lines.append(header)
    lines.append("-" * len(header))
    for path, row in table.items():
        line = path.ljust(name_w)
        for c in present:
            line += (_fmt(row[c]) if c in row else "-").rjust(col_w)
        lines.append(line)
    return "\n".join(lines) + "\n"


def serve_table(events):
    """Serving-run scorecard over the serving-layer events: finished
    requests are ``inference_request`` events with ``path:"serving"``
    (carrying queue_ms/ttft_ms/deadline_met from the ServingEngine event
    hook); sheds/expiries/cancellations are ``serving_event`` lifecycle
    records. Reports queue-wait and TTFT p50/p95, shed rate, deadline-met
    fraction, and goodput (deadline-met output tokens over the event-time
    span). Per-tick ``serving_tick`` events add the host-overhead
    breakdown — mean dispatch vs blocked ms, the overlap fraction (tick-
    loop time NOT spent blocked on device results), host-blocked ms per
    decoded token, and tokens computed past done flags (wasted) — so the
    dispatch-pipelining win is measurable from the trace alone. Empty
    dict when the trace holds no serving activity."""
    finished = [e for e in events if e.get("kind") == "inference_request"
                and e.get("path") == "serving"]
    lifecycle = [e for e in events if e.get("kind") == "serving_event"]
    ticks = [e for e in events if e.get("kind") == "serving_tick"]
    faults = [e for e in events if e.get("kind") == "serving_fault"]
    scales = [e for e in events if e.get("kind") == "fleet_scale"]
    if (not finished and not lifecycle and not ticks and not faults
            and not scales):
        return {}
    by_event = {}
    for e in lifecycle:
        by_event.setdefault(e.get("event", "?"), []).append(e)
    shed = len(by_event.get("shed", []))
    expired = len(by_event.get("expired", []))
    cancelled = len(by_event.get("cancelled", []))
    total = len(finished) + shed + expired + cancelled
    out = {"finished": len(finished), "shed": shed, "expired": expired,
           "cancelled": cancelled, "requests": total}
    out["shed_rate"] = round((shed + expired) / total, 4) if total else 0.0
    for fld in ("queue_ms", "prefill_wait_ms", "prefill_ms", "ttft_ms"):
        vals = sorted(float(e[fld]) for e in finished
                      if isinstance(e.get(fld), (int, float))
                      and not isinstance(e.get(fld), bool))
        if vals:
            out[f"{fld}_p50"] = percentile(vals, 50.0)
            out[f"{fld}_p95"] = percentile(vals, 95.0)
    with_deadline = [e for e in finished if isinstance(e.get("deadline_met"), bool)]
    if with_deadline:
        out["deadline_met_frac"] = round(
            sum(1 for e in with_deadline if e["deadline_met"])
            / len(with_deadline), 4)
    ts = [float(e["ts"]) for e in finished + lifecycle
          if isinstance(e.get("ts"), (int, float))]
    span = max(ts) - min(ts) if len(ts) > 1 else 0.0
    good = sum(int(e.get("new_tokens", 0)) for e in finished
               if e.get("deadline_met", True) is True)
    out["good_tokens"] = good
    if span > 0:
        out["goodput_tok_s"] = round(good / span, 3)
    if ticks:
        def _tot(fld):
            return sum(float(e.get(fld, 0.0)) for e in ticks)

        dispatch, block = _tot("dispatch_ms"), _tot("block_ms")
        emitted = _tot("emitted")
        out["tick_steps"] = len(ticks)
        out["tick_dispatch_ms_mean"] = round(dispatch / len(ticks), 4)
        out["tick_block_ms_mean"] = round(block / len(ticks), 4)
        if dispatch + block > 0:
            out["overlap_frac"] = round(1.0 - block / (dispatch + block), 4)
        if emitted > 0:
            out["block_ms_per_token"] = round(block / emitted, 4)
        out["wasted_tokens"] = int(_tot("wasted"))
        depths = [int(e["inflight"]) for e in ticks
                  if isinstance(e.get("inflight"), (int, float))]
        if depths:
            out["inflight_max"] = max(depths)
    # speculative sub-table: serving_tick events from a speculative pool
    # carry spec_gamma plus per-step drafted/accepted deltas, so the
    # tick-window acceptance rate is Σ accepted / Σ drafted; the finished
    # request stream adds the per-request acceptance spread
    spec_ticks = [e for e in ticks if e.get("spec_gamma")]
    if spec_ticks:
        drafted = sum(int(e.get("spec_drafted", 0)) for e in spec_ticks)
        accepted = sum(int(e.get("spec_accepted", 0)) for e in spec_ticks)
        spec = {"gamma": int(spec_ticks[-1]["spec_gamma"]),
                "ticks": len(spec_ticks),
                "drafted": drafted, "accepted": accepted}
        if drafted:
            spec["acceptance"] = round(accepted / drafted, 4)
            spec["accepted_per_draft"] = round(
                accepted / drafted * spec["gamma"], 3)
        rates = sorted(
            float(e["spec_accepted"]) / float(e["spec_drafted"])
            for e in finished
            if isinstance(e.get("spec_drafted"), int)
            and not isinstance(e.get("spec_drafted"), bool)
            and e.get("spec_drafted"))
        if rates:
            spec["request_acceptance_p50"] = round(percentile(rates, 50.0), 4)
            spec["request_acceptance_p95"] = round(percentile(rates, 95.0), 4)
        out["speculative"] = spec
    if faults:
        # recovery section: serving_fault events are the fault-tolerance
        # layer's journal — tick failures, retry outcomes, engine
        # rebuilds (with recovery_ms + lost in-flight ticks), circuit-
        # breaker transitions, terminal failures (docs/telemetry.md)
        by_fault = {}
        for e in faults:
            by_fault.setdefault(e.get("event", "?"), []).append(e)
        rebuilds = by_fault.get("rebuild", [])
        out["fault_events"] = len(faults)
        # a failed retry is another observed fault — this total matches
        # serve_fault_total and ServingEngine.recovery_stats()["faults"]
        out["faults"] = (len(by_fault.get("fault", []))
                         + len(by_fault.get("retry_failed", [])))
        out["fault_retries"] = (len(by_fault.get("retried", []))
                                + len(by_fault.get("retry_failed", [])))
        out["rebuilds"] = len(rebuilds)
        out["degraded_rebuilds"] = sum(1 for e in rebuilds
                                       if e.get("degraded") is True)
        out["lost_ticks"] = sum(int(e.get("lost_ticks", 0)) for e in rebuilds)
        out["readmitted"] = sum(int(e.get("readmitted", 0)) for e in rebuilds)
        out["lost_requests"] = sum(1 for e in lifecycle
                                   if e.get("reason") == "engine_lost")
        out["unrecoverable"] = len(by_fault.get("unrecoverable", []))
        rms = sorted(float(e["recovery_ms"]) for e in rebuilds
                     if isinstance(e.get("recovery_ms"), (int, float))
                     and not isinstance(e.get("recovery_ms"), bool))
        if rms:
            out["recovery_ms_p50"] = percentile(rms, 50.0)
            out["recovery_ms_max"] = rms[-1]
        out["outage_ms_total"] = round(sum(
            float(e.get("outage_ms", 0.0)) for e in by_fault.get("breaker", [])
            if e.get("state") == "closed"), 3)
    # honest-retry accounting per shed reason, from the event stream
    # alone: MUST agree with what ds_loadgen's in-process summary reports
    # for the same run (tests/unit/serving/test_shed_hints.py) — a shed
    # verdict whose Admission carried retry_after_s carries the same hint
    # in its serving_event record
    reasons = {}
    for e in by_event.get("shed", []):
        d = reasons.setdefault(str(e.get("reason", "?")),
                               {"count": 0, "with_hint": 0, "hints": []})
        d["count"] += 1
        ra = e.get("retry_after_s")
        if isinstance(ra, (int, float)) and not isinstance(ra, bool):
            d["with_hint"] += 1
            d["hints"].append(float(ra))
    if reasons:
        out["shed_by_reason"] = {
            k: {"count": v["count"], "with_hint": v["with_hint"],
                "retry_after_s_mean": (round(sum(v["hints"]) / len(v["hints"]),
                                             4) if v["hints"] else None)}
            for k, v in sorted(reasons.items())}
    # fleet section: router_event is the FleetRouter's journal (routing,
    # spillover, migration, replica lifecycle) and every replica-scoped
    # serving event carries a ``replica`` tag — together they yield the
    # per-replica breakdown without any in-process state
    routers = [e for e in events if e.get("kind") == "router_event"]
    if routers:
        per = {}

        def _rep(rid):
            return per.setdefault(str(rid), {
                "admitted": 0, "finished": 0, "shed": 0, "good_tokens": 0,
                "migrated_in": 0, "migrated_out": 0})

        deaths = lost = migrated = spillovers = no_replica_sheds = 0
        degraded_sheds = 0
        for e in routers:
            ev = e.get("event")
            if ev == "route":
                _rep(e.get("replica"))["admitted"] += 1
            elif ev == "spillover":
                spillovers += 1
            elif ev == "migrated":
                _rep(e.get("to_replica"))["migrated_in"] += 1
                _rep(e.get("from_replica"))["migrated_out"] += 1
                migrated += 1
            elif ev == "replica_dead":
                deaths += 1
                lost += int(e.get("lost", 0))
            elif ev == "shed":
                # admission-plane sheds split by cause: fleet-empty
                # ("no_replicas") vs the degradation ladder dropping
                # batch backfill ("degraded_backfill")
                if e.get("reason") == "degraded_backfill":
                    degraded_sheds += 1
                else:
                    no_replica_sheds += 1
        for e in lifecycle:
            if (e.get("event") in ("shed", "expired")
                    and e.get("replica") is not None):
                _rep(e["replica"])["shed"] += 1
        for e in finished:
            if e.get("replica") is not None:
                r = _rep(e["replica"])
                r["finished"] += 1
                if e.get("deadline_met", True) is True:
                    r["good_tokens"] += int(e.get("new_tokens", 0))
        if span > 0:
            for r in per.values():
                r["goodput_tok_s"] = round(r["good_tokens"] / span, 3)
        out["fleet"] = {
            "replicas": {k: per[k] for k in sorted(per)},
            "router_events": len(routers),
            "replica_deaths": deaths, "lost": lost,
            "migrated": migrated, "spillovers": spillovers,
            "no_replica_sheds": no_replica_sheds,
        }
        if degraded_sheds:
            out["fleet"]["degraded_sheds"] = degraded_sheds
    # scenario section: fleet_scale is the autoscaler's journal (plus
    # the scenario marker the scenario engine emits when armed) — the
    # per-scenario SLO verdict is the scorecard above, this section adds
    # WHAT the control loop did about the load: every scale/degrade
    # transition and the replica count over time
    if scales:
        sc = {"events": len(scales)}
        name = next((e.get("scenario") for e in scales
                     if e.get("event") == "scenario"), None)
        if name is not None:
            sc["scenario"] = name
        sc["scale_ups"] = sum(1 for e in scales
                              if e.get("event") == "scale_up")
        sc["scale_downs"] = sum(1 for e in scales
                                if e.get("event") == "scale_down")
        sc["scale_down_skipped"] = sum(
            1 for e in scales if e.get("event") == "scale_down_skipped")
        degrades = [e for e in scales if e.get("event") == "degrade"]
        sc["degrade_transitions"] = len(degrades)
        levels = [int(e.get("to_level", 0)) for e in degrades]
        if degrades:
            sc["max_degrade_level"] = max(levels)
            sc["final_degrade_level"] = levels[-1]
        timeline = [[int(e.get("tick", 0)), int(e["replicas"])]
                    for e in scales
                    if e.get("event") in ("autoscaler", "scale_up",
                                          "scale_down")
                    and isinstance(e.get("replicas"), int)]
        if timeline:
            sc["replicas_timeline"] = timeline
            sc["replicas_min"] = min(r for _, r in timeline)
            sc["replicas_max"] = max(r for _, r in timeline)
        out["scenario"] = sc
    return out


def format_serve_table(table):
    if not table:
        return ""
    lines = ["== serving summary (path=serving + serving_event) =="]
    counts = " ".join(f"{k}={table[k]}"
                      for k in ("finished", "shed", "expired", "cancelled")
                      if table.get(k))
    lines.append(f"requests          {table['requests']}"
                 + (f"  ({counts})" if counts else ""))
    for fld, label in (("queue_ms", "queue wait"),
                       ("prefill_wait_ms", "prefill wait"),
                       ("prefill_ms", "prefill"), ("ttft_ms", "ttft")):
        if f"{fld}_p50" in table:
            lines.append(f"{label:<17} p50 {_fmt(table[f'{fld}_p50'])} ms"
                         f"   p95 {_fmt(table[f'{fld}_p95'])} ms")
    lines.append(f"shed rate         {table['shed_rate'] * 100:.2f}%")
    if "deadline_met_frac" in table:
        lines.append(f"deadline met      {table['deadline_met_frac'] * 100:.2f}%")
    if "goodput_tok_s" in table:
        lines.append(f"goodput           {_fmt(table['goodput_tok_s'])} tok/s "
                     f"({table['good_tokens']} deadline-met tokens)")
    if "tick_dispatch_ms_mean" in table:
        line = (f"tick host         dispatch {_fmt(table['tick_dispatch_ms_mean'])} ms"
                f"   blocked {_fmt(table['tick_block_ms_mean'])} ms")
        if "overlap_frac" in table:
            line += f"   overlap {table['overlap_frac'] * 100:.1f}%"
        lines.append(line)
        tail = []
        if "block_ms_per_token" in table:
            tail.append(f"blocked/token {_fmt(table['block_ms_per_token'])} ms")
        if table.get("wasted_tokens"):
            tail.append(f"wasted {table['wasted_tokens']} tok")
        if "inflight_max" in table:
            tail.append(f"inflight<= {table['inflight_max']}")
        if tail:
            lines.append(f"                  {'   '.join(tail)}")
    spec = table.get("speculative")
    if spec:
        line = (f"speculative       gamma {spec['gamma']}"
                f"   drafted {spec['drafted']}"
                f"   accepted {spec['accepted']}")
        if "acceptance" in spec:
            line += (f"   acceptance {spec['acceptance'] * 100:.1f}%"
                     f" ({_fmt(spec['accepted_per_draft'])}/{spec['gamma']}"
                     f" per draft)")
        lines.append(line)
        if "request_acceptance_p50" in spec:
            lines.append(
                f"                  per-request acceptance p50 "
                f"{spec['request_acceptance_p50'] * 100:.1f}%   p95 "
                f"{spec['request_acceptance_p95'] * 100:.1f}%")
    if "fault_events" in table:
        line = (f"recovery          faults {table['faults']}"
                f"   retries {table['fault_retries']}"
                f"   rebuilds {table['rebuilds']}")
        if table.get("degraded_rebuilds"):
            line += f" ({table['degraded_rebuilds']} degraded)"
        lines.append(line)
        tail = []
        if "recovery_ms_p50" in table:
            tail.append(f"recovery_ms p50 {_fmt(table['recovery_ms_p50'])}"
                        f" max {_fmt(table['recovery_ms_max'])}")
        tail.append(f"lost ticks {table['lost_ticks']}")
        tail.append(f"re-admitted {table['readmitted']}")
        if table.get("lost_requests"):
            tail.append(f"lost requests {table['lost_requests']}")
        if table.get("outage_ms_total"):
            tail.append(f"outage {_fmt(table['outage_ms_total'])} ms")
        lines.append(f"                  {'   '.join(tail)}")
        if table.get("unrecoverable"):
            lines.append(f"                  UNRECOVERABLE terminal "
                         f"failure(s): {table['unrecoverable']}")
    if "shed_by_reason" in table:
        parts = []
        for reason, v in table["shed_by_reason"].items():
            hint = (f" ~{_fmt(v['retry_after_s_mean'])}s"
                    if v["retry_after_s_mean"] is not None else "")
            parts.append(f"{reason}={v['count']} "
                         f"({v['with_hint']} hinted{hint})")
        lines.append(f"shed reasons      {'   '.join(parts)}")
    fleet = table.get("fleet")
    if fleet:
        lines.append(f"fleet             deaths {fleet['replica_deaths']}"
                     f"   migrated {fleet['migrated']}"
                     f"   lost {fleet['lost']}"
                     f"   spillovers {fleet['spillovers']}"
                     + (f"   no-replica sheds {fleet['no_replica_sheds']}"
                        if fleet.get("no_replica_sheds") else "")
                     + (f"   degraded sheds {fleet['degraded_sheds']}"
                        if fleet.get("degraded_sheds") else ""))
        lines.append("  replica    admitted  finished  shed   mig in/out"
                     "   goodput tok/s")
        for rid, r in fleet["replicas"].items():
            mig = f"{r['migrated_in']}/{r['migrated_out']}"
            lines.append(f"  {rid:<10} {r['admitted']:<9} {r['finished']:<9} "
                         f"{r['shed']:<6} {mig:<12} "
                         f"{_fmt(r.get('goodput_tok_s', '-'))}")
    sc = table.get("scenario")
    if sc:
        head = "scenario          "
        if sc.get("scenario"):
            head += f"{sc['scenario']}   "
        head += (f"scale ups {sc['scale_ups']}   downs {sc['scale_downs']}"
                 f"   skipped {sc['scale_down_skipped']}"
                 f"   degrade transitions {sc['degrade_transitions']}")
        lines.append(head)
        tail = []
        if "replicas_min" in sc:
            tail.append(f"replicas {sc['replicas_min']}"
                        f"→{sc['replicas_max']}")
        if "max_degrade_level" in sc:
            tail.append(f"degrade<= L{sc['max_degrade_level']} "
                        f"(final L{sc['final_degrade_level']})")
        verdict = []
        if "deadline_met_frac" in table:
            verdict.append(f"deadline met {table['deadline_met_frac'] * 100:.2f}%")
        verdict.append(f"shed {table['shed_rate'] * 100:.2f}%")
        if "goodput_tok_s" in table:
            verdict.append(f"goodput {_fmt(table['goodput_tok_s'])} tok/s")
        lines.append(f"                  {'   '.join(tail + ['SLO: ' + ', '.join(verdict)])}")
    return "\n".join(lines) + "\n"


def train_table(events):
    """Training-run recovery scorecard over ``train_fault`` events (the
    TrainSupervisor's fault/recovery journal — docs/telemetry.md) plus
    per-step ``train_step`` timing when present: observed faults and
    clean micro-step retries, engine rebuilds split by restore source
    (memory snapshot / disk checkpoint / cold restart) with replayed
    steps and recovery_ms percentiles, snapshot cadence with
    checkpoint_ms percentiles, torn checkpoint writes and refused tags
    (the integrity walk's evidence), degraded restarts with the final
    world size, and terminal failures. ``numeric_health`` events add a
    numerical-health sub-table (anomalies by kind, quarantined batches,
    rewinds with replayed steps, SDC probe outcomes). Empty dict when
    the trace holds no training fault or numeric-health activity."""
    faults = [e for e in events if e.get("kind") == "train_fault"]
    nh = [e for e in events if e.get("kind") == "numeric_health"]
    if not faults and not nh:
        return {}
    by_event = {}
    for e in faults:
        by_event.setdefault(e.get("event", "?"), []).append(e)
    rebuilds = by_event.get("rebuild", [])
    snapshots = by_event.get("snapshot", [])
    out = {"fault_events": len(faults),
           "faults": len(by_event.get("fault", [])),
           "retries": len(by_event.get("retried", [])),
           "rebuilds": len(rebuilds)}
    by_source = {}
    for e in rebuilds:
        src = str(e.get("source", "?"))
        by_source[src] = by_source.get(src, 0) + 1
    if by_source:
        out["rebuilds_by_source"] = by_source
    out["replayed_steps"] = sum(int(e.get("replayed_steps", 0))
                                for e in rebuilds)
    degraded = [e for e in rebuilds if e.get("degraded") is True]
    if degraded:
        out["degraded_rebuilds"] = len(degraded)
        ws = [int(e["world_size"]) for e in degraded
              if isinstance(e.get("world_size"), int)
              and not isinstance(e.get("world_size"), bool)]
        if ws:
            out["final_world_size"] = ws[-1]
    rms = sorted(float(e["recovery_ms"]) for e in rebuilds
                 if isinstance(e.get("recovery_ms"), (int, float))
                 and not isinstance(e.get("recovery_ms"), bool))
    if rms:
        out["recovery_ms_p50"] = percentile(rms, 50.0)
        out["recovery_ms_max"] = rms[-1]
    if snapshots:
        out["snapshots"] = len(snapshots)
        out["snapshots_committed"] = sum(1 for e in snapshots
                                         if e.get("committed") is True)
        cms = sorted(float(e["checkpoint_ms"]) for e in snapshots
                     if isinstance(e.get("checkpoint_ms"), (int, float))
                     and not isinstance(e.get("checkpoint_ms"), bool))
        if cms:
            out["checkpoint_ms_p50"] = percentile(cms, 50.0)
            out["checkpoint_ms_max"] = cms[-1]
    out["torn_writes"] = len(by_event.get("ckpt_torn", []))
    out["refused_tags"] = len(by_event.get("ckpt_refused", []))
    out["terminal_failures"] = len(by_event.get("failed", []))
    # snapshot overhead against the train_step stream when both exist:
    # checkpoint_ms total over step_ms total = the cadence's step-time tax
    steps = [e for e in events if e.get("kind") == "train_step"]
    step_ms = sum(float(e["step_ms"]) for e in steps
                  if isinstance(e.get("step_ms"), (int, float))
                  and not isinstance(e.get("step_ms"), bool))
    ckpt_total = sum(float(e.get("checkpoint_ms", 0.0)) for e in snapshots
                     if isinstance(e.get("checkpoint_ms"), (int, float))
                     and not isinstance(e.get("checkpoint_ms"), bool))
    if step_ms > 0 and ckpt_total > 0:
        out["snapshot_overhead_frac"] = round(ckpt_total / step_ms, 4)
    if nh:
        nh_by = {}
        for e in nh:
            nh_by.setdefault(e.get("event", "?"), []).append(e)
        anomalies = {}
        for e in nh_by.get("anomaly", []) + nh_by.get("quarantine", []):
            for reason in (e.get("reasons") or []):
                anomalies[str(reason)] = anomalies.get(str(reason), 0) + 1
        rewinds = nh_by.get("rewind", [])
        probes = nh_by.get("sdc_probe", [])
        numeric = {
            "events": len(nh),
            "anomalies": anomalies,
            "quarantines": len(nh_by.get("quarantine", [])),
            "rewinds": len(rewinds),
            "rewind_replayed_steps": sum(
                int(e.get("replayed_steps", 0)) for e in rewinds
                if not isinstance(e.get("replayed_steps"), bool)),
            "sdc_probes": len(probes),
            "sdc_mismatches": sum(1 for e in probes
                                  if e.get("match") is False),
        }
        out["numeric"] = numeric
    return out


def format_train_table(table):
    if not table:
        return ""
    lines = ["== training recovery (train_fault) =="]
    lines.append(f"recovery          faults {table['faults']}"
                 f"   retries {table['retries']}"
                 f"   rebuilds {table['rebuilds']}"
                 + (f" ({table['degraded_rebuilds']} degraded"
                    f" -> world {table['final_world_size']})"
                    if table.get("degraded_rebuilds") else ""))
    tail = []
    if table.get("rebuilds_by_source"):
        srcs = " ".join(f"{k}={v}" for k, v in
                        sorted(table["rebuilds_by_source"].items()))
        tail.append(f"sources {srcs}")
    if table.get("replayed_steps"):
        tail.append(f"replayed steps {table['replayed_steps']}")
    if "recovery_ms_p50" in table:
        tail.append(f"recovery_ms p50 {_fmt(table['recovery_ms_p50'])}"
                    f" max {_fmt(table['recovery_ms_max'])}")
    if tail:
        lines.append(f"                  {'   '.join(tail)}")
    if table.get("snapshots"):
        line = (f"snapshots         {table['snapshots']}"
                f"   committed {table['snapshots_committed']}")
        if "checkpoint_ms_p50" in table:
            line += (f"   checkpoint_ms p50 {_fmt(table['checkpoint_ms_p50'])}"
                     f" max {_fmt(table['checkpoint_ms_max'])}")
        lines.append(line)
    if "snapshot_overhead_frac" in table:
        lines.append(f"snapshot overhead {table['snapshot_overhead_frac'] * 100:.2f}%"
                     f" of step time")
    if table.get("torn_writes") or table.get("refused_tags"):
        lines.append(f"integrity         torn writes {table['torn_writes']}"
                     f"   refused tags {table['refused_tags']}")
    if table.get("terminal_failures"):
        lines.append(f"                  TERMINAL failure(s): "
                     f"{table['terminal_failures']}")
    nh = table.get("numeric")
    if nh:
        line = (f"numeric health    quarantines {nh['quarantines']}"
                f"   rewinds {nh['rewinds']}")
        if nh.get("rewind_replayed_steps"):
            line += f" (replayed {nh['rewind_replayed_steps']} steps)"
        if nh.get("sdc_probes"):
            line += (f"   sdc probes {nh['sdc_probes']}"
                     f" (mismatches {nh['sdc_mismatches']})")
        lines.append(line)
        if nh.get("anomalies"):
            kinds = "   ".join(f"{k}={v}" for k, v in
                               sorted(nh["anomalies"].items()))
            lines.append(f"                  anomalies {kinds}")
    return "\n".join(lines) + "\n"


def memory_table(events):
    """Per-component HBM table over ``memory_snapshot`` events (the live
    ops plane's attribution — docs/telemetry.md): peak and latest bytes
    per component, snapshot count per reason (build/rebuild/migration),
    and the latest total/headroom. Empty dict when the trace carries no
    snapshots."""
    snaps = [e for e in events if e.get("kind") == "memory_snapshot"]
    if not snaps:
        return {}
    comps = {}
    reasons = {}
    for e in snaps:
        reasons[e.get("reason", "?")] = reasons.get(e.get("reason", "?"), 0) + 1
        for name, b in (e.get("components") or {}).items():
            if isinstance(b, bool) or not isinstance(b, (int, float)):
                continue
            c = comps.setdefault(name, {"peak": 0, "latest": 0})
            c["peak"] = max(c["peak"], b)
            c["latest"] = b
    out = {"snapshots": len(snaps), "reasons": reasons, "components": comps}
    last = snaps[-1]
    if isinstance(last.get("total_bytes"), (int, float)):
        out["total_latest"] = last["total_bytes"]
    out["total_peak"] = max((e["total_bytes"] for e in snaps
                             if isinstance(e.get("total_bytes"), (int, float))),
                            default=0)
    if isinstance(last.get("headroom_bytes"), (int, float)):
        out["headroom_latest"] = last["headroom_bytes"]
    return out


def format_memory_table(table):
    if not table:
        return ""
    reasons = " ".join(f"{k}={v}" for k, v in sorted(table["reasons"].items()))
    lines = ["== memory (memory_snapshot, bytes per chip) ==",
             f"snapshots         {table['snapshots']}  ({reasons})"]
    name_w = max(len("component"), max((len(n) for n in table["components"]),
                                       default=0))
    col_w = 14
    header = "component".ljust(name_w) + "peak".rjust(col_w) + "latest".rjust(col_w)
    lines.append(header)
    lines.append("-" * len(header))
    for name in sorted(table["components"]):
        c = table["components"][name]
        lines.append(name.ljust(name_w) + _fmt(c["peak"]).rjust(col_w)
                     + _fmt(c["latest"]).rjust(col_w))
    lines.append("total".ljust(name_w) + _fmt(table["total_peak"]).rjust(col_w)
                 + _fmt(table.get("total_latest", 0)).rjust(col_w))
    if "headroom_latest" in table:
        lines.append(f"headroom (latest) {_fmt(table['headroom_latest'])}")
    return "\n".join(lines) + "\n"


_BUILD_SPLIT = ("trace_ms", "lower_ms", "backend_ms", "load_ms", "other_ms")


def compile_table(events):
    """Compile flight-recorder totals over ``compile_event`` events:
    count, total compile_ms (the first dispatches' wall time), and
    recompiles — overall and per program family; where the events carry
    the build journal's split (traces written before it do not), also
    trace / lower / backend (of which load) / other ms and the
    persistent-cache hits. A non-zero recompile count at serve time is the
    runtime recompile storm ds-lint can only guess at statically. Empty
    dict when the trace carries no compile events."""
    evs = [e for e in events if e.get("kind") == "compile_event"]
    if not evs:
        return {}
    split = _BUILD_SPLIT if any("trace_ms" in e for e in evs) else ()
    blank = dict({"count": 0, "compile_ms": 0.0, "recompiles": 0},
                 **dict.fromkeys(split, 0.0), **({"cache_hits": 0} if split else {}))
    families = {}
    for e in evs:
        fam = families.setdefault(e.get("family", "?"), dict(blank))
        fam["count"] += 1
        for field in ("compile_ms",) + split:
            ms = e.get(field)
            if isinstance(ms, (int, float)) and not isinstance(ms, bool):
                fam[field] += float(ms)
        fam["recompiles"] += e.get("recompile") is True
        if split:
            fam["cache_hits"] += e.get("cache_hit") is True
    return {
        "count": len(evs),
        "compile_ms_total": round(sum(f["compile_ms"]
                                      for f in families.values()), 3),
        "recompiles": sum(f["recompiles"] for f in families.values()),
        "families": {k: {f: (round(x, 3) if isinstance(x, float) else x)
                         for f, x in v.items()}
                     for k, v in families.items()},
    }


def format_compile_table(table):
    if not table:
        return ""
    lines = ["== compiles (compile_event) ==",
             f"compiles          {table['count']}   total "
             f"{_fmt(table['compile_ms_total'])} ms   recompiles "
             f"{table['recompiles']}"]
    name_w = max(len("family"), max(len(n) for n in table["families"]))
    cols = list(next(iter(table["families"].values())))
    cols.append(cols.pop(cols.index("recompiles")))  # last, as it was
    col_w = 14 if len(cols) == 3 else 12
    header = "family".ljust(name_w) + "".join(c.rjust(col_w) for c in cols)
    lines.append(header)
    lines.append("-" * len(header))
    for name in sorted(table["families"]):
        f = table["families"][name]
        lines.append(name.ljust(name_w) + "".join(
            (_fmt(f[c]) if isinstance(f[c], float) else str(f[c])).rjust(col_w)
            for c in cols))
    return "\n".join(lines) + "\n"


def audit_crosscheck(events, audit_report, tolerance=0.5):
    """Static-vs-runtime comm cross-check: ds-audit's per-program
    collective bytes (the ``programs`` block of ``ds_audit.py --format
    json``) against what the trace actually logged — ``train_step``
    events' per-step ``comm_bytes`` deltas when present, else the last
    ``comm_summary`` totals averaged over the step span.

    Returns rows keyed by op kind: ``static_bytes`` (summed operand
    bytes per dispatch over every audited program), ``measured_bytes``
    (per step), ``ratio`` and ``verdict``:

    - ``WARN``: both sides nonzero but the ratio falls outside
      ``[tolerance, 1/tolerance]`` — the measurement and the artifact
      disagree (a CommsLogger.append drifted from the real op, or the
      audited program is not the one serving), OR runtime traffic exists
      with no static counterpart at all.
    - ``static-only``: the audited programs contain the collective but
      the trace never logged it. NOT a warning: XLA-inserted collectives
      (sharding-implicit) are invisible to CommsLogger by design — only
      explicit ``comm.*`` wrapper calls log (docs/telemetry.md).
    - ``ok``: within tolerance.

    The same honesty rule as the unsynced-timing lint: numbers that
    cannot be reconciled should say so, loudly, in the report."""
    kinds = {}
    for prog in (audit_report.get("programs") or {}).values():
        for kind, stats in (prog.get("collectives") or {}).items():
            key = kind.replace("-", "_")
            kinds[key] = kinds.get(key, 0) + int(stats.get("bytes", 0))

    steps = [ev for ev in events if ev.get("kind") == "train_step"]
    measured = {}
    if steps:
        for ev in steps:
            for op, b in (ev.get("comm_bytes") or {}).items():
                measured[op] = measured.get(op, 0.0) + float(b)
        measured = {op: total / len(steps) for op, total in measured.items()}
    else:
        summaries = [ev for ev in events if ev.get("kind") == "comm_summary"]
        if summaries:
            ops = summaries[-1].get("ops") or {}
            span = max(len(summaries), 1)
            measured = {op: float(stats.get("total_bytes", 0)) / span
                        for op, stats in ops.items()}

    rows = {}
    for op in sorted(set(kinds) | set(measured)):
        static = kinds.get(op, 0)
        runtime = measured.get(op, 0.0)
        if static <= 0 and runtime <= 0:
            # an op that ran once at init shows up in every later step's
            # comm_bytes with delta 0 — zero on both sides carries no
            # information, and certainly not a warning
            continue
        row = {"static_bytes": static, "measured_bytes": round(runtime, 1)}
        if static > 0 and runtime > 0:
            ratio = runtime / static
            row["ratio"] = round(ratio, 3)
            row["verdict"] = ("ok" if tolerance <= ratio <= 1.0 / tolerance
                              else "WARN")
        elif static > 0:
            row["verdict"] = "static-only"
        else:
            row["verdict"] = "WARN"  # runtime bytes nothing static explains
        rows[op] = row
    return rows


def format_audit_crosscheck(rows, tolerance):
    lines = ["Comm cross-check — ds-audit static vs CommsLogger runtime "
             f"(tolerance {tolerance}x)",
             f"  {'op':<20} {'static B/dispatch':>18} {'measured B/step':>16} "
             f"{'ratio':>8}  verdict"]
    for op, row in rows.items():
        ratio = row.get("ratio")
        lines.append(
            f"  {op:<20} {row['static_bytes']:>18} "
            f"{row['measured_bytes']:>16} "
            f"{ratio if ratio is not None else '-':>8}  {row['verdict']}")
    warns = [op for op, row in rows.items() if row["verdict"] == "WARN"]
    if warns:
        lines.append(f"  warning: {len(warns)} op kind(s) beyond tolerance "
                     f"({', '.join(warns)}) — static artifact and runtime "
                     f"measurement disagree")
    return "\n".join(lines) + "\n"


_PERF_KEY_RE = re.compile(r"^program://(?P<family>[^\[@#]+)")


def perf_crosscheck(events, perf_report, slack=0.1):
    """Static-vs-runtime step-time cross-check: ds-perf's roofline lower
    bound per compiled program (the ``programs`` block of ``ds_perf.py
    --json-out`` / ``--format json``) against what the trace measured.

    Only some families have a measured counterpart in the trace today:

    - ``pool_tick*`` / ``pool_spec_tick*`` -> mean ``serving_tick``
      dispatch_ms + block_ms (one tick = one dispatch plus the device
      block that drains it)
    - ``train_micro`` -> mean ``train_step`` iter_ms (at accumulation 1
      the iteration is micro-step dominated)
    - ``train_apply`` -> mean ``train_step`` step_ms

    The roofline is a LOWER bound at the report's device peaks, so the
    verdicts read differently from --audit's ratio band:

    - ``ok``: measured >= predicted * (1 - slack). Reality respects the
      bound; beating it by less than ``slack`` is measurement noise.
    - ``WARN``: measured < predicted * (1 - slack) — the measurement
      beats physics, so the audited program is NOT the one that ran, or
      the peaks table is wrong for this host.
    - ``static-only``: no measured counterpart in the trace.
    """
    tick_vals = []
    for ev in events:
        if ev.get("kind") != "serving_tick":
            continue
        d, b = ev.get("dispatch_ms"), ev.get("block_ms")
        if isinstance(d, (int, float)) and not isinstance(d, bool):
            total = float(d)
            if isinstance(b, (int, float)) and not isinstance(b, bool):
                total += float(b)
            tick_vals.append(total)
    iter_vals, step_vals = [], []
    for ev in events:
        if ev.get("kind") != "train_step":
            continue
        for field, dest in (("iter_ms", iter_vals), ("step_ms", step_vals)):
            v = ev.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                dest.append(float(v))
    measured = {}
    if tick_vals:
        measured["tick"] = (sum(tick_vals) / len(tick_vals),
                            f"serving_tick dispatch+block x{len(tick_vals)}")
    if iter_vals:
        measured["iter"] = (sum(iter_vals) / len(iter_vals),
                            f"train_step iter_ms x{len(iter_vals)}")
    if step_vals:
        measured["step"] = (sum(step_vals) / len(step_vals),
                            f"train_step step_ms x{len(step_vals)}")

    rows = {}
    for key in sorted(perf_report.get("programs") or {}):
        entry = perf_report["programs"].get(key) or {}
        pred = entry.get("predicted") or {}
        lb = pred.get("lb_ms")
        if not isinstance(lb, (int, float)) or isinstance(lb, bool):
            continue
        m = _PERF_KEY_RE.match(key)
        family = m.group("family") if m else ""
        if family.startswith(("pool_tick", "pool_spec_tick")):
            bucket = "tick"
        elif family == "train_micro":
            bucket = "iter"
        elif family == "train_apply":
            bucket = "step"
        else:
            bucket = None
        row = {"family": family, "predicted_lb_ms": float(lb),
               "bound_by": pred.get("bound_by")}
        got = measured.get(bucket) if bucket else None
        if got is None:
            row["verdict"] = "static-only"
        else:
            mean_ms, source = got
            row["measured_ms"] = round(mean_ms, 3)
            row["source"] = source
            if lb > 0:
                row["ratio"] = round(mean_ms / float(lb), 3)
            row["verdict"] = ("ok" if mean_ms >= float(lb) * (1.0 - slack)
                              else "WARN")
        rows[key] = row
    return rows


def format_perf_crosscheck(rows, slack):
    lines = ["Perf cross-check — ds-perf roofline lower bound vs trace "
             f"measurement (slack {slack:g})",
             f"  {'program':<40} {'predicted lb_ms':>16} {'measured_ms':>12} "
             f"{'ratio':>10}  verdict"]
    for key, row in rows.items():
        short = key[len("program://"):] if key.startswith("program://") else key
        ratio = row.get("ratio")
        lines.append(
            f"  {short:<40} {row['predicted_lb_ms']:>16} "
            f"{row.get('measured_ms', '-'):>12} "
            f"{ratio if ratio is not None else '-':>10}  {row['verdict']}")
    warns = [k for k, r in rows.items() if r["verdict"] == "WARN"]
    if warns:
        lines.append(f"  warning: {len(warns)} program(s) measured BELOW "
                     "their static roofline lower bound — the audited "
                     "program is not the one that ran, or the peaks table "
                     "is wrong for this host")
    return "\n".join(lines) + "\n"


def find_timeline(timelines, needle):
    """Resolve --request: an exact trace_id match first, else the unique
    timeline whose trace_id ends with ``/<needle>`` (so ``--request 5``
    finds ``r0/5`` in a fleet trace when unambiguous)."""
    if needle in timelines:
        return timelines[needle], None
    suffix = [tid for tid in timelines if tid.endswith(f"/{needle}")]
    if len(suffix) == 1:
        return timelines[suffix[0]], None
    if len(suffix) > 1:
        return None, (f"ambiguous request {needle!r}: matches "
                      f"{', '.join(sorted(suffix))}")
    return None, (f"no trace_id {needle!r} in the trace "
                  f"(have: {', '.join(sorted(timelines)) or 'none'})")


def format_request_timeline(tl):
    """The "why is this request slow" view: the span tree indented by
    causal depth, then the critical-path ledger."""
    reps = "->".join(str(r) for r in tl.replicas) or "-"
    lines = [f"== request timeline {tl.trace_id} ==",
             f"duration          {_fmt(tl.duration_ms)} ms   "
             f"spans {len(tl.spans)}   orphans {len(tl.orphans)}   "
             f"replicas {reps}"]
    origin = tl.t_start
    for s in tl.spans:
        pad = "  " * tl.depth(s)
        rep = f" @{s.replica}" if s.replica is not None else ""
        orphan = "  [ORPHAN]" if s in tl.orphans else ""
        extras = " ".join(f"{k}={v}" for k, v in sorted(s.attrs.items()))
        lines.append(f"  +{(s.t0 - origin) * 1000.0:>9.3f} ms "
                     f"{pad}{s.kind} ({_fmt(s.dur_ms)} ms){rep}"
                     + (f"  {extras}" if extras else "") + orphan)
    path = tl.critical_path()
    lines.append("critical path     "
                 + "   ".join(f"{k} {_fmt(v)} ms" for k, v in
                              sorted(path.items(), key=lambda kv: -kv[1])))
    attr = tl.attribution()
    lines.append("attribution       "
                 + "   ".join(f"{k} {_fmt(v)} ms" for k, v in
                              sorted(attr.items(), key=lambda kv: -kv[1])))
    return "\n".join(lines) + "\n"


def slowest_rows(timelines, n):
    """Top-N request timelines by wall duration, each with its dominant
    span kind and queue/compute/recovery split — the triage queue."""
    tls = sorted(timelines.values(), key=lambda t: -t.duration_ms)[:n]
    return [{
        "trace_id": tl.trace_id,
        "duration_ms": round(tl.duration_ms, 3),
        "spans": len(tl.spans),
        "orphans": len(tl.orphans),
        "dominant": tl.dominant_kind(),
        "attribution": {k: round(v, 3)
                        for k, v in sorted(tl.attribution().items())},
        "replicas": tl.replicas,
        "migrated": any(s.kind == "migration" for s in tl.spans),
    } for tl in tls]


def format_slowest(rows):
    lines = [f"== slowest requests ({len(rows)}) =="]
    head = (f"{'trace_id':<20} {'dur_ms':>12} {'dominant':>18} "
            f"{'queue':>10} {'compute':>10} {'recovery':>10}  replicas")
    lines.append(head)
    lines.append("-" * len(head))
    for r in rows:
        attr = r["attribution"]
        reps = "->".join(str(x) for x in r["replicas"]) or "-"
        mark = (" MIGRATED" if r["migrated"] else "") + \
               (" ORPHANS" if r["orphans"] else "")
        lines.append(
            f"{r['trace_id']:<20} {_fmt(r['duration_ms']):>12} "
            f"{r['dominant'] or '-':>18} "
            f"{_fmt(attr.get('queue', 0.0)):>10} "
            f"{_fmt(attr.get('compute', 0.0)):>10} "
            f"{_fmt(attr.get('recovery', 0.0)):>10}  {reps}{mark}")
    return "\n".join(lines) + "\n"


def format_blame(rows):
    lines = [f"== SLO-miss blame ({len(rows)} missed requests) =="]
    head = (f"{'trace_id':<20} {'ttft_ms':>10} {'queue_ms':>10} "
            f"{'dominant':>18}  blame")
    lines.append(head)
    lines.append("-" * len(head))
    for r in rows:
        attr = r.get("attribution") or {}
        blame = "   ".join(f"{k} {_fmt(v)} ms" for k, v in
                           sorted(attr.items(), key=lambda kv: -kv[1])) \
                or "(no spans: trace sampled out or rotated away)"
        lines.append(f"{str(r['trace_id']):<20} "
                     f"{_fmt(r['ttft_ms'] or 0.0):>10} "
                     f"{_fmt(r['queue_ms'] or 0.0):>10} "
                     f"{r['dominant'] or '-':>18}  {blame}")
    return "\n".join(lines) + "\n"


def _fmt(v):
    if v == 0:
        return "0"
    if abs(v) >= 1e6 or abs(v) < 1e-3:
        return f"{v:.3e}"
    return f"{v:,.3f}".rstrip("0").rstrip(".")


def format_tables(report):
    lines = []
    for kind in sorted(report):
        fields = report[kind]
        if not fields:
            continue
        n_events = max(stats["count"] for stats in fields.values())
        lines.append(f"== {kind} ({n_events} events) ==")
        name_w = max(len("metric"), max(len(n) for n in fields))
        cols = ("count", "mean", "p50", "p95", "max")
        col_w = 12
        header = "metric".ljust(name_w) + "".join(c.rjust(col_w) for c in cols)
        lines.append(header)
        lines.append("-" * len(header))
        for name, stats in fields.items():
            row = name.ljust(name_w)
            row += str(stats["count"]).rjust(col_w)
            for c in ("mean", "p50", "p95", "max"):
                row += _fmt(stats[c]).rjust(col_w)
            lines.append(row)
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="p50/p95/max tables from a deepspeed_tpu telemetry JSONL trace"
    )
    ap.add_argument("trace", help="path to the JSONL trace file")
    ap.add_argument("--kind", action="append", default=None,
                    help="restrict to this event kind (repeatable)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the aggregate as JSON instead of tables")
    ap.add_argument("--all-fields", action="store_true",
                    help="include bookkeeping fields (ts, step, ...)")
    ap.add_argument("--decode", action="store_true",
                    help="only the per-path decode summary (TTFT/tok-s/"
                         "kv_bytes_read percentiles over inference_request "
                         "events)")
    ap.add_argument("--serve", action="store_true",
                    help="only the serving summary (queue-wait/TTFT "
                         "percentiles, shed rate, deadline-met fraction, "
                         "goodput over ServingEngine events)")
    ap.add_argument("--train", action="store_true",
                    help="only the training recovery summary (faults/"
                         "retries/rebuilds by source, snapshot cadence & "
                         "checkpoint_ms, torn/refused checkpoints over "
                         "TrainSupervisor train_fault events, plus the "
                         "numerical-health sub-table over numeric_health "
                         "events)")
    ap.add_argument("--memory", action="store_true",
                    help="only the per-component HBM table (peak + latest "
                         "bytes per chip over memory_snapshot events)")
    ap.add_argument("--audit", metavar="AUDIT_JSON", default=None,
                    help="cross-check ds-audit's predicted per-program "
                         "collective bytes (ds_audit.py --format json "
                         "output) against the trace's CommsLogger "
                         "comm_summary/train_step volume; mismatch beyond "
                         "tolerance prints a warning row")
    ap.add_argument("--audit-tolerance", type=float, default=0.5,
                    help="accepted measured/static ratio band "
                         "[T, 1/T] for --audit (default 0.5)")
    ap.add_argument("--perf", metavar="PERF_JSON", default=None,
                    help="cross-check ds-perf's roofline lower bound per "
                         "program (ds_perf.py --json-out report) against "
                         "the trace's measured serving_tick/train_step "
                         "times; a measurement below the bound warns")
    ap.add_argument("--perf-slack", type=float, default=0.1,
                    help="fraction below the predicted lower bound still "
                         "accepted as measurement noise for --perf "
                         "(default 0.1)")
    ap.add_argument("--request", metavar="RID", default=None,
                    help="one request's reconstructed span timeline: the "
                         "causal tree + critical-path breakdown for this "
                         "trace_id ('r0/5', 'step:12'; a bare rid matches "
                         "any replica when unambiguous)")
    ap.add_argument("--slowest", type=int, metavar="N", default=None,
                    help="top-N slowest request timelines with dominant "
                         "span kind and queue/compute/recovery split")
    ap.add_argument("--blame", action="store_true",
                    help="SLO-miss blame: deadline-missing requests joined "
                         "with their timeline's dominant span kind")
    args = ap.parse_args(argv)

    try:
        events, skipped = load_events(args.trace)
    except OSError as e:
        print(f"error: cannot read {args.trace}: {e}", file=sys.stderr)
        return 2
    newer = sum(1 for ev in events if ev.get("schema", 0) > SUPPORTED_SCHEMA)
    if newer:
        print(f"warning: {newer} events use a schema newer than "
              f"{SUPPORTED_SCHEMA}; fields may be missing from this report",
              file=sys.stderr)
    if skipped:
        print(f"warning: skipped {skipped} malformed line(s)", file=sys.stderr)
    if not events:
        print(f"no events in {args.trace}", file=sys.stderr)
        return 1

    if args.audit:
        try:
            with open(args.audit) as fh:
                audit_report = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: cannot read audit report {args.audit}: {e}",
                  file=sys.stderr)
            return 2
        if not (0.0 < args.audit_tolerance <= 1.0):
            print("error: --audit-tolerance must be in (0, 1]",
                  file=sys.stderr)
            return 2
        rows = audit_crosscheck(events, audit_report,
                                tolerance=args.audit_tolerance)
        if not rows:
            print("no collective traffic on either side (audit programs "
                  "carry none, trace logged none)", file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps({"audit_crosscheck": rows}, indent=2,
                             sort_keys=True))
        else:
            sys.stdout.write(
                format_audit_crosscheck(rows, args.audit_tolerance))
        return 0

    if args.perf:
        try:
            with open(args.perf) as fh:
                perf_report = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            print(f"error: cannot read perf report {args.perf}: {e}",
                  file=sys.stderr)
            return 2
        if not (0.0 <= args.perf_slack < 1.0):
            print("error: --perf-slack must be in [0, 1)", file=sys.stderr)
            return 2
        rows = perf_crosscheck(events, perf_report, slack=args.perf_slack)
        if not rows:
            print("no programs with roofline predictions in the perf "
                  "report (run ds_perf.py with --json-out)", file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps({"perf_crosscheck": rows}, indent=2,
                             sort_keys=True))
        else:
            sys.stdout.write(format_perf_crosscheck(rows, args.perf_slack))
        return 0

    if args.request or args.slowest is not None or args.blame:
        tm = _load_timeline()
        timelines = tm.build_timelines(events)
        if not timelines and not args.blame:
            print("no span events in the trace (is request tracing "
                  "enabled? see docs/telemetry.md)", file=sys.stderr)
            return 1
        if args.request:
            tl, err = find_timeline(timelines, args.request)
            if tl is None:
                print(f"error: {err}", file=sys.stderr)
                return 2
            if args.as_json:
                print(json.dumps(slowest_rows({tl.trace_id: tl}, 1)[0],
                                 indent=2, sort_keys=True))
            else:
                sys.stdout.write(format_request_timeline(tl))
        if args.slowest is not None:
            rows = slowest_rows(timelines, args.slowest)
            if args.as_json:
                print(json.dumps({"slowest": rows}, indent=2,
                                 sort_keys=True))
            else:
                sys.stdout.write(format_slowest(rows))
        if args.blame:
            rows = tm.slo_blame(events, timelines)
            if not rows:
                print("no deadline-missing inference_request events in "
                      "the trace", file=sys.stderr)
                return 1
            if args.as_json:
                print(json.dumps({"blame": rows}, indent=2, sort_keys=True))
            else:
                sys.stdout.write(format_blame(rows))
        return 0

    if args.decode:
        table = decode_table(events)
        if not table:
            print("no inference_request events in the trace", file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps({"decode": table}, indent=2, sort_keys=True))
        else:
            sys.stdout.write(format_decode_table(table))
        return 0

    if args.serve:
        table = serve_table(events)
        if not table:
            print("no serving events in the trace", file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps({"serve": table}, indent=2, sort_keys=True))
        else:
            sys.stdout.write(format_serve_table(table))
        return 0

    if args.train:
        table = train_table(events)
        if not table:
            print("no train_fault or numeric_health events in the trace",
                  file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps({"train": table}, indent=2, sort_keys=True))
        else:
            sys.stdout.write(format_train_table(table))
        return 0

    if args.memory:
        table = memory_table(events)
        if not table:
            print("no memory_snapshot events in the trace", file=sys.stderr)
            return 1
        if args.as_json:
            print(json.dumps({"memory": table}, indent=2, sort_keys=True))
        else:
            sys.stdout.write(format_memory_table(table))
        return 0

    report = aggregate(events, kinds=args.kind, all_fields=args.all_fields)
    if args.as_json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        sys.stdout.write(format_tables(report))
        if not args.kind or "inference_request" in args.kind:
            table = decode_table(events)
            if table:
                sys.stdout.write("\n" + format_decode_table(table))
        if not args.kind:
            table = serve_table(events)
            if table:
                sys.stdout.write("\n" + format_serve_table(table))
            table = train_table(events)
            if table:
                sys.stdout.write("\n" + format_train_table(table))
            table = memory_table(events)
            if table:
                sys.stdout.write("\n" + format_memory_table(table))
            table = compile_table(events)
            if table:
                sys.stdout.write("\n" + format_compile_table(table))
    return 0


if __name__ == "__main__":
    sys.exit(main())
