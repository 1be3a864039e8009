"""Per-request timeline reconstruction from ``"span"`` trace events.

The write side (``telemetry/spans.py`` + the serving/inference/router/
train emit sites) records request-scoped spans into the same JSONL trace
every other telemetry event rides: one ``kind: "span"`` line per closed
span, carrying ``trace_id`` (the request identity — stable across
migration and engine rebuilds), ``span_id`` / ``parent_id`` causality,
and a monotonic-clock ``t0``/``t1`` window. This module is the READ
side: group spans by trace_id, stitch the parent/child tree (a
``migration`` span bridges replica tags, so one trace_id reconstructs
across engine generations), find orphans, and attribute each request's
wall time to the span kind that dominated it — the "why is THIS request
slow" answer the aggregate tables cannot give.

Deliberately stdlib-only and self-contained (no intra-package imports):
``tools/ds_trace_report.py`` / ``tools/ds_trace_timeline.py`` load this
file by path so the CLIs stay runnable off-pod, and the jax-free CI
stage imports it under the namespace-stubbed package. The span-kind
tables live HERE for that reason; ``telemetry/spans.py`` (the write
side) imports them from this module, never the reverse.
"""

import heapq
import json
from typing import Dict, Iterable, List, Optional

# Every span kind the stack emits. Serving request lifecycle: queue
# (submit -> handover), admission (the handover/engine-submit work),
# prefill_wait (handover -> the request's first prefill dispatch: the
# wait in the batcher's prefill queue, one chunk a tick for the pool),
# then per-tick windows (prefill_chunk / decode_window /
# spec_verify_round) from the continuous engine's retire path.
# Cross-replica: migration (router-emitted, bridges the dead replica's
# spans to the survivor's). Recovery: recovery_replay (in-process
# rebuild re-admission). Ops: drain_wait (drain() -> queue dry).
# Training reuses the same model: train_step / train_retry /
# train_rebuild under a ``step:N`` trace_id.
SPAN_KINDS = (
    "queue",
    "admission",
    "prefill_wait",
    "prefill_chunk",
    "decode_window",
    "spec_verify_round",
    "migration",
    "recovery_replay",
    "drain_wait",
    "train_step",
    "train_retry",
    "train_rebuild",
)

# Coarse queue-vs-compute-vs-recovery attribution for the blame tables.
SPAN_CATEGORY = {
    "queue": "queue",
    "drain_wait": "queue",
    "prefill_wait": "queue",
    "admission": "compute",
    "prefill_chunk": "compute",
    "decode_window": "compute",
    "spec_verify_round": "compute",
    "train_step": "compute",
    "migration": "recovery",
    "recovery_replay": "recovery",
    "train_retry": "recovery",
    "train_rebuild": "recovery",
}

# Host spans the program writes into the PROFILER's trace carry this prefix
# (``spans.host_span``); the capture entry point marks the instant it starts
# with a zero-length ``dstpu:clock_sync monotonic_ns=<n>`` annotation and a
# ``profile_window`` JSONL event holding the same ``n`` (``Telemetry.
# start_capture``), which is what lets the JSONL spans below (monotonic
# seconds) be placed on the xplane's axis.
HOST_SPAN_PREFIX = "dstpu:"
CLOCK_SYNC_PREFIX = "dstpu:clock_sync monotonic_ns="


class Span:
    """One closed span parsed off a trace event."""

    __slots__ = ("trace_id", "span_id", "parent_id", "kind", "t0", "t1",
                 "replica", "attrs", "ts")

    def __init__(self, event: dict):
        self.trace_id = str(event["trace_id"])
        self.span_id = str(event["span_id"])
        parent = event.get("parent_id")
        self.parent_id = str(parent) if parent is not None else None
        self.kind = str(event["span"])
        self.t0 = float(event["t0"])
        self.t1 = max(float(event["t1"]), self.t0)
        self.replica = event.get("replica")
        self.attrs = event.get("attrs") or {}
        self.ts = event.get("ts")

    @property
    def dur_ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0

    def __repr__(self):  # pragma: no cover — debugging aid
        return (f"Span({self.kind} {self.span_id} trace={self.trace_id} "
                f"[{self.t0:.6f},{self.t1:.6f}])")


class Timeline:
    """All spans of one trace_id, stitched into a parent/child forest.

    ``orphans`` lists spans whose ``parent_id`` names a span_id absent
    from the trace — causality the writer claimed but the file cannot
    back (a missed migration stitch, a rotated-away parent). A clean
    reconstruction has zero."""

    def __init__(self, trace_id: str, spans: List[Span]):
        self.trace_id = trace_id
        self.spans = sorted(spans, key=lambda s: (s.t0, s.t1, s.span_id))
        self.by_id = {s.span_id: s for s in self.spans}
        self.orphans = [s for s in self.spans
                        if s.parent_id is not None
                        and s.parent_id not in self.by_id]
        self.roots = [s for s in self.spans if s.parent_id is None]

    @property
    def t_start(self) -> float:
        return min(s.t0 for s in self.spans)

    @property
    def t_end(self) -> float:
        return max(s.t1 for s in self.spans)

    @property
    def duration_ms(self) -> float:
        return (self.t_end - self.t_start) * 1000.0

    @property
    def replicas(self) -> List[str]:
        """Replica tags touched, in first-seen (time) order."""
        seen = []
        for s in self.spans:
            if s.replica is not None and s.replica not in seen:
                seen.append(s.replica)
        return seen

    def depth(self, span: Span) -> int:
        """Ancestor count via parent links (root = 0); an orphan's chain
        stops at the missing parent."""
        d, cur, hops = 0, span, 0
        while cur.parent_id is not None and hops <= len(self.spans):
            nxt = self.by_id.get(cur.parent_id)
            if nxt is None:
                break
            d += 1
            cur = nxt
            hops += 1
        return d

    def children(self, span_id: str) -> List[Span]:
        return [s for s in self.spans if s.parent_id == span_id]

    # -- attribution ----------------------------------------------------
    def critical_path(self) -> Dict[str, float]:
        """{span kind: ms} — every instant of [t_start, t_end] charged to
        the DEEPEST span covering it (ties: the later-starting one — the
        most specific work running then). Instants no span covers are
        charged to ``"gap"``. Sums exactly to ``duration_ms``."""
        if not self.spans:
            return {}
        cuts = sorted({t for s in self.spans for t in (s.t0, s.t1)})
        out: Dict[str, float] = {}
        for lo, hi in zip(cuts, cuts[1:]):
            if hi <= lo:
                continue
            covering = [s for s in self.spans if s.t0 <= lo and s.t1 >= hi]
            if covering:
                best = max(covering, key=lambda s: (self.depth(s), s.t0))
                kind = best.kind
            else:
                kind = "gap"
            out[kind] = out.get(kind, 0.0) + (hi - lo) * 1000.0
        return out

    def attribution(self) -> Dict[str, float]:
        """Critical-path ms folded to queue / compute / recovery / gap."""
        out: Dict[str, float] = {}
        for kind, ms in self.critical_path().items():
            cat = SPAN_CATEGORY.get(kind, "gap")
            out[cat] = out.get(cat, 0.0) + ms
        return out

    def dominant_kind(self) -> Optional[str]:
        """The span kind holding the most critical-path time (gap
        excluded unless it is all there is)."""
        path = self.critical_path()
        real = {k: v for k, v in path.items() if k != "gap"}
        pool = real or path
        if not pool:
            return None
        return max(sorted(pool), key=lambda k: pool[k])


def iter_events(path: str) -> Iterable[dict]:
    """Parsed events off a JSONL trace, torn/malformed lines skipped —
    the same tolerance as ``telemetry.trace.read_trace`` (duplicated
    here so this module stays loadable by file path, off-repo)."""
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(ev, dict):
                yield ev


def spans_of(events: Iterable[dict]) -> List[Span]:
    out = []
    for ev in events:
        if ev.get("kind") != "span":
            continue
        try:
            out.append(Span(ev))
        except (KeyError, TypeError, ValueError):
            continue  # torn span line: same tolerance as read_trace
    return out


def build_timelines(events: Iterable[dict]) -> Dict[str, Timeline]:
    """{trace_id: Timeline} over every span event in the iterable."""
    grouped: Dict[str, List[Span]] = {}
    for span in spans_of(events):
        grouped.setdefault(span.trace_id, []).append(span)
    return {tid: Timeline(tid, spans) for tid, spans in grouped.items()}


def slo_blame(events: Iterable[dict],
              timelines: Optional[Dict[str, Timeline]] = None) -> List[dict]:
    """SLO-miss blame rows: join ``inference_request`` events that missed
    their deadline (``deadline_met: false``) with their reconstructed
    timeline's dominant span kind. Rows sorted worst-first by ttft."""
    events = list(events)
    if timelines is None:
        timelines = build_timelines(events)
    rows = []
    for ev in events:
        if ev.get("kind") != "inference_request":
            continue
        if ev.get("deadline_met") is not False:
            continue
        tid = ev.get("trace_id")
        tl = timelines.get(str(tid)) if tid is not None else None
        rows.append({
            "trace_id": str(tid) if tid is not None else None,
            "request": ev.get("request"),
            "tenant": ev.get("tenant"),
            "deadline_ms": ev.get("deadline_ms"),
            "ttft_ms": ev.get("ttft_ms"),
            "queue_ms": ev.get("queue_ms"),
            "dominant": tl.dominant_kind() if tl is not None else None,
            "attribution": tl.attribution() if tl is not None else None,
            "replicas": tl.replicas if tl is not None else [],
        })
    rows.sort(key=lambda r: -(r["ttft_ms"] or 0.0))
    return rows


# -- Chrome-trace / Perfetto export -------------------------------------

def to_chrome_trace(timelines: Dict[str, Timeline]) -> dict:
    """Chrome trace-event JSON (the format Perfetto / chrome://tracing
    load): one complete (``ph: "X"``) event per span, microsecond
    timestamps rebased to the earliest span in the export, one pid per
    replica tag (spans with no tag share pid 0), one tid per trace_id —
    so a migrated request renders as the SAME thread lane crossing
    process (replica) groups. ``process_name`` / ``thread_name``
    metadata events label the lanes."""
    all_spans = [s for tl in timelines.values() for s in tl.spans]
    if not all_spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    origin = min(s.t0 for s in all_spans)
    replicas = sorted({s.replica for s in all_spans if s.replica is not None})
    pid_of = {rep: i + 1 for i, rep in enumerate(replicas)}
    tid_of = {tid: i + 1 for i, tid in enumerate(sorted(timelines))}
    events = []
    for rep, pid in [(None, 0)] + sorted(pid_of.items()):
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": rep if rep is not None else "unscoped"}})
    for tid_str, tl in sorted(timelines.items()):
        for pid in sorted({pid_of.get(s.replica, 0) for s in tl.spans}):
            events.append({
                "name": "thread_name", "ph": "M", "pid": pid,
                "tid": tid_of[tid_str],
                "args": {"name": f"trace {tid_str}"}})
    for s in sorted(all_spans, key=lambda s: (s.t0, s.t1, s.span_id)):
        args = {"trace_id": s.trace_id, "span_id": s.span_id}
        if s.parent_id is not None:
            args["parent_id"] = s.parent_id
        args.update(s.attrs)
        events.append({
            "name": s.kind,
            "cat": SPAN_CATEGORY.get(s.kind, "other"),
            "ph": "X",
            "ts": round((s.t0 - origin) * 1e6, 3),
            "dur": round((s.t1 - s.t0) * 1e6, 3),
            "pid": pid_of.get(s.replica, 0),
            "tid": tid_of[s.trace_id],
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def validate_chrome_trace(doc: dict) -> List[str]:
    """Structural lint for an export (the golden-format gate): returns
    human-readable problems, empty when the document is loadable
    trace-event JSON."""
    problems = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["not a trace-event document (no traceEvents key)"]
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in ("X", "M"):
            problems.append(f"event {i}: unexpected ph {ph!r}")
            continue
        for field in ("name", "pid", "tid"):
            if field not in ev:
                problems.append(f"event {i}: missing {field}")
        if ph == "X":
            if not isinstance(ev.get("ts"), (int, float)) or ev["ts"] < 0:
                problems.append(f"event {i}: bad ts {ev.get('ts')!r}")
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                problems.append(f"event {i}: bad dur {ev.get('dur')!r}")
    return problems


# -- the profiler's axis -------------------------------------------------

def clock_offset_ns(host_events) -> Optional[int]:
    """``xplane_ns - monotonic_ns`` from the capture's ``clock_sync``
    annotation among ``host_events`` ((name, start_ns, dur_ns) rows of the
    xplane's host plane); None where the capture wrote none (a trace not
    started through ``Telemetry.start_capture``)."""
    for name, start_ns, _ in host_events:
        if name.startswith(CLOCK_SYNC_PREFIX):
            try:
                return int(start_ns) - int(name[len(CLOCK_SYNC_PREFIX):])
            except ValueError:
                return None
    return None


def place_on_xplane(spans: Iterable["Span"], offset_ns: int) -> List[tuple]:
    """JSONL spans (monotonic seconds) as xplane rows ``(name, start_ns,
    dur_ns)``, named ``<kind> <trace_id>``."""
    return [(f"{s.kind} {s.trace_id}", int(round(s.t0 * 1e9)) + offset_ns,
             int(round((s.t1 - s.t0) * 1e9))) for s in spans]


def _merged(intervals) -> List[list]:
    """Sorted, disjoint [start, end] lists covering the same instants."""
    merged: List[list] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def blame_idle_gaps(busy: List[tuple], host_events: List[tuple],
                    min_gap_ns: int = 1_000_000) -> List[dict]:
    """Every device-idle gap longer than ``min_gap_ns`` between the first
    and the last of the ``busy`` intervals ((start_ns, end_ns), any order,
    may overlap), each with the host event ((name, start_ns, dur_ns): the
    program's ``dstpu:`` spans, and JSONL spans through
    ``place_on_xplane``) to blame — ``(no span)`` where none overlaps —
    and the share of the gap that event covers. The blame goes to the
    NARROWEST event among those covering at least nine tenths of what the
    best one covers: a request-long ``decode_window`` covers every gap of
    its lifetime whole and says nothing, the 120 ms ``dstpu:`` span inside
    it that covers 98 % is what the host was doing."""
    merged = _merged(busy)
    rows = []
    for (_, g0), (g1, _) in zip(merged, merged[1:]):
        if g1 - g0 <= min_gap_ns:
            continue
        over = [(min(g1, s + d) - max(g0, s), d, name) for name, s, d in host_events]
        over = [o for o in over if o[0] > 0]
        most = max((o[0] for o in over), default=0)
        covered, _, blamed = min((o for o in over if o[0] >= 0.9 * most),
                                 key=lambda o: o[1], default=(0, 0, "(no span)"))
        rows.append({"start_ns": g0, "gap_ms": (g1 - g0) / 1e6, "span": blamed,
                     "covered": covered / (g1 - g0)})
    return rows


# The serving loop's host spans (``spans.host_span`` sites of ``serving/
# engine.py`` and ``inference/continuous.py``) under the names of the host
# ledger's rows (``ServingEngine.tick_stats()``; docs/telemetry.md "The serving
# loop's ledger"): the ledger's ``dispatch_ms`` is ``admit`` + ``dispatch``
# here, a ``step()`` outside its inner spans is ``step_other``. Any other
# ``dstpu:`` span is a phase under its own name.
PHASE_OF_SPAN = {
    "serve.step": "step_other",
    "serve.schedule": "schedule",
    "tick.admit": "admit",
    "tick.dispatch.plain": "dispatch",
    "tick.dispatch.fused": "dispatch",
    "tick.retire": "block",
    "tick.attribute": "attribute",
    "serve.emit": "emit",
}
STEP_SPAN = HOST_SPAN_PREFIX + "serve.step"
EMPTIED_MARK = HOST_SPAN_PREFIX + "serve.emptied"
REFILLED_MARK = HOST_SPAN_PREFIX + "serve.refilled"
EMPTY, BETWEEN_STEPS, NO_SPAN = "empty", "between_steps", "(no span)"


def _empty_stretches(host_events, lo: int, hi: int) -> List[list]:
    """[start, end] of the server's emptinesses inside [lo, hi], from the
    ``serve.emptied`` / ``serve.refilled`` markers; a trace that opens on a
    ``refilled`` began empty, one that closes on an ``emptied`` ends so."""
    marks = sorted((s, name == EMPTIED_MARK) for name, s, _ in host_events
                   if name in (EMPTIED_MARK, REFILLED_MARK))
    out, since = [], (lo if marks and not marks[0][1] else None)
    for t, emptied in marks:
        if emptied:
            since = t if since is None else since
        elif since is not None:
            out.append([since, t])
            since = None
    if since is not None:
        out.append([since, hi])
    return out


def idle_by_phase(busy: List[tuple], host_events: List[tuple],
                  window: Optional[tuple] = None) -> Dict[str, float]:
    """Seconds of device-idle time by what the host was doing, SPLIT: each
    idle nanosecond goes to the innermost ``dstpu:`` span that covers it
    (``PHASE_OF_SPAN`` names the serving loop's; the latest to start wins
    where spans nest), to ``empty`` between a ``serve.emptied`` and the next
    ``serve.refilled`` marker, to ``between_steps`` outside every
    ``serve.step`` span of a trace that holds one, and to ``(no span)``
    otherwise. ``busy`` and ``host_events`` as for ``blame_idle_gaps``, which
    awards a whole gap to one span and stays beside this for the question
    "which span"; here the rows sum to the idle time. Idle is what ``busy``
    leaves of ``window`` ((start_ns, end_ns); default: from the first busy
    interval's start to the last one's end)."""
    merged = _merged(busy)
    if window is None:
        if not merged:
            return {}
        window = (merged[0][0], merged[-1][1])
    lo, hi = window
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    gaps = [g for g in gaps if g[1] > g[0]]
    if not gaps:
        return {}

    marks = (EMPTIED_MARK, REFILLED_MARK, CLOCK_SYNC_PREFIX)
    spans = sorted((s, s + d, name) for name, s, d in host_events
                   if d > 0 and not name.startswith(marks))
    steps = _merged((s, e) for s, e, name in spans if name == STEP_SPAN)
    # what an instant no span covers reads: empty, between two steps, or nothing known
    ground = [(s, e, EMPTY) for s, e in _empty_stretches(host_events, lo, hi)]
    if steps:
        ground.append((min(lo, steps[0][0]), max(hi, steps[-1][1]), BETWEEN_STEPS))

    cuts = sorted({lo, hi}
                  | {t for s, e, _ in spans for t in (s, e) if lo < t < hi}
                  | {t for s, e, _ in ground for t in (s, e) if lo < t < hi}
                  | {t for g in gaps for t in g})
    out: Dict[str, float] = {}
    active: list = []          # (-start, end, name): the top is the innermost still open
    si = gi = 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while si < len(spans) and spans[si][0] <= t0:
            s, e, name = spans[si]
            heapq.heappush(active, (-s, e, name))
            si += 1
        while gi < len(gaps) and gaps[gi][1] <= t0:
            gi += 1
        if gi == len(gaps):
            break
        if not (gaps[gi][0] <= t0 and t1 <= gaps[gi][1]):
            continue           # the device was busy
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        if active:
            name = active[0][2][len(HOST_SPAN_PREFIX):]
            phase = PHASE_OF_SPAN.get(name, name)
        else:
            phase = next((p for s, e, p in ground if s <= t0 and t1 <= e), NO_SPAN)
        out[phase] = out.get(phase, 0.0) + (t1 - t0) / 1e9
    return out
