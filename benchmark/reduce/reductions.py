"""Reductions from a ``Trace`` to numbers. Pure functions of the events, so
that the tests check them on a recorded trace and on hand-made intervals.

Times are nanoseconds in, seconds or milliseconds out as named. Op events
nest (a ``while`` encloses its body's ops): a union of intervals is immune
to that; a sum leaves the containers out (``CONTAINERS``).
"""

import re
from collections import defaultdict
from typing import Iterable, List, Optional, Tuple

from benchmark.reduce.xplane import Event, Trace

CONTAINERS = re.compile(r"^(while|conditional|call|async-start|async-done) ")
COLLECTIVES = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|collective-broadcast)"
    r"(-start|-done)? ")

WINDOW_SPAN = "bench:window"

Interval = Tuple[int, int]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same instants."""
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def covered_ns(intervals: Iterable[Interval]) -> int:
    return sum(end - start for start, end in merge(intervals))


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return out


def spans(events: Iterable[Event]) -> List[Interval]:
    return [(s, s + d) for _, s, d in events]


def clip(events: Iterable[Event], window: Optional[Interval]) -> List[Event]:
    """Events that start inside ``window`` (all of them when it is None):
    what a count or a time per call is taken over."""
    if window is None:
        return list(events)
    return [e for e in events if window[0] <= e[1] < window[1]]


def inside(events: Iterable[Event], window: Optional[Interval]) -> List[Interval]:
    """The events' intervals cut to ``window``: what a busy time is taken
    over, so that it can never pass the window's own length."""
    if window is None:
        return spans(events)
    lo, hi = window
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events if s < hi and s + d > lo]


def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def busy_s(trace: Trace, window: Optional[Interval] = None) -> Optional[float]:
    """Seconds in which some op ran on a device: the union of its op
    intervals, averaged over the devices. None where no device op was seen."""
    per_device = [covered_ns(inside(d.ops, window)) / 1e9 for d in trace.devices.values()]
    per_device = [b for b in per_device if b > 0]
    return _mean(per_device)


def module_ms_per_call(trace: Trace, pattern: str, window=None) -> Optional[float]:
    """Mean device time of one run of the programs whose name matches."""
    rx = re.compile(pattern)
    per_device = []
    for dev in trace.devices.values():
        hits = [d for n, _, d in clip(dev.modules, window) if rx.search(n)]
        if hits:
            per_device.append(sum(hits) / len(hits) / 1e6)
    return _mean(per_device)


def module_ms(trace: Trace, pattern: str, window=None) -> Optional[float]:
    """Summed device time of the runs of the programs whose name matches,
    averaged over the devices; None where none matched."""
    rx = re.compile(pattern)
    per_device = []
    for dev in trace.devices.values():
        hits = [d for n, _, d in clip(dev.modules, window) if rx.search(n)]
        if hits:
            per_device.append(sum(hits) / 1e6)
    return _mean(per_device)


def op_ms(trace: Trace, pattern: str, window=None) -> Optional[float]:
    """Summed device time of the matching ops (containers left out),
    averaged over the devices; None where none matched."""
    rx = re.compile(pattern)
    per_device = []
    for dev in trace.devices.values():
        hits = [d for n, _, d in clip(dev.ops, window)
                if rx.search(n) and not CONTAINERS.search(n)]
        if hits:
            per_device.append(sum(hits) / 1e6)
    return _mean(per_device)


def collective_ms(trace: Trace, window=None):
    """(milliseconds in which a collective was in flight on a device, the
    share of that time in which no other op ran there), averaged over the
    devices. A collective shows as a synchronous op on ``XLA Ops`` or as a
    start..done span on ``Async XLA Ops``; both are taken. (None, None)
    where the trace holds no collective."""
    total, exposed = [], []
    for dev in trace.devices.values():
        ops = clip(dev.ops, window)
        coll = merge(spans(e for e in ops + clip(dev.async_ops, window)
                           if COLLECTIVES.search(e[0])))
        if not coll:
            continue
        other = merge(spans(e for e in ops
                            if not COLLECTIVES.search(e[0]) and not CONTAINERS.search(e[0])))
        coll_ns = covered_ns(coll)
        total.append(coll_ns / 1e6)
        exposed.append(covered_ns(subtract(coll, other)) / coll_ns)
    return _mean(total), _mean(exposed)


def top_ops(trace: Trace, n: int = 10, window=None):
    """[[name, seconds]]: the ops with most summed time on the first device
    (containers left out), names as the compiler printed them."""
    for dev in trace.devices.values():
        totals = defaultdict(int)
        for name, _, dur in clip(dev.ops, window):
            if not CONTAINERS.search(name):
                totals[name] += dur
        if totals:
            ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
            return [[name, ns / 1e9] for name, ns in ranked]
    return []


def idle_gaps(trace: Trace, n: int = 10, window=None):
    """[[what the host was doing, seconds]]: the first device's idle time,
    each gap between its ops charged to the benchmark's own host span
    (``bench:*``) that overlaps it most, ``(no span)`` where none does."""
    for dev in trace.devices.values():
        busy = merge(inside(dev.ops, window))
        if not busy:
            continue
        lo, hi = (busy[0][0], busy[-1][1]) if window is None else window
        gaps = subtract([(lo, hi)], busy)
        totals = defaultdict(int)
        host = [e for e in trace.host_spans if e[0] != WINDOW_SPAN]  # it encloses every gap
        for g0, g1 in gaps:
            best, best_ns = "(no span)", 0
            for name, s, d in host:
                if s >= g1:
                    break
                overlap = min(g1, s + d) - max(g0, s)
                if overlap > best_ns:
                    best, best_ns = name, overlap
            totals[best] += g1 - g0
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]
    return []


def span_window(trace: Trace, name: str = WINDOW_SPAN) -> Optional[Interval]:
    """The interval of the first host span called ``name``."""
    for n, s, d in trace.host_spans:
        if n == name:
            return (s, s + d)
    return None
