"""Build journal: every program an engine builds leaves one entry, hub on
or off — when it was built, what its first dispatch cost and how that
splits into trace / lower / compile-or-load, and what the chip's memory
read after it. The hub, where one is on, still gets the ``compile_event``
and ``compile_ms{family}`` it always got (runtime recompile storms as a
counter on ``/metrics``), now with the split beside them.

Mechanism: ``jax.jit`` compiles lazily at the first dispatch, so
:func:`record_build` wraps a freshly built jitted callable and times that
FIRST call (dispatch blocks through tracing + lowering + XLA compile or a
persistent-cache load, then returns futures — the span is build cost, not
execution; no ``block_until_ready`` is added). JAX fires its own duration
events synchronously on the dispatching thread; one pair of listeners adds
them into the entry open on that thread, and durations that arrive with
no entry open (a caller's own ``jit``\\ s) are summed per phase as
``unwrapped``. Every later call goes straight through: a cache the caller
owns gets the bare callable back (``settle``), an attribute keeps one flag
test.

Phases: the program marks its own entry points (:func:`phase`); between
and after them it is ``running``, and :func:`summary` reports ``running``
time that a later phase follows as ``caller``. Clock: ``time.monotonic``
throughout (``ServeRequest``'s marks, ``dstpu:clock_sync``). This module
imports no jax: it reads ``sys.modules`` and stays inert without it.
"""

import bisect
import collections
import contextlib
import os
import sys
import threading
import time

from deepspeed_tpu.telemetry.spans import host_span

_IMPORT_T = time.monotonic()
JOURNAL_ENTRIES = 4096
RUNNING, CALLER = "running", "caller"
# ``load`` is the part of ``backend`` spent reading the persistent cache
STAGES = ("trace", "lower", "backend", "load")
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_local = threading.local()
_journal = collections.deque(maxlen=JOURNAL_ENTRIES)
_readings = collections.deque(maxlen=2 * JOURNAL_ENTRIES)  # (t, what, in_use, peak)
_seen = set()            # (family, key) built before in this process
_stack = []              # names of the entry points that have not returned
_armed = False
_hbm_devices = None      # local devices that report memory_stats(), once known


_phases = collections.deque([(RUNNING, _IMPORT_T)], maxlen=JOURNAL_ENTRIES)  # (name, t0)
# builds no entry was open for: (t, stage, seconds of its own)
_unwrapped = collections.deque(maxlen=8 * JOURNAL_ENTRIES)


def _process_start_t():
    """The process's start on ``time.monotonic``'s axis, from
    ``/proc/self/stat`` (field 22, clock ticks since boot); None where that
    is not to be had."""
    try:
        with open("/proc/self/stat") as fh:
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        t = ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return t if 0.0 <= _IMPORT_T - t < 86400.0 else None


_PROCESS_START_T = _process_start_t()


class _Stages:
    """Seconds by stage for one open entry (or one thread's unwrapped
    builds). JAX's timed regions nest (an inner ``jit`` traced inside an
    outer trace, a cache read inside the backend's region) and report
    inner first, so each arrival takes off what it already counted inside
    itself: the stages add up to no more than the wall time."""

    __slots__ = ("secs", "tops", "hits")

    def __init__(self):
        self.secs = dict.fromkeys(STAGES, 0.0)
        self.tops = []   # (start, seconds) of regions no later one contained
        self.hits = 0

    def add(self, stage, secs, now):
        start, inner = now - secs, 0.0
        while self.tops and self.tops[-1][0] >= start:
            inner += self.tops.pop()[1]
        self.tops.append((start, secs))
        own = max(secs - inner, 0.0)
        self.secs[stage] += own
        return own


def _on_duration(event, secs, **_):
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    now = time.monotonic()
    entry = getattr(_local, "entry", None)
    if entry is not None:
        entry.add(stage, secs, now)
        return
    loose = getattr(_local, "loose", None)
    if loose is None:
        loose = _local.loose = _Stages()
    _unwrapped.append((now, stage, loose.add(stage, secs, now)))
    del loose.tops[:-1024]  # this one lives as long as its thread


def _on_event(event, **_):
    if event == _CACHE_HIT:
        entry = getattr(_local, "entry", None)
        if entry is not None:
            entry.hits += 1


def _arm():
    """Register the one pair of listeners, once, as soon as jax is there."""
    global _armed
    if _armed or "jax" not in sys.modules:
        return
    with _lock:
        if _armed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _armed = True


_arm()


def _read_hbm():
    """``bytes_in_use`` / ``peak_bytes_in_use`` of the fullest local
    device, or None where the backend keeps no such count (the CPU) or is
    not up yet (a reading never starts it)."""
    global _hbm_devices
    if _hbm_devices is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            return None
        _hbm_devices = [d for d in jax.local_devices() if d.memory_stats()]
    best = None
    for d in _hbm_devices:
        stats = d.memory_stats()
        if best is None or stats["bytes_in_use"] > best["bytes_in_use"]:
            best = stats
    if best is None:
        return None
    return {"bytes_in_use": int(best["bytes_in_use"]),
            "peak_bytes_in_use": int(best["peak_bytes_in_use"])}


def _note_reading(t, what, hbm):
    if hbm is not None:
        _readings.append((t, what, hbm["bytes_in_use"], hbm["peak_bytes_in_use"]))


# -- phases --------------------------------------------------------------
def mark(name: str):
    """Close the open phase and open ``name``, with a memory reading
    between them (it belongs to the phase that closes)."""
    _arm()
    hbm = _read_hbm()
    now = time.monotonic()
    with _lock:
        _note_reading(now, "phase:" + _phases[-1][0], hbm)
        _phases.append((name, now))


@contextlib.contextmanager
def phase(name: str):
    """One of the program's entry points: ``name`` while it runs (a
    ``dstpu:setup.<name>`` span too), and on return whatever entry point
    encloses it, or ``running``."""
    mark(name)
    _stack.append(name)  # ds-lint: disable=module-mutable-state — the journal IS process-wide
    try:
        with host_span("setup." + name):
            yield
    finally:
        _stack.pop()  # ds-lint: disable=module-mutable-state
        mark(_stack[-1] if _stack else RUNNING)


# -- builds --------------------------------------------------------------
class _Build:
    """Callable wrapper journalling only the first invocation (the one
    that pays trace + lower + compile). Forwards attribute access to the
    wrapped function so AOT surfaces (``.lower``) keep working."""

    __slots__ = ("_fn", "_family", "_key", "_hub", "_tick", "_sums", "_settle",
                 "_fields", "_done")

    def __init__(self, fn, family, key, hub, tick, sums, settle, fields):
        self._fn = fn
        self._family = family
        self._key = key
        self._hub = hub
        self._tick = tick
        self._sums = sums
        self._settle = settle
        self._fields = fields
        self._done = False

    def __call__(self, *args, **kwargs):
        if self._done:
            return self._fn(*args, **kwargs)
        self._done = True
        _arm()
        outer = getattr(_local, "entry", None)
        stages = _local.entry = _Stages()
        t = time.monotonic()
        try:
            with host_span("build." + self._family):
                out = self._fn(*args, **kwargs)
            # the first dispatch of a jitted fn blocks through trace +
            # lower + XLA compile and returns execution FUTURES — the
            # unsynced span IS the build cost, by design
            # ds-lint: disable=unsynced-timing
            wall_ms = (time.monotonic() - t) * 1000.0
        finally:
            _local.entry = outer
        if self._settle is not None:
            self._settle(self._fn)
        self._record(t, wall_ms, stages)
        # a wrapper that stays on an attribute keeps nothing of its engine alive
        self._hub = self._tick = self._sums = self._settle = self._fields = None
        return out

    def _record(self, t, wall_ms, stages):
        ms = {s: v * 1000.0 for s, v in stages.secs.items()}
        backend_ms = ms["backend"] + ms["load"]
        ident = (self._family, str(self._key))
        entry = {"family": ident[0], "key": ident[1], "t": t,
                 "phase": _phases[-1][0],
                 "wall_ms": round(wall_ms, 3),
                 "trace_ms": round(ms["trace"], 3),
                 "lower_ms": round(ms["lower"], 3),
                 "backend_ms": round(backend_ms, 3),
                 "load_ms": round(ms["load"], 3),
                 "cache_hit": stages.hits > 0,
                 "other_ms": round(wall_ms - ms["trace"] - ms["lower"] - backend_ms, 3)}
        if self._tick is not None:
            entry["tick"] = int(self._tick())
        hbm = _read_hbm()
        if hbm is not None:
            entry["hbm"] = hbm
        with _lock:
            entry["recompile"] = ident in _seen
            _seen.add(ident)  # ds-lint: disable=module-mutable-state
            _journal.append(entry)
            _note_reading(time.monotonic(), f"build:{ident[0]} {ident[1]}", hbm)
        sums = self._sums() if self._sums is not None else None
        if sums is not None:
            sums["programs_built"] += 1
            sums["program_build_ms"] += wall_ms
        tele = self._hub() if self._hub is not None else None
        if tele is not None and tele.enabled:
            split = {k: entry[k] for k in ("trace_ms", "lower_ms", "backend_ms", "load_ms",
                                           "other_ms", "cache_hit", "phase", "tick")
                     if k in entry}
            tele.compile_recorder().record(ident[0], ident[1], wall_ms,
                                           **split, **self._fields)

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_fn"), name)


def record_build(fn, family: str, key, *, hub=None, tick=None, sums=None,
                 settle=None, **fields):
    """Arm ``fn`` (a freshly built jitted callable) to journal its first
    dispatch. ``hub`` / ``tick`` / ``sums`` are getters resolved AT THAT
    CALL: the telemetry hub (a serving recovery factory builds replacement
    engines with telemetry off and injects the shared hub afterwards; jit
    compiles lazily, so the first dispatch lands after injection), the
    caller's tick or step index, and the dict whose ``programs_built`` /
    ``program_build_ms`` the build adds to. ``settle(fn)`` hands the bare
    callable back to the cache that owns it, so a steady tick runs no
    wrapper; ``fields`` ride on the hub's ``compile_event``."""
    return _Build(fn, family, key, hub, tick, sums, settle, fields)


class CompileRecorder:
    """Per-telemetry-hub side of the journal. ``record`` emits one
    ``compile_event`` (family, shapes key, ``compile_ms`` = the first
    dispatch's wall time, its split, first-vs-recompile) and folds the
    durations into ``compile_ms{family}`` and ``build_stage_ms{family,
    stage}``. ``recompile`` here is keyed per HUB: the hub survives
    serving-engine rebuilds (PR 7 re-injects it into replacement engines),
    so an LRU-evicted-and-rebuilt program or a rebuilt engine's re-compile
    is flagged while a genuinely new shape is a first compile."""

    def __init__(self, telemetry):
        self._tele = telemetry
        self._seen = set()

    def record(self, family: str, key, compile_ms: float, **fields) -> bool:
        """Journal one compile. Returns the recompile flag (True when
        this (family, key) compiled before under this hub)."""
        ident = (family, str(key))
        recompile = ident in self._seen
        self._seen.add(ident)
        tele = self._tele
        if tele.enabled:
            reg = tele.registry
            reg.histogram("compile_ms", {"family": family}).observe(compile_ms)
            reg.counter("compile_event_total", {"family": family}).inc()
            if recompile:
                reg.counter("recompile_total", {"family": family}).inc()
            for stage in ("trace", "lower", "backend", "other"):
                if stage + "_ms" in fields:
                    reg.histogram("build_stage_ms", {"family": family, "stage": stage}
                                  ).observe(fields[stage + "_ms"])
            event = {"family": family, "key": str(key),
                     "compile_ms": round(compile_ms, 3),
                     "recompile": recompile}
            event.update(fields)
            tele.emit("compile_event", event)
        return recompile


# -- readers -------------------------------------------------------------
def journal():
    """The entries, oldest first (the newest ``JOURNAL_ENTRIES``)."""
    with _lock:
        return [dict(e) for e in _journal]


_SUMS = ("wall", "trace", "lower", "backend", "load", "other")


def _row():
    return dict({"programs": 0, "peak_raised_bytes": 0}, **{k + "_s": 0.0 for k in _SUMS})


def _add_entry(row, entry):
    row["programs"] += 1
    for k in _SUMS:
        row[k + "_s"] += entry[k + "_ms"] / 1000.0


def summary(since_t=None):
    """Where a set-up's seconds and the chip's memory went, up to
    ``since_t`` (the instant the caller's own window opened; now, if
    None): ``phases`` in order of first appearance (``before_import``,
    the program's entry points, ``caller`` between them, ``running`` after
    the last), each with its seconds, the programs built in it and their
    trace / lower / backend / load / other seconds, the ``unwrapped``
    builds' seconds, ``rest_s`` (neither) and the lifetime peak it raised;
    ``families`` the same by program family; ``peak_raisers`` the phases
    and builds between whose readings the peak rose; ``hbm`` the newest
    reading up to ``since_t`` (one is taken now; its ``bytes_in_use`` is
    the steady floor); ``built_after``
    the programs built at or after ``since_t``. ``remainder_s`` is what of
    ``span_s`` (process start, or this module's import, to ``since_t``) no
    phase holds."""
    hbm = _read_hbm()
    now = time.monotonic()
    with _lock:
        _note_reading(now, "phase:" + _phases[-1][0], hbm)
        phases, entries, readings = list(_phases), list(_journal), list(_readings)
        loose = list(_unwrapped)
    end = now if since_t is None else float(since_t)
    phases = [p for p in phases if p[1] < end] or phases[:1]
    starts = [t0 for _, t0 in phases]
    last_setup = max((i for i, p in enumerate(phases) if p[0] != RUNNING), default=-1)
    names = [CALLER if name == RUNNING and i < last_setup else name
             for i, (name, _) in enumerate(phases)]

    def name_at(t):
        return names[max(bisect.bisect_right(starts, t) - 1, 0)]

    def phase_row():
        return dict(_row(), seconds=0.0, unwrapped_s=0.0, unwrapped_programs=0)

    table, families = collections.OrderedDict(), {}
    start = _PROCESS_START_T if _PROCESS_START_T is not None else starts[0]
    if _PROCESS_START_T is not None:
        table["before_import"] = dict(phase_row(), seconds=starts[0] - start)
    for i, name in enumerate(names):
        table.setdefault(name, phase_row())["seconds"] += (
            starts[i + 1] if i + 1 < len(names) else end) - starts[i]
    for t, stage, secs in loose:
        if starts[0] <= t < end:
            row = table[name_at(t)]
            row["unwrapped_s"] += secs
            row["unwrapped_programs"] += stage == "backend"
    built_after = []
    for e in entries:
        if e["t"] >= end:
            built_after.append({k: e[k] for k in ("family", "key", "tick", "wall_ms") if k in e})
            built_after[-1]["at_s"] = round(e["t"] - end, 3)
            continue
        _add_entry(table[name_at(e["t"])], e)
        _add_entry(families.setdefault(e["family"], _row()), e)
    # the allocator keeps one lifetime peak: a rise between two readings belongs
    # to what ran between them (a mark's reading to the phase it closes, so it is
    # looked up just before t; the first reading to all that came before it)
    raisers, prev_peak = collections.OrderedDict(), 0
    for t, what, _, peak in readings:
        rise, prev_peak = peak - prev_peak, max(peak, prev_peak)
        if rise <= 0:
            continue
        if t > end:
            what += " (after since_t)"
        else:
            closing = name_at(t - 1e-9)
            table[closing]["peak_raised_bytes"] += rise
            family = families.get(what[6:].split(" ", 1)[0]) if what.startswith("build:") else None
            if family is not None:
                family["peak_raised_bytes"] += rise
            elif what.startswith("phase:"):
                what = "phase:" + closing
        raisers[what] = raisers.get(what, 0) + rise
    for row in table.values():
        row["rest_s"] = row["seconds"] - row["wall_s"] - row["unwrapped_s"]
    held = sum(row["seconds"] for row in table.values())
    out = {"since_t": end, "import_t": _IMPORT_T, "span_s": end - start,
           "remainder_s": (end - start) - held,
           "phases": table, "families": families,
           "peak_raisers": [{"what": k, "bytes": v} for k, v in raisers.items()],
           "built_after": built_after}
    if _PROCESS_START_T is not None:
        out["before_import_s"] = _IMPORT_T - _PROCESS_START_T
    at_end = [r for r in readings if r[0] <= end]
    if at_end:  # the newest reading as the window opened: the steady floor
        out["hbm"] = {"bytes_in_use": at_end[-1][2], "peak_bytes_in_use": at_end[-1][3]}
    return out
