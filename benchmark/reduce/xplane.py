"""Read a JAX profiler trace (``*.xplane.pb``) into plain Python events.

``jax.profiler.ProfileData`` gives planes, their lines, and events with a
start and a duration in nanoseconds. What this module hands on is a
``Trace``: for every device, its op events and its module (program)
events; and the host's ``TraceAnnotation`` spans the benchmark wrote
around its own calls. Names are kept as the compiler printed them.

Layouts read (seen by hand on jax/jaxlib 0.9.0, libtpu 0.0.34):

- TPU: one plane per chip, ``/device:TPU:<n>``, with a line ``XLA Modules``
  (one event per executed program, named ``jit_<fn>(<fingerprint>)``) and a
  line ``XLA Ops`` (one event per HLO op, named by its whole instruction
  text; a ``while`` encloses the ops of its body, so events nest) and a line
  ``Async XLA Ops`` (start..done spans of asynchronous copies and
  collectives). ``Steps`` is ignored.
- CPU (rehearsals and tests only; never a device number): ops run on host
  threads ``tf_XLA.../...`` of the plane ``/host:CPU`` and carry
  the stats ``hlo_op`` and ``hlo_module``; they are read as one pseudo
  device per thread so that the reductions are exercised end to end.
- Host spans: events of the plane ``/host:CPU``, on the Python threads'
  lines (named after the process), whose name starts with ``bench:``
  (written by ``harness.span``).
"""

import dataclasses
import glob
import gzip
import json
import os
import re
import warnings
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

SPAN_PREFIX = "bench:"
_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")


_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> str:
    """The profiler names a TPU op by its whole HLO instruction,
    ``%fusion.2 = bf16[..]{..} fusion(...), kind=kLoop, ...``. Reduced here to
    ``<opcode>[:<custom call target>] <result name>``: ``fusion fusion.2``,
    ``custom-call:tpu_custom_call closed_call.11``, ``all-gather all-gather.3``,
    ``while while.7``. A name that is not an instruction stays as it is."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else "op"
    if opcode == "custom-call":
        t = _TARGET.search(rest)
        if t:
            opcode += ":" + t.group(1)
    return f"{opcode} {head.strip().lstrip('%')}"


@dataclasses.dataclass
class DeviceTrace:
    ops: List[Event] = dataclasses.field(default_factory=list)  # line "XLA Ops": nested (a while encloses its body)
    async_ops: List[Event] = dataclasses.field(default_factory=list)  # line "Async XLA Ops": start..done spans
    modules: List[Event] = dataclasses.field(default_factory=list)  # line "XLA Modules": one per program run


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceTrace] = dataclasses.field(default_factory=dict)
    host_spans: List[Event] = dataclasses.field(default_factory=list)

    def to_json(self) -> dict:
        return {"devices": {k: dataclasses.asdict(v) for k, v in self.devices.items()},
                "host_spans": self.host_spans}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        as_events = lambda rows: [(str(n), int(s), int(d)) for n, s, d in rows]
        return cls(
            devices={k: DeviceTrace(ops=as_events(v["ops"]), modules=as_events(v["modules"]),
                                    async_ops=as_events(v.get("async_ops", [])))
                     for k, v in obj["devices"].items()},
            host_spans=as_events(obj["host_spans"]))


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a ``jax.profiler`` trace left under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    with warnings.catch_warnings():  # reading an event's stats warns once per event on this jaxlib
        warnings.simplefilter("ignore", DeprecationWarning)
        _read_planes(ProfileData.from_file(path).planes, trace)
    for dev in trace.devices.values():
        dev.ops.sort(key=lambda e: e[1])
        dev.async_ops.sort(key=lambda e: e[1])
        dev.modules.sort(key=lambda e: e[1])
    trace.host_spans.sort(key=lambda e: e[1])
    return trace


def _read_planes(planes, trace: Trace) -> None:
    for plane in planes:
        tpu = _TPU_PLANE.match(plane.name)
        if tpu:
            dev = trace.devices.setdefault(plane.name, DeviceTrace())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                               for e in line.events]
                elif line.name == "Async XLA Ops":
                    dev.async_ops = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                                     for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(e.name, int(e.start_ns), int(e.duration_ns))
                                   for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if not line.name.startswith("tf_"):  # a Python thread, named after the process
                    trace.host_spans += [(e.name, int(e.start_ns), int(e.duration_ns))
                                         for e in line.events if e.name.startswith(SPAN_PREFIX)]
                else:  # XLA:CPU's worker threads (tf_XLAPjRtCpuClient/.., tf_XLAEigen/..)
                    ops, mods = [], []
                    for e in line.events:
                        stats = dict(e.stats)
                        if "hlo_module" in stats:
                            ops.append((f"{e.name.split('.')[0]} {e.name}", int(e.start_ns),
                                        int(e.duration_ns)))
                            mods.append((str(stats["hlo_module"]), int(e.start_ns),
                                         int(e.duration_ns)))
                    if ops:
                        dev = trace.devices.setdefault(f"/host:CPU/{line.name}", DeviceTrace())
                        dev.ops += ops
                        dev.modules += mods  # CPU: no program events; each op stands for its module


def describe(path: str, per_line: int = 6) -> dict:
    """Every plane and line of a trace with its first few event names: what
    a builder looks at by hand before writing a reduction against it."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            names = []
            for e in events:
                if e.name not in names:
                    names.append(e.name)
                if len(names) >= per_line:
                    break
            lines[line.name] = {"events": len(events), "names": names}
        out[plane.name] = lines
    return out


def dump(trace: Trace, path: str, max_events: int = 0) -> None:
    """Write ``trace`` as gzipped JSON (the fixture format of the tests)."""
    obj = trace.to_json()
    if max_events:
        for dev in obj["devices"].values():
            for key in ("ops", "async_ops", "modules"):
                dev[key] = dev[key][:max_events]
        obj["host_spans"] = obj["host_spans"][:max_events]
    with gzip.open(path, "wt") as fh:
        json.dump(obj, fh)


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as fh:
        return Trace.from_json(json.load(fh))
