"""unsynced-timing: wall-clock spans that stop without draining the device.

JAX dispatch is async: ``fn(x)`` returns as soon as the work is *enqueued*.
A ``t0 = time.time() ... time.time() - t0`` span around device computation
therefore measures dispatch latency, not compute, unless something blocks
(``jax.block_until_ready``, ``device_get``, a ``_sync()`` helper) before
the stop timestamp is taken. This protects the telemetry layer's wall-time
numbers (docs/telemetry.md) from silently going optimistic.

Three span shapes are recognized:

- local:  ``t0 = time.time()`` ... ``<stop> - t0`` in the same function —
  flagged when calls (potential device work) sit between start and stop
  with no sync call before the stop timestamp;
- param:  the start timestamp arrives as a parameter named like a
  timestamp (``t0``, ``start_time``, ...) — the measured region lives in
  the caller, so the stop site must sync unconditionally;
- attr:   ``self._start = time.time()`` in one method, ``... - self._start``
  in another (timer objects) — same unconditional-sync requirement.
"""

import ast
import re

from ..core import Rule, SEVERITY_WARNING, dotted_name, terminal_name

_TIMING_DOTTED = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.time_ns", "time.perf_counter_ns", "time.monotonic_ns",
}
_TIMING_BARE = {"perf_counter", "monotonic", "perf_counter_ns", "monotonic_ns"}

_SYNC_TERMINALS = {"block_until_ready", "device_get", "effects_barrier", "_sync", "sync"}

_TS_PARAM_RE = re.compile(r"^(t0|t1|t_start|tstart|start|start_time|start_s|begin|begin_s)$")

# host-side calls that cannot be device work — everything else between the
# timestamps counts as potentially-async computation
_TRIVIAL_NAME_CALLS = {
    "str", "repr", "len", "isinstance", "issubclass", "getattr", "hasattr",
    "setattr", "max", "min", "abs", "round", "sorted", "list", "dict", "set",
    "tuple", "enumerate", "zip", "range", "print", "id", "type", "format",
    "sum", "any", "all",
}
_TRIVIAL_ATTR_CALLS = {
    "append", "extend", "get", "items", "keys", "values", "pop", "setdefault",
    "update", "format", "join", "split", "startswith", "endswith", "strip",
    "lower", "upper", "info", "debug", "warning", "error", "exception",
    "write", "flush", "add",
}
_TRIVIAL_MODULE_HEADS = {"logger", "logging", "os", "math", "json", "re", "sys"}


def _is_timing_call(node):
    if isinstance(node, ast.IfExp):
        # `t0 = time.time() if telemetry_on else 0.0` — the engines' gated
        # timestamp idiom still starts a span
        return _is_timing_call(node.body) or _is_timing_call(node.orelse)
    if not isinstance(node, ast.Call):
        return False
    dn = dotted_name(node.func)
    return dn in _TIMING_DOTTED or (
        isinstance(node.func, ast.Name) and node.func.id in _TIMING_BARE
    )


_HOST_FETCH_MODULES = {"np", "numpy", "onp"}


def _is_sync_call(node):
    """Explicit syncs AND host fetches — `float(jnp.sum(out))`,
    `np.asarray(out)`, `.item()` — which force completion just as hard as
    block_until_ready: the value cannot reach the host before the
    program that makes it has run."""
    if not isinstance(node, ast.Call):
        return False
    func = node.func
    if terminal_name(func) in _SYNC_TERMINALS:
        return True
    if isinstance(func, ast.Attribute) and func.attr == "item" and not node.args:
        return True
    if isinstance(func, ast.Name) and func.id in ("float", "int", "bool"):
        return len(node.args) == 1 and not isinstance(node.args[0], ast.Constant)
    if isinstance(func, ast.Attribute) and func.attr in ("asarray", "array"):
        dn = dotted_name(func)
        return bool(dn) and dn.split(".")[0] in _HOST_FETCH_MODULES
    return False


def _is_trivial_call(node):
    func = node.func
    if isinstance(func, ast.Name):
        return func.id in _TRIVIAL_NAME_CALLS
    if isinstance(func, ast.Attribute):
        if func.attr in _TRIVIAL_ATTR_CALLS:
            return True
        dn = dotted_name(func)
        return bool(dn) and dn.split(".")[0] in _TRIVIAL_MODULE_HEADS
    return False


def _scoped_walk(root_stmts):
    """Walk statements without descending into nested function/class
    scopes — those get their own analysis pass."""
    stack = list(root_stmts)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)):
            continue  # nested scope: gets its own analysis pass
        stack.extend(ast.iter_child_nodes(node))


class UnsyncedTimingRule(Rule):
    id = "unsynced-timing"
    severity = SEVERITY_WARNING
    description = (
        "time.time()/perf_counter span stops without block_until_ready — "
        "measures async dispatch, not device compute"
    )

    def check(self, ctx):
        # class attr timestamps: {class node id: {attr names set by any method}}
        attr_timestamps = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            names = set()
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) and _is_timing_call(sub.value):
                    for target in sub.targets:
                        if (
                            isinstance(target, ast.Attribute)
                            and isinstance(target.value, ast.Name)
                            and target.value.id == "self"
                        ):
                            names.add(target.attr)
            if names:
                attr_timestamps[id(node)] = names

        for func, class_node in _functions_with_class(ctx.tree):
            class_attrs = attr_timestamps.get(id(class_node), set()) if class_node else set()
            yield from self._check_function(ctx, func, class_attrs)

    def _check_function(self, ctx, func, class_attrs):
        local_ts = {}  # name -> assignment line
        sync_lines = []
        work_lines = []
        stops = []  # (stop_node, kind, start_line, acq_line)

        param_ts = {
            a.arg for a in func.args.posonlyargs + func.args.args + func.args.kwonlyargs
            if _TS_PARAM_RE.match(a.arg)
        }

        nodes = sorted(_scoped_walk(func.body), key=lambda n: (getattr(n, "lineno", 0), getattr(n, "col_offset", 0)))
        for node in nodes:
            if isinstance(node, ast.Assign) and _is_timing_call(node.value):
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        local_ts[target.id] = node.lineno
            if isinstance(node, ast.Call):
                if _is_sync_call(node):
                    sync_lines.append(node.lineno)
                elif not _is_timing_call(node) and not _is_trivial_call(node):
                    work_lines.append(node.lineno)
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                stop = self._classify_stop(node, local_ts, param_ts, class_attrs, func)
                if stop is not None:
                    stops.append((node,) + stop)

        for node, kind, start_line, acq_line in stops:
            if kind == "local":
                has_work = any(start_line < w < acq_line for w in work_lines)
                synced = any(start_line <= s <= acq_line for s in sync_lines)
                if has_work and not synced:
                    yield self.finding(
                        ctx, node,
                        "timing span stops without a device sync — add "
                        "jax.block_until_ready(...) before the stop timestamp "
                        f"(span starts line {start_line})",
                    )
            else:  # param / attr: measured region is in another scope
                synced = any(s <= acq_line for s in sync_lines)
                if not synced:
                    origin = "a caller-provided start timestamp" if kind == "param" \
                        else "a start timestamp taken in another method"
                    yield self.finding(
                        ctx, node,
                        f"timing span over {origin} stops without a device "
                        "sync in this function — add jax.block_until_ready(...) "
                        "(or a _sync()) before reading the clock",
                    )

    def _classify_stop(self, binop, local_ts, param_ts, class_attrs, func):
        """(kind, start_line, acq_line) when ``binop`` is `<stop> - <start>`
        over a tracked timestamp, else None. ``acq_line`` is where the stop
        timestamp was taken (the sync must land at or before it)."""
        right = binop.right
        kind = start_line = None
        if isinstance(right, ast.Name):
            if right.id in local_ts:
                kind, start_line = "local", local_ts[right.id]
            elif right.id in param_ts:
                kind, start_line = "param", func.lineno
        elif (
            isinstance(right, ast.Attribute)
            and isinstance(right.value, ast.Name)
            and right.value.id == "self"
            and right.attr in class_attrs
        ):
            kind, start_line = "attr", func.lineno
        if kind is None:
            return None
        left = binop.left
        acq_line = binop.lineno
        left_is_clock = _is_timing_call(left)
        if isinstance(left, ast.Name) and left.id in local_ts:
            left_is_clock = True
            acq_line = local_ts[left.id]
        if kind != "local" and not left_is_clock:
            # param/attr matching is name-based ('start', 't0', ...); without
            # a clock read on the stop side this is ordinary arithmetic
            # (`len(xs) - start`), not a timing span
            return None
        return kind, start_line, acq_line


def _functions_with_class(tree):
    """Yield (function node, enclosing ClassDef or None) pairs."""

    def visit(node, cls):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                yield from visit(child, child)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield child, cls
                yield from visit(child, None)
            else:
                yield from visit(child, cls)

    yield from visit(tree, None)
