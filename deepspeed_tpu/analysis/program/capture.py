"""Lowering hook: the bridge between the program-building sites and the
auditor.

The sites that build compiled programs (continuous pool ticks, the
engine decode pair, the train micro/apply jits) call
:func:`notify_program` right after ``jax.jit(...)`` — with NO hook
installed that is one module-global ``is None`` check (zero hot-path
cost, no tracing, no lowering). When a hook IS installed
(``dstpu_prewarm --audit``, ``tools/ds_audit.py``, the gate test), the
site's ``args_thunk`` supplies abstract args (ShapeDtypeStructs) and
the program is lowered + compiled into a
:class:`~.artifact.ProgramArtifact` handed to the hook.

jax is imported lazily inside functions only: this module must stay
importable by the stdlib-only ds-lint standalone loader.
"""

from .artifact import ProgramArtifact

_hook = None  # callable(ProgramArtifact) | None


def set_hook(callback):
    """Install ``callback`` to receive every notified program's artifact.
    Returns the previous hook (restore it when done — hooks nest)."""
    global _hook
    prev = _hook
    _hook = callback
    return prev


def clear_hook():
    global _hook
    _hook = None


def active() -> bool:
    return _hook is not None


class ArtifactCollector:
    """The common hook: append every artifact to a list.

        collector = ArtifactCollector()
        prev = set_hook(collector)
        try:  ... build programs ...
        finally: set_hook(prev)
        auditor.audit(collector.artifacts)
    """

    def __init__(self):
        self.artifacts = []

    def __call__(self, artifact):
        self.artifacts.append(artifact)


def shape_structs(tree):
    """jax.ShapeDtypeStruct pytree mirroring ``tree``'s leaves (shape,
    dtype, and sharding when present) — what ``Lowered`` wants in place
    of live buffers."""
    import jax

    def one(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype,
                sharding=getattr(leaf, "sharding", None))
        return leaf

    return jax.tree.map(one, tree)


def param_leaf_shapes(params):
    """Global shapes of every ≥2-D param leaf (the param-collective
    rule's match set; int8-quantized {"q8","s"} leaves are plain leaves
    here)."""
    import jax

    return tuple(tuple(leaf.shape) for leaf in jax.tree.leaves(params)
                 if getattr(leaf, "ndim", 0) >= 2)


def extract_artifact(family: str, variant: str, fn, args, meta=None,
                     compile_program: bool = True) -> ProgramArtifact:
    """Lower (and by default compile) ``fn(*args)`` into a
    ProgramArtifact. Never raises: extraction failures come back as an
    artifact with ``error`` set, which the audit reports as a finding
    (``audit-extraction-error``) rather than crashing the build site.

    ``fn`` may be the build journal's wrapper (``compile_log.record_build``)
    — it forwards ``.lower`` via ``__getattr__``."""
    meta = dict(meta or {})
    art = ProgramArtifact(family=family, variant=variant, meta=meta)
    try:
        import jax

        # the cost model picks its peaks row from this (ds-perf predictions)
        meta.setdefault("device_kind", jax.devices()[0].device_kind)
        lowered = fn.lower(*args)
        art.stable_text = lowered.as_text()
        try:
            donated = sum(1 for a in jax.tree.leaves(lowered.args_info)
                          if getattr(a, "donated", False))
        except Exception:  # noqa: BLE001 — args_info is a best-effort surface
            donated = 0
        meta["donated_leaves"] = donated
        if compile_program:
            compiled = lowered.compile()
            art.hlo_text = compiled.as_text()
            art.memory = _memory_dict(compiled)
            art.cost = _cost_dict(compiled)
    except Exception as exc:  # noqa: BLE001 — failure IS the finding
        art.error = f"{type(exc).__name__}: {exc}"
    return art


def _memory_dict(compiled) -> dict:
    """memory_analysis() fields as a plain dict (adds ``alias_bytes`` on
    top of telemetry/memory.py's view — the donation-honored byte
    count); {} where the backend lacks the analysis."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 — optional backend surface
        return {}
    if mem is None:
        return {}
    out = {}
    for attr, name in (("temp_size_in_bytes", "temp_bytes"),
                       ("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(mem, attr, None)
        if isinstance(v, int):
            out[name] = v
    return out


def _cost_dict(compiled) -> dict:
    """cost_analysis() flattened to one dict (this jaxlib returns a
    one-element list)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — optional backend surface
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost or {})


def _resolve_meta(meta):
    if callable(meta):
        meta = meta()
    return dict(meta or {})


def notify_program(family: str, variant: str, fn, args_thunk, meta=None):
    """Program-build sites call this. No-op (one global check) without a
    hook; with one, extracts the artifact and delivers it. ``args_thunk``
    (and ``meta`` when callable) run only when a hook is active, so
    sites may build ShapeDtypeStruct trees inside them without hot-path
    cost."""
    if _hook is None:
        return
    meta = _resolve_meta(meta)
    try:
        args = args_thunk()
    except Exception as exc:  # noqa: BLE001 — surface as extraction error
        art = ProgramArtifact(family=family, variant=variant, meta=meta,
                              error=f"args_thunk failed: {exc}")
        _hook(art)
        return
    _hook(extract_artifact(family, variant, fn, args, meta=meta))


def notify_lowered(family: str, variant: str, lowered, meta=None,
                   compiled=None):
    """Variant of :func:`notify_program` for sites that already hold a
    ``jax.stages.Lowered`` (runtime/engine._micro_cost_analysis keeps
    one for the MFU capture) — no re-trace, the existing artifact is
    read as-is. ``compiled`` skips the compile when the site has it."""
    if _hook is None:
        return
    import jax

    meta = _resolve_meta(meta)
    art = ProgramArtifact(family=family, variant=variant, meta=meta)
    try:
        meta.setdefault("device_kind", jax.devices()[0].device_kind)
        art.stable_text = lowered.as_text()
        try:
            meta["donated_leaves"] = sum(
                1 for a in jax.tree.leaves(lowered.args_info)
                if getattr(a, "donated", False))
        except Exception:  # noqa: BLE001 — args_info is best-effort
            meta["donated_leaves"] = 0
        if compiled is None:
            compiled = lowered.compile()
        art.hlo_text = compiled.as_text()
        art.memory = _memory_dict(compiled)
        art.cost = _cost_dict(compiled)
    except Exception as exc:  # noqa: BLE001 — failure IS the finding
        art.error = f"{type(exc).__name__}: {exc}"
    _hook(art)
