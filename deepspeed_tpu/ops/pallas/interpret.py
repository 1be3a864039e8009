"""The one rule for Pallas interpret mode.

Every kernel in this package takes ``interpret: Optional[bool] = None``
and resolves ``None`` here: compiled (Mosaic) unless the process's
default backend is the CPU, where only the interpreter can run a kernel
(the unit tests). An explicit argument always wins.

The ambient backend is the wrong answer in one case: an ahead-of-time
compile for a *described* TPU from a CPU-only process
(``jax.experimental.topologies``) — there the default backend is
``cpu`` while the target is a chip, and inferring ``True`` would lower
the interpreter's loops instead of the kernel, so the compile proves
nothing. Such a caller wraps its lowering in :func:`force_interpret`
(``False``), which reaches kernels called from deep inside a model
(``TransformerModel.loss``) without threading an argument through it.
"""

import contextlib
from typing import Optional

import jax

_forced: Optional[bool] = None


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    if interpret is not None:
        return interpret
    if _forced is not None:
        return _forced
    return jax.default_backend() == "cpu"


@contextlib.contextmanager
def force_interpret(value: bool):
    """Pin what ``interpret=None`` resolves to while tracing inside the
    block (trace-time only: an already-compiled program is unaffected)."""
    global _forced
    prev, _forced = _forced, value
    try:
        yield
    finally:
        _forced = prev
