#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/mimo-v2.5.json`` with
ONE thing changed, through the harness as the driver runs it. Two uses:

* a planted fault, to see that the cell's comparison refuses it at full size
  (``compare.serve_routed.why`` has the readings):
  ``ring_slot_off_by_one`` — the program's decode rows write a window
  layer's ring one slot late (the newest key lands on the oldest in the
  window and the row never sees its own);
  ``no_sink`` — the reference leaves the sink out of the window layers'
  softmax (the disagreement of a program that dropped it, seen from the other
  side, which keeps the program's compiled ticks);
* ``ragged_dot`` — the held experts through ``jax.lax.ragged_dot`` in place
  of the Pallas grouped matmul, to time one against the other.

The last line is the contract's result object; ``correct`` false is what a
planted fault is expected to give. ``--set path=value`` overrides a value of
the cell's files (``config.compare.serve_routed.sample=2``).
"""

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cell_variant  # noqa: E402  (its clock starts at import, as the harness wants)


@contextlib.contextmanager
def ring_slot_off_by_one(config):
    import jax.numpy as jnp

    from deepspeed_tpu.models import layer_plan

    ring, sound = int(config["model"]["sliding_window"]), layer_plan._write_rows

    def late(pool, layer, new, cols, size):
        if pool.shape[3] == ring == size:                # a window layer's pool
            cols = jnp.where(cols < size, (cols + 1) % size, cols)   # a parked row stays parked
        return sound(pool, layer, new, cols, size)

    layer_plan._write_rows = late
    try:
        yield
    finally:
        layer_plan._write_rows = sound


@contextlib.contextmanager
def no_sink(config):
    from benchmark import compare

    reference = compare.reference_of(config)
    sound = reference.arch
    reference.arch = lambda c: sound(c)._replace(sink=(False, False))
    try:
        yield
    finally:
        reference.arch = sound


@contextlib.contextmanager
def ragged_dot(config):
    from deepspeed_tpu.moe import held_experts

    sound = held_experts._matmul
    held_experts._matmul = lambda x, w, lay, tm, grad, layer: sound(x, w, lay, tm, True, layer)
    try:
        yield
    finally:
        held_experts._matmul = sound


FAULTS = {f.__name__: f for f in (ring_slot_off_by_one, no_sink)}
VARIANTS = dict(FAULTS, ragged_dot=ragged_dot)


def main(argv=None, manifest=None, require_tpu=True):
    return cell_variant.main(argv, manifest, require_tpu, variants=VARIANTS,
                             workload="serve-mimo-v2.5-longdoc-batch", doc=__doc__)


if __name__ == "__main__":
    main()
