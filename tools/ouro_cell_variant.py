#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/ouro-2.6b.json`` with
ONE thing changed, through the harness as the driver runs it
(``tools/cell_variant.py`` has the command line). Uses:

* a planted fault, to see that the cell's comparison refuses it at full size
  (``compare.serve_looped.why`` has the readings):
  ``shared_cache`` — every pass reads and writes pass 1's layer-caches (the
  fault this architecture invites: prefill and decoding then agree with each
  other, and only the reference knows better);
  ``previous_pass_cache`` — pass t uses pass t - 1's layer-caches (passes 1
  and 2 share one: an index off by one);
  ``no_last_pass``, ``no_loop_norm``, ``no_post_norm``, ``no_theta`` — the
  reference with three passes instead of four, without the norm between
  passes, without the two norms on the sublayers' outputs, with rotary base
  1e4 (the disagreement of a program that did the same, seen from the other
  side, which keeps the program's compiled ticks);
* ``fp8`` — the reference with float8 operands in every matmul
  (``compare.fp8``), in the float32 reference's place: the precision below
  the configuration's, which the cell's limit has to refuse.

The last line is the contract's result object; ``correct`` false is what a
planted fault is expected to give. ``--set path=value`` overrides a value of
the cell's files (``config.compare.serve_looped.sample=4``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cell_variant  # noqa: E402  (its clock starts at import, as the harness wants)
from cell_variant import fp8, reference_without, swapped  # noqa: E402


def shared_cache(config):
    from deepspeed_tpu.models import layer_plan

    return swapped(layer_plan, "_pass_slot", lambda per_pass, step, pool_index: pool_index)


def previous_pass_cache(config):
    import jax.numpy as jnp

    from deepspeed_tpu.models import layer_plan

    return swapped(layer_plan, "_pass_slot", lambda per_pass, step, pool_index: (
        jnp.maximum(step - 1, 0) * per_pass + pool_index))


FAULTS = {f.__name__: f for f in (shared_cache, previous_pass_cache) + tuple(
    reference_without(piece) for piece in ("last_pass", "loop_norm", "post_norm", "theta"))}
VARIANTS = dict(FAULTS, fp8=fp8)


def main(argv=None, manifest=None, require_tpu=True):
    return cell_variant.main(argv, manifest, require_tpu, variants=VARIANTS,
                             workload="serve-ouro-2.6b-chat-batch", doc=__doc__)


if __name__ == "__main__":
    main()
