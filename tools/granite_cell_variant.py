#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/granite-4.0-h-small.json``
with ONE thing changed, through the harness as the driver runs it
(``tools/cell_variant.py`` has the command line). Uses:

* a planted fault, to see that the cell's comparison refuses it at full size
  (``compare.serve_ssm.why`` has the readings):
  ``no_reset`` — the program admits a request to a slot without zeroing the
  slot's state and convolution tail (the last request's state leaks into the
  next one's first tokens; nothing in this recurrence overwrites it, it only
  decays);
  ``pads_step`` — a chunk's pad tokens and the parked rows step the state
  like real tokens;
  ``no_decay``, ``no_skip``, ``no_nope``, ``no_residual_multiplier`` — the
  reference without the decay, without ``D x``, with rotary positions turned
  on, with ``residual_multiplier`` 1 (the disagreement of a program that did
  the same, seen from the other side, which keeps the program's compiled
  ticks); ``no_shared``, ``no_conv_bias``, ``no_gate_before_norm`` likewise;
* ``fp8`` — the reference with float8 operands in every matmul
  (``compare.fp8``), in the float32 reference's place: the precision below
  the configuration's, which the cell's limit has to refuse.

The last line is the contract's result object; ``correct`` false is what a
planted fault is expected to give. ``--set path=value`` overrides a value of
the cell's files (``config.compare.serve_ssm.sample=2``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cell_variant  # noqa: E402  (its clock starts at import, as the harness wants)
from cell_variant import fp8, no_reset, reference_without, swapped  # noqa: E402


def pads_step(config):
    from deepspeed_tpu.models import layer_plan

    return swapped(layer_plan, "_hold_dt", lambda dt, valid: dt)


FAULTS = {f.__name__: f for f in (no_reset, pads_step) + tuple(
    reference_without(piece) for piece in ("decay", "skip", "nope", "residual_multiplier", "shared",
                                           "conv_bias", "gate_before_norm"))}
VARIANTS = dict(FAULTS, fp8=fp8)


def main(argv=None, manifest=None, require_tpu=True):
    return cell_variant.main(argv, manifest, require_tpu, variants=VARIANTS,
                             workload="serve-granite-4.0-h-small-longdoc-batch", doc=__doc__)


if __name__ == "__main__":
    main()
