#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/nemotron-3-super-120b-a12b.json``
with ONE thing changed, through the harness as the driver runs it
(``tools/cell_variant.py`` has the command line). Uses:

* a planted fault, to see that the cell's comparison refuses it at full size
  (``compare.serve_latent_moe.why`` has the readings):
  ``no_reset`` — the program admits a request to a slot without zeroing the
  slot's state and convolution tail (the last request's state leaks into the
  next one's first tokens);
  ``pads_step`` — a chunk's pad tokens and the parked rows step the state
  like real tokens;
  ``whole_norm`` — the PROGRAM's gated norm over all 8,192 channels at once
  instead of over each group's 1,024;
  ``no_group_norm``, ``no_groups``, ``no_latent_up``, ``no_routed_scale``,
  ``no_nope`` — the reference with ONE norm over the whole inner width, with
  group 0's ``B`` and ``C`` given to every head, without the latent
  up-projection (the routed experts' sum never reaches the stream), with a
  routed scale of 1 in 5's place, with rotary positions turned on (the
  disagreement of a program that did the same, seen from the other side,
  which keeps the program's compiled ticks); ``no_shared``, ``no_skip``,
  ``no_decay``, ``no_select_bias``, ``no_relu2``, ``no_gate_before_norm``
  likewise;
* ``fp8`` — the reference with float8 operands in every matmul
  (``compare.fp8``), in the float32 reference's place: the precision below
  the configuration's, which the cell's limit has to refuse.

The last line is the contract's result object; ``correct`` false is what a
planted fault is expected to give. ``--set path=value`` overrides a value of
the cell's files (``config.compare.serve_latent_moe.sample=2``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cell_variant  # noqa: E402  (its clock starts at import, as the harness wants)
from cell_variant import fp8, no_reset, reference_without, swapped  # noqa: E402


def pads_step(config):
    from deepspeed_tpu.models import layer_plan

    return swapped(layer_plan, "_hold_dt", lambda dt, valid: dt)


def whole_norm(config):
    from deepspeed_tpu.models import layer_plan

    return swapped(layer_plan, "_group_mean_square", lambda y, cfg: (y * y).mean(-1, keepdims=True))


FAULTS = {f.__name__: f for f in (no_reset, pads_step, whole_norm) + tuple(
    reference_without(piece) for piece in ("group_norm", "groups", "latent_up", "routed_scale", "nope",
                                           "shared", "skip", "decay", "select_bias", "relu2",
                                           "gate_before_norm"))}
VARIANTS = dict(FAULTS, fp8=fp8)


def main(argv=None, manifest=None, require_tpu=True):
    return cell_variant.main(argv, manifest, require_tpu, variants=VARIANTS,
                             workload="serve-nemotron-3-super-reasoning-batch", doc=__doc__)


if __name__ == "__main__":
    main()
