#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/qwen3-next-80b-a3b.json``
with ONE thing changed, through the harness as the driver runs it
(``tools/cell_variant.py`` has the command line). Uses:

* a planted fault, to see that the cell's comparison refuses it at full size
  (``compare.serve_hybrid.why`` has the readings):
  ``no_reset`` — the program admits a request to a slot without zeroing the
  slot's recurrent state and convolution tail (the last request's state
  leaks into the next one's first token);
  ``pads_step`` — a chunk's pad tokens and the parked rows step the state
  like real tokens;
  ``no_decay`` — the reference leaves the decay out of the delta rule (the
  disagreement of a program that dropped it, seen from the other side, which
  keeps the program's compiled ticks); ``no_shared``, ``no_attn_gate``, ``no_conv``:
  the reference without the shared expert, the attention's output gate, the
  convolution;
* ``fp8`` — the reference with float8 operands in every matmul
  (``compare.fp8``), in the float32 reference's place: the precision below
  the configuration's, which the cell's limit has to refuse.

The last line is the contract's result object; ``correct`` false is what a
planted fault is expected to give. ``--set path=value`` overrides a value of
the cell's files (``config.compare.serve_hybrid.sample=2``).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cell_variant  # noqa: E402  (its clock starts at import, as the harness wants)
from cell_variant import fp8, reference_without, swapped  # noqa: E402


def no_reset(config):
    from deepspeed_tpu.ops.transformer import kv_cache

    return swapped(kv_cache, "reset_row", lambda state, slot: state)


def pads_step(config):
    from deepspeed_tpu.models import layer_plan

    return swapped(layer_plan, "_hold", lambda g, beta, valid: (g, beta))


FAULTS = {f.__name__: f for f in (no_reset, pads_step, reference_without("decay"),
                                  reference_without("shared"), reference_without("attn_gate"),
                                  reference_without("conv"))}
VARIANTS = dict(FAULTS, fp8=fp8)


def main(argv=None, manifest=None, require_tpu=True):
    return cell_variant.main(argv, manifest, require_tpu, variants=VARIANTS,
                             workload="serve-qwen3-next-longdoc-batch", doc=__doc__)


if __name__ == "__main__":
    main()
