#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/glm-4.7-flash.json``
with ONE thing changed, through the harness as the driver runs it
(``tools/cell_variant.py`` has the command line). Uses:

* a planted fault, to see that the cell's comparison refuses it at full size
  (``compare.serve_latent.why`` has the readings):
  ``absorbed_scale`` — the decoding rows' absorbed scores are divided by
  sqrt(576), the width of what they are taken over, where the function
  divides by sqrt(256), the width of a head's query (the prefill chunks'
  expanded form stays sound);
  ``late_rope`` — every token's shared rotated key is turned by the NEXT
  position's angle before it is cached (the queries stay sound: every score's
  rotary part sees its key one position late);
  ``no_routed_scale``, ``no_shared``, ``no_rope_key``, ``no_kv_norm``: the
  reference without the factor 1.8, the shared expert, the shared key's part
  of the scores, the latent's norm (the disagreement of a program that
  dropped the piece, seen from the other side, which keeps the program's
  compiled ticks);
* ``fp8`` — the reference with float8 operands in every matmul
  (``compare.fp8``), in the float32 reference's place: the precision below
  the configuration's, which the cell's limit has to refuse.

The last line is the contract's result object; ``correct`` false is what a
planted fault is expected to give. ``--set path=value`` overrides a value of
the cell's files (``config.compare.serve_latent.sample=2``).
"""

import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cell_variant  # noqa: E402  (its clock starts at import, as the harness wants)
from cell_variant import fp8, reference_without, swapped  # noqa: E402


def absorbed_scale(config):
    from deepspeed_tpu.ops.pallas import mla_attention

    sound = mla_attention.mla_decode
    width = config["model"]["kv_lora_rank"] + config["model"]["qk_rope_head_dim"]
    return swapped(mla_attention, "mla_decode", lambda *a, sm_scale, **kw: sound(
        *a, sm_scale=1.0 / math.sqrt(width), **kw))


def late_rope(config):
    from deepspeed_tpu.models import layer_plan

    sound = layer_plan._mla_project

    def late(h, p, kind, cfg, positions):     # the queries at their positions, the entries one late
        return sound(h, p, kind, cfg, positions)[0], sound(h, p, kind, cfg, positions + 1)[1]

    return swapped(layer_plan, "_mla_project", late)


FAULTS = {f.__name__: f for f in (absorbed_scale, late_rope, reference_without("routed_scale"),
                                  reference_without("shared"), reference_without("rope_key"),
                                  reference_without("kv_norm"))}
VARIANTS = dict(FAULTS, fp8=fp8)


def main(argv=None, manifest=None, require_tpu=True):
    return cell_variant.main(argv, manifest, require_tpu, variants=VARIANTS,
                             workload="serve-glm-4.7-flash-longdoc-batch", doc=__doc__)


if __name__ == "__main__":
    main()
