#!/usr/bin/env python3
"""By hand, on the chip: a cell of ``benchmark/configs/qwen3-next-80b-a3b.json``
with ONE thing changed, through the harness as the driver runs it
(``tools/mimo_cell_variant.py`` for this family). Uses:

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

import argparse
import contextlib
import functools
import json
import os
import sys
import time

T_PROCESS_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@contextlib.contextmanager
def _swapped(owner, name, value):
    sound = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, sound)


def no_reset(config):
    from deepspeed_tpu.ops.transformer import kv_cache

    return _swapped(kv_cache, "reset_row", lambda state, slot: state)


def pads_step(config):
    from deepspeed_tpu.models import layer_plan

    return _swapped(layer_plan, "_hold", lambda g, beta, valid: (g, beta))


def _reference_without(piece):
    def variant(config):
        from benchmark import compare

        reference = compare.reference_of(config)
        sound = reference.arch
        return _swapped(reference, "arch", lambda c: sound(c)._replace(without=(piece,)))

    variant.__name__ = "no_" + piece
    return variant


def fp8(config):
    from benchmark import compare

    reference = compare.reference_of(config)
    return _swapped(reference, "logits_at",
                    functools.partial(reference.logits_at, operand=compare.fp8))


FAULTS = {f.__name__: f for f in (no_reset, pads_step, _reference_without("decay"),
                                  _reference_without("shared"), _reference_without("attn_gate"),
                                  _reference_without("conv"))}
VARIANTS = dict(FAULTS, fp8=fp8)


def main(argv=None, manifest=None, require_tpu=True):
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--workload", default="serve-qwen3-next-longdoc-batch")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="PATH=VALUE")
    args = ap.parse_args(argv)
    manifest = manifest or os.path.join(harness.ROOT, "BENCHMARK.json")
    listed = harness.load_json(manifest)
    entry = next(w for w in listed["workloads"] if w["name"] == args.workload)
    config_entry = next(c for c in listed["configs"] if c["name"] == entry["config"])
    with VARIANTS[args.variant](harness.load_json(os.path.join(harness.ROOT, config_entry["file"]))):
        line = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                                bool(args.trace), require_tpu=require_tpu, overrides=args.set,
                                t_process_start=T_PROCESS_START)
    print(json.dumps(dict(line, variant=args.variant)), flush=True)
    return line


if __name__ == "__main__":
    main()
