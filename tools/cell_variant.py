"""What the per-configuration variant tools share (``tools/mimo_cell_variant.py``,
``tools/qwen3_next_cell_variant.py``, ``tools/glm_cell_variant.py``,
``tools/granite_cell_variant.py``, ``tools/ouro_cell_variant.py``): one
command line that runs a cell through the harness, as the driver runs it, with
ONE thing swapped for the length of the run, and the swaps that are the same
for every configuration. A variant is a function of the cell's configuration
that returns a context manager; a family's tool names its variants and its
default cell, and hands both to :func:`main`."""

import argparse
import contextlib
import functools
import json
import os
import sys
import time

T_PROCESS_START = time.perf_counter()   # the harness counts set-up from the process's start
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@contextlib.contextmanager
def swapped(owner, name, value):
    """``owner.name`` is ``value`` inside the block, and what it was after it
    (the program looks its functions up when a tick is traced)."""
    sound = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, sound)


def reference_without(piece):
    """The variant ``no_<piece>``: the configuration's reference leaves
    ``piece`` out (``arch(...).without``) — the disagreement of a program that
    dropped it, seen from the other side, which keeps the program's compiled
    ticks."""
    def variant(config):
        from benchmark import compare

        reference = compare.reference_of(config)
        sound = reference.arch
        return swapped(reference, "arch", lambda c: sound(c)._replace(without=(piece,)))

    variant.__name__ = "no_" + piece
    return variant


def no_reset(config):
    """The program admits a request to a slot without zeroing the slot's row
    of the state pool (the last request's state leaks into the next one's)."""
    from deepspeed_tpu.ops.transformer import kv_cache

    return swapped(kv_cache, "reset_row", lambda state, slot: state)


def fp8(config):
    """The reference with float8 operands in every matmul (``compare.fp8``),
    in the float32 reference's place: the precision below the
    configuration's, which the cell's limit has to refuse."""
    from benchmark import compare

    reference = compare.reference_of(config)
    return swapped(reference, "logits_at",
                   functools.partial(reference.logits_at, operand=compare.fp8))


def main(argv, manifest, require_tpu, *, variants, workload, doc):
    """Run ``--workload`` (default: the family's cell ``workload``) under
    ``--variant``; print and return the contract's result object."""
    from benchmark import harness

    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--variant", required=True, choices=sorted(variants))
    ap.add_argument("--workload", default=workload)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="PATH=VALUE")
    args = ap.parse_args(argv)
    manifest = manifest or os.path.join(harness.ROOT, "BENCHMARK.json")
    listed = harness.load_json(manifest)
    entry = next(w for w in listed["workloads"] if w["name"] == args.workload)
    config_entry = next(c for c in listed["configs"] if c["name"] == entry["config"])
    with variants[args.variant](harness.load_json(os.path.join(harness.ROOT, config_entry["file"]))):
        line = harness.run_cell(manifest, args.workload, args.seed, args.seconds,
                                bool(args.trace), require_tpu=require_tpu, overrides=args.set,
                                t_process_start=T_PROCESS_START)
    print(json.dumps(dict(line, variant=args.variant)), flush=True)
    return line
