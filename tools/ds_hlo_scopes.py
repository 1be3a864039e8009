#!/usr/bin/env python
"""Put a profiler's op names down to the model's ``jax.named_scope`` paths.

A traced benchmark line names the device ops that took the time by their
HLO instruction (``fusion.172``, ``copy.61``). This tool builds the cell's
programs as its runner does (the model and the engine of the cell's files,
on whatever backend JAX finds: the chip for real names, the CPU for a
rehearsal whose names are the CPU compiler's), compiles them ahead of time
and prints, for each op asked for, the scope path of its instruction in
every program variant that has an instruction of that name
(``deepspeed_tpu/telemetry/hlo_scopes.py``).

    python tools/ds_hlo_scopes.py --cell serve-gpt2-medium-chat --ops fusion.172,copy.111
    python tools/ds_hlo_scopes.py --cell train-gpt2-medium-1chip --breakdown line.json
    python tools/ds_hlo_scopes.py --cell serve-gpt2-xl-batch --summary

``--breakdown`` takes a file whose last line is a ``--trace 1`` result
(ops from ``breakdown.device_ops``). A serving cell's variants are the
tick programs ``plain`` and ``fused:<chunk width>`` at each tight-read
length; a training cell's are ``micro`` and ``apply``. Several programs
reuse an instruction name for different instructions, so an op that reads
differently per variant is printed once per reading.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _files(manifest_path, workload):
    """(cell, config, chips) of a workload, found as the harness finds them."""
    from benchmark import harness

    manifest = harness.load_json(manifest_path)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        sys.exit(f"ds_hlo_scopes: no workload {workload!r} in {manifest_path}")
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    dirs = [os.path.join(REPO, p) for p in manifest["paths"]]
    try:
        cell = harness.load_json(harness.find_file(dirs, "cells", workload + ".json"))
    except harness.BenchmarkError as e:
        sys.exit(f"ds_hlo_scopes: {e}")
    return cell, harness.load_json(os.path.join(REPO, config_entry["file"])), int(entry["chips"])


def serve_programs(cell, config, chips, read_lens=None, chunks=None):
    """{variant: compiled} for the cell's tick family."""
    import deepspeed_tpu
    from benchmark import compare
    from deepspeed_tpu.inference import ContinuousBatchingEngine

    s = cell[cell["runner"]]   # a serving runner's group is named after it (serve, serve_routed, ...)
    model = compare.builder_of(config).build_model(config, max_seq_len=s["cache_len"], remat=False,
                                                   attn_impl=s["attn_impl"])
    ds_config = {"dtype": config["dtype"], "mesh": {"shape": {"data": 1, "tensor": chips}}}
    params = deepspeed_tpu.init_inference(model, config=ds_config).params
    eng = ContinuousBatchingEngine(model, config=ds_config, params=params,
                                   max_slots=s["slots"], cache_len=s["cache_len"],
                                   **s.get("engine", {}))
    pool = eng._pools[0]
    if read_lens is None:
        read_lens = sorted({eng._read_len(pool, e) for e in range(1, pool.length + 1)},
                           key=lambda r: (r is None, r))
    if chunks is None:
        chunks = [None, pool.chunk_cap]
    out = {}
    for rl in read_lens:
        for ch in chunks:
            fn = eng._tick_fn(pool, rl, chunk=ch)
            kind = "plain" if ch is None else f"fused:{ch}"
            out[f"{kind} read={rl or pool.length}"] = \
                fn.lower(*eng._tick_arg_structs(pool, ch)).compile()
    return out


def train_programs(cell, config, chips):
    """{variant: compiled} for the engine's micro-step and apply programs."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from benchmark import compare
    from benchmark.runners.train import Runner

    t = cell["train"]
    model = compare.builder_of(config).build_model(config, max_seq_len=t["seq"], remat=t["remat"],
                                                   attn_impl=t["attn_impl"])
    ds_config = Runner(dict(cell=cell, config=config))._ds_config(chips, 0)
    engine = deepspeed_tpu.initialize(model=model, config=ds_config)[0]
    sds = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (t["micro_batch_per_chip"] * chips, t["seq"]), jnp.int32)}
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    micro = engine._micro_fn.lower(sds(engine.params), sds(engine.grad_acc), batch,
                                   sds(engine._next_rng()), scalar, scalar,
                                   *sds(engine._counter_args())).compile()
    apply = engine._apply_fn.lower(sds(engine.params), sds(engine.master_params),
                                   sds(engine.opt_state), sds(engine.grad_acc),
                                   sds(engine.scale_state), scalar).compile()
    return {"micro": micro, "apply": apply}


def instruction_of(op: str) -> str:
    """``fusion fusion.172`` / ``custom-call:tpu_custom_call closed_call.11``
    (the benchmark's op names) or a bare ``fusion.172`` -> ``fusion.172``."""
    return op.strip().split(" ")[-1].lstrip("%")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True, help="a workload of the manifest")
    ap.add_argument("--manifest", default=os.path.join(REPO, "BENCHMARK.json"))
    ap.add_argument("--ops", default="", help="comma-separated op / instruction names")
    ap.add_argument("--breakdown", default=None,
                    help="file whose last line is a --trace 1 result line")
    ap.add_argument("--summary", action="store_true",
                    help="instructions per innermost model scope, per variant")
    ap.add_argument("--json", action="store_true", dest="as_json")
    args = ap.parse_args(argv)

    from deepspeed_tpu.telemetry.hlo_scopes import model_scope, scope_of, scope_table
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    ops = [o for o in args.ops.split(",") if o]
    if args.breakdown:
        with open(args.breakdown) as fh:
            line = json.loads([ln for ln in fh.read().splitlines() if ln.strip()][-1])
        ops += [name for name, _ in line["breakdown"]["device_ops"]]
    cell, config, chips = _files(args.manifest, args.cell)
    # a training runner's group is "train" whatever the runner is called (train, train_routed)
    programs = (train_programs if "train" in cell else serve_programs)(
        cell, config, chips)
    tables = {variant: scope_table(c) for variant, c in programs.items()}

    report = {"cell": args.cell, "variants": sorted(tables), "ops": {}}
    for op in ops:
        name = instruction_of(op)
        readings = {}
        for variant, table in tables.items():
            if name in table:
                readings.setdefault(scope_of(table[name]), []).append(variant)
        report["ops"][op] = readings
    if args.summary:
        report["summary"] = {}
        for variant, table in tables.items():
            counts = {}
            for path in table.values():
                key = model_scope(path) or "(no model scope)"
                counts[key] = counts.get(key, 0) + 1
            report["summary"][variant] = counts

    if args.as_json:
        print(json.dumps(report, indent=1, sort_keys=True))
        return 0
    print(f"== {args.cell}: {len(tables)} program(s): {', '.join(sorted(tables))}")
    for op, readings in report["ops"].items():
        if not readings:
            print(f"{op}: no instruction of that name in these programs "
                  f"(another program of the run, or a name of another compile)")
        for path, variants in readings.items():
            where = "all variants" if len(variants) == len(tables) else ", ".join(variants)
            print(f"{op}: {path}   [{model_scope(path) or 'no model scope'}; {where}]")
    for variant, counts in report.get("summary", {}).items():
        row = "  ".join(f"{k} {v}" for k, v in sorted(counts.items(), key=lambda kv: -kv[1]))
        print(f"-- {variant}: {row}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
