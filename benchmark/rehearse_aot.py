"""Does every program of every cell fit one chip's 15.75 GiB (16.91 GB)? Compiled for a DESCRIBED
``v5e:2x2`` here, without a chip, and read off ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_aot.py [name ...]

Run by hand and quoted in PERF.md; not a test (the repo's AOT tests live in
the one file tests/unit/ops/test_tpu_compile.py, and a second such file would
be skipped in silence on another xdist worker). A compile that passes is not
a chip run: it says what the chip's compiler accepts and how many bytes a
program needs, never a time.

What is compiled is the program's own code where a function can be had
without a live device (``model.loss``, ``compile_pool_tick_fn``,
``ShardingPolicy``) and the benchmark's own references as they are. The
engine's micro-step is rebuilt here from ``model.loss`` under the engine's
sharding policy, because ``initialize`` places weights and cannot run on
described devices.
"""

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark import compare  # noqa: E402
from benchmark.harness import load_json  # noqa: E402

GB = 1e9
LIMIT = 15.75 * 2 ** 30  # bytes_limit of one v5e chip as memory_stats() reports it: 16.91 GB


def report(name, compiled, beside=0.0, note=""):
    m = compiled.memory_analysis()
    need = (m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)
    line = dict(program=name, temp_gb=m.temp_size_in_bytes / GB,
                arguments_gb=m.argument_size_in_bytes / GB, outputs_gb=m.output_size_in_bytes / GB,
                aliased_gb=m.alias_size_in_bytes / GB, program_needs_gb=need / GB,
                beside_gb=beside / GB, fits=bool(need + beside <= LIMIT), note=note)
    print(json.dumps(line), flush=True)
    return line


def sds(tree, dtype=None, shardings=None):
    if shardings is None:
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=s),
                        tree, shardings)


def cell_and_config(workload):
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(w for w in manifest["workloads"] if w["name"] == workload)
    cfg = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    return (load_json(os.path.join(ROOT, "benchmark", "cells", workload + ".json")),
            load_json(os.path.join(ROOT, cfg["file"])),
            load_json(os.path.join(ROOT, "benchmark", "traffic", entry["traffic"] + ".json")),
            entry["chips"])


def train_cell(topo, workload, micro_batches=None):
    """The engine's micro-step (loss + grad + fp32 accumulate) under its
    sharding policy, and the float32 reference's training step."""
    from deepspeed_tpu import comm
    from deepspeed_tpu.ops.pallas.interpret import force_interpret
    from deepspeed_tpu.runtime.zero.sharding import ShardingPolicy

    cell, config, _, chips = cell_and_config(workload)
    t = cell["train"]
    devices = topo.devices[:chips]
    mesh = comm.build_mesh({"fsdp": chips}, devices=devices)
    comm.set_mesh(mesh)
    model = compare.builder_of(config).build_model(config, max_seq_len=t["seq"], remat=t["remat"],
                                                   attn_impl=t["attn_impl"])
    reference = compare.reference_of(config)
    arch = reference.arch(config)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    policy = ShardingPolicy(mesh, stage=t["zero_stage"], logical_specs=model.logical_specs(abstract))
    p_sh, g_sh = policy.param_shardings(abstract), policy.grad_shardings(abstract)
    o_sh = policy.opt_shardings(abstract)
    per_chip = lambda tree, sh, nbytes: sum(
        np.prod(s.shard_shape(a.shape)) * nbytes for a, s in zip(jax.tree.leaves(tree), jax.tree.leaves(sh)))
    state = (per_chip(abstract, p_sh, 2) + per_chip(abstract, g_sh, 4) + 3 * per_chip(abstract, o_sh, 4))
    print(json.dumps(dict(workload=workload, engine_state_per_chip_gb=state / GB)), flush=True)

    def micro(params, acc, batch):
        loss, grads = jax.value_and_grad(lambda p: model.loss(p, batch, None).astype(jnp.float32))(params)
        return loss, jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)

    for mb in micro_batches or [t["micro_batch_per_chip"]]:
        batch = {"input_ids": jax.ShapeDtypeStruct(
            (mb * chips, t["seq"]), jnp.int32, sharding=NamedSharding(mesh, policy.batch_spec()))}
        with force_interpret(False):
            compiled = jax.jit(micro, donate_argnums=(1,), out_shardings=(None, g_sh)).lower(
                sds(abstract, jnp.bfloat16, p_sh), sds(abstract, jnp.float32, g_sh), batch).compile()
        # beside it: fp32 masters and two moments (the micro-step's arguments are the bf16 copy
        # and the accumulator)
        report(f"{workload}: engine micro-step, micro-batch {mb}/chip", compiled,
               beside=3 * per_chip(abstract, o_sh, 4),
               note=f"{compiled.as_text().count('tpu_custom_call')} Mosaic calls")
    comm.destroy()

    # the reference trains once the engine is released: it has the chip to itself
    r_sh, batch_sh = compare.reference_shardings(abstract, devices)
    tol = dict(config["compare"]["train"], **t.get("compare", {}))
    distinct = tol.get("micro_batches") == "distinct"
    rows = t["micro_batch_per_chip"] * chips * (t["gradient_accumulation_steps"] if distinct else 1)
    toks = jax.ShapeDtypeStruct((rows, t["seq"]), jnp.int32, sharding=batch_sh)
    opt = dict(lr=1e-4, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)
    norm = reference.global_norm
    if compare.by_leaf(tol):  # the first gradient leaf by leaf: the engine's first moment, then
        # the reference's gradient inside its step
        compiled = jax.jit(compare.leaf_readings).lower(sds(abstract, jnp.float32, o_sh)).compile()
        report(f"{workload}: leaf readings of the engine's first moment", compiled, beside=state)
        norm = lambda g: (reference.global_norm(g), compare.leaf_readings(g))

    def ref_fn(operand):
        def step(p, m, v, step, tokens):
            hook = {} if operand is None else dict(
                loss_sum=functools.partial(reference.loss_sum, operand=operand))
            loss, g = reference.loss_and_grads(p, tokens, arch, t["reference_rows_per_pass"],
                                               row_sharding=batch_sh if chips > 1 else None, **hook)
            return reference.adamw(p, g, m, v, step, **opt) + (loss, norm(g))

        return step

    f32 = sds(abstract, jnp.float32, r_sh)
    for operand, name in ((None, "float32 reference"), (compare.fp8, "its fp8 control")):
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(ref_fn(operand), donate_argnums=(1, 2),
                               out_shardings=(r_sh, r_sh, r_sh, None, None)).lower(
                f32, f32, f32, jax.ShapeDtypeStruct((), jnp.float32), toks).compile()
        report(f"{workload}: {name} train step, {rows} rows, {t['reference_rows_per_pass']} a pass",
               compiled, note="runs after the window, with the engine released")


def serve_cell(topo, workload, slot_counts=None):
    """The tick family's largest members and the float32 reference forward."""
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.ops.pallas.interpret import force_interpret

    cell, config, traffic, chips = cell_and_config(workload)
    s = cell["serve"]
    from deepspeed_tpu import comm

    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, P())
    model = compare.builder_of(config).build_model(config, max_seq_len=s["cache_len"], remat=False,
                                                   attn_impl=s["attn_impl"])
    reference = compare.reference_of(config)
    cfg = model.cfg
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda a: one, abstract)
    params = sds(abstract, jnp.bfloat16, p_sh)
    weights = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves(abstract))
    chunk = s.get("engine", {}).get("prefill_chunk", 128)
    for slots in slot_counts or [s["slots"]]:
        pool = slots * s["cache_len"] * 2 * cfg.num_layers * cfg.hidden_size * 2
        print(json.dumps(dict(workload=workload, slots=slots, weights_gb=weights / GB,
                              kv_pool_gb=pool / GB,
                              rule_weights_plus_twice_pool_gb=(weights + 2 * pool) / GB,
                              rule_limit_gb=0.85 * LIMIT / GB)), flush=True)
        for ch in (None, chunk):
            with force_interpret(False):
                fn, cache_sh, _ = compile_pool_tick_fn(
                    mesh, cfg, p_sh, slots, s["cache_len"], 1, 0.0, 0, 1.0, read_len=None, chunk=ch)
                cache = sds(jax.eval_shape(lambda: tf.init_cache(cfg, slots, s["cache_len"])),
                            shardings=cache_sh)
                row = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
                args = [params, cache, row, row, row, row, row, row,
                        jax.ShapeDtypeStruct((2,), jnp.uint32)]
                if ch is not None:
                    wide = jax.ShapeDtypeStruct((ch,), jnp.int32)
                    args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
                compiled = fn.lower(*args).compile()
            report(f"{workload}: tick, {slots} slots, full read, chunk {ch}", compiled,
                   note="weights and the KV pool are its arguments")
    sample = int(config["compare"]["serve"]["sample"])
    new_max = int(traffic["output_tokens"]["max"])
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(reference.logits_at, static_argnums=3).lower(
            params, jax.ShapeDtypeStruct((sample, s["cache_len"]), jnp.int32),
            jax.ShapeDtypeStruct((sample, new_max), jnp.int32), reference.arch(config)).compile()
    report(f"{workload}: float32 reference forward, {sample} requests x {s['cache_len']}", compiled,
           note="runs after the window with the KV pool released; the bf16 weights are its argument")


def main():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    wanted = sys.argv[1:]
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in manifest["workloads"]:
        spec = next((a for a in wanted if a.split(":")[0] == w["name"]), None)
        if wanted and spec is None:
            continue
        sizes = [int(x) for x in spec.split(":")[1].split(",")] if spec and ":" in spec else None
        cell = load_json(os.path.join(ROOT, "benchmark", "cells", w["name"] + ".json"))
        (train_cell if cell["runner"] == "train" else serve_cell)(topo, w["name"], sizes)


if __name__ == "__main__":
    main()
