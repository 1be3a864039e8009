#!/usr/bin/env python3
"""By hand, on the chip: the readings behind ``compare.serve_routed`` of
``benchmark/configs/mimo-v2.5.json``.

At the configuration's widths and a sequence of ``--seq`` positions, for each
query scale: the program's forward (bfloat16, its own kernels for the expert
layers) against the float32 reference, scored as ``compare.serve_verdict``
scores a stream (how far the reference logit of the program's greedy token
sits below the reference's top logit); the same measure for the reference
itself with float8 operands (the precision below, which has to fail); and
the share of positions at which the PROGRAM and the float32 reference choose
a different set of experts, layer by layer (the program's choices are read
out of its own ``route`` by a host callback planted here; beside it the
reference against itself with bfloat16-rounded operands, which is how much
of that the rounding of operands alone explains). One JSON line a reading;
``--experts-only`` skips the sweep over query scales.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=os.path.join("benchmark", "configs", "mimo-v2.5.json"))
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--scales", type=float, nargs="+", default=[1.0, 2.0, 3.0, 4.0])
    ap.add_argument("--experts-scale", type=float, default=3.0)
    ap.add_argument("--experts-only", action="store_true")
    args = ap.parse_args()

    from benchmark import compare, harness, models_mimo_v2
    from benchmark.reference import mimo_v2

    config = harness.load_json(os.path.join(harness.ROOT, args.config))
    arch = mimo_v2.arch(config)
    model = models_mimo_v2.build_model(config, max_seq_len=args.seq, remat=False, attn_impl="pallas")
    base = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    tokens = np.random.RandomState(args.seed).randint(
        0, config["model"]["vocab_size"], (args.rows, args.seq)).astype(np.int32)
    at = np.tile(np.arange(args.seq, dtype=np.int32), (args.rows, 1))

    def gaps(ref, picked):
        own = np.take_along_axis(ref, picked[..., None], axis=-1)[..., 0]
        return (ref.max(-1) - own).ravel()

    def reading(name, scale, g, **more):
        print(json.dumps(dict(
            what=name, query_scale=scale, positions=int(g.size), worst=float(g.max()),
            p99=float(np.percentile(g, 99)), p50=float(np.median(g)),
            **{f"within_{m}": float((g <= m).mean()) for m in (0.25, 0.5, 1.0, 1.5, 2.0)},
            **more)), flush=True)

    ref_fn = jax.jit(mimo_v2.logits_at, static_argnums=(3, 4))
    prog_fn = jax.jit(lambda p, t: model.apply(p, t))
    for scale in ([] if args.experts_only else args.scales):
        params = models_mimo_v2.sharpen(jax.tree.map(lambda a: a, base), config, scale)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(ref_fn(params, tokens, at, arch, mimo_v2._as_is))
            low = np.asarray(ref_fn(params, tokens, at, arch, compare.fp8))
        prog = np.asarray(prog_fn(params, tokens), np.float32)
        top2 = np.sort(ref, axis=-1)[..., -2:]
        reading("program_bf16", scale, gaps(ref, prog.argmax(-1)),
                logit_abs_diff_p99=float(np.percentile(np.abs(prog - ref), 99)),
                reference_top1_minus_top2_p50=float(np.median(top2[..., 1] - top2[..., 0])),
                reference_logit_std=float(ref.std()))
        reading("reference_fp8", scale, gaps(ref, low.argmax(-1)),
                logit_abs_diff_p99=float(np.percentile(np.abs(low - ref), 99)))
        rs = np.random.RandomState(1)
        reading("random_tokens", scale, gaps(ref, rs.randint(0, ref.shape[-1], ref.shape[:2])))

    # expert choices, layer by layer: the program's own against the float32 reference's
    from deepspeed_tpu.moe import held_experts

    params = models_mimo_v2.sharpen(jax.tree.map(lambda a: a, base), config, args.experts_scale)
    rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    chosen = {}
    inner, route = mimo_v2._experts, held_experts.route

    def spy(tag):
        def experts(h, m, ar, r):
            scores = jax.nn.sigmoid(r(h) @ r(m["gate"].astype(jnp.float32)))
            _, picked = jax.lax.top_k(scores + m["gate_bias"].astype(jnp.float32), ar.top_k)
            chosen.setdefault(tag, []).append(np.sort(np.asarray(picked), axis=-1))
            return inner(h, m, ar, r)
        return experts

    def spied_route(h, gate_w, gate_bias, k):
        picked, weights = route(h, gate_w, gate_bias, k)
        jax.debug.callback(lambda c: chosen.setdefault("program", []).append(
            np.sort(np.asarray(c), axis=-1)), picked, ordered=True)
        return picked, weights

    row = jnp.asarray(tokens[0])
    with jax.default_matmul_precision("highest"):
        for tag, operand in (("float32", mimo_v2._as_is), ("bfloat16", rounded)):
            mimo_v2._experts = spy(tag)
            mimo_v2._row_hidden(params, row, arch, False, operand).block_until_ready()
    mimo_v2._experts = inner
    held_experts.route = spied_route
    try:
        jax.jit(lambda p, t: model.apply(p, t))(params, tokens[:1]).block_until_ready()
        jax.effects_barrier()
    finally:
        held_experts.route = route
    held = lambda s: ((s >= arch.held_first) & (s < arch.held_first + arch.held_count))
    for tag in ("program", "bfloat16"):
        for layer, (a, b) in enumerate(zip(chosen["float32"], chosen[tag])):
            differ = (a != b).any(-1)
            print(json.dumps(dict(
                what=f"expert_sets_differ.{tag}_against_float32_reference", expert_layer=layer,
                query_scale=args.experts_scale, positions=int(differ.size),
                share_of_positions=float(differ.mean()),
                share_where_a_held_expert_differs=float(np.mean(
                    [set(x[hx]) != set(y[hy]) for x, y, hx, hy in zip(a, b, held(a), held(b))])))),
                flush=True)


if __name__ == "__main__":
    main()
