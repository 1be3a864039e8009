"""``dstpu_prewarm`` — precompile a serving program set into the persistent
XLA compile cache, so servers cold-start warm.

On TPU every distinct compiled program costs seconds to tens of seconds,
and a serving stack touches several per configuration: the fused generate
(per prompt-length/new-tokens combo), or the chunked-prefill + per-token
decode pair, plus the continuous engine's per-bucket prefill/insert and
burst programs. Run this once per model configuration with
``JAX_COMPILATION_CACHE_DIR`` (or ``--cache-dir``) pointing at the
directory the server will use and every later process reuses the
executables (placement rule: ``deepspeed_tpu/utils/compile_cache.py``).

The reference has no analogue (CUDA kernels load from prebuilt .so); this
is the XLA-world equivalent of shipping compiled kernels.

Usage:
  dstpu_prewarm --preset gpt2-125m --batch 8 --prompt 128 --new 128 \\
                --cache-dir /path/to/xla_cache [--chunk 128] \\
                [--continuous --slots 8 --cache-len 512 --burst 4] \\
                [--dtype bfloat16] [--kv-int8]
"""

import argparse
import sys
import time


def _parse_value(val: str):
    """KEY=VALUE override values: int, float, bool, None, or string."""
    low = val.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    for cast in (int, float):
        try:
            return cast(val)
        except ValueError:
            continue
    return val


def main(argv=None):
    p = argparse.ArgumentParser(
        description="precompile serving programs into the persistent XLA cache")
    p.add_argument("--preset", default="gpt2-125m",
                   help="model preset name (models/transformer.py PRESETS)")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--prompt", type=int, default=128,
                   help="prompt length to compile for (fused generate is "
                        "shape-specialized; pass several runs for several "
                        "lengths, or --chunk for length-agnostic prefill)")
    p.add_argument("--new", type=int, default=128)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--kv-int8", action="store_true")
    p.add_argument("--no-tight-read", action="store_true",
                   help="warm the full-length-read program set instead of "
                        "the (default) tight-read bucket stages")
    p.add_argument("--kv-floor", type=int, default=0,
                   help="tight-read bucket floor override (0 = config "
                        "default); must match the serving config or the "
                        "warmed executables miss")
    p.add_argument("--chunk", type=int, default=0,
                   help="also warm the chunked-prefill program set")
    p.add_argument("--continuous", action="store_true",
                   help="warm the continuous-batching pool programs — the "
                        "FULL tick family: every (read bucket x {plain/"
                        "burst, fused-prefill chunk width}) variant a serve "
                        "could dispatch, so serve-time requests never pay "
                        "a compile")
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--cache-len", type=int, default=512)
    p.add_argument("--burst", type=int, default=1)
    p.add_argument("--speculative", default=None, metavar="GAMMA[:MODE]",
                   help="warm the SPECULATIVE tick family (--continuous): "
                        "gamma draft tokens verified per round, mode "
                        "'ngram' (default, draft-free) or 'draft' (a "
                        "second model on the same mesh — needs "
                        "--draft-preset). Implies single-token ticks "
                        "(--burst ignored); docs/inference.md "
                        "'Speculative decoding'")
    p.add_argument("--draft-preset", default=None,
                   help="draft-model preset for --speculative GAMMA:draft "
                        "(must share the target's vocabulary)")
    p.add_argument("--pipeline-depth", type=int, default=1,
                   help="pipeline depth the warmed serve will run at (a "
                        "host-loop knob: it does not change the compiled "
                        "program set, recorded for the drive-through warm)")
    p.add_argument("--no-fused-prefill", action="store_true",
                   help="skip the fused-prefill tick variants (warm the "
                        "separate B=1 prefill + splice programs instead)")
    p.add_argument("--mesh", default=None, metavar="DATA:TENSOR[,..]",
                   help="serving mesh shape(s) to warm under, e.g. 1:2 or "
                        "1:1,1:2 — the tick-program family is compiled PER "
                        "tensor width (sharded programs are distinct "
                        "executables), so warm every width the serve will "
                        "run or the first sharded request pays the compile")
    p.add_argument("--cache-dir", default=None,
                   help="persistent XLA cache dir (default: "
                        "JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache)")
    p.add_argument("--audit", action="store_true",
                   help="run ds-audit over every program this warm "
                        "compiles (the REAL serving configuration, not "
                        "the tiny-config table) and fail the warm on "
                        "contract findings — docs/static_analysis.md "
                        "'Program audit'")
    p.add_argument("--override", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="TransformerConfig field override (repeatable), e.g. "
                        "--override num_layers=2 to prewarm a truncated "
                        "model while debugging a serving config")
    args = p.parse_args(argv)

    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import TransformerModel
    from deepspeed_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache(args.cache_dir)
    overrides = {}
    for item in args.override:
        key, sep, val = item.partition("=")
        assert sep and val, f"--override needs KEY=VALUE, got {item!r}"
        overrides[key] = _parse_value(val)
    model = TransformerModel.from_preset(args.preset, dtype=args.dtype, **overrides)
    cfg = {"dtype": args.dtype}
    if args.kv_int8:
        cfg["kv_cache_dtype"] = "int8"
    if args.no_tight_read:
        cfg["kv_tight_read"] = False
    if args.kv_floor:
        cfg["kv_read_floor"] = args.kv_floor
    rs = np.random.RandomState(0)

    # --audit: collect every program family the warm builds and contract-
    # check the artifacts at the end (exit 1 on findings) — this audits
    # the REAL serving configuration on the REAL mesh widths, where the
    # standalone tools/ds_audit.py audits a tiny calibration table
    collector = None
    if args.audit:
        from deepspeed_tpu.analysis.program.capture import (
            ArtifactCollector,
            set_hook,
        )

        collector = ArtifactCollector()
        set_hook(collector)

    def tick(name, fn):
        t0 = time.time()
        # drain the dispatch: without this the "ready in" time would report
        # enqueue latency while the compile/run still executes
        jax.block_until_ready(fn())
        print(f"prewarm: {name} ready in {time.time() - t0:.1f}s", flush=True)

    toks = rs.randint(0, model.cfg.vocab_size,
                      (args.batch, args.prompt)).astype(np.int32)
    # one param init shared by every engine: a second engine would
    # re-initialize AND hold another full on-device copy (3x HBM at 7B)
    params = model.init(jax.random.PRNGKey(0))

    # each requested serving mesh compiles its OWN program family (a
    # sharded executable is a different program — warming 1:1 does nothing
    # for a 1:2 serve); None = the engine's default mesh
    meshes = [None]
    if args.mesh:
        from deepspeed_tpu.parallel.partition import parse_mesh_arg

        meshes = [parse_mesh_arg(s) for s in args.mesh.split(",")]

    # the hook must not outlive the warm even when a build raises —
    # a leaked hook would capture (and re-lower) every later program
    # in the process (test engines included)
    try:
        for shape in meshes:
            mcfg = dict(cfg)
            label = ""
            if shape is not None:
                mcfg["mesh"] = {"shape": shape}
                label = (f", mesh={shape.get('data', 1)}:"
                         f"{shape.get('tensor', 1)}")
            eng = deepspeed_tpu.init_inference(model, params=params, config=dict(mcfg))
            tick(f"fused generate (B={args.batch}, S={args.prompt}, "
                 f"new={args.new}{label})",
                 lambda: np.asarray(eng.generate(toks, max_new_tokens=args.new)))

            if args.chunk:
                eng_c = deepspeed_tpu.init_inference(
                    model, params=params,
                    config=dict(mcfg, prefill_chunk_size=args.chunk))
                tick(f"chunked prefill (chunk={args.chunk}) + per-token decode"
                     f"{label}",
                     lambda: np.asarray(eng_c.generate(toks, max_new_tokens=2)))
                del eng_c

            if args.continuous:
                from deepspeed_tpu.inference import ContinuousBatchingEngine

                scfg, spec_kw, burst = dict(mcfg), {}, args.burst
                if args.speculative:
                    g, _, m = args.speculative.partition(":")
                    mode = m or "ngram"
                    scfg["speculative"] = {
                        "enabled": True, "pool": True, "mode": mode,
                        "num_draft_tokens": int(g)}
                    burst = 1  # the gamma-wide verify round IS the burst
                    if mode == "draft":
                        if not args.draft_preset:
                            p.error("--speculative GAMMA:draft needs "
                                    "--draft-preset")
                        dmodel = TransformerModel.from_preset(
                            args.draft_preset, dtype=args.dtype)
                        spec_kw = dict(
                            draft_model=dmodel,
                            draft_params=dmodel.init(jax.random.PRNGKey(1)))
                serve = ContinuousBatchingEngine(
                    model, params=params, config=scfg, max_slots=args.slots,
                    cache_len=args.cache_len, tokens_per_tick=burst,
                    pipeline_depth=args.pipeline_depth,
                    fused_prefill=not args.no_fused_prefill, **spec_kw)

                def run_pool():
                    # drive a real request through: warms the admission programs
                    # (prefill/splice or the first chunk width) plus the tick
                    # read-buckets this prompt actually crosses
                    pool_new = min(args.new, 8)
                    plen = min(args.prompt, args.cache_len - pool_new)
                    assert plen >= 1, (
                        f"--cache-len {args.cache_len} leaves no room for a prompt "
                        f"(warming {pool_new} tokens)")
                    serve.submit(toks[0, :plen], max_new_tokens=pool_new)
                    while serve.has_work():
                        serve.step()
                    serve.finished()

                spec_label = (f", speculative={args.speculative}"
                              if args.speculative else "")
                tick(f"continuous pool (slots={args.slots}, cache={args.cache_len}, "
                     f"burst={burst}{spec_label}{label})", run_pool)
                # then the FULL tick-program family (bucket x read_len x {plain,
                # burst, fused-prefill}) under THIS mesh: a live serve dispatches
                # whichever variant its mix demands — every one missing
                # costs a compile mid-serve
                n_fns = serve.precompile_tick_programs(
                    progress=lambda msg: print(f"prewarm: {msg}", flush=True))
                print(f"prewarm: tick-program family complete "
                      f"({n_fns} variants resident{label})", flush=True)
                del serve
            # drop this width's engines (and their on-device param placements
            # + KV pools) before the next width builds its own — two resident
            # placements is exactly the 3x-HBM-at-7B hazard the shared param
            # init above exists to avoid
            del eng
    finally:
        if collector is not None:
            from deepspeed_tpu.analysis.program.capture import clear_hook

            clear_hook()
    if collector is not None:
        from deepspeed_tpu.analysis.program import audit_artifacts
        from deepspeed_tpu.analysis.program.auditor import (
            build_report,
            print_text,
        )

        result = audit_artifacts(collector.artifacts)
        report = build_report(result, result.findings, [],
                              collector.artifacts)
        print(f"prewarm: ds-audit over {len(collector.artifacts)} captured "
              f"program(s)", flush=True)
        print_text(report)
        if result.findings:
            return 1
    print(f"prewarm: done — executables persisted to {cache_dir}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
