"""Family-table builders: lower the SHIPPED program families on
tiny-config models over the virtual mesh, per tensor width — the
standalone audit surface (``tools/ds_audit.py``) and the tier-1 gate
test both drive this.

Nothing here executes a program: engines are built (param init only),
programs are lowered + compiled from ShapeDtypeStructs, and the
resulting :class:`~.artifact.ProgramArtifact` list goes to the auditor.
Donation therefore stays ON by default even on the CPU backend — the
donation-blocks-dispatch caveat (docs/serving.md) is an *execution*
behavior; lowering a donated program is free.

jax/deepspeed_tpu imports stay inside functions: the analysis package
must remain importable by the stdlib-only standalone loader.
"""

SERVING_FAMILIES = (
    "pool_tick[plain]", "pool_tick[burst]", "pool_tick[fused]",
    "pool_segment", "pool_row_update", "pool_spec_tick_ngram",
    "pool_spec_tick_draft", "pool_spec_row_update",
    "decode_prefill", "decode_step",
)
TRAIN_FAMILIES = ("train_micro", "train_apply")
ALL_FAMILIES = SERVING_FAMILIES + TRAIN_FAMILIES

# allowed dot_general accumulation dtypes per model dtype: f32 models
# must accumulate f32; reduced-precision models may keep bf16/f16 dots
# or widen to f32 (XLA's default on TPU)
_ACCUM_DTYPES = {
    "float32": ("f32",),
    "bfloat16": ("bf16", "f32"),
    "float16": ("f16", "f32"),
}

# allowed dot_general OPERAND dtypes per model dtype (the hot-dot-upcast
# rule): a bf16 model's matmuls must feed bf16 operands — an f32 operand
# halves MXU rate and doubles weight traffic. Distinct from
# _ACCUM_DTYPES, which governs the dot OUTPUT (accumulation) width.
_DOT_DTYPES = {
    "float32": ("f32",),
    "bfloat16": ("bf16",),
    "float16": ("f16",),
}


def tiny_config(layers: int = 1, hidden: int = 32, heads: int = 2,
                vocab: int = 64, seq: int = 64, dtype: str = "float32"):
    """The smallest TransformerConfig that still exercises every program
    dimension (sharded heads/mlp/vocab at tp=2, a layer scan, rope)."""
    from deepspeed_tpu.models.transformer import TransformerConfig

    return TransformerConfig(vocab_size=vocab, hidden_size=hidden,
                             num_layers=layers, num_heads=heads,
                             max_seq_len=seq, dtype=dtype)


def _base_meta(tp, donate, params, cfg, hbm_limit_bytes, kv_int8):
    from .capture import param_leaf_shapes

    return {
        "tp": int(tp),
        "donate": bool(donate),
        "param_shapes": param_leaf_shapes(params),
        "dims": {"hidden": cfg.hidden_size, "vocab": cfg.vocab_size},
        "accum_dtypes": _ACCUM_DTYPES.get(cfg.dtype, ()),
        "dot_dtypes": _DOT_DTYPES.get(cfg.dtype, ()),
        "int8_kv": bool(kv_int8),
        "hbm_limit_bytes": int(hbm_limit_bytes),
    }


def build_serving_artifacts(tp: int = 1, *, donate: bool = True,
                            layers: int = 1, slots: int = 2,
                            cache_len: int = 32, hbm_limit_bytes: int = 0,
                            kv_int8: bool = False, families=None,
                            model_dtype: str = "float32"):
    """Artifacts for the serving program families at mesh 1×``tp``
    (a SUBSET serving mesh — tp=1 really is one device, so its programs
    must carry zero collectives)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu import comm
    from deepspeed_tpu.inference.decoding import (
        compile_decode_fns,
        compile_pool_tick_fn,
        compile_row_update_fn,
        compile_segment_fn,
        compile_spec_pool_tick_fn,
        compile_spec_row_update_fn,
    )
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.ops.transformer import kv_cache

    from .capture import extract_artifact, shape_structs

    wanted = set(families) if families is not None else set(SERVING_FAMILIES)
    comm.destroy()
    cfg = tiny_config(layers=layers, dtype=model_dtype)
    model = tf.TransformerModel(cfg)
    config = {"dtype": model_dtype,
              "mesh": {"shape": {"data": 1, "tensor": int(tp)}}}
    if kv_int8:
        config["kv_cache_dtype"] = "int8"
    eng = InferenceEngine(model, config=config)
    mesh, cfg = eng.mesh, eng.cfg
    shardings = eng.param_shardings
    meta = _base_meta(tp, donate, eng.params, cfg, hbm_limit_bytes, kv_int8)

    # abstract args carry NO shardings: the compile_* builders pass
    # explicit in_shardings for every mesh-placed operand, and an SDS
    # sharding copied from a live array (PRNGKey lands on default device
    # 0) would conflict with a subset mesh's device set at lowering
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    params_s = jax.tree.map(sds, eng.params)
    cache_s = jax.eval_shape(lambda: kv_cache.init(cfg, slots, cache_len))
    row = jax.ShapeDtypeStruct((slots,), jnp.int32)
    key_s = sds(jax.random.PRNGKey(0))
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    tick_args = (params_s, cache_s, row, row, row, row, row, row, key_s)

    out = []

    def tick(variant, n_tokens, chunk, temperature):
        fn = compile_pool_tick_fn(
            mesh, cfg, shardings, slots, cache_len, n_tokens,
            temperature=temperature, top_k=0, top_p=1.0, eos_token_id=1,
            read_len=None, chunk=chunk, donate=donate)[0]
        args = tick_args
        if chunk is not None:
            cvec = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args = args + (cvec, cvec, scalar, row, row)
        return extract_artifact(
            "pool_tick", variant, fn, args,
            meta=dict(meta, sampled=temperature > 0.0))

    if "pool_tick[plain]" in wanted:
        # both compiled sampler heads: greedy argmax and per-row
        # categorical have different collective profiles at tp>1
        out.append(tick("plain", 1, None, 0.0))
        out.append(tick("plain", 1, None, 0.7))
    if "pool_tick[burst]" in wanted:
        out.append(tick("burst", 2, None, 0.7))
    if "pool_tick[fused]" in wanted:
        out.append(tick("fused", 1, 16, 0.7))
    if "pool_segment" in wanted:
        fn = compile_segment_fn(mesh, cfg, shardings, slots, cache_len)[0]
        toks = jax.ShapeDtypeStruct((slots, 8), jnp.int32)
        out.append(extract_artifact(
            "pool_segment", "", fn, (params_s, toks, cache_s, row),
            meta=meta))
    if "pool_row_update" in wanted:
        fn = compile_row_update_fn(mesh, cfg, slots, donate=donate)
        out.append(extract_artifact(
            "pool_row_update", "", fn, (row, row, scalar, scalar, scalar),
            meta=meta))
    gamma = 3  # any gamma > 1: the accept scan's collectives are width-free
    spec_rows = (row,) * 7  # last_tok, done, pos, gen, quota, rids, run_mask
    if "pool_spec_tick_ngram" in wanted:
        drafts_s = jax.ShapeDtypeStruct((slots, gamma), jnp.int32)
        for temp in (0.0, 0.7):  # both compiled accept heads (see pool_tick)
            fn = compile_spec_pool_tick_fn(
                mesh, cfg, shardings, slots, cache_len, gamma, temp,
                0, 1.0, eos_token_id=1, read_len=None, donate=donate)[0]
            out.append(extract_artifact(
                "pool_spec_tick_ngram", "", fn,
                (params_s,) + (cache_s,) + spec_rows + (drafts_s, key_s),
                meta=dict(meta, sampled=temp > 0.0)))
    if "pool_spec_tick_draft" in wanted:
        # the draft rides the SAME mesh with its own (smaller) param tree
        # and pool-geometry cache; meta param_shapes is the UNION so the
        # param-collective rule recognizes draft-shaped operands too
        from .capture import param_leaf_shapes

        dcfg_t = tiny_config(layers=layers, hidden=16, heads=2,
                             dtype=model_dtype)
        dmodel = tf.TransformerModel(dcfg_t)
        deng = InferenceEngine(dmodel, config=config, mesh=mesh)
        dcfg = deng._ring_off_cfg
        dcache_s = jax.eval_shape(lambda: kv_cache.init(dcfg, slots,
                                                        cache_len))
        dparams_s = jax.tree.map(sds, deng.params)
        dmeta = dict(meta, param_shapes=(meta["param_shapes"]
                                         + param_leaf_shapes(deng.params)))
        for temp in (0.0, 0.7):
            fn = compile_spec_pool_tick_fn(
                mesh, cfg, shardings, slots, cache_len, gamma, temp,
                0, 1.0, eos_token_id=1, read_len=None, donate=donate,
                draft_cfg=dcfg,
                draft_param_shardings=deng.param_shardings)[0]
            out.append(extract_artifact(
                "pool_spec_tick_draft", "", fn,
                (params_s, dparams_s, cache_s, dcache_s) + spec_rows
                + (key_s,),
                meta=dict(dmeta, sampled=temp > 0.0)))
    if "pool_spec_row_update" in wanted:
        fn = compile_spec_row_update_fn(mesh, cfg, slots, donate=donate)
        out.append(extract_artifact(
            "pool_spec_row_update", "", fn,
            (row, row, row, row, scalar, scalar, scalar, scalar, scalar),
            meta=meta))
    if "decode_prefill" in wanted or "decode_step" in wanted:
        batch = 2
        prefill_fn, decode_fn, _, _ = compile_decode_fns(
            mesh, cfg, shardings, batch, cache_len)
        d_cache = shape_structs(
            jax.eval_shape(lambda: kv_cache.init(cfg, batch, cache_len)))
        if "decode_prefill" in wanted:
            toks = jax.ShapeDtypeStruct((batch, 8), jnp.int32)
            out.append(extract_artifact(
                "decode_prefill", "", prefill_fn, (params_s, toks, d_cache),
                meta=meta))
        if "decode_step" in wanted:
            tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
            out.append(extract_artifact(
                "decode_step", "", decode_fn, (params_s, tok, d_cache, scalar),
                meta=meta))
    return out


def build_train_artifacts(tp: int = 1, *, layers: int = 1, seq: int = 16,
                          hbm_limit_bytes: int = 0, families=None,
                          model_dtype: str = "float32"):
    """Artifacts for the train step programs (micro + apply) on a
    1×``tp`` SUBSET mesh (grad sync over ``data`` is out of scope here:
    the contract dimension under audit is tensor sharding, and dp=1
    keeps the tp=1 table honestly collective-free)."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu import comm

    from .capture import extract_artifact

    wanted = set(families) if families is not None else set(TRAIN_FAMILIES)
    comm.destroy()
    cfg = tiny_config(layers=layers, seq=seq, dtype=model_dtype)
    from deepspeed_tpu.models.transformer import TransformerModel

    mesh = comm.build_mesh({"data": 1, "tensor": int(tp)},
                           devices=jax.devices()[:int(tp)])
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=TransformerModel(cfg), mesh=mesh,
        config={"train_batch_size": 2, "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    meta = _base_meta(tp, True, engine.params, cfg, hbm_limit_bytes, False)

    # sharding-free abstract args (see build_serving_artifacts): the
    # micro/apply jits declare explicit in_shardings for every operand
    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") and hasattr(a, "dtype") else a, tree)

    params_s = sds(engine.params)
    batch_s = {"input_ids": jax.ShapeDtypeStruct(
        (engine.train_micro_batch_size_per_gpu, seq), jnp.int32)}
    rng_s = sds(jax.random.PRNGKey(0))
    f32 = jax.ShapeDtypeStruct((), jnp.float32)

    out = []
    if "train_micro" in wanted and engine._micro_fn is not None:
        out.append(extract_artifact(
            "train_micro", "", engine._micro_fn,
            (params_s, sds(engine.grad_acc), batch_s, rng_s, f32, f32) + sds(engine._counter_args()),
            meta=meta))
    if "train_apply" in wanted and engine._apply_fn is not None:
        out.append(extract_artifact(
            "train_apply", "", engine._apply_fn,
            (params_s, sds(engine.master_params), sds(engine.opt_state),
             sds(engine.grad_acc), sds(engine.scale_state), f32),
            meta=meta))
    return out


def build_family_artifacts(tensor_widths=(1, 2), *, donate: bool = True,
                           hbm_limit_bytes: int = 0, kv_int8: bool = False,
                           families=None, include_train: bool = True,
                           layers: int = 1, model_dtype: str = "float32"):
    """The full audit table: every requested family at every requested
    tensor width. Returns a flat ProgramArtifact list."""
    import jax

    out = []
    for tp in tensor_widths:
        if int(tp) > len(jax.devices()):
            raise ValueError(
                f"tensor width {tp} needs {tp} devices, "
                f"only {len(jax.devices())} visible — set XLA_FLAGS="
                f"--xla_force_host_platform_device_count before jax "
                f"initializes (tools/ds_audit.py does this itself)")
        serving = None if families is None else [
            f for f in families if f in SERVING_FAMILIES]
        if serving is None or serving:
            out.extend(build_serving_artifacts(
                int(tp), donate=donate, hbm_limit_bytes=hbm_limit_bytes,
                kv_int8=kv_int8, families=serving, layers=layers,
                model_dtype=model_dtype))
        if include_train:
            train = None if families is None else [
                f for f in families if f in TRAIN_FAMILIES]
            if train is None or train:
                out.extend(build_train_artifacts(
                    int(tp), hbm_limit_bytes=hbm_limit_bytes,
                    families=train, layers=layers, model_dtype=model_dtype))
    return out
