"""Inference engine.

Reference: ``deepspeed/inference/engine.py`` (InferenceEngine :89 — dtype
conversion, TP group creation :261, kernel injection :384, CUDA-graph
capture :500, generate wrapper :588). TPU redesign:

  - "kernel injection" is the compiler: the decode path is two jitted
    programs (prefill + single-token decode) over the cache-aware model
    forward; fused attention/norm come from XLA/Pallas, not swapped modules.
  - CUDA-graph capture has no analogue to build — jit IS whole-program
    capture (SURVEY.md "deliberately not ported").
  - TP: weights carry logical axes; placing them over the ``tensor`` mesh
    axis shards qkv/mlp exactly like the reference's AutoTP column/row split,
    with the per-layer allreduce inserted by GSPMD.
  - int8: weight-only groupwise quantization at load (ZeroQuant-style W8),
    dequantized in-register by XLA at matmul sites.

Decode loop: ``generate`` defaults to a FUSED whole-generation program —
prefill + ``lax.scan`` over decode steps in one jit, one dispatch per call
(``fused_generate`` in InferenceConfig; the pre-r5 per-token dispatch loop
remains as the opt-out). Greedy or temperature/top-k/top-p sampling; KV
cache donated into the program.
"""

import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu import comm
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.runtime.zero.sharding import ShardingPolicy
from deepspeed_tpu.telemetry import compile_log
from deepspeed_tpu.utils.logging import log_dist, logger


class InferenceEngine:
    def __init__(self, model, config=None, params=None, mesh=None, seed: int = 0):
        self.config = InferenceConfig.parse(config)
        # auto-dispatch (reference: _apply_injection_policy at
        # inference/engine.py:384 + sharded loading at :338): a checkpoint
        # path converts shard-by-shard; an HF torch module converts in place
        if isinstance(model, str):
            from deepspeed_tpu.module_inject.load_checkpoint import convert_hf_checkpoint

            model, np_params = convert_hf_checkpoint(model)
            if params is None:
                params = np_params
        elif model is not None and hasattr(model, "state_dict") and hasattr(model, "config") \
                and not isinstance(model, (tf.TransformerModel, tf.TransformerConfig)):
            from deepspeed_tpu.module_inject.policies import convert_hf_model

            model, np_params = convert_hf_model(model)
            if params is None:
                params = np_params
        builtin = isinstance(model, (tf.TransformerModel, tf.TransformerConfig))
        if isinstance(model, tf.TransformerConfig):
            model = tf.TransformerModel(model)
        self.model = model  # builtin or any object with cfg/init/apply protocol
        cfg = self.model.cfg

        dtype_name = self.config.dtype
        self._weight_quant = dtype_name == "int8" or self.config.quant.enabled
        want_dtype = None
        if dtype_name in ("float32", "float16", "bfloat16") and dtype_name != cfg.dtype:
            want_dtype = dtype_name
        elif self._weight_quant and cfg.dtype == "float32":
            want_dtype = "bfloat16"
        if self.config.kv_cache_dtype not in ("model", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'model' or 'int8', got {self.config.kv_cache_dtype!r}"
            )
        floor = self.config.kv_read_floor
        if not (isinstance(floor, int) and floor >= 1 and (floor & (floor - 1)) == 0):
            raise ValueError(
                f"kv_read_floor must be a positive power of 2, got {floor!r}"
            )
        overrides = {}
        if self.config.kv_cache_dtype != cfg.kv_cache_dtype:
            overrides["kv_cache_dtype"] = self.config.kv_cache_dtype
        if want_dtype is not None:
            overrides["dtype"] = want_dtype
        if self.config.attn_impl is not None and self.config.attn_impl != cfg.attn_impl:
            assert self.config.attn_impl in ("xla", "pallas", "block_sparse"), \
                self.config.attn_impl
            overrides["attn_impl"] = self.config.attn_impl
        # rolling KV cache: exact for uniform-window models when prefill
        # rides the flash band kernel (segment attention never reads the
        # ring) and positions are relative (rope) or absent. Speculative
        # decoding writes per-row segments at varying depths — its paths
        # compile ring-off (full-length caches), so leave it off entirely.
        if (self.config.rolling_kv_cache
                and cfg.uniform_window is not None
                and cfg.pos_embedding in ("rope", "none")
                and overrides.get("attn_impl", cfg.attn_impl) == "pallas"
                and cfg.causal
                and not self.config.speculative.enabled):
            overrides["rolling_kv_cache"] = True
        if overrides:
            import dataclasses

            cfg = dataclasses.replace(cfg, **overrides)
            if builtin:
                self.model = tf.TransformerModel(cfg)
        if want_dtype is not None:
            if not builtin:
                # custom model object: keep it (its apply defines the network);
                # cfg carries the override so caches/compute use the new dtype
                logger.warning(
                    f"config dtype {want_dtype} != model cfg dtype {self.model.cfg.dtype}; "
                    "casting params, keeping the custom model's forward"
                )
        self.cfg = cfg

        # mesh: inference default is tensor-parallel (+ expert-parallel for
        # MoE models, reference moe_inference ep groups) over available chips.
        # An EXPLICIT config.mesh.shape with no -1 wildcard builds a subset
        # mesh over the first prod(shape) devices WITHOUT touching the global
        # comm state — several serving widths coexist in one process (the
        # sharded-vs-replicated loadgen A/B, the bench width sweep).
        mesh_cfg = self.config.mesh
        if mesh is None:
            shape = mesh_cfg.shape
            if shape is not None:
                # ALWAYS a LOCAL mesh for an explicit config shape — a
                # wildcard absorbs the whole host, a no-wildcard shape
                # takes the first prod(shape) devices — so a serving
                # engine never overwrites the process-global comm mesh a
                # training engine may be using
                devs = jax.devices()
                if -1 not in shape.values():
                    need = int(np.prod(list(shape.values()) or [1]))
                    if need > len(devs):
                        raise ValueError(
                            f"mesh shape {shape} needs {need} devices, "
                            f"only {len(devs)} available")
                    devs = devs[:need]
                mesh = comm.build_mesh(shape, devices=devs)
            elif comm.is_initialized():
                mesh = comm.get_mesh()
            else:
                shape = {"data": -1, "tensor": self.config.tensor_parallel.tp_size}
                ep = self.config.moe.ep_size
                if (self.config.moe.enabled or cfg.moe_num_experts > 0) and ep > 1:
                    shape["expert"] = ep
                mesh = comm.init_distributed(mesh_shape=shape, verbose=False)
        self.mesh = mesh

        self.policy = ShardingPolicy(mesh, stage=0, logical_specs=None)
        abstract = jax.eval_shape(self.model.init, jax.random.PRNGKey(seed))
        logical = self.model.logical_specs(abstract) if hasattr(self.model, "logical_specs") else None
        self.policy.logical_specs = logical
        if mesh_cfg.use_rules or logical is None:
            # whole-tree regex partition table (parallel/partition.py —
            # the module_inject layer for a mesh backend): user overrides
            # first, then the model-family defaults; serves models
            # WITHOUT logical_specs annotations, or any config forcing
            # the regex path with use_rules
            from deepspeed_tpu.parallel.partition import partition_params

            self.param_shardings = partition_params(mesh, abstract,
                                                    rules=mesh_cfg.rules)
        elif mesh_cfg.rules:
            # annotations win, user rules override PER-LEAF: only params
            # a rule matches change placement — one attention override
            # must not strip the expert/vocab intent annotations carry
            from deepspeed_tpu.parallel.partition import apply_rule_overrides

            self.param_shardings = apply_rule_overrides(
                mesh, abstract, self.policy.param_shardings(abstract),
                mesh_cfg.rules)
        else:
            self.param_shardings = self.policy.param_shardings(abstract)
        self.replicated = NamedSharding(mesh, PartitionSpec())
        self.batch_sharding = NamedSharding(mesh, PartitionSpec(("data", "fsdp")))

        if params is None:
            params = jax.jit(self.model.init, out_shardings=self.param_shardings)(jax.random.PRNGKey(seed))
        else:
            params = jax.device_put(params, self.param_shardings)
        # cast to model dtype (fp32 master irrelevant at inference), THEN
        # quantize — scales stay fp32 rather than riding the cast
        dt = cfg.jnp_dtype
        params = jax.tree.map(
            lambda p: p.astype(dt) if jnp.issubdtype(p.dtype, jnp.floating) else p, params
        )
        if self._weight_quant:
            params, self.param_shardings = self._quantize_weights(params)
        self.params = params

        self._prefill_fn = None
        self._decode_fn = None
        self._forward_fn = None
        self._model_times = []
        # --- telemetry hub (telemetry/: JSONL request traces, TTFT/decode
        # latency, compile-cache counters; inert when the block is disabled)
        from deepspeed_tpu.telemetry import Telemetry

        self.telemetry = Telemetry(self.config.telemetry, role="inference")
        self._request_id = 0
        self._compile_hits = 0
        self._compile_misses = 0
        # (B, max_len, alloc-bucket) shapes the migrating decode loop has
        # already traced — compile_cache_hit accounting (see generate())
        self._traced_geoms = set()
        if self.telemetry.enabled:
            # HBM baseline for the live ops plane: params are the only
            # resident allocation at build time (decode caches are
            # per-request; bucket migrations emit their own snapshots)
            from deepspeed_tpu.telemetry import memory as hbm

            hbm.emit_snapshot(self.telemetry,
                              {"params": hbm.tree_device_bytes(self.params)},
                              "build")
        log_dist(
            f"InferenceEngine ready: dtype={cfg.dtype} quant={self._weight_quant} "
            f"mesh={dict(mesh.shape)}",
            ranks=[0],
        )

    # ------------------------------------------------------------------
    # matmul weight leaves that switch to int8 storage ("w" = untied lm head;
    # biases / norms / the MoE router gate stay float)
    _QUANT_KEYS = ("wq", "wk", "wv", "wo", "wi", "wg", "w")

    def _is_quant_target(self, path, ndim: int) -> bool:
        names = [getattr(x, "key", "") for x in path]
        return (ndim >= 2 and names[-1] in self._QUANT_KEYS
                and any(n in ("attn", "mlp", "lm_head") for n in names))

    def _quantize_weights(self, params):
        """REAL weight-only int8 storage (num_bits=8): each matmul weight
        becomes {"q8": int8, "s": fp32 per-output-channel scales} and the
        model's matmul sites (models/transformer._linear) run W8A8 on the
        MXU int8 path — HBM truly holds int8, halving the decode bandwidth
        bound, unlike fake-quant which only reproduces the numerics.
        (Reference: module_inject weight_quantizer.py + the int8 GEMM /
        dequant kernel family, csrc/transformer/inference pt_binding.cpp.)
        num_bits != 8 falls back to fake-quant storage. Returns
        (params, shardings) transformed in lockstep so every jit
        in_shardings pytree keeps matching."""
        nbits = self.config.quant.num_bits
        if nbits != 8:
            from deepspeed_tpu.ops.quantizer import fake_quantize

            def fq(path, p):
                if p.ndim >= 2 and any(
                    getattr(x, "key", "") in ("attn", "mlp", "lm_head") for x in path
                ):
                    groups = max(1, p.shape[-1] // 128) if p.size % max(1, p.shape[-1] // 128) == 0 else 1
                    return fake_quantize(p, num_bits=nbits, num_groups=groups)
                return p

            return jax.tree_util.tree_map_with_path(fq, params), self.param_shardings

        def quant_leaf(path, p):
            if not self._is_quant_target(path, p.ndim):
                return p
            w32 = jnp.asarray(p, jnp.float32)
            absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)  # over contraction dim
            s = jnp.maximum(absmax / 127.0, 1e-12)
            q8 = jnp.clip(jnp.round(w32 / s), -128, 127).astype(jnp.int8)
            return {"q8": q8, "s": s}

        def shard_leaf(path, p, sh):
            if not self._is_quant_target(path, p.ndim):
                return sh
            spec = list(sh.spec) + [None] * (p.ndim - len(sh.spec))
            s_spec = list(spec)
            s_spec[-2] = None  # scales have extent 1 on the contraction dim
            return {"q8": sh, "s": NamedSharding(self.mesh, PartitionSpec(*s_spec))}

        new_params = jax.tree_util.tree_map_with_path(quant_leaf, params)
        new_shardings = jax.tree_util.tree_map_with_path(shard_leaf, params, self.param_shardings)
        return new_params, new_shardings

    # ------------------------------------------------------------------
    def _record_build(self, fn, family: str, key, **kw):
        """``compile_log.record_build`` with this engine's hub, read at the
        program's first dispatch."""
        return compile_log.record_build(fn, family, key,
                                        hub=lambda: self.telemetry, **kw)

    def _compile(self, batch_size: int, max_len: int):
        from deepspeed_tpu.inference.decoding import compile_decode_fns

        self._prefill_fn, self._decode_fn, self._cache_sharding, self.batch_sharding = (
            compile_decode_fns(self.mesh, self.cfg, self.param_shardings, batch_size, max_len)
        )
        self._compiled_shape = (batch_size, max_len)
        self._prefill_fn = self._record_build(
            self._prefill_fn, "decode_prefill", self._compiled_shape)
        self._decode_fn = self._record_build(
            self._decode_fn, "decode_step", self._compiled_shape)
        # ds-audit capture (zero cost without a hook): the decode pair is
        # the engine's hot program family — contract-checked as built
        from deepspeed_tpu.analysis.program import capture

        if capture.active():
            def sds(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype)

            params_s = jax.tree.map(sds, self.params)
            cache_s = jax.tree.map(sds, jax.eval_shape(
                lambda: kv_cache.init(self.cfg, batch_size, max_len)))
            capture.notify_program(
                "decode_prefill", "", self._prefill_fn,
                lambda: (params_s,
                         jax.ShapeDtypeStruct((batch_size, 8), jnp.int32),
                         cache_s),
                meta=self._audit_meta)
            capture.notify_program(
                "decode_step", "", self._decode_fn,
                lambda: (params_s,
                         jax.ShapeDtypeStruct((batch_size, 1), jnp.int32),
                         cache_s, jax.ShapeDtypeStruct((), jnp.int32)),
                meta=self._audit_meta)
        # fresh jit objects hold no traces — geoms recorded against the
        # discarded pair must not claim their shapes are still compiled
        self._traced_geoms = set()

    def _ensure_compiled(self, batch_size: int, max_len: int):
        miss = self._prefill_fn is None or self._compiled_shape != (batch_size, max_len)
        if miss:
            self._compile(batch_size, max_len)
            self._compile_misses += 1
        else:
            self._compile_hits += 1
        if self.telemetry.enabled:
            self.telemetry.registry.counter(
                "compile_cache", {"kind": "decode", "outcome": "miss" if miss else "hit"}
            ).inc()

    def _audit_meta(self) -> dict:
        """ProgramArtifact meta for ds-audit captures from this engine
        (analysis/program/capture.py) — built only while a hook is
        installed. The decode pair always donates its cache
        (compile_decode_fns donate_argnums=(2,))."""
        from deepspeed_tpu.analysis.program.capture import param_leaf_shapes
        from deepspeed_tpu.parallel.partition import mesh_tensor_width

        accum = {"float32": ("f32",), "bfloat16": ("bf16", "f32"),
                 "float16": ("f16", "f32")}.get(self.cfg.dtype, ())
        tp = mesh_tensor_width(self.mesh)
        return {
            "tp": tp,
            # dp/fsdp/... width: >1 means the calibrated tensor-only
            # collective tables don't apply (the inventory rule skips)
            "other_axes": int(self.mesh.devices.size) // max(tp, 1),
            "donate": True,
            "param_shapes": param_leaf_shapes(self.params),
            "accum_dtypes": accum,
            "int8_kv": self.cfg.kv_cache_dtype == "int8",
            "hbm_limit_bytes": getattr(self.telemetry.cfg,
                                       "hbm_limit_bytes", 0),
        }

    # ------------------------------------------------------------------
    def forward(self, input_ids, **kwargs):
        """Full-sequence logits (HF-pipeline parity surface)."""
        t0 = time.time()
        tokens = jnp.asarray(np.asarray(input_ids), jnp.int32)
        if self._forward_fn is None:
            cfg = self.cfg
            self._forward_fn = jax.jit(lambda p, t: tf.apply(p, cfg, t))
        logits = self._forward_fn(self.params, tokens)
        return self._finish_request(
            "forward", t0, logits,
            prompt_tokens=tokens.shape[1], new_tokens=0, batch=tokens.shape[0],
        )

    __call__ = forward

    def model_times(self):
        times = self._model_times
        self._model_times = []
        return times

    def _kv_fields(self, prompt_len: int, new_tokens: int, cache_len: int,
                   floor: Optional[int], batch: int,
                   alloc: Optional[int] = None) -> Optional[dict]:
        """Deterministic KV-read accounting for a generate call (None when
        telemetry is off): total cache bytes the decode steps streamed
        (``kv_bytes_read``), the per-decoded-token rate, the cache dtype,
        and how much of the allocation the request actually used. Pure host
        math mirroring the compiled read geometry (decoding.read_stages),
        so tests assert it exactly and the byte counts are the same on
        the CPU mesh as on a chip. On a tensor-parallel mesh
        the bytes are PER-CHIP — each chip streams only its head shard, so
        kv_cache.shard_width divides them out (that per-chip rate is what bounds
        a bandwidth-limited decode step)."""
        if not self.telemetry.enabled:
            return None
        from deepspeed_tpu.inference.decoding import decode_kv_bytes

        per_row = decode_kv_bytes(self.cfg, prompt_len, new_tokens, cache_len,
                                  floor, tp=kv_cache.shard_width(self.mesh, self.cfg))
        decoded = max(new_tokens - 1, 0)
        alloc = alloc if alloc is not None else cache_len
        fields = {
            "kv_dtype": "int8" if self.cfg.kv_cache_dtype == "int8" else self.cfg.dtype,
            "kv_bytes_read": int(batch) * per_row,
            "cache_utilization": round(min((prompt_len + new_tokens) / alloc, 1.0), 4),
        }
        if decoded:
            fields["kv_bytes_per_token"] = round(per_row / decoded, 1)
        return fields

    def _finish_request(self, path: str, t0: float, result, prompt_tokens: int,
                        new_tokens: int, batch: int, cache_len: Optional[int] = None,
                        timings: Optional[dict] = None,
                        misses_before: Optional[int] = None,
                        kv: Optional[dict] = None):
        """Single exit point for every forward/generate path. Preserves the
        reference's ``profile_model_time`` wall-clock list (``model_times()``
        drain semantics unchanged) and emits one structured
        "inference_request" telemetry event: TTFT when the path exposes a
        first-token boundary (the host-driven loops; the fused program is
        one dispatch, so TTFT degenerates to total), batch-aggregate decode
        tokens/sec, the chosen KV-cache length, and whether the request hit
        the compiled-fn cache or paid a compile."""
        want_time = self.config.profile_model_time or self.telemetry.enabled
        if not want_time:
            return result
        jax.block_until_ready(result)
        now = time.time()
        total_s = now - t0
        if self.config.profile_model_time:
            self._model_times.append(total_s)
        if self.telemetry.enabled:
            self._request_id += 1
            event = {
                "request": self._request_id,
                "path": path,
                "batch": int(batch),
                "prompt_tokens": int(prompt_tokens),
                "new_tokens": int(new_tokens),
                "total_ms": total_s * 1000.0,
            }
            if cache_len is not None:
                event["cache_len"] = int(cache_len)
            if kv is not None:
                event.update(kv)
            if misses_before is not None:
                event["compile_cache_hit"] = self._compile_misses == misses_before
            ttft_s = (timings or {}).get("first_token_s")
            if ttft_s is not None:
                event["ttft_ms"] = (ttft_s - t0) * 1000.0
            if new_tokens > 0 and total_s > 0:
                event["tokens_per_sec"] = int(batch) * (prompt_tokens + new_tokens) / total_s
                if ttft_s is None:
                    event["decode_tokens_per_sec"] = int(batch) * new_tokens / total_s
                elif new_tokens > 1:
                    # the first token lands at TTFT; rate the remaining
                    # tokens over the decode span (a 1-token request has no
                    # decode span — omit rather than divide by ~0)
                    event["decode_tokens_per_sec"] = (
                        int(batch) * (new_tokens - 1) / max(now - ttft_s, 1e-9)
                    )
            self.telemetry.emit("inference_request", event)
        return result

    def generate(
        self,
        input_ids,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        rng: Optional[jax.Array] = None,
        eos_token_id: Optional[int] = None,
        draft: Optional["InferenceEngine"] = None,
        num_draft_tokens: Optional[int] = None,
        attention_mask=None,
    ):
        """Greedy / temperature sampling with a compiled decode loop.

        ``attention_mask`` ((B, S) of 0/1, HF semantics) enables ragged
        prompts — left or right padding; pad slots never enter the KV
        cache, and each row decodes from its own length.

        Passing ``draft`` (a second, smaller InferenceEngine on the same
        tokenizer/vocab) switches to lossless speculative decoding: the
        draft proposes ``num_draft_tokens`` tokens per round and this
        engine verifies them in one segment forward (config block
        ``speculative.num_draft_tokens`` sets the default)."""
        tokens = jnp.asarray(np.asarray(input_ids), jnp.int32)
        B, S = tokens.shape
        if max_new_tokens <= 0:
            return tokens
        # with a mask, capacity is governed by the longest REAL prompt, not
        # the padded width (padding='max_length' batches are legal even at
        # S == max_seq_len)
        longest = int(np.asarray(attention_mask).sum(axis=1).max()) if attention_mask is not None else S
        total = longest + max_new_tokens
        assert total <= self.cfg.max_seq_len, (
            f"prompt {longest} + {max_new_tokens} new > max_seq_len {self.cfg.max_seq_len}"
        )
        # KV-cache allocation bounded by max_out_tokens (reference
        # inference/config.py max_out_tokens), grown only if the request needs it
        from deepspeed_tpu.inference.decoding import bounded_cache_len, decode_loop

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        # telemetry: compile-cache snapshot (events tag compile-paying
        # requests) and the TTFT stamp dict for the host-driven loops
        misses0 = self._compile_misses
        timings = {} if self.telemetry.enabled else None
        if self.config.prefill_chunk_size and draft is None \
                and not self.config.speculative.enabled:
            # fixed-shape (B, chunk) prefill program for EVERY prompt
            # length and padded width — including attention_mask batches,
            # the varied-width serving workload that motivates chunking.
            # Rides the ragged/segment families (ring-off, full cache).
            from deepspeed_tpu.inference.decoding import chunked_generate

            max_len = bounded_cache_len(total, self.cfg.max_seq_len,
                                        self.config.max_out_tokens)
            prefill_fn, segment_fn, cache_sh = self._ragged_fns_for(B, max_len)
            cache = jax.device_put(kv_cache.init(self.cfg, B, max_len), cache_sh)
            t0 = time.time()
            result = chunked_generate(
                prefill_fn, segment_fn, self.params, tokens, cache, max_len,
                self.config.prefill_chunk_size, max_new_tokens, temperature,
                top_k, rng, top_p, attention_mask=attention_mask,
                timings=timings, tight_read=self.config.kv_tight_read)
            result = self._finish_request(
                "chunked_prefill", t0, result, prompt_tokens=S,
                new_tokens=max_new_tokens, batch=B, cache_len=max_len,
                timings=timings, misses_before=misses0,
                kv=self._kv_fields(longest, max_new_tokens, max_len,
                                   self._tight_floor(), B))
            if eos_token_id is not None:
                result = self._truncate_eos(result, S, eos_token_id)
            return result
        if attention_mask is not None:
            if draft is not None or self.config.speculative.enabled:
                raise NotImplementedError(
                    "speculative decoding does not take attention_mask yet"
                )
            from deepspeed_tpu.inference.decoding import ragged_decode_loop

            max_len = bounded_cache_len(total, self.cfg.max_seq_len, self.config.max_out_tokens)
            prefill_fn, segment_fn, cache_sh = self._ragged_fns_for(B, max_len)
            cache = jax.device_put(kv_cache.init(self.cfg, B, max_len), cache_sh)
            t0 = time.time()
            result = ragged_decode_loop(
                prefill_fn, segment_fn, self.params, tokens, attention_mask,
                cache, max_len, max_new_tokens, temperature, top_k, rng, top_p,
                timings=timings, tight_read=self.config.kv_tight_read,
            )
            result = self._finish_request(
                "ragged", t0, result, prompt_tokens=S,
                new_tokens=max_new_tokens, batch=B, cache_len=max_len,
                timings=timings, misses_before=misses0,
                kv=self._kv_fields(longest, max_new_tokens, max_len,
                                   self._tight_floor(), B))
            if eos_token_id is not None:
                result = self._truncate_eos(result, S, eos_token_id)
            return result
        if draft is None and self.config.speculative.enabled:
            draft = getattr(self, "_draft_engine", None)
            if draft is None:
                raise ValueError(
                    "speculative.enabled but no draft model: pass draft= to "
                    "generate() or draft_model= to init_inference(), or set "
                    "speculative.mode='ngram' for draft-free self-drafting "
                    "(pooled serving, ContinuousBatcher)"
                )
        if draft is not None:
            gamma = (num_draft_tokens if num_draft_tokens is not None
                     else self.config.speculative.num_draft_tokens)
            if gamma < 1:
                raise ValueError(
                    f"speculative.num_draft_tokens must be >= 1, got {gamma}")
            result = self._generate_speculative(
                draft, tokens, max_new_tokens, temperature, top_k, top_p, rng,
                gamma, eos_token_id,
            )
            if eos_token_id is not None:
                result = self._truncate_eos(result, S, eos_token_id)
            return result

        max_len = bounded_cache_len(total, self.cfg.max_seq_len, self.config.max_out_tokens)
        max_len = self._ring_cache_len(max_len, S)
        # tight reads never apply to the ring geometry (already O(window))
        floor = None if self.cfg.rolling_kv_cache else self._tight_floor()
        if self.config.fused_generate:
            # one dispatch for the whole generation (prefill + scan over
            # decode steps) — identical token stream to decode_loop; tight
            # reads ride as bucket-staged scans inside the same program
            fused_fn, cache_sh = self._fused_generate_fn(
                B, max_len, max_new_tokens, temperature, top_k, top_p,
                read_floor=floor)
            cache = jax.device_put(kv_cache.init(self.cfg, B, max_len), cache_sh)
            t0 = time.time()
            result = fused_fn(self.params, tokens, cache, rng)
            result = self._finish_request(
                "fused", t0, result, prompt_tokens=S,
                new_tokens=max_new_tokens, batch=B, cache_len=max_len,
                misses_before=misses0,
                kv=self._kv_fields(S, max_new_tokens, max_len, floor, B))
            if eos_token_id is not None:
                result = self._truncate_eos(result, S, eos_token_id)
            return result
        self._ensure_compiled(B, max_len)

        from deepspeed_tpu.inference.decoding import read_bucket

        # bucket-migrated allocation: the per-token loop starts its cache at
        # the prompt's bucket and grows by migration (decode reads therefore
        # stream the bucketed active length); tight-read off or a ring-sized
        # cache keeps the full allocation. The final allocation stops at
        # bucket(total-1): the LAST write lands at total-2 (the closing
        # sampled token is never cached) — bucket(total) would overstate
        # alloc 2x at exact boundaries and halve the reported utilization.
        alloc = max_len if floor is None else min(read_bucket(S + 1, max_len, floor), max_len)
        final_alloc = (max_len if floor is None else
                       min(read_bucket(max(S + 1, total - 1), max_len, floor),
                           max_len))
        fresh_allocs: set = set()
        if floor is not None:
            # honest compile accounting: the prefill/decode jit OBJECTS are
            # keyed (B, max_len), but migration retraces them per allocation
            # bucket — a request whose bucket walk meets an untraced shape
            # pays real XLA compiles and must not be tagged a cache hit
            geoms, b = {(B, max_len, alloc)}, alloc
            while b < final_alloc:
                b = min(b * 2, max_len)
                geoms.add((B, max_len, b))
            fresh = geoms - self._traced_geoms
            if fresh:
                self._compile_misses += 1
                self._traced_geoms |= fresh
                # allocation buckets whose migration dispatch will pay a
                # real re-trace this request — the build journal only
                # records those (an already-traced bucket re-migrated by
                # a later request dispatches from the jit cache)
                fresh_allocs = {g[2] for g in fresh}
        decode_fn = (self._decode_fn if floor is None
                     else self._migrating_decode_fn(max_len, floor,
                                                    fresh_allocs))
        cache = jax.device_put(kv_cache.init(self.cfg, B, alloc), self._cache_sharding)
        t0 = time.time()
        result = decode_loop(
            self._prefill_fn, decode_fn, self.params, tokens, cache,
            max_new_tokens, temperature, top_k, rng, top_p=top_p,
            timings=timings,
        )
        result = self._finish_request(
            "decode_loop", t0, result, prompt_tokens=S,
            new_tokens=max_new_tokens, batch=B, cache_len=max_len,
            timings=timings, misses_before=misses0,
            kv=self._kv_fields(S, max_new_tokens, max_len, floor, B,
                               alloc=final_alloc))
        if eos_token_id is not None:
            result = self._truncate_eos(result, S, eos_token_id)
        return result

    def _tight_floor(self) -> Optional[int]:
        """The tight-read bucket floor, or None when the knob is off."""
        return self.config.kv_read_floor if self.config.kv_tight_read else None

    def _migrating_decode_fn(self, max_len: int, floor: int,
                             fresh_allocs: Optional[set] = None):
        """Wrap the compiled decode step with bucket-migrated cache growth:
        when the write position reaches the current allocation, one jitted
        pad (memoized per target length) migrates the cache to the next
        power-of-2 bucket. Every step's read then streams the bucketed
        active length — the tight-read geometry — without any per-step
        slicing in the compiled program."""
        from deepspeed_tpu.inference.decoding import read_bucket

        fresh = set() if fresh_allocs is None else fresh_allocs
        first = True

        def dispatch(params, tok, cache, pos):
            nonlocal first
            if pos + 1 > kv_cache.alloc_len(self.cfg, cache):
                new_len = min(read_bucket(pos + 1, max_len, floor), max_len)
                cache = self._grow_cache(cache, new_len)
                # every migration snapshots the grown allocation (hub on);
                # the decode jit RE-TRACES only at an untraced bucket —
                # that runtime recompile is what the build journal records,
                # hub or no hub (each fresh bucket compiles exactly once)
                retrace = new_len in fresh
                fresh.discard(new_len)
                return self._migrated_decode(params, tok, cache, pos,
                                             new_len, retrace)
            if first:
                # a request can also pay a re-trace at its STARTING bucket
                # (a longer prompt opening an untraced allocation, no
                # migration involved) — journal that compile too, unless
                # the decode fn's own journal wrapper is still armed (the
                # genuine first compile, which records itself)
                first = False
                start_alloc = kv_cache.alloc_len(self.cfg, cache)
                if (start_alloc in fresh
                        and getattr(self._decode_fn, "_done", True)):
                    fresh.discard(start_alloc)
                    return self._timed_decode_retrace(params, tok, cache,
                                                      pos, start_alloc)
                fresh.discard(start_alloc)
            return self._decode_fn(params, tok, cache, pos)

        return dispatch

    def _migrated_decode(self, params, tok, cache, pos, new_len: int,
                         retrace: bool):
        """First decode dispatch after a bucket migration: emit the
        ``memory_snapshot`` (reason ``migration``) for the grown
        allocation and — when this bucket is genuinely untraced — journal
        the decode re-trace as a compile_event under the same family+key
        as the original ``decode_step`` compile, so the event is
        recompile-flagged (the visible counter behind runtime recompile
        storms)."""
        if self.telemetry.enabled:
            from deepspeed_tpu.telemetry import memory as hbm

            hbm.emit_snapshot(self.telemetry, {
                "params": hbm.tree_device_bytes(self.params),
                "kv_cache": hbm.tree_device_bytes(cache),
            }, "migration")
        if not retrace:
            return self._decode_fn(params, tok, cache, pos)
        return self._timed_decode_retrace(params, tok, cache, pos, new_len)

    def _timed_decode_retrace(self, params, tok, cache, pos, alloc: int):
        """Dispatch one decode step that is known to pay a runtime
        re-trace (an untraced allocation bucket) and journal it as a
        compile_event under the same family+key as the original
        ``decode_step`` compile — recompile-flagged, ``cache_alloc``
        attached (the visible counter behind runtime recompile storms)."""
        return self._record_build(self._decode_fn, "decode_step", self._compiled_shape,
                                  cache_alloc=alloc)(params, tok, cache, pos)

    def _grow_cache(self, cache, new_len: int):
        """Migrate a KV cache to a longer time axis (zero-padded tail; the
        position mask keeps the tail inert until real writes reach it).
        No donation — the output shape differs from the input's, so XLA
        could not alias the buffers anyway; the old cache frees when its
        last reference (the caller's local) drops after the dispatch."""
        sharding = self._cache_sharding  # snapshot: the closure must match
        # the cache THIS call grows, and _cache_sharding flips between
        # batch-sharded and replicated with the request's batch size — so
        # the memo key carries the batch dim alongside the target length
        batch = jax.tree.leaves(cache)[0].shape[1]

        def build():
            def grow(c):
                return kv_cache.grow(self.cfg, c, new_len)

            return jax.jit(grow, in_shardings=(sharding,),
                           out_shardings=sharding)

        # every bucket from floor to max_len is a distinct target length —
        # keep them all resident, not the default-4 LRU window
        return self._cached_fn("grow_cache", (batch, new_len), build,
                               slots=16)(cache)

    def _ring_cache_len(self, max_len: int, prompt_len: int) -> int:
        """Rolling-cache sizing: shrink the cache to the sliding window when
        prefill will ride the flash band path (segment attention never reads
        the ring) — or the prompt is a single token. Otherwise keep the full
        length: the ring math degenerates to a plain cache when nothing
        wraps, so correctness never depends on this choice."""
        if not self.cfg.rolling_kv_cache:
            return max_len
        from deepspeed_tpu.ops.pallas.flash_attention import supports_seq_len

        if prompt_len > 1 and not supports_seq_len(prompt_len):
            return max_len  # einsum prefill must see an unwrapped cache
        return min(max_len, self.cfg.uniform_window)

    @property
    def _ring_off_cfg(self):
        """cfg clone for the per-row-depth compiled families (speculative /
        ragged / continuous segments): they write rows at varying offsets,
        which the ring's aligned-path math does not cover — they run with
        full-length caches instead."""
        if not self.cfg.rolling_kv_cache:
            return self.cfg
        import dataclasses

        return dataclasses.replace(self.cfg, rolling_kv_cache=False)

    def _cached_fn(self, kind: str, key, builder, slots: int = 4):
        """Bounded memoization for every compiled-fn family on the engine
        (plain decode, speculative, ragged) — decoding.cached_fn, shared
        with the hybrid engine. Multiple slots matter: the speculative and
        ragged paths share the "segment" family but legitimately use
        different cache lengths (the spec path adds gamma+1 slack), and
        tight-read families multiply keys by the bucket count."""
        from deepspeed_tpu.inference.decoding import cached_fn

        return cached_fn(self, kind, key, builder, slots=slots)

    def _segment_fn(self, batch_size: int, max_len: int):
        """Per-row-position segment forward, shared by the speculative and
        ragged paths (any segment width retraces under the same wrapper).
        Returns a DISPATCHER ``fn(params, toks, cache, pos, active=None)``:
        callers that know the live rows' max cached extent (the ragged /
        chunked decode tails) pass ``active`` and get a tight-read variant
        compiled per bucket; 4-arg callers (speculative verify) read the
        full cache as before."""
        from deepspeed_tpu.inference.decoding import compile_segment_fn, read_bucket

        floor = self._tight_floor()

        def fn_for(read_len):
            # one long generation walks every bucket up to max_len (~6 keys
            # at 4096/128) — the default 4 slots would evict and recompile
            # the early buckets on EVERY subsequent request
            return self._cached_fn(
                "segment", (batch_size, max_len, read_len),
                lambda: compile_segment_fn(self.mesh, self._ring_off_cfg,
                                           self.param_shardings, batch_size,
                                           max_len, read_len=read_len)[0],
                slots=16,
            )

        local = {}  # dispatcher-local memo: the per-token decode tail must
        # not touch the LRU (dict pop/reinsert + a telemetry counter inc)
        # on EVERY step — one cached_fn hit per bucket per request, like
        # the one-fetch-per-generate accounting before tight reads

        def dispatch(params, toks, cache, pos, active=None):
            read_len = None
            if floor is not None and active is not None:
                r = read_bucket(active, max_len, floor)
                read_len = None if r >= max_len else r
            if read_len not in local:
                local[read_len] = fn_for(read_len)
            return local[read_len](params, toks, cache, pos)

        return dispatch

    def _fused_generate_fn(self, batch_size: int, max_len: int,
                           max_new_tokens: int, temperature: float,
                           top_k: int, top_p: float,
                           read_floor: Optional[int] = None):
        """(generate_fn, cache_sharding) for the fused whole-generation
        program — shared wiring in decoding.fused_generate_fn."""
        from deepspeed_tpu.inference.decoding import fused_generate_fn

        return fused_generate_fn(self, self.mesh, self.cfg, self.param_shardings,
                                 batch_size, max_len, max_new_tokens,
                                 temperature, top_k, top_p,
                                 read_floor=read_floor)

    def _ragged_fns_for(self, batch_size: int, max_len: int):
        """(ragged_prefill_fn, segment_fn, cache_sharding) for attention_mask
        generation."""
        from deepspeed_tpu.inference.decoding import compile_ragged_prefill_fn

        prefill_fn, cache_sh = self._cached_fn(
            "ragged_prefill", (batch_size, max_len),
            lambda: compile_ragged_prefill_fn(self.mesh, self._ring_off_cfg, self.param_shardings,
                                              batch_size, max_len)[:2],
        )
        return prefill_fn, self._segment_fn(batch_size, max_len), cache_sh

    def _spec_fns(self, batch_size: int, max_len: int):
        """(prefill_fn, segment_fn, cache_sharding) for speculative decoding.
        Keyed by (B, cache_len) only, so target (gamma+1-wide) and draft
        (1-wide) roles share one compiled-fn cache even when one engine
        plays both (self-draft)."""
        from deepspeed_tpu.inference.decoding import compile_decode_fns

        prefill_fn, cache_sh = self._cached_fn(
            "spec_prefill", (batch_size, max_len),
            lambda: (lambda r: (r[0], r[2]))(compile_decode_fns(
                self.mesh, self._ring_off_cfg, self.param_shardings, batch_size, max_len)),
        )
        return prefill_fn, self._segment_fn(batch_size, max_len), cache_sh

    def _generate_speculative(self, draft, tokens, max_new_tokens, temperature,
                              top_k, top_p, rng, gamma: int,
                              eos_token_id: Optional[int] = None):
        from deepspeed_tpu.inference.decoding import speculative_generate

        misses0 = self._compile_misses
        t0 = time.time()
        result = speculative_generate(
            self._ring_off_cfg, self.params, draft, tokens, max_new_tokens, temperature,
            top_k, top_p, rng, gamma, self.config.max_out_tokens,
            get_fns=self._spec_fns, eos_token_id=eos_token_id,
        )
        return self._finish_request(
            "speculative", t0, result, prompt_tokens=tokens.shape[1],
            new_tokens=max_new_tokens, batch=tokens.shape[0],
            misses_before=misses0)

    @staticmethod
    def _select(logits, temperature, top_k, rng, top_p=1.0):
        from deepspeed_tpu.inference.decoding import select_token

        return select_token(logits, temperature, top_k, rng, top_p)

    @staticmethod
    def _truncate_eos(tokens, prompt_len, eos_id):
        """Pad everything after each row's first generated EOS with EOS.

        One host transfer (read-only ``np.asarray`` view), and the writable
        copy + device re-dispatch happen ONLY for rows that actually need
        rewriting — the common no-EOS case (and the speculative path, which
        already EOS-pads) used to pay a full host copy AND a full re-upload
        of the token buffer on every call."""
        arr = np.asarray(tokens)
        gen = arr[:, prompt_len:]
        need = []
        for b in np.nonzero((gen == eos_id).any(axis=1))[0]:
            first = int(np.argmax(gen[b] == eos_id))
            if not (gen[b, first + 1:] == eos_id).all():
                need.append((b, first))
        if not need:
            return tokens
        arr = arr.copy()
        for b, first in need:
            arr[b, prompt_len + first + 1:] = eos_id
        return jnp.asarray(arr)


@compile_log.phase("params_place")
def init_inference(model, config=None, params=None, mesh=None, draft_model=None,
                   draft_params=None, seed: int = 0, **kwargs) -> InferenceEngine:
    """Reference: deepspeed.init_inference (deepspeed/__init__.py:251).

    ``draft_model`` (plus ``config.speculative.enabled``) attaches a smaller
    same-vocabulary model whose engine drives speculative decoding on every
    generate() call."""
    if kwargs and config is None:
        config = kwargs
    engine = InferenceEngine(model, config=config, params=params, mesh=mesh, seed=seed)
    if draft_model is not None:
        engine._draft_engine = InferenceEngine(
            draft_model,
            # the draft shares the cache format: int8 KV's memory halving
            # must cover both engines or long-context speculative serving
            # silently loses it
            config={"dtype": engine.config.dtype,
                    "kv_cache_dtype": engine.config.kv_cache_dtype,
                    "kv_tight_read": engine.config.kv_tight_read,
                    "kv_read_floor": engine.config.kv_read_floor},
            params=draft_params, mesh=mesh, seed=seed,
        )
    return engine
