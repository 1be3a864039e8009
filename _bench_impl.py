"""Bench CHILD-side implementation: the actual measurements.

This module is only ever imported inside a bench child process
(``python bench.py --child <phase>``). The parent orchestrator in
``bench.py`` is stdlib-only and never touches jax — every device
contact (including the first ``jax.devices()``) happens here, in a child
that owns the chip for its lifetime and that the parent can SIGKILL on
timeout.

Phases (BASELINE.json tracked-config classes that fit one chip):

  probe           — first device contact + tiny matmul; names the platform.
  primary         — headline GPT-2 125M causal-LM training (self-tuning).
  zero3_offload   — ZeRO-3 + optimizer host offload (max-params story).
  moe_ep          — MoE GPT (8 experts, top-1 GShard gating) training.
  decode          — KV-cache greedy decode tokens/s (+ int8 A/B).
  hybrid_rlhf     — hybrid-engine rollout + train step, tokens/s.
  bert_mlm        — BERT-large MLM samples/s + TFLOPS/chip (reference's
                    headline bench: 64 TFLOPS/V100 @ seq 128).

Each phase prints exactly one sentinel line ``DSTPU_RESULT {json}``; the
parent relays it as a bare JSON line. vs_baseline for training configs is
MFU / 0.45 (the north-star MFU from BASELINE.md).
"""

import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.compile_cache import configure_compile_cache

# device peaks live in ONE place — analysis/program/costmodel.py — shared
# with tools/perf_budget.py and the ds-perf roofline gate. A device kind
# the table does not hold raises: no MFU is computed at another part's peak.


_SMOKE = os.environ.get("DSTPU_BENCH_SMOKE") == "1"


def _smoke_model(seq=64, **overrides):
    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

    kw = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
              max_seq_len=seq, dtype="bfloat16")
    kw.update(overrides)
    return TransformerModel(TransformerConfig(**kw))


def _device_kind() -> str:
    return jax.devices()[0].device_kind.lower()


def peak_flops() -> float:
    from deepspeed_tpu.analysis.program.costmodel import peaks_for

    return peaks_for(_device_kind()).flops


def peak_bw() -> float:
    from deepspeed_tpu.analysis.program.costmodel import peaks_for

    return peaks_for(_device_kind()).hbm_bw


def _sync(engine, loss):
    # host fetch as the completion barrier: the values cannot arrive
    # before the programs that make them have run, and loss(+params)
    # close the dependency chain over every prior step
    return float(loss) + float(jnp.sum(jax.tree.leaves(engine.params)[0]))


def _progress(msg):
    # milestones go to stderr as they happen: when the parent SIGKILLs an
    # over-budget phase, the log still says WHERE the budget went
    print(f"bench progress: {msg}", file=sys.stderr, flush=True)


def _release_device_memory():
    """Free every device buffer and compiled-executable reference this
    process holds. The r5 self-tune OOM'd because four probe engines'
    params/optimizer states (~2 GB each) stayed resident in HBM while the
    winner's full measurement compiled — each probe must hand back its HBM
    before the next starts."""
    import gc

    gc.collect()
    try:
        jax.clear_caches()
    except Exception:
        pass
    gc.collect()
    for arr in list(jax.live_arrays()):
        try:
            arr.delete()
        except Exception:
            pass


def _train_bench(model, config, micro_bs, seq, iters, warmup_steps=1, batch=None,
                 timings=None):
    """Shared measurement protocol (warmup, host-transfer sync barrier,
    timed loop) for every training bench; ``batch`` overrides the default
    causal-LM batch (the MLM bench passes labels/loss_mask/token_types).
    ``timings``: optional dict filled with the phase breakdown
    (init_s / warmup_s / step_s) so a timed-out run tells us WHERE the
    budget went (VERDICT r3 #3)."""
    assert warmup_steps >= 1, "at least one warmup step (compile) is required"
    import deepspeed_tpu

    t_init0 = time.time()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    jax.block_until_ready(engine.params)
    t_init = time.time() - t_init0
    _progress(f"engine init done in {t_init:.1f}s")
    rs = np.random.RandomState(0)
    n_dev = jax.device_count()
    if batch is None:
        batch = {"input_ids": rs.randint(0, model.cfg.vocab_size, (micro_bs * n_dev, seq)).astype(np.int32)}

    def step():
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        return loss

    t_warm0 = time.time()
    for _ in range(warmup_steps):
        loss = step()
    _sync(engine, loss)
    t_warm = time.time() - t_warm0
    _progress(f"warmup (compile + {warmup_steps} step) done in {t_warm:.1f}s")
    t0 = time.time()
    for i in range(iters):
        loss = step()
        # per-step sync + milestone only for slow phases (timings callers,
        # e.g. zero3_offload, whose steps are tens of seconds and already
        # host-synchronous on the offload path — the extra barrier is one
        # host round trip, noted in the timings contract below). Fast benches
        # stay fully pipelined: a mid-loop sync would add a host round
        # trip to a loop measured in ms.
        if timings is not None and i < iters - 1:
            _sync(engine, loss)
            _progress(f"measured step {i + 1}/{iters} done at {time.time() - t0:.1f}s")
    _sync(engine, loss)
    dt = (time.time() - t0) / iters
    if timings is not None:
        timings["init_s"] = round(t_init, 1)
        timings["warmup_s"] = round(t_warm, 1)
        # step_s includes one host-sync RTT per step (the progress
        # barrier above) — honest wall time for host-synchronous phases
        timings["step_s"] = round(dt, 2)
    toks = micro_bs * n_dev * seq / dt
    return toks / n_dev, dt, float(loss), engine


def _transfer_bandwidth_probe(nbytes=1 << 27):
    """Measured D2H + H2D bandwidth (bytes/s) of this host's link to the
    chip. Used to pre-size the offload bench instead of timing out
    (VERDICT r2 weak #3)."""
    dev = jax.devices()[0]
    x_host = np.zeros(nbytes // 4, np.float32)
    x = jax.device_put(x_host, dev)
    x.block_until_ready()
    t0 = time.time()
    _ = np.asarray(x)
    d2h = nbytes / max(time.time() - t0, 1e-9)
    t0 = time.time()
    y = jax.device_put(x_host, dev)
    y.block_until_ready()
    h2d = nbytes / max(time.time() - t0, 1e-9)
    return d2h, h2d


def bench_zero3_offload(budget_s=240):
    """ZeRO-3 + optimizer host offload (the max-params-per-chip story).

    Re-sized per VERDICT r2 weak #3: GPT-2 ~760M (not 1.5B), 1 measured
    iter, bf16 grad wire, and a bandwidth pre-probe that emits a
    diagnostic skip line instead of burning the cap when the host link is
    too slow for the transfer volume."""
    from deepspeed_tpu.models.transformer import TransformerModel

    seq, micro_bs = 1024, 1
    size = "760m"
    if _SMOKE:
        seq = 64
        model = _smoke_model(seq, remat=True, remat_policy="nothing_saveable")
    else:
        # pre-probe: per step the offload path moves ~2 bytes/param D2H
        # (bf16 grad wire) + ~2 bytes/param H2D (bf16 params back). When the
        # measured link is too slow for 760M inside the budget, size down to
        # 125M so the phase still produces a MEASURED number (the metric
        # name carries the size) that localizes the cost to the wire.
        d2h, h2d = _transfer_bandwidth_probe()
        _progress(f"zero3 bw probe d2h={d2h / 1e9:.3f} GB/s h2d={h2d / 1e9:.3f} GB/s")
        n_steps = 3  # warmup + 2 measured
        compile_margin = 120.0
        model = None
        for size in ("760m", "125m"):
            cand = TransformerModel.from_preset(
                f"gpt2-{size}", dtype="bfloat16", remat=True,
                remat_policy="nothing_saveable", max_seq_len=seq)
            n_params = cand.cfg.num_params()
            est_step = 2 * n_params / d2h + 2 * n_params / h2d
            if est_step * n_steps + compile_margin <= budget_s:
                model = cand
                break
        if model is None:
            return {
                "metric": "gpt2_760m_zero3_offload_skipped",
                "value": None,
                "unit": None,
                "vs_baseline": None,
                "extra": {
                    "reason": "transfer bandwidth too low for budget (even at 125m)",
                    "d2h_gbps": round(d2h / 1e9, 2),
                    "h2d_gbps": round(h2d / 1e9, 2),
                    "est_step_s": round(est_step, 1),
                    "budget_s": budget_s,
                },
            }
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {
            "stage": 3,
            # bf16 grad wire: half the D2H bytes per step (the transfer is
            # the offload bottleneck)
            "offload_optimizer": {"device": "cpu", "wire_dtype": "bfloat16"},
        },
        "steps_per_print": 1000000,
        "mesh": {"data": -1},
    }
    timings = {}
    toks, dt, loss, engine = _train_bench(model, config, micro_bs, seq, iters=2,
                                          timings=timings)
    n_params = model.cfg.num_params()
    mfu = toks * model.flops_per_token(seq) / peak_flops()
    return {
        "metric": f"gpt2_{size}_zero3_offload_tokens_per_sec_per_chip",
        "value": round(toks, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "params": n_params,
            "params_per_chip": n_params,
            "mfu": round(mfu, 4),
            "step_ms": round(dt * 1e3, 1),
            "offload": "cpu",
            "loss": loss,
            **timings,
        },
    }


def bench_long_ctx():
    """Long-sequence training throughput (the long-context story on one
    chip: flash attention never materializes the S x S logits, so seq 4096
    trains where the xla path's fp32 softmax chain pays ~1.6 GB of HBM
    traffic per layer per direction). Reports the flash number as the
    metric; the xla+full-remat arm rides along in extra as the A/B.

    Sequence parallelism (ring / Ulysses, parallel/sequence.py) is the
    multi-chip half of the long-context story — exercised by the dryrun's
    sp x ep phase; this bench is the single-chip kernel half."""
    t_phase0 = time.time()
    budget_s = int(os.environ.get("DSTPU_BENCH_PHASE_BUDGET", "240"))
    seq, micro_bs = (128, 2) if _SMOKE else (4096, 2)

    # full remat for the xla A/B arm: dots_saveable's stacked-logits stash
    # is (L,B,H,S,S) bf16 = 9.7 GB at seq 4096 — it cannot ride along
    model = _gpt2_model(seq, "pallas", remat=False)
    toks, dt, loss, _ = _train_bench(
        model, _gpt2_config(micro_bs), micro_bs, seq, iters=8)
    mfu = toks * model.cfg.flops_per_token(seq) / peak_flops()
    _release_device_memory()

    # extra arms, each budget-guarded so a slow arm cannot get the whole
    # child SIGKILLed after the flash headline is already measured
    def _arm(need_s, fn):
        remaining = budget_s - (time.time() - t_phase0)
        if remaining < need_s:
            return {"skipped": f"{int(remaining)}s left of {budget_s}s budget"}
        try:
            return fn()
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"[:200]}
        finally:
            # a failed arm's engine state must not stay resident in HBM
            # and poison the next arm
            _release_device_memory()

    def _sliding_window():
        # Mistral-style uniform sliding window: the tile-pruned band kernel
        # does O(S*window) work — at seq 4096 / window 1024 the band visits
        # ~2/8 of the k-blocks per q-block
        import dataclasses

        win_model = type(model)(dataclasses.replace(
            model.cfg, local_attn_windows=(1024,) * model.cfg.num_layers))
        toks_w, _, _, _ = _train_bench(
            win_model, _gpt2_config(micro_bs), micro_bs, seq, iters=8)
        return {"window1024_tokens_per_sec": round(toks_w, 1),
                "window1024_speedup_vs_full": round(toks_w / toks, 2)}

    def _xla_arm():
        toks_x, _, _, _ = _train_bench(
            _gpt2_model(seq, "xla", remat=True, remat_policy="nothing_saveable"),
            _gpt2_config(micro_bs), micro_bs, seq, iters=4)
        return {"xla_remat_tokens_per_sec": round(toks_x, 1),
                "flash_speedup_vs_xla": round(toks / toks_x, 2)}

    win_ab = {f"sliding_{k}" if k in ("skipped", "error") else k: v
              for k, v in _arm(100, _sliding_window).items()}
    xla_ab = {f"xla_remat_{k}" if k in ("skipped", "error") else k: v
              for k, v in _arm(90, _xla_arm).items()}
    return {
        "metric": "gpt2_125m_seq4096_train_tokens_per_sec_per_chip",
        "value": round(toks, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "seq_len": seq,
            "micro_bs": micro_bs,
            "mfu": round(mfu, 4),
            "step_ms": round(dt * 1e3, 1),
            "attn_impl": "pallas",
            "remat": False,
            "loss": loss,
            **win_ab,
            **xla_ab,
        },
    }


def bench_moe_ep():
    from deepspeed_tpu.models.transformer import TransformerModel, get_config

    seq, micro_bs = (64, 2) if _SMOKE else (1024, 8)
    cfg = get_config(
        "gpt2-125m", dtype="bfloat16", remat=True, remat_policy="nothing_saveable",
        max_seq_len=seq, moe_num_experts=8, moe_top_k=1,
    )
    if _SMOKE:
        import dataclasses
        cfg = dataclasses.replace(cfg, hidden_size=64, num_layers=2, num_heads=4, vocab_size=512)
    model = TransformerModel(cfg)
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 1000000,
        "mesh": {"data": -1},  # expert axis folds to 1 on a single chip
    }
    toks, dt, loss, _ = _train_bench(model, config, micro_bs, seq, iters=8)
    mfu = toks * cfg.flops_per_token(seq) / peak_flops()
    return {
        "metric": "moe_gpt_8e_train_tokens_per_sec_per_chip",
        "value": round(toks, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "experts": 8,
            "params": cfg.num_params(),
            "mfu": round(mfu, 4),
            "step_ms": round(dt * 1e3, 1),
            "loss": loss,
        },
    }


def _decode_window(engine, tokens, new_tokens):
    """Steady-state decode seconds: total generate minus (prefill + one
    decode step), both paths pre-compiled."""
    out = engine.generate(tokens, max_new_tokens=new_tokens)  # compile + warmup
    _ = np.asarray(out)
    _ = np.asarray(engine.generate(tokens, max_new_tokens=1))  # compile 1-token path
    t0 = time.time()
    _ = np.asarray(engine.generate(tokens, max_new_tokens=1))
    t_prefill = time.time() - t0
    t0 = time.time()
    _ = np.asarray(engine.generate(tokens, max_new_tokens=new_tokens))
    return max(time.time() - t0 - t_prefill, 1e-9)


def _decode_winner_key(device_kind):
    return f"decode/{device_kind}/n{jax.device_count()}"


def _cached_decode_winner(device_kind):
    entry = _winner_cache_get(_decode_winner_key(device_kind))
    if entry is not None:
        return entry["kv_cache_dtype"], entry["tight"], entry["bounded"]
    return None


def _save_decode_winner(device_kind, kv_cache_dtype, tight, bounded):
    _winner_cache_put(_decode_winner_key(device_kind),
                      {"kv_cache_dtype": kv_cache_dtype, "tight": tight,
                       "bounded": bounded})


def bench_decode():
    """Decode throughput, SELF-TUNING over KV-cache geometry. The three
    probes are genuinely distinct read programs: (a) the historical
    baseline — cache manually right-sized to the request via
    max_out_tokens, full-length reads; (b) tight reads at the DEFAULT
    allocation (max_seq_len) — the geometry the overhaul fixes: no manual
    sizing, bucket-staged reads stream the active length out of the 4x-
    oversized cache; (c) int8 KV on the right-sized cache — halves the
    bytes per slot. Winner measured and persisted per device kind like the
    train bench (probe list bounded at 3). Decode on TPU is an HBM
    roofline — weight bytes + KV-cache bytes per token — so ``extra``
    reports ``kv_bytes_per_token`` and roofline utilization including
    cache traffic for every probe, not just wall clock."""
    import deepspeed_tpu
    from deepspeed_tpu.inference.decoding import decode_kv_bytes
    from deepspeed_tpu.models.transformer import TransformerModel

    B, prompt_len, new_tokens = (2, 8, 8) if _SMOKE else (8, 128, 128)
    if _SMOKE:
        model = _smoke_model(64)
    else:
        model = TransformerModel.from_preset("gpt2-350m", dtype="bfloat16", max_seq_len=1024)
    decoded = max(new_tokens - 1, 1)
    # right-sized KV cache (prompt + new tokens): the bounded variants pass
    # it as max_out_tokens; the tight-read variant deliberately does NOT —
    # it serves from the default max_seq_len allocation to show bucketed
    # reads recover the right-sized bytes without per-request sizing
    cache_len = prompt_len + new_tokens
    weight_bytes = model.cfg.num_params() * 2  # bf16
    rs = np.random.RandomState(0)
    # host-side prompt: _release_device_memory between probes deletes every
    # live device array, so each probe materializes its own device copy
    tokens_np = rs.randint(0, model.cfg.vocab_size, (B, prompt_len)).astype(np.int32)

    def measure(kv_dtype, tight, bounded):
        config = {"dtype": "bfloat16", "kv_cache_dtype": kv_dtype,
                  "kv_tight_read": tight}
        if bounded:
            config["max_out_tokens"] = cache_len
        engine = deepspeed_tpu.init_inference(model, config=config)
        alloc = cache_len if bounded else model.cfg.max_seq_len
        dt = _decode_window(engine, jnp.asarray(tokens_np), new_tokens)
        kv_per_tok = decode_kv_bytes(
            engine.cfg, prompt_len, new_tokens, alloc,
            engine.config.kv_read_floor if tight else None) / decoded
        return dt, kv_per_tok, alloc

    device_kind = jax.devices()[0].device_kind
    variants = [("model", False, True), ("model", True, False),
                ("int8", True, True)]
    cached = None if (_SMOKE or os.environ.get("DSTPU_BENCH_NOCACHE") == "1") \
        else _cached_decode_winner(device_kind)
    candidates = [cached] if cached is not None else variants
    probes, best = {}, None

    def _probe(cand_list):
        nonlocal best
        for kv_dtype, tight, bounded in cand_list:
            key = (f"kv-{kv_dtype}{'+tight' if tight else ''}"
                   f"@{cache_len if bounded else model.cfg.max_seq_len}")
            if key in probes:
                continue  # the failed cached winner is already recorded
            try:
                dt, kv_per_tok, _ = measure(kv_dtype, tight, bounded)
                tok_s = B * decoded / dt
                bw = (tok_s / B) * (weight_bytes + kv_per_tok)
                probes[key] = {
                    "tokens_per_sec": round(tok_s, 1),
                    "kv_bytes_per_token": round(kv_per_tok, 1),
                    "roofline_util": round(bw / peak_bw(), 4),
                }
                if best is None or tok_s > best[0]:
                    best = (tok_s, dt, kv_per_tok, kv_dtype, tight, bounded)
            except Exception as e:
                probes[key] = f"{type(e).__name__}: {e}"[:200]
            _release_device_memory()

    _probe(candidates)
    if best is None and cached is not None:
        # the cached winner failed (code drift the digest missed a
        # dependency of, OOM after topology change): re-probe from scratch
        _probe(variants)
        candidates = variants
    assert best is not None, f"every decode cache config failed: {probes}"
    tok_s, dt, kv_per_tok, kv_dtype, tight, bounded = best
    if len(candidates) > 1 and not _SMOKE:
        _save_decode_winner(device_kind, kv_dtype, tight, bounded)

    # bandwidth roofline: every decoded token streams all weights once plus
    # its KV-cache read; vs_baseline stays the weights-only utilization for
    # trend continuity with earlier rounds
    achieved_bw = (tok_s / B) * weight_bytes

    # A/B: REAL-int8 weight storage (W8A8 MXU path) on the winning cache
    # config — decode is bandwidth-bound, so int8 weights push toward 2x
    extra_int8 = {}
    try:
        cfg8 = {"dtype": "int8", "kv_cache_dtype": kv_dtype,
                "kv_tight_read": tight}
        if bounded:
            cfg8["max_out_tokens"] = cache_len
        eng8 = deepspeed_tpu.init_inference(model, config=cfg8)
        dt8 = _decode_window(eng8, jnp.asarray(tokens_np), new_tokens)
        extra_int8 = {
            "int8_tokens_per_sec": round(B * decoded / dt8, 1),
            "int8_speedup": round(dt / dt8, 3),
        }
    except Exception as e:
        extra_int8 = {"int8_error": f"{type(e).__name__}: {e}"[:200]}

    # speculative-generate self-tune: probe the draft length (gamma) for
    # the lossless draft-model path on the winning cache config — a
    # truncated-depth draft of the same preset proposes gamma tokens per
    # round, the target verifies them in one forward. Winner persisted
    # per device kind like the cache-geometry winner; a probe failure
    # records its error and never fails the bench.
    spec_probes, spec_winner = {}, None
    try:
        if _SMOKE:
            draft_model = _smoke_model(64, num_layers=1)
        else:
            draft_model = TransformerModel.from_preset(
                "gpt2-350m", dtype="bfloat16", max_seq_len=1024,
                num_layers=4)
        cached_spec = None if (_SMOKE or os.environ.get(
            "DSTPU_BENCH_NOCACHE") == "1") else _cached_spec_decode(device_kind)
        spec_gammas = ([2] if _SMOKE else [2, 4, 8]) \
            if cached_spec is None else [cached_spec]
        for g in spec_gammas:
            try:
                cfg_s = {"dtype": "bfloat16", "kv_cache_dtype": kv_dtype,
                         "kv_tight_read": tight,
                         "speculative": {"enabled": True, "mode": "draft",
                                         "num_draft_tokens": g}}
                if bounded:
                    cfg_s["max_out_tokens"] = cache_len
                eng_s = deepspeed_tpu.init_inference(
                    model, config=cfg_s, draft_model=draft_model)
                dt_s = _decode_window(eng_s, jnp.asarray(tokens_np),
                                      new_tokens)
                tok_s_g = B * decoded / dt_s
                spec_probes[f"draft@g{g}"] = {
                    "tokens_per_sec": round(tok_s_g, 1),
                    "speedup_vs_plain": round(tok_s_g / tok_s, 3)}
                if spec_winner is None or tok_s_g > spec_winner[1]:
                    spec_winner = (g, tok_s_g)
            except Exception as e:
                spec_probes[f"draft@g{g}"] = f"{type(e).__name__}: {e}"[:200]
            _release_device_memory()
        if spec_winner is not None and len(spec_gammas) > 1 and not _SMOKE:
            _save_spec_decode(device_kind, spec_winner[0])
    except Exception as e:
        spec_probes["error"] = f"{type(e).__name__}: {e}"[:200]

    return {
        "metric": "gpt2_350m_decode_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(achieved_bw / peak_bw(), 4),
        "extra": {
            "batch": B,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "ms_per_step": round(dt / decoded * 1e3, 2),
            "roofline_gbps": round(achieved_bw / 1e9, 1),
            "roofline_util_with_kv": round(
                ((tok_s / B) * (weight_bytes + kv_per_tok)) / peak_bw(), 4),
            "kv_cache_dtype": kv_dtype,
            "kv_tight_read": tight,
            "cache_len": cache_len if bounded else model.cfg.max_seq_len,
            "kv_bytes_per_token": round(kv_per_tok, 1),
            "probes": probes,
            "speculative": {
                "probes": spec_probes,
                **({"gamma": spec_winner[0], "mode": "draft",
                    "tokens_per_sec": round(spec_winner[1], 1)}
                   if spec_winner else {}),
            },
            **extra_int8,
        },
    }


def bench_serving():
    """Continuous-batching serving throughput: varied-length requests flow
    through a fixed slot pool with burst decode ticks — the serving story
    the reference's static-batch generate cannot express (vs_baseline null:
    beyond-reference feature, tracked for trend). Self-tuning like the
    decode bench: a sync (pipeline_depth=0) vs dispatch-pipelined
    (depth=1) A/B picks the headline config, the winner is cached per
    device kind in .bench_winner.json, and ``extra`` carries both sides'
    tokens/s plus the host dispatch/block breakdown."""
    import deepspeed_tpu
    from deepspeed_tpu.inference import ContinuousBatchingEngine
    from deepspeed_tpu.models.transformer import TransformerModel

    if _SMOKE:
        model = _smoke_model(64)
        slots, cache_len, burst = 2, 48, 2
        arrivals = [(0, 5, 6), (0, 9, 6), (1, 3, 6), (2, 7, 6)]
    else:
        model = TransformerModel.from_preset("gpt2-125m", dtype="bfloat16",
                                             max_seq_len=1024)
        slots, cache_len, burst = 8, 256, 4
        rs = np.random.RandomState(7)
        # 32 requests, prompts 32-128, 64 new tokens each; a few arrive per
        # tick so the pool runs at high occupancy with churn
        arrivals = [(t // 2, int(rs.randint(32, 129)), 64) for t in range(32)]

    t_phase0 = time.time()
    budget_s = int(os.environ.get("DSTPU_BENCH_PHASE_BUDGET", "240"))
    rs = np.random.RandomState(0)
    # host-side prompts: _release_device_memory between speculative probes
    # deletes every live device array, so a device-resident queue would
    # arrive dead at the second probe; submit() canonicalizes via np.asarray
    queue = [(t, rs.randint(0, model.cfg.vocab_size, (n,)).astype(np.int32), new)
             for t, n, new in arrivals]

    from deepspeed_tpu.inference.continuous import _bucket

    def build_engine(tensor):
        """One serving engine on a ("data","tensor") mesh of the given
        tensor width (1 = the incumbent default mesh), warmed: the FULL
        tick family (every read-bucket/chunk variant the A/B runs could
        dispatch — a partial warm would bill the stragglers to whichever
        side runs first) plus one driven request per prompt bucket for
        the admission prefill/splice programs."""
        cfg = {"dtype": model.cfg.dtype}
        if tensor > 1:
            cfg["mesh"] = {"shape": {"data": 1, "tensor": tensor}}
        eng = ContinuousBatchingEngine(
            model, config=cfg, max_slots=slots,
            cache_len=cache_len, tokens_per_tick=burst)
        eng.precompile_tick_programs()
        for b in sorted({_bucket(int(p.size), cache_len) for _, p, _ in queue}):
            eng.submit(jnp.zeros((b,), jnp.int32), max_new_tokens=4)
        while eng.has_work():
            eng.step()
        eng.finished()
        return eng

    device_kind = jax.devices()[0].device_kind
    nocache = _SMOKE or os.environ.get("DSTPU_BENCH_NOCACHE") == "1"
    # tensor-width sweep (MULTICHIP numbers): power-of-2 widths that fit
    # the host and divide the model's q AND kv heads — the serving column
    # self-tunes its mesh exactly like the PR 3/5 geometry/depth sweeps.
    # The cached width winner short-circuits to one engine build.
    widths = [1]
    if not _SMOKE:
        w = 2
        while (w <= jax.device_count() and model.cfg.num_heads % w == 0
               and model.cfg.kv_heads % w == 0):
            widths.append(w)
            w *= 2
    cached_width = None if nocache else _cached_serving_width(device_kind)
    if cached_width in widths and len(widths) > 1:
        widths = [cached_width]

    engine = build_engine(widths[0])
    warm_s = time.time() - t_phase0
    _progress(f"serving warmup (engine + bucket compiles) done in {warm_s:.1f}s")
    if budget_s - warm_s < 30:
        # compiles ate the cap: report WHERE the time went instead of
        # letting the parent SIGKILL a half-measured loop
        return {
            "metric": "bench_serving_skipped",
            "value": None, "unit": None, "vs_baseline": None,
            "extra": {"reason": "warmup compiles exhausted the phase budget",
                      "warmup_s": round(warm_s, 1), "budget_s": budget_s},
        }

    def run_serve(depth):
        """One full replay of the arrival schedule at a pipeline depth
        (a host-loop knob: same compiled programs, so flipping it between
        runs recompiles nothing). Returns the throughput + host stats."""
        engine.pipeline_depth = depth
        stats0 = dict(engine._tick_stats)
        t0 = time.time()
        tick, done_tokens, completed = 0, 0, 0
        pending = list(queue)
        while pending or engine.has_work():
            for item in [it for it in pending if it[0] <= tick]:
                engine.submit(item[1], max_new_tokens=item[2])
            pending = [it for it in pending if it[0] > tick]
            emitted = engine.step()
            done_tokens += sum(len(v) for v in emitted.values())
            completed += len(engine.finished())
            tick += 1
        dt = max(time.time() - t0, 1e-9)
        stats1 = engine._tick_stats
        block = stats1["block_ms"] - stats0["block_ms"]
        dispatch = stats1["dispatch_ms"] - stats0["dispatch_ms"]
        host = dispatch + block
        return {
            "tokens_per_sec": round(done_tokens / dt, 1),
            "completed": completed,
            "ticks": tick,
            "wall_s": round(dt, 2),
            "tick_dispatch_ms": round(dispatch, 1),
            "tick_block_ms": round(block, 1),
            "block_ms_per_token": (round(block / done_tokens, 4)
                                   if done_tokens else None),
            "overlap_frac": round(1.0 - block / host, 4) if host > 0 else None,
        }

    def tune_depth(tensor):
        """Depth A/B (or its cached winner) for ONE serving mesh; the
        winner is cached PER MESH — a depth probed single-chip is never
        replayed on a sharded tick chain."""
        mesh_shape = {"data": 1, "tensor": tensor}
        cached_depth = (None if nocache
                        else _cached_serving_depth(device_kind, mesh_shape))
        if cached_depth is not None:
            side = run_serve(cached_depth)
            return {"pipeline_depth": cached_depth, "ab": "cached", **side}
        sync = run_serve(0)
        piped = run_serve(1)
        winner_depth = 1 if piped["tokens_per_sec"] >= sync["tokens_per_sec"] else 0
        side = piped if winner_depth else sync
        if not _SMOKE:
            _save_serving_depth(device_kind, winner_depth, mesh_shape)
        return {"pipeline_depth": winner_depth,
                "ab": {"sync": sync, "pipelined": piped}, **side}

    sweep = {}
    swept_all = True
    for t in widths:
        if engine is None:
            if time.time() - t_phase0 > budget_s - 60:
                swept_all = False  # out of budget: keep what we measured
                _progress(f"serving mesh sweep stopped before 1x{t} "
                          f"(phase budget)")
                break
            engine = build_engine(t)
        sweep[f"1x{t}"] = tune_depth(t)
        engine = None  # free the width's params/caches before the next
    best_key = max(sweep, key=lambda k: sweep[k]["tokens_per_sec"])
    best = sweep[best_key]
    best_tensor = int(best_key.split("x")[1])
    if not _SMOKE and swept_all and len(sweep) > 1:
        _save_serving_width(device_kind, best_tensor)

    # speculative pooled-tick self-tune (docs/inference.md "Speculative
    # decoding"): replay the same arrival schedule through a speculative
    # pool — ngram self-drafting at gamma 2/4/8, then the draft-model
    # mode at the best ngram gamma — and persist the winning (gamma,
    # mode) per device kind. The probe list is bounded (<=4), budget-
    # checked like the mesh sweep, and a probe failure records its error
    # without failing the bench.
    def run_spec(gamma, mode, draft_kw):
        cfg = {"dtype": model.cfg.dtype,
               "speculative": {"enabled": True, "pool": True, "mode": mode,
                               "num_draft_tokens": gamma}}
        eng = ContinuousBatchingEngine(
            model, config=cfg, max_slots=slots, cache_len=cache_len,
            tokens_per_tick=1, **draft_kw)
        # warm like build_engine: the spec tick family per read bucket
        # plus one driven request per prompt bucket, so the timed replay
        # measures ticks, not compiles
        eng.precompile_tick_programs()
        for b in sorted({_bucket(int(p.size), cache_len) for _, p, _ in queue}):
            eng.submit(jnp.zeros((b,), jnp.int32), max_new_tokens=4)
        while eng.has_work():
            eng.step()
        eng.finished()
        t0 = time.time()
        tick, done_tokens = 0, 0
        pending = list(queue)
        while pending or eng.has_work():
            for item in [it for it in pending if it[0] <= tick]:
                eng.submit(item[1], max_new_tokens=item[2])
            pending = [it for it in pending if it[0] > tick]
            emitted = eng.step()
            done_tokens += sum(len(v) for v in emitted.values())
            eng.finished()
            tick += 1
        dt = max(time.time() - t0, 1e-9)
        st = eng.tick_stats()
        return {"tokens_per_sec": round(done_tokens / dt, 1),
                "acceptance": st.get("spec_acceptance")}

    spec_probes, spec_winner, spec_all = {}, None, True
    try:
        if _SMOKE:
            draft_model = _smoke_model(64, num_layers=1)
        else:
            from deepspeed_tpu.models.transformer import TransformerModel
            draft_model = TransformerModel.from_preset(
                "gpt2-125m", dtype="bfloat16", max_seq_len=1024,
                num_layers=3)
        def draft_kw():
            # fresh params per probe: _release_device_memory between
            # probes deletes every live device array, a pre-built tree
            # would arrive dead at the second build
            return dict(draft_model=draft_model,
                        draft_params=draft_model.init(jax.random.PRNGKey(1)))

        cached_spec = None if nocache else _cached_spec_serving(device_kind)
        if cached_spec is not None:
            plan = [cached_spec]
        else:
            gammas = [2] if _SMOKE else [2, 4, 8]
            plan = [(g, "ngram") for g in gammas]  # draft appended below
        while plan:
            gamma, mode = plan.pop(0)
            if time.time() - t_phase0 > budget_s - 60:
                spec_all = False
                _progress(f"speculative probe stopped before "
                          f"{mode}@g{gamma} (phase budget)")
                break
            try:
                side = run_spec(gamma, mode,
                                draft_kw() if mode == "draft" else {})
                spec_probes[f"{mode}@g{gamma}"] = side
                if spec_winner is None or \
                        side["tokens_per_sec"] > spec_winner[2]["tokens_per_sec"]:
                    spec_winner = (gamma, mode, side)
            except Exception as e:
                spec_probes[f"{mode}@g{gamma}"] = \
                    f"{type(e).__name__}: {e}"[:200]
            _release_device_memory()
            if not plan and mode == "ngram" and spec_winner is not None \
                    and cached_spec is None:
                # mode axis: one draft-model probe at the best ngram gamma
                plan.append((spec_winner[0], "draft"))
        if spec_winner is not None and spec_all and cached_spec is None \
                and not _SMOKE:
            _save_spec_serving(device_kind, spec_winner[0], spec_winner[1])
    except Exception as e:
        spec_probes["error"] = f"{type(e).__name__}: {e}"[:200]

    extra = {
        "requests": len(arrivals),
        "slots": slots,
        "cache_len": cache_len,
        "tokens_per_tick": burst,
        "mesh": {"data": 1, "tensor": best_tensor},
        "mesh_sweep": sweep,
        "mesh_sweep_complete": swept_all,
        "speculative": {
            "probes": spec_probes,
            "complete": spec_all,
            **({"gamma": spec_winner[0], "mode": spec_winner[1],
                "tokens_per_sec": spec_winner[2]["tokens_per_sec"],
                "acceptance": spec_winner[2]["acceptance"],
                "speedup_vs_plain": round(
                    spec_winner[2]["tokens_per_sec"]
                    / max(best["tokens_per_sec"], 1e-9), 3)}
               if spec_winner else {}),
        },
        **best,
    }
    return {
        "metric": "serving_continuous_tokens_per_sec",
        "value": best["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": None,
        "extra": extra,
    }


def bench_hybrid_rlhf():
    """RLHF hybrid-engine roundtrip: generate (rollout) + train step on the
    same weights (BASELINE.json tracked config class; reference
    DeepSpeed-Chat loop, hybrid_engine.py:168)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import TransformerModel

    seq, gen_tokens, micro_bs = (32, 8, 2) if _SMOKE else (256, 128, 4)
    if _SMOKE:
        model = _smoke_model(64)
    else:
        model = TransformerModel.from_preset(
            "gpt2-125m", dtype="bfloat16", remat=True, remat_policy="dots_saveable", max_seq_len=1024
        )
    config = {
        "train_micro_batch_size_per_gpu": micro_bs,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-5}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "hybrid_engine": {"enabled": True},
        "steps_per_print": 1000000,
        "mesh": {"data": -1},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    rs = np.random.RandomState(0)
    n_dev = jax.device_count()
    prompts = jnp.asarray(rs.randint(0, model.cfg.vocab_size, (micro_bs * n_dev, seq)), jnp.int32)

    def roundtrip():
        rollout = engine.generate(prompts, max_new_tokens=gen_tokens)
        batch = {"input_ids": np.asarray(rollout)}
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        return loss

    loss = roundtrip()  # compile both programs
    _sync(engine, loss)
    iters = 2 if _SMOKE else 5
    t0 = time.time()
    for _ in range(iters):
        loss = roundtrip()
    _sync(engine, loss)
    dt = (time.time() - t0) / iters
    # end-to-end RLHF tokens/s: generated tokens pushed through rollout+train
    tok_s = micro_bs * n_dev * gen_tokens / dt
    return {
        "metric": "rlhf_hybrid_rollout_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": None,  # reference reports wall-clock-to-train, not tok/s
        "extra": {
            "roundtrip_ms": round(dt * 1e3, 1),
            "prompt_len": seq,
            "gen_tokens": gen_tokens,
            "micro_bs": micro_bs,
            "loss": float(loss),
        },
    }


def bench_bert_mlm():
    """BERT-large MLM pretrain throughput — the reference's headline bench
    (docs/_posts/2020-05-28-fastest-bert-training.md: 64 TFLOPS/V100 @ seq
    128, 52% of peak per 2020-05-19-bert-record.md). Same task shape: seq
    128, 15% tokens masked, samples/s + achieved TFLOPS per chip."""
    from deepspeed_tpu.models.transformer import TransformerModel

    seq = 64 if _SMOKE else 128
    pinned_bs = os.environ.get("DSTPU_BENCH_BERT_BS")
    # r5 on-chip: bs 64 without remat needs 18.99 GB > 15.75 GB HBM (AOT
    # compile OOM) — fall back through remat, then smaller batch, instead
    # of dying without a number
    attempts = ([(4, False)] if _SMOKE else
                [(int(pinned_bs), False), (int(pinned_bs), True)] if pinned_bs else
                [(64, False), (64, True), (32, True)])
    last_err = None
    for micro_bs, remat in attempts:
        if _SMOKE:
            model = _smoke_model(seq, causal=False, norm_position="post", type_vocab_size=2,
                                 embed_norm=True)
        else:
            model = TransformerModel.from_preset(
                "bert-large", dtype="bfloat16", max_seq_len=seq,
                remat=remat, remat_policy="dots_saveable")
        config = {
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 0},
            "steps_per_print": 1000000,
            "mesh": {"data": -1},
        }
        rs = np.random.RandomState(0)
        n_dev = jax.device_count()
        B = micro_bs * n_dev
        ids = rs.randint(0, model.cfg.vocab_size, (B, seq)).astype(np.int32)
        mask = (rs.rand(B, seq) < 0.15).astype(np.float32)
        masked = np.where(mask > 0, 103, ids).astype(np.int32)  # [MASK] id
        batch = {"input_ids": masked, "labels": ids, "loss_mask": mask,
                 "token_type_ids": np.zeros((B, seq), np.int32)}
        try:
            toks, dt, loss, _ = _train_bench(model, config, micro_bs, seq,
                                             iters=2 if _SMOKE else 20, batch=batch)
            break
        except Exception as e:
            last_err = f"bs{micro_bs}{'+remat' if remat else ''}: {type(e).__name__}: {e}"[:200]
            _release_device_memory()
    else:
        raise RuntimeError(f"every bert config failed; last: {last_err}")
    samples = toks / seq  # per chip
    flops_per_sample = model.cfg.flops_per_token(seq) * seq
    mfu = samples * flops_per_sample / peak_flops()
    return {
        "metric": "bert_large_mlm_samples_per_sec_per_chip",
        "value": round(samples, 1),
        "unit": "samples/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "tflops_per_chip": round(samples * flops_per_sample / 1e12, 1),
            "seq_len": seq,
            "micro_bs": micro_bs,
            "remat": remat,
            "step_ms": round(dt * 1e3, 2),
            "loss": float(loss),
            "reference": "64 TFLOPS/V100 (52% peak) seq128",
        },
    }


def _gpt2_model(seq, attn, remat, block=None, remat_policy="dots_saveable"):
    from deepspeed_tpu.models.transformer import TransformerModel

    kw = dict(dtype="bfloat16", remat=remat, remat_policy=remat_policy,
              max_seq_len=seq, attn_impl=attn, flash_block=block)
    if _SMOKE:
        return _smoke_model(seq, **{k: v for k, v in kw.items() if k != "max_seq_len"})
    return TransformerModel.from_preset("gpt2-125m", **kw)


def _gpt2_config(micro_bs):
    return {
        "train_micro_batch_size_per_gpu": micro_bs,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 1000000,
        "mesh": {"data": -1},
    }


_WINNER_CACHE = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".bench_winner.json")


def _bench_digest():
    """Cache-invalidation key: the probe winner is only valid for the code
    that produced it — digest this file + the kernels/model the candidates
    exercise, so any perf-relevant change re-probes."""
    import hashlib

    root = os.path.dirname(os.path.abspath(__file__))
    h = hashlib.sha256()
    for rel in ("_bench_impl.py", "deepspeed_tpu/ops/pallas/flash_attention.py",
                "deepspeed_tpu/models/transformer.py", "deepspeed_tpu/runtime/engine.py",
                "deepspeed_tpu/inference/decoding.py",
                "deepspeed_tpu/inference/continuous.py",
                "deepspeed_tpu/parallel/partition.py",
                # ds-audit pins the program contracts the bench candidates
                # compile under (donation, collective inventory); a contract
                # or capture change can alter the compiled programs the
                # winner was probed on — re-probe rather than replay stale
                "deepspeed_tpu/analysis/program/contracts.py",
                "deepspeed_tpu/analysis/program/capture.py",
                "deepspeed_tpu/analysis/program/families.py",
                # ds-perf: the peaks table feeds the MFU column and the
                # inventory fingerprint pins the compiled-program shape
                "deepspeed_tpu/analysis/program/costmodel.py",
                "deepspeed_tpu/analysis/program/inventory.py"):
        try:
            with open(os.path.join(root, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:16]


def _winner_key(device_kind):
    # keyed by device kind AND count (ADVICE r3: a winner probed on a
    # many-chip host — e.g. bs16 no-remat — can OOM replayed single-chip)
    return f"{device_kind}/n{jax.device_count()}"


def _winner_cache_get(key):
    """ONE digest-checked reader for every .bench_winner.json entry family
    (train/decode/serving); None on miss, stale digest, or corrupt file."""
    try:
        with open(_WINNER_CACHE) as f:
            entry = json.load(f).get(key)
        if entry and entry.get("digest") == _bench_digest():
            return entry
    except Exception:
        pass
    return None


def _winner_cache_put(key, entry):
    """Merge one digest-stamped entry into .bench_winner.json; best-effort
    (a read-only filesystem must never fail the bench)."""
    try:
        cache = {}
        if os.path.exists(_WINNER_CACHE):
            with open(_WINNER_CACHE) as f:
                cache = json.load(f)
        cache[key] = {**entry, "digest": _bench_digest()}
        with open(_WINNER_CACHE, "w") as f:
            json.dump(cache, f)
    except Exception:
        pass


def _cached_winner(device_kind):
    entry = _winner_cache_get(_winner_key(device_kind))
    if entry is not None:
        return entry["attn"], entry["remat"], entry["bs"], entry.get("block")
    return None


def _save_winner(device_kind, attn, remat, bs, block=None):
    _winner_cache_put(_winner_key(device_kind),
                      {"attn": attn, "remat": remat, "bs": bs, "block": block})


def _serving_winner_key(device_kind, mesh_shape):
    """Serving winners are keyed by the SERVING MESH as well as the device
    kind/count: a pipeline depth probed single-chip says nothing about the
    sharded tick chain (collectives sit on the dispatch path), so a
    ``mesh1x1`` winner must never be replayed on a ``mesh1x4`` serve."""
    d = int(mesh_shape.get("data", 1))
    t = int(mesh_shape.get("tensor", 1))
    return f"serving/{_winner_key(device_kind)}/mesh{d}x{t}"


def _cached_serving_depth(device_kind, mesh_shape=None):
    """Serving-bench winner (pipeline depth of the sync-vs-pipelined A/B)
    for one serving mesh, cached alongside the decode winner under a
    ``serving/`` key and digest-invalidated the same way."""
    entry = _winner_cache_get(
        _serving_winner_key(device_kind, mesh_shape or {}))
    return int(entry["pipeline_depth"]) if entry is not None else None


def _save_serving_depth(device_kind, depth, mesh_shape=None):
    _winner_cache_put(_serving_winner_key(device_kind, mesh_shape or {}),
                      {"pipeline_depth": int(depth)})


def _cached_serving_width(device_kind):
    """Tensor-width winner of the bench_serving mesh sweep (None = never
    swept on this host/digest)."""
    entry = _winner_cache_get(f"serving_mesh/{_winner_key(device_kind)}")
    return int(entry["tensor"]) if entry is not None else None


def _save_serving_width(device_kind, tensor):
    _winner_cache_put(f"serving_mesh/{_winner_key(device_kind)}",
                      {"tensor": int(tensor)})


def _cached_spec_serving(device_kind):
    """(gamma, mode) winner of the bench_serving speculative probe —
    draft length and ngram-vs-draft mode for pooled speculative ticks
    (docs/inference.md "Speculative decoding"); digest-invalidated like
    every other winner (decoding.py/continuous.py are in the digest)."""
    entry = _winner_cache_get(f"spec/{_winner_key(device_kind)}")
    if entry is not None:
        return int(entry["gamma"]), str(entry["mode"])
    return None


def _save_spec_serving(device_kind, gamma, mode):
    _winner_cache_put(f"spec/{_winner_key(device_kind)}",
                      {"gamma": int(gamma), "mode": str(mode)})


def _cached_spec_decode(device_kind):
    """Gamma winner of the bench_decode speculative-generate probe (the
    single-request draft-model path; ngram self-drafting has no
    engine.generate path, so the mode axis lives in the serving probe)."""
    entry = _winner_cache_get(f"spec_decode/{_winner_key(device_kind)}")
    return int(entry["gamma"]) if entry is not None else None


def _save_spec_decode(device_kind, gamma):
    _winner_cache_put(f"spec_decode/{_winner_key(device_kind)}",
                      {"gamma": int(gamma), "mode": "draft"})


def bench_gpt2_train():
    """Headline bench, SELF-TUNING: unless DSTPU_BENCH_ATTN pins a config,
    briefly probe ≤6 candidate attention/remat/micro-batch configs (PERF.md
    sweep: attention softmax HBM traffic + the dots_saveable remat stash are
    the two dominant costs; the Pallas flash kernel removes both) and run
    the full measurement on the winner. The winner is cached per device
    kind in .bench_winner.json (untracked) so later runs skip the probes
    (VERDICT r2 #1: bounded probe list). A failing candidate (e.g. OOM at
    no-remat) is printed with its traceback and recorded under
    ``probes``; if every candidate fails the phase fails."""
    seq = 64 if _SMOKE else 1024
    pinned_attn = os.environ.get("DSTPU_BENCH_ATTN")
    pinned_remat = os.environ.get("DSTPU_BENCH_REMAT")
    pinned_bs = os.environ.get("DSTPU_BENCH_BS")
    pinned_block = os.environ.get("DSTPU_BENCH_FLASH_BLOCK")
    default_bs = 2 if _SMOKE else 8
    device_kind = jax.devices()[0].device_kind
    cached = None if (pinned_attn or pinned_remat or pinned_bs or _SMOKE
                      or os.environ.get("DSTPU_BENCH_NOCACHE") == "1") else _cached_winner(device_kind)
    # PERF.md sweep: flash kernel (no softmax HBM traffic, no 2.4 GB remat
    # stash) at bs 8/16/32 and the silicon-tuned auto tile (None -> 512)
    # vs a pinned 256. bs32 OOM'd with xla attention (r1); with flash
    # no-remat the residuals are ~0.15 GB/layer so it should fit — a
    # failing candidate prints and records its error and the sweep moves on.
    sweep = [
        ("xla", True, 8, None),
        ("pallas", False, 8, None),   # flash frees the logits stash: no-remat may fit
        ("pallas", False, 8, 256),
        ("pallas", False, 16, None),
        # bs16 at the auto tile (512) did not compile on 2026-07-31
        # (compile-side OOM); smaller tiles shrink Mosaic's compile
        # footprint, so bs16 gets a second candidate
        ("pallas", False, 16, 256),
        ("pallas", False, 32, None),  # biggest per-core tiles (MXU efficiency)
    ]
    if pinned_attn or pinned_remat or _SMOKE:
        # any explicit A/B pin disables self-tuning for that axis
        attn = pinned_attn or "xla"
        remat = (pinned_remat or "1") == "1"
        candidates = [(attn, remat, int(pinned_bs or default_bs),
                       int(pinned_block) if pinned_block else None)]
    elif cached is not None:
        candidates = [cached]
    else:
        candidates = sweep
        if pinned_bs:
            candidates = list(dict.fromkeys(
                (a, r, int(pinned_bs), blk) for a, r, _, blk in candidates))

    probes = {}
    best = None

    def _probe(cand_list, iters):
        nonlocal best
        for attn, remat, bs, blk in cand_list:
            key = f"{attn}{'+remat' if remat else ''}{f'+blk{blk}' if blk else ''}@bs{bs}"
            try:
                toks, dt, loss, _ = _train_bench(
                    _gpt2_model(seq, attn, remat, blk), _gpt2_config(bs), bs, seq,
                    iters=iters)
                probes[key] = round(toks, 1)
                if best is None or toks > best[0]:
                    best = (toks, dt, loss, attn, remat, bs, blk)
            except Exception as e:  # noqa: BLE001 — sweep boundary: report, go on
                _progress(f"candidate {key} FAILED:\n{traceback.format_exc()}")
                probes[key] = f"{type(e).__name__}: {e}"[:160]
            # probe HBM must not leak into the next probe, the fallback
            # sweep after a failed cached winner, or the winner re-measure
            _release_device_memory()

    _probe(candidates, iters=(2 if _SMOKE else 20) if len(candidates) == 1 else 5)
    if best is None and cached is not None:
        # the cached winner failed (e.g. OOM after a topology change that
        # the key didn't capture): drop it and re-probe from scratch
        _probe(sweep, iters=5)
        candidates = [None, None]  # >1 → triggers the full winner re-measurement below
    assert best is not None, f"every bench candidate failed: {probes}"
    toks, dt, loss, attn, remat, bs, blk = best
    if len(candidates) > 1:
        # full measurement on the winning config
        toks, dt, loss, _ = _train_bench(
            _gpt2_model(seq, attn, remat, blk), _gpt2_config(bs), bs, seq, iters=20)
        _save_winner(device_kind, attn, remat, bs, blk)

    model = _gpt2_model(seq, attn, remat, blk)
    mfu = toks * model.cfg.flops_per_token(seq) / peak_flops()
    return {
        "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
        "value": round(toks, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "mfu": round(mfu, 4),
            "loss": loss,
            "seq_len": seq,
            "micro_bs": bs,
            "attn_impl": attn,
            "remat": remat,
            "flash_block": blk,
            "probes": probes,
            "n_devices": jax.device_count(),
            "device_kind": jax.devices()[0].device_kind,
            "step_ms": round(dt * 1e3, 2),
        },
    }


def bench_probe():
    """First device contact + a tiny matmul, in its own child before
    anything else: names the platform, device kind and count the run
    sees. The parent refuses to go on unless the platform is ``tpu``."""
    t0 = time.time()
    devs = jax.devices()
    t_devices = time.time() - t0
    x = jnp.ones((256, 256), jnp.bfloat16)
    val = float((x @ x).sum())
    return {
        "metric": "bench_probe_ok",
        "value": round(time.time() - t0, 1),
        "unit": "s",
        "vs_baseline": None,
        "extra": {
            "device_kind": devs[0].device_kind,
            "n_devices": len(devs),
            "platform": devs[0].platform,
            "devices_s": round(t_devices, 1),
            "matmul_checksum": val,
        },
    }


def _zero3_offload_with_parent_budget():
    # the parent tells the child its actual kill deadline so the
    # bandwidth pre-probe sizes against the real budget, not a constant
    budget = int(os.environ.get("DSTPU_BENCH_PHASE_BUDGET", "240"))
    return bench_zero3_offload(budget_s=budget)


PHASES = {
    "probe": bench_probe,
    "primary": bench_gpt2_train,
    "decode": bench_decode,
    "long_ctx": bench_long_ctx,
    "serving": bench_serving,
    "bert_mlm": bench_bert_mlm,
    "moe_ep": bench_moe_ep,
    "hybrid_rlhf": bench_hybrid_rlhf,
    "zero3_offload": _zero3_offload_with_parent_budget,
}

RESULT_SENTINEL = "DSTPU_RESULT "


def run_phase(name: str) -> int:
    # Persistent XLA compile cache: the self-tune probes, the winner's final
    # measurement and every later child on unchanged code reuse executables.
    # Placed by the repo's one rule (JAX_COMPILATION_CACHE_DIR, else
    # <repo>/.jax_cache); a failure to set it up is an error, not a skip.
    configure_compile_cache()
    result = PHASES[name]()
    print(RESULT_SENTINEL + json.dumps(result), flush=True)
    return 0
