"""Does the system still start on the chip?  One process, the normal entry
points, gpt2-350m at its published width and depth, weights from a seed.

    python chip_smoke.py             # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 training and
                                     # tensor-parallel serving, each against
                                     # its one-chip run, and nothing else

*train*: ``deepspeed_tpu.initialize`` -> ``engine.forward/backward/step`` for
six steps on one fixed batch (seq 1024, bf16, AdamW, flash attention, remat).
*serve*: ``deepspeed_tpu.init_inference`` -> ``ContinuousBatchingEngine`` ->
``serving.ServingEngine``; eight greedy requests through ``submit()`` /
``stream()``, again on a second engine at ``pipeline_depth=0``, every
emitted token held to a plain float32 ``jax.numpy`` forward written here,
and the same tokens shown to FAIL that check against a context the engine
did not see (prompt permuted, prompt one position early).

Each phase prints one JSON line of plain observations (seconds, bytes,
losses, counts — no rate is a result). Any failed check, any exception, or
a platform other than ``tpu`` exits non-zero before the last line, which is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--rehearse`` swaps in a toy model and accepts whatever platform JAX
finds: it rehearses this script's control flow on the CPU (see
tests/unit/test_chip_smoke.py) and proves nothing about a chip — its last
line names the platform it ran on.
"""

import argparse
import gc
import json
import math
import sys
import time
from importlib import metadata

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.request import FINISHED
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

# Stated before any run. MARGIN: how far below the float32 reference's top
# logit the reference logit of an emitted token may sit. The engine computes
# in bf16 (8-bit mantissa) through 24 layers and rounds its logits to bf16
# (spacing 2^-6 near the top logit of ~3 that seed-made weights give), so
# near-ties resolve differently; a token picked from the wrong position or
# a corrupted cache sits whole units below the top instead.
MARGIN = 0.25
# Seed weights at the model's own init scale give a next token that ignores
# its context: attention averages every position into a vector the residual
# stream drowns, each request repeats one token (9 distinct tokens in 512,
# first chip run), and a stale or shifted KV cache would pass every check
# below. So the serve phase scales the seed's queries by this much (softmax
# then picks positions instead of averaging them) and undoes init's
# 1/sqrt(2L) on the attention output: which tokens sit where now decides the
# argmax. Not more: from about 5 on, 24 layers of sharp attention amplify
# bf16 rounding until the engine's stream leaves the float32 reference by
# whole logits (measured on the chip; the engine in float32 stays on it).
QUERY_SCALE = 3.0
# That the MARGIN check can fail at all is shown on every run by negative
# controls: the emitted streams, scored against a context the engine did NOT
# see, must sit more than MARGIN below the reference's top logit at this
# share of the positions at least ...
CONTROL_SHARE = 0.5
# ... and the requests must not each repeat one token.
DISTINCT_PER_REQUEST = 3
# Per-step |loss(4 chips, ZeRO-3) - loss(1 chip)|, losses ~10: same math,
# another reduction order and other fusions, in bf16.
LOSS_TOL = 0.05
# A chip of four may hold this many times a quarter of what one chip holds
# alone: leaves no axis divides stay whole on every chip (GPT-2's odd vocab
# table under tensor parallelism, biases and norms under ZeRO-3).
QUARTER_FACTOR = 1.5

REAL = dict(
    model=dict(preset="gpt2-350m"), seq=1024, micro_batch=8, steps=6,
    prompt_lens=(64, 128, 192, 256, 320, 384, 448, 512), new_tokens=64,
    slots=8, cache_len=1024)
TOY = dict(
    model=dict(vocab_size=503, hidden_size=256, num_layers=2, num_heads=4),
    seq=128, micro_batch=8, steps=6, prompt_lens=(8, 24, 40, 64),
    new_tokens=8, slots=4, cache_len=128)


def emit(**fields):
    print(json.dumps(fields), flush=True)


def check(ok, what):
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {what}")


class CompileLog:
    """Counts JAX's own compile events: executables requested (each a
    persistent-cache hit or a compilation), cache hits, seconds spent."""

    def __init__(self):
        self.programs, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def since(self, mark=(0, 0.0, 0)):
        return (self.programs - mark[0], self.seconds - mark[1],
                self.cache_hits - mark[2])

    def fields(self, mark):
        programs, seconds, hits = self.since(mark)
        return dict(programs_compiled_or_loaded=programs, cache_hits=hits,
                    compile_s=round(seconds, 2))


def memory_stat(devices, key):
    """``memory_stats()[key]`` per device, or None where the backend keeps
    no count (CPU)."""
    stats = [d.memory_stats() for d in devices]
    return None if any(s is None for s in stats) else [s[key] for s in stats]


def held_since(devices, base):
    """Per-device growth of ``bytes_in_use`` over ``base``."""
    now = memory_stat(devices, "bytes_in_use")
    return None if now is None else [n - b for n, b in zip(now, base)]


def build_model(size):
    kw = dict(dtype="bfloat16", remat=True, attn_impl="pallas", max_seq_len=size["seq"])
    spec = dict(size["model"])
    if "preset" in spec:
        return TransformerModel.from_preset(spec.pop("preset"), **kw)
    return TransformerModel(TransformerConfig(**spec, **kw))


def sharded_leaves(tree, axis):
    """(path, array) of every leaf whose sharding names mesh axis ``axis``."""
    def names(spec):
        return {a for e in spec if e is not None for a in ((e,) if isinstance(e, str) else e)}

    return [(jax.tree_util.keystr(p), x) for p, x in jax.tree_util.tree_leaves_with_path(tree)
            if axis in names(x.sharding.spec)]


def check_quartered(what, leaves, must_include):
    """Every listed array really is spread: four addressable shards of a
    quarter of the array each, on four distinct devices."""
    check(leaves, f"{what}: nothing is sharded")
    for path, x in leaves:
        shards = x.addressable_shards
        check(len({s.device for s in shards}) == 4 and len(shards) == 4
              and all(s.data.size * 4 == x.size for s in shards),
              f"{what}{path}: not a quarter on each of four devices: "
              f"{[(str(s.device), s.data.shape) for s in shards]}")
    for key in must_include:
        check(any(key in path for path, _ in leaves), f"{what}: {key} is not sharded")


def check_quarter_bytes(what, four, one):
    if four is None:  # backend keeps no count (CPU rehearsal)
        return
    bound = QUARTER_FACTOR * one[0] / 4
    check(all(b <= bound for b in four),
          f"{what}: per-chip bytes {four} exceed {QUARTER_FACTOR} x a quarter of "
          f"the one-chip {one[0]}")


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def run_train(size, n_chips, seed, log, platform):
    comm.destroy()
    devices = jax.devices()[:n_chips]
    mark, t0, base = log.since(), time.time(), memory_stat(devices, "bytes_in_use")
    model = build_model(size)
    config = {
        "train_micro_batch_size_per_gpu": size["micro_batch"] // n_chips,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3 if n_chips > 1 else 0},
        "mesh": {"fsdp": n_chips},
        "steps_per_print": 10 ** 9,
        "seed": seed,
    }
    # a run on fewer chips than the host holds names its devices; the
    # whole-host run takes the mesh from its config like any user's
    mesh = (None if n_chips == jax.device_count()
            else comm.build_mesh(config["mesh"], devices=devices))
    engine = deepspeed_tpu.initialize(model=model, config=config, mesh=mesh)[0]
    jax.block_until_ready((engine.params, engine.master_params, engine.opt_state))
    held = held_since(devices, base)
    leaf = jax.tree.leaves(engine.params)[0]
    check({d.platform for d in leaf.devices()} == {platform},
          f"params live on {leaf.devices()}, not on {platform}")

    batch = {"input_ids": np.random.RandomState(seed).randint(
        0, model.cfg.vocab_size, (size["micro_batch"], size["seq"])).astype(np.int32)}

    # the micro-step program, compiled ahead: what it needs and what is in it
    _, compiled = engine._micro_cost_analysis(engine._shard_batch(batch), jax.random.PRNGKey(0))
    mem = compiled.memory_analysis()
    program_bytes = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    if platform == "tpu":
        check(kernels >= 3, f"flash fwd/dq/dkv Mosaic custom calls missing from the "
                            f"compiled micro-step ({kernels} found): interpreted?")
        limit = devices[0].memory_stats()["bytes_limit"]
        check(program_bytes < limit, f"micro-step needs {program_bytes} of {limit} bytes")

    losses, after_first = [], None
    for _ in range(size["steps"]):
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
        if after_first is None:
            jax.block_until_ready(engine.params)
            after_first, t_first = log.since(), time.time()
    jax.block_until_ready(engine.params)
    t_end = time.time()
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    late = log.since(after_first)[0]
    check(late == 0, f"{late} program(s) compiled after the first step")

    out = dict(
        losses=losses, held=held, engine=engine,
        line=dict(
            phase="train", chips=n_chips, model=size["model"], seq=size["seq"],
            micro_batch=size["micro_batch"], zero_stage=config["zero_optimization"]["stage"],
            steps=size["steps"], loss_first=losses[0], loss_last=losses[-1], losses=losses,
            mosaic_kernels_in_micro_step=kernels,
            micro_step_memory_analysis=dict(
                temp=mem.temp_size_in_bytes, arguments=mem.argument_size_in_bytes,
                outputs=mem.output_size_in_bytes, aliased=mem.alias_size_in_bytes),
            bytes_in_use_after_init=held, peak_bytes_in_use=memory_stat(devices, "peak_bytes_in_use"),
            wall_s=round(t_end - t0, 2), first_step_s=round(t_first - t0, 2),
            later_steps_s=round(t_end - t_first, 2), compiled_after_first_step=late,
            **log.fields(mark)))
    return out


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def reference_logits(params, tokens, at, n_heads):
    """GPT-2 forward in float32 ``jax.numpy``, written here and nowhere
    shared with models/transformer.py: tokens (B, S) -> logits (B, K, V) at
    the positions ``at`` (B, K). Pre-LN blocks, learned positions, tanh
    GELU, tied output embedding, eps 1e-5."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    B, S = tokens.shape
    D = p["embed"]["tok"].shape[1]
    hd = D // n_heads

    def norm(x, w):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * w["scale"] + w["bias"]

    causal = jnp.tril(jnp.ones((S, S), bool))

    def block(x, w):
        a, m = w["attn"], w["mlp"]
        h = norm(x, w["ln1"])
        q, k, v = ((h @ a["w" + n] + a["b" + n]).reshape(B, S, n_heads, hd) for n in "qkv")
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        x = x + jnp.einsum("bhqk,bkhd->bqhd", s, v).reshape(B, S, D) @ a["wo"] + a["bo"]
        u = norm(x, w["ln2"]) @ m["wi"] + m["bi"]
        u = 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (u + 0.044715 * u ** 3)))
        return x + u @ m["wo"] + m["bo"], None

    x = p["embed"]["tok"][tokens] + p["embed"]["pos"][:S]
    x, _ = jax.lax.scan(block, x, p["layers"])
    x = jnp.take_along_axis(norm(x, p["final_norm"]), at[:, :, None], axis=1)
    return x @ p["embed"]["tok"].T


def reference_gaps(params, contexts, streams, width, n_heads):
    """Teacher-forced: one float32 forward over context + emitted tokens.
    (requests, new) array: how far the reference logit of each emitted token
    sits below the reference maximum at the position that emitted it."""
    new = len(streams[0])
    tokens = np.zeros((len(contexts), width), np.int32)  # causal: the tail pad is never seen
    for i, (c, s) in enumerate(zip(contexts, streams)):
        tokens[i, :len(c) + new] = np.concatenate([c, s])
    # position j predicts token j + 1
    at = np.stack([len(c) - 1 + np.arange(new) for c in contexts]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(reference_logits, static_argnums=3)(params, tokens, at, n_heads)
    picked = jnp.take_along_axis(logits, jnp.asarray(np.array(streams))[:, :, None], axis=2)[:, :, 0]
    gap = np.asarray(logits.max(-1) - picked)
    check(np.isfinite(gap).all(), "reference logits not finite")
    return gap


def check_against_reference(params, prompts, streams, n_heads, seed):
    """Every emitted token's reference logit is within MARGIN of the
    reference maximum; and, so that this means something, the same tokens
    are NOT within it against a perturbed context. Returns the fields."""
    params = jax.device_put(params, jax.devices()[0])
    width = max(len(p) for p in prompts) + len(streams[0])
    gap = reference_gaps(params, prompts, streams, width, n_heads)
    check(gap.max() <= MARGIN,
          f"an emitted token sits {gap.max():.4f} below the float32 reference's top "
          f"logit (margin {MARGIN}); per-request worst: {gap.max(1).round(4).tolist()}")
    rs = np.random.RandomState(seed + 1)
    controls = {  # what a cache holding the wrong rows, or the right rows one slot off, computes
        "prompt_permuted": [np.concatenate([rs.permutation(p[:-1]), p[-1:]]) for p in prompts],
        "prompt_one_position_early": [p[1:] for p in prompts],
    }
    over = {}
    for name, contexts in controls.items():
        over[name] = float((reference_gaps(params, contexts, streams, width, n_heads) > MARGIN).mean())
        check(over[name] >= CONTROL_SHARE,
              f"negative control {name}: only {over[name]:.3f} of the emitted tokens leave the "
              f"margin when the reference sees the wrong context (need {CONTROL_SHARE}): "
              f"the reference check has no power on these streams")
    distinct = len({t for s in streams for t in s})
    check(distinct >= DISTINCT_PER_REQUEST * len(streams),
          f"only {distinct} distinct tokens from {len(streams)} requests: the streams collapsed")
    return dict(
        reference_margin=MARGIN, worst_gap_below_reference_top=round(float(gap.max()), 4),
        share_equal_reference_argmax=round(float((gap == 0).mean()), 4),
        control_share_required=CONTROL_SHARE,
        control_share_outside_margin={k: round(v, 4) for k, v in over.items()},
        distinct_tokens_emitted=distinct)


def seed_params(model, seed):
    """The model's own init from ``seed``, attention rescaled (QUERY_SCALE)."""
    def make(key):
        params = model.init(key)
        attn = params["layers"]["attn"]
        attn["wq"] = attn["wq"] * QUERY_SCALE
        attn["wo"] = attn["wo"] * math.sqrt(2 * model.cfg.num_layers)
        return params

    return jax.jit(make)(jax.random.PRNGKey(seed))


def run_serve(size, n_chips, seed, log, platform):
    comm.destroy()
    devices = jax.devices()[:n_chips]
    mark, t0, base = log.since(), time.time(), memory_stat(devices, "bytes_in_use")
    model = build_model(size)
    config = {"dtype": "bfloat16", "mesh": {"shape": {"data": 1, "tensor": n_chips}}}
    engine = deepspeed_tpu.init_inference(model, config=config, params=seed_params(model, seed))
    rs = np.random.RandomState(seed)
    prompts = [rs.randint(0, model.cfg.vocab_size, n).astype(np.int32)
               for n in size["prompt_lens"]]
    new = size["new_tokens"]

    streams, serve_s, held, batcher = {}, {}, None, None
    for depth in (1, 0):
        batcher = ContinuousBatchingEngine(
            model, config=config, params=engine.params, max_slots=size["slots"],
            cache_len=size["cache_len"], pipeline_depth=depth, seed=seed)
        if held is None:
            jax.block_until_ready(batcher.cache)
            held = held_since(devices, base)
        serving = ServingEngine(batcher)
        t_serve = time.time()
        admitted = [serving.submit(p, max_new_tokens=new) for p in prompts]
        check(all(admitted), f"a request was shed: {admitted}")
        pulls = [serving.stream(a.rid) for a in admitted]
        streams[depth] = [[int(t) for t in pull] for pull in pulls]
        serve_s[depth] = round(time.time() - t_serve, 2)
        serving.close()
        check(all(len(s) == new and pull.request.state == FINISHED
                  for s, pull in zip(streams[depth], pulls)),
              f"pipeline_depth={depth}: a request did not complete its {new} tokens: "
              f"{[(len(s), pull.request.state) for s, pull in zip(streams[depth], pulls)]}")
    check(streams[1] == streams[0], "pipeline_depth 1 and 0 gave different token streams")
    leaf = jax.tree.leaves(engine.params)[0]
    check({d.platform for d in leaf.devices()} == {platform},
          f"params live on {leaf.devices()}, not on {platform}")

    reference = check_against_reference(
        engine.params, prompts, streams[1], model.cfg.num_heads, seed)

    # observation, not a check: InferenceEngine.generate runs the same
    # prompts as ONE padded batch — other shapes, other bf16 roundings
    width = max(size["prompt_lens"])
    padded = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(padded)
    for i, p in enumerate(prompts):
        padded[i, :len(p)], mask[i, :len(p)] = p, 1
    generated = np.asarray(engine.generate(padded, max_new_tokens=new, attention_mask=mask))
    same_as_generate = float((generated[:, width:] == np.array(streams[1])).mean())

    out = dict(
        streams=streams[1], held=held, engine=engine, batcher=batcher,
        line=dict(
            phase="serve", chips=n_chips, model=size["model"], requests=len(prompts),
            prompt_lens=list(size["prompt_lens"]), new_tokens=new,
            tokens_generated=sum(len(s) for s in streams[1]),
            depth0_equals_depth1=True, **reference,
            share_equal_generate=round(same_as_generate, 4),
            bytes_in_use_after_init=held, peak_bytes_in_use=memory_stat(devices, "peak_bytes_in_use"),
            wall_s=round(time.time() - t0, 2), serve_s_depth1=serve_s[1],
            serve_s_depth0=serve_s[0], **log.fields(mark)))
    return out


# --------------------------------------------------------------------------

def release(run):
    """Drop a finished run's engines so the next one finds the memory."""
    run.pop("engine", None)
    run.pop("batcher", None)
    gc.collect()


def one_chip(size, seed, log, platform):
    train = run_train(size, 1, seed, log, platform)
    emit(**train["line"])
    release(train)
    serve = run_serve(size, 1, seed, log, platform)
    emit(**serve["line"])


def four_chips(size, seed, log, platform):
    emit(phase="devices", devices=[dict(id=d.id, coords=getattr(d, "coords", None))
                                   for d in jax.devices()[:4]])
    # (a) ZeRO-3 over fsdp=4 against the same seed and batch on one chip
    one = run_train(size, 1, seed, log, platform)
    emit(**one["line"])
    release(one)
    four = run_train(size, 4, seed, log, platform)
    for what in ("params", "master_params", "opt_state", "grad_acc"):
        check_quartered(f"ZeRO-3 {what}", sharded_leaves(getattr(four["engine"], what), "fsdp"),
                        ("wq", "wk", "wv", "wo", "wi", "tok"))
    check_quarter_bytes("ZeRO-3 state", four["held"], one["held"])
    diffs = [abs(a - b) for a, b in zip(four["losses"], one["losses"])]
    check(max(diffs) <= LOSS_TOL, f"ZeRO-3 on four chips left the one-chip losses by "
                                  f"{diffs} (tolerance {LOSS_TOL})")
    emit(**four["line"], loss_tolerance=LOSS_TOL,
         max_loss_diff_vs_one_chip=round(max(diffs), 5))
    release(four)

    # (b) tensor-parallel serving, held to the same reference as one chip
    one = run_serve(size, 1, seed, log, platform)
    emit(**one["line"])
    release(one)
    four = run_serve(size, 4, seed, log, platform)
    check_quartered("tensor-parallel params", sharded_leaves(four["engine"].params, "tensor"),
                    ("wq", "wo", "wi"))
    check_quartered("KV cache", sharded_leaves(four["batcher"].cache, "tensor"), ("k", "v"))
    check_quarter_bytes("serving state", four["held"], one["held"])
    emit(**four["line"], share_equal_one_chip_streams=round(float(
        (np.array(four["streams"]) == np.array(one["streams"])).mean()), 4))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy model, any platform: control flow only, proves nothing "
                         "about a chip")
    args = ap.parse_args()

    cache_dir = configure_compile_cache()
    log = CompileLog()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        sys.exit(f"chip_smoke: JAX found platform {platform!r} "
                 f"({devices[0].device_kind}), not a TPU: nothing to prove here")
    check(len(devices) >= args.chips, f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # an observation; some CPU installs lack it
        libtpu = None
    emit(phase="start", platform=platform, device_kind=devices[0].device_kind,
         count=len(devices), jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
         compile_cache_dir=cache_dir, rehearsal=args.rehearse, seed=args.seed)

    size = TOY if args.rehearse else REAL
    (one_chip if args.chips == 1 else four_chips)(size, args.seed, log, platform)

    total = log.since()
    emit(phase="done", compile_cache_dir=cache_dir, cache_hits=total[2],
         programs_compiled_or_loaded=total[0], compile_s=round(total[1], 2))
    emit(ok=True, device=dict(platform=platform, kind=devices[0].device_kind,
                              count=len(devices)))


if __name__ == "__main__":
    main()
