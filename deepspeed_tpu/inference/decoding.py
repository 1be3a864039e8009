"""Shared KV-cached decode machinery.

Single home for the compile-and-sample logic used by BOTH the standalone
``InferenceEngine`` (inference/engine.py) and the RLHF ``TpuHybridEngine``
(runtime/hybrid_engine.py) — same sharding selection, same prefill/decode
jits, same sampling loop, so fixes propagate to both surfaces.
"""

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.telemetry.hlo_scopes import Scope


def _mark_first_token(timings: Optional[dict], token):
    """TTFT hook: when the caller passes a ``timings`` dict (telemetry
    enabled), block on the first sampled token and stamp its wall-clock.
    ``None`` (the default everywhere) keeps the async dispatch pipeline
    untouched."""
    if timings is not None:
        jax.block_until_ready(token)
        timings["first_token_s"] = time.time()


def read_bucket(n: int, cap: int, floor: int = 16) -> int:
    """Smallest power-of-2 length >= n (starting at ``floor``), clamped to
    ``cap``. The ONE bucketing rule for the whole decode stack: continuous-
    batching admission buckets, tight-read lengths, and the bucket-migrated
    cache growth all use it, so their geometries can never disagree."""
    b = floor
    while b < n:
        b *= 2
    return min(b, cap)


def read_stages(prompt_len: int, n_steps: int, cache_len: int,
                floor: Optional[int]):
    """[(read_len_or_None, n_steps)] decode-step stages for a generation:
    step j attends ``prompt_len + j + 1`` cached slots, so it reads the
    bucket covering that extent. Consecutive steps sharing a bucket fuse
    into one stage (one ``lax.scan`` in the fused program, one compiled
    read geometry on the host-driven loop). ``floor=None`` = tight reads
    off — a single full-length stage. A read_len of ``None`` inside a
    stage means "the whole allocation" (bucket reached cache_len)."""
    if n_steps <= 0:
        return []
    if floor is None:
        return [(None, n_steps)]
    stages, j = [], 0
    while j < n_steps:
        r = read_bucket(prompt_len + j + 1, cache_len, floor)
        if r >= cache_len:
            stages.append((None, n_steps - j))
            break
        n = min(n_steps, r - prompt_len) - j
        stages.append((r, n))
        j += n
    return stages


def decode_kv_bytes(cfg, prompt_len: int, new_tokens: int, cache_len: int,
                    floor: Optional[int] = None, tp: int = 1) -> int:
    """Deterministic host-side accounting: KV-cache bytes ONE sequence row
    streams across the ``new_tokens - 1`` decode steps of a generation
    (prefill excluded — its read is the segment itself). This mirrors the
    read geometry the compiled programs actually execute (read_stages), so
    telemetry's ``kv_bytes_read`` is assertable in tests and comparable
    across tight/full configurations. ``tp`` (the cache's heads-axis shard
    width, kv_cache.shard_width) makes the number PER-CHIP:
    each chip of a tensor-parallel mesh streams only its head shard."""
    total = 0
    for r, n in read_stages(prompt_len, new_tokens - 1, cache_len, floor):
        total += n * kv_cache.read_bytes_per_row(cfg, r if r is not None else cache_len,
                                                 tp=tp)
    return total


# routing counters a tick of a model with expert layers returns beside its
# tokens: assignments made, to held experts, the most one held expert got in
# a layer, expert layers run, held experts hit (layer_plan.forward_plan_cached);
# a plan with delta-rule layers appends layer_plan.GDN_STATS more
# (layer_plan.stats_len is the count a configuration's ticks return)
TICK_STATS = 5


def _decode_shardings(mesh, cfg, batch_size: int):
    """(batch_sharding, cache_sharding) — the ONE sharding-selection policy
    for every cached-decode program (plain and speculative paths must place
    batch/KV identically or each call pays a reshard)."""
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    batch_axes = ("data", "fsdp") if batch_size % dp == 0 else None
    batch_sh = NamedSharding(mesh, PartitionSpec(batch_axes))
    cache_sh = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                            kv_cache.partition_spec(cfg, mesh, batch_axes))
    return batch_sh, cache_sh


def _tick_shardings(mesh, cfg, batch_size: int):
    """(row_sh, cache_sh, batch_sh) for the serving tick programs. The
    per-row scheduling state (pos/gen/quota/rids, the threaded
    last_tok/done) and the packed ``(B, k+2)`` acceptance buffer stay
    FULLY REPLICATED over the mesh: the host uploads/fetches them every
    tick, and a replicated buffer keeps that one coalesced transfer per
    tick instead of a per-device gather — the row vectors are a few
    hundred int32s, so replication costs nothing while the KV cache and
    params carry the real sharding (heads/hidden/vocab on ``tensor``)."""
    batch_sh, cache_sh = _decode_shardings(mesh, cfg, batch_size)
    row_sh = NamedSharding(mesh, PartitionSpec())
    return row_sh, cache_sh, batch_sh


def _jit_cached(mesh, fn, **jit_kwargs):
    """``jax.jit`` of a program that writes into a pool placed by
    :func:`_decode_shardings`. The cache's writer sees shapes, not
    shardings: a program whose pools span several chips is traced knowing
    so (``kv_cache.split_over_chips``) and keeps the window write."""
    if kv_cache.spans_chips(mesh):
        fn = kv_cache.split_over_chips(fn)
    return jax.jit(fn, **jit_kwargs)


def compile_decode_fns(mesh, cfg, param_shardings, batch_size: int, cache_len: int):
    """Build (prefill_fn, decode_fn, cache_sharding, batch_sharding) for a
    TransformerConfig ``cfg`` with params placed per ``param_shardings``."""
    from deepspeed_tpu.models import transformer as tf

    batch_sh, cache_sh = _decode_shardings(mesh, cfg, batch_size)

    def prefill(params, tokens, cache):
        return tf.forward_with_cache(params, cfg, tokens, cache, 0)

    def decode(params, tok, cache, pos):
        logits, cache = tf.forward_with_cache(params, cfg, tok, cache, pos)
        return logits[:, -1], cache

    prefill_fn = _jit_cached(
        mesh, prefill,
        in_shardings=(param_shardings, batch_sh, cache_sh),
        out_shardings=(batch_sh, cache_sh),
        donate_argnums=(2,),
    )
    decode_fn = _jit_cached(
        mesh, decode,
        in_shardings=(param_shardings, batch_sh, cache_sh, None),
        out_shardings=(batch_sh, cache_sh),
        donate_argnums=(2,),
    )
    return prefill_fn, decode_fn, cache_sh, batch_sh


def compile_generate_fn(mesh, cfg, param_shardings, batch_size: int, cache_len: int,
                        max_new_tokens: int, temperature: float, top_k: int,
                        top_p: float, read_floor: Optional[int] = None):
    """Whole-generation jit: prefill + ``lax.scan`` over the decode steps in
    ONE compiled program — one dispatch per ``generate()`` call instead of
    one per token (a per-token host round trip is pure overhead on a decode
    step that is a few ms of device work). Token stream is bitwise-identical to ``decode_loop``:
    same rng split order, same select_token calls.

    ``read_floor`` enables tight cache reads inside the fused program: the
    decode scan splits into bucket stages (read_stages) so early steps
    attend a power-of-2 window over the active cache prefix instead of the
    full allocation — same token stream (the masked tail is exact zeros),
    roughly half the cache bytes per generation at typical lengths.

    Returns ``(generate_fn, cache_sh, batch_sh)`` with
    ``generate_fn(params, tokens, cache, rng) -> (B, S + max_new_tokens)``.
    """
    from functools import partial

    from deepspeed_tpu.models import transformer as tf

    batch_sh, cache_sh = _decode_shardings(mesh, cfg, batch_size)

    def run(params, tokens, cache, rng):
        S = tokens.shape[1]
        logits, cache = tf.forward_with_cache(params, cfg, tokens, cache, 0)
        first = select_token(logits[:, -1], temperature, top_k, rng, top_p)

        def body(carry, _, read_len=None):
            last, cache, rng, pos = carry
            rng, sub = jax.random.split(rng)
            step_logits, cache = tf.forward_with_cache(
                params, cfg, last[:, None], cache, pos, read_len=read_len)
            tok = select_token(step_logits[:, -1], temperature, top_k, sub, top_p)
            return (tok, cache, rng, pos + 1), tok

        carry = (first, cache, rng, jnp.int32(S))
        outs = []
        for r, n in read_stages(S, max_new_tokens - 1, cache_len, read_floor):
            carry, toks = jax.lax.scan(partial(body, read_len=r), carry, None,
                                       length=n)
            outs.append(toks)
        cache = carry[1]
        rest = (jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)
                if outs else tokens[:, :0])
        seq = jnp.concatenate([tokens, first[:, None], rest], axis=1)
        # the final cache is returned (and dropped by the caller) so the
        # donated input cache aliases an output instead of warning
        return seq, cache

    jitted = _jit_cached(
        mesh, run,
        in_shardings=(param_shardings, batch_sh, cache_sh, None),
        out_shardings=(batch_sh, cache_sh),
        donate_argnums=(2,),
    )

    def fn(params, tokens, cache, rng):
        seq, _ = jitted(params, tokens, cache, rng)
        return seq

    return fn, cache_sh, batch_sh


def compile_ragged_prefill_fn(mesh, cfg, param_shardings, batch_size: int, cache_len: int):
    """Jit a prefill over LEFT- or RIGHT-padded prompts: explicit (B, S)
    positions (pads carry position >= cache_len so their KV writes drop;
    real tokens pack densely at 0..len-1 per row). Returns
    (ragged_prefill_fn, cache_sh, batch_sh)."""
    from deepspeed_tpu.models import transformer as tf

    batch_sh, cache_sh = _decode_shardings(mesh, cfg, batch_size)

    def prefill(params, tokens, positions, cache):
        zero = jnp.zeros((tokens.shape[0],), jnp.int32)
        return tf.forward_with_cache(params, cfg, tokens, cache, zero, positions=positions)

    fn = _jit_cached(
        mesh, prefill,
        in_shardings=(param_shardings, batch_sh, batch_sh, cache_sh),
        out_shardings=(batch_sh, cache_sh),
        donate_argnums=(3,),
    )
    return fn, cache_sh, batch_sh


def _segment_decode_tail(segment_fn, params, first_tok, cache, prompt_lens,
                         n_more: int, temperature: float, top_k: int, rng,
                         top_p: float, active0: Optional[int] = None):
    """Per-row-position decode loop shared by the ragged and chunked-prefill
    generate paths: ``first_tok`` (B,) was already sampled from the prefill
    logits; emits ``n_more`` further tokens. ``active0`` (the longest row's
    cached extent before the first step, host int) opts into tight reads:
    each step passes the active extent to a read-geometry-aware
    ``segment_fn`` dispatcher (the engine's) — plain 4-arg compiled segment
    fns are called unchanged when it is None."""
    out = [first_tok]
    pos = jnp.asarray(prompt_lens)
    for i in range(n_more):
        rng, sub = jax.random.split(rng)
        if active0 is None:
            step_logits, cache = segment_fn(params, out[-1][:, None], cache, pos)
        else:
            step_logits, cache = segment_fn(params, out[-1][:, None], cache, pos,
                                            active=active0 + i + 1)
        out.append(select_token(step_logits[:, 0], temperature, top_k, sub, top_p))
        pos = pos + 1
    return jnp.stack(out, axis=1)


def ragged_decode_loop(ragged_prefill_fn, segment_fn, params, tokens, attention_mask,
                       cache, cache_len: int, max_new_tokens: int, temperature: float,
                       top_k: int, rng, top_p: float = 1.0,
                       timings: Optional[dict] = None,
                       tight_read: bool = False) -> jnp.ndarray:
    """Generate over a PADDED prompt batch (HF attention_mask semantics,
    left or right padding): prefill once with per-row dense positions, then
    per-row-position decode. Returns (B, S + max_new_tokens) — the prompt
    region is returned as given (pads included); generated tokens follow.
    """
    import numpy as np

    mask = np.asarray(attention_mask)
    B, S = tokens.shape
    if max_new_tokens <= 0:
        return tokens
    assert mask.shape == (B, S), (mask.shape, (B, S))
    prompt_lens = mask.sum(axis=1).astype(np.int32)
    assert (prompt_lens > 0).all(), "every row needs at least one real token"
    # dense per-row positions; pads land at cache_len -> dropped writes
    positions = np.where(mask > 0, np.cumsum(mask, axis=1) - 1, cache_len).astype(np.int32)
    logits, cache = ragged_prefill_fn(params, jnp.asarray(tokens), jnp.asarray(positions), cache)
    # logits column of each row's LAST real token
    last_col = np.array([np.nonzero(mask[b])[0][-1] for b in range(B)])
    last_logits = jnp.take_along_axis(
        logits, jnp.asarray(last_col)[:, None, None], axis=1
    )[:, 0]
    nxt = select_token(last_logits, temperature, top_k, rng, top_p)
    _mark_first_token(timings, nxt)
    gen = _segment_decode_tail(segment_fn, params, nxt, cache, prompt_lens,
                               max_new_tokens - 1, temperature, top_k, rng, top_p,
                               active0=int(prompt_lens.max()) if tight_read else None)
    return jnp.concatenate([jnp.asarray(tokens), gen], axis=1)


def chunked_generate(ragged_prefill_fn, segment_fn, params, tokens, cache,
                     cache_len: int, chunk: int, max_new_tokens: int,
                     temperature: float, top_k: int, rng,
                     top_p: float = 1.0, attention_mask=None,
                     timings: Optional[dict] = None,
                     tight_read: bool = False) -> jnp.ndarray:
    """Generate with CHUNKED prefill: the prompt streams through a fixed
    (B, chunk) prefill program, so ONE compiled program serves every prompt
    length (each distinct length otherwise compiles its own prefill, seconds
    each) and prefill peak memory is
    bounded by the chunk, not the prompt. The final (padded) chunk drops its
    pad writes via out-of-range positions; decode then shares the ragged
    per-row segment tail. Token streams are identical to the unchunked path
    (same cache contents, same sampling order).

    ``attention_mask`` ((B, S) of 0/1, HF semantics, left or right padding)
    composes: per-row dense positions come from the mask — the varied-width
    serving batches that motivate chunking in the first place still reuse
    the one chunk program.
    """
    import numpy as np

    B, S = tokens.shape
    if max_new_tokens <= 0:
        return tokens
    assert chunk >= 1, chunk
    if attention_mask is None:
        mask = np.ones((B, S), np.int64)
    else:
        mask = np.asarray(attention_mask)
        assert mask.shape == (B, S), (mask.shape, (B, S))
        assert (mask.sum(axis=1) > 0).all(), "every row needs at least one real token"
    prompt_lens = mask.sum(axis=1).astype(np.int32)
    # dense per-row positions; pads park at cache_len -> writes drop and
    # their garbage logits are never selected
    positions_all = np.where(mask > 0, np.cumsum(mask, axis=1) - 1, cache_len).astype(np.int32)
    last_col_all = np.array([np.nonzero(mask[b])[0][-1] for b in range(B)])

    n_chunks = -(-S // chunk)
    padded_toks = np.zeros((B, n_chunks * chunk), np.int32)
    padded_toks[:, :S] = np.asarray(tokens)
    padded_pos = np.full((B, n_chunks * chunk), cache_len, np.int32)
    padded_pos[:, :S] = positions_all

    last_logits = None
    for i in range(n_chunks):
        lo, hi = i * chunk, (i + 1) * chunk
        if (padded_pos[:, lo:hi] >= cache_len).all():
            continue  # all-pad chunk (left padding / width padding)
        logits, cache = ragged_prefill_fn(
            params, jnp.asarray(padded_toks[:, lo:hi]),
            jnp.asarray(padded_pos[:, lo:hi]), cache)
        # rows whose LAST real token lands in this chunk take their logits
        in_chunk = (last_col_all >= lo) & (last_col_all < hi)
        col = jnp.asarray(np.where(in_chunk, last_col_all - lo, 0))
        picked = jnp.take_along_axis(logits, col[:, None, None], axis=1)[:, 0]
        sel = jnp.asarray(in_chunk)[:, None]
        last_logits = picked if last_logits is None else jnp.where(sel, picked, last_logits)
    nxt = select_token(last_logits, temperature, top_k, rng, top_p)
    _mark_first_token(timings, nxt)
    gen = _segment_decode_tail(segment_fn, params, nxt, cache, prompt_lens,
                               max_new_tokens - 1, temperature, top_k, rng, top_p,
                               active0=int(prompt_lens.max()) if tight_read else None)
    return jnp.concatenate([jnp.asarray(tokens), gen], axis=1)


def _filter_logits(logits, temperature: float, top_k: int, top_p: float):
    """Temperature / top-k / nucleus filtering over (B, V) logits. The ONE
    implementation shared by plain sampling (select_token) and the
    speculative p/q distributions — speculative losslessness requires both
    paths to filter identically."""
    logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        # keep the smallest prefix of the sorted distribution with
        # cumulative mass >= top_p; the first token is always kept
        # (top_p <= 0 therefore means top-1)
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # mass BEFORE this token still below p; the epsilon floor keeps the
        # top token in-support even at top_p=0.0
        keep = cum - probs < max(top_p, 1e-9)
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return logits


def select_token(logits, temperature: float, top_k: int, rng, top_p: float = 1.0) -> jnp.ndarray:
    """Greedy / temperature / top-k / nucleus (top-p) sampling, one token
    per row."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        rng, _filter_logits(logits, temperature, top_k, top_p), axis=-1
    ).astype(jnp.int32)


def decode_loop(prefill_fn, decode_fn, params, tokens, cache, max_new_tokens: int,
                temperature: float, top_k: int, rng, top_p: float = 1.0,
                timings: Optional[dict] = None) -> jnp.ndarray:
    """Prefill + token-by-token decode; returns (B, S + max_new_tokens)."""
    if max_new_tokens <= 0:
        return tokens
    S = tokens.shape[1]
    logits, cache = prefill_fn(params, tokens, cache)
    last = select_token(logits[:, -1], temperature, top_k, rng, top_p)
    _mark_first_token(timings, last)
    out = [last]
    pos = S
    for _ in range(max_new_tokens - 1):
        rng, sub = jax.random.split(rng)
        step_logits, cache = decode_fn(params, out[-1][:, None], cache, pos)
        out.append(select_token(step_logits, temperature, top_k, sub, top_p))
        pos += 1
    return jnp.concatenate([tokens, jnp.stack(out, axis=1)], axis=1)


def compile_segment_fn(mesh, cfg, param_shardings, batch_size: int, cache_len: int,
                       read_len: Optional[int] = None):
    """Jit a cached segment forward with PER-ROW positions (``pos``: (B,)
    int32); any segment width retraces under the same jit wrapper. Used by
    speculative decoding, where rows advance by their own accepted counts.
    ``read_len`` builds the tight-read variant: attention streams only the
    first ``read_len`` cache slots — the caller (the engine's bucket
    dispatcher, the continuous pools' tick) guarantees every live row's
    extent fits. Returns (segment_fn, cache_sh, batch_sh)."""
    from deepspeed_tpu.models import transformer as tf

    batch_sh, cache_sh = _decode_shardings(mesh, cfg, batch_size)

    def segment(params, toks, cache, pos):
        return tf.forward_with_cache(params, cfg, toks, cache, pos,
                                     read_len=read_len)

    segment_fn = _jit_cached(
        mesh, segment,
        in_shardings=(param_shardings, batch_sh, cache_sh, batch_sh),
        out_shardings=(batch_sh, cache_sh),
        donate_argnums=(2,),
    )
    return segment_fn, cache_sh, batch_sh


def request_keys(base_key, rids, gens):
    """Per-row sampling keys for the serving tick programs:
    ``fold_in(fold_in(base, rid), gen)`` vmapped over the batch. A request's
    sampled stream therefore depends only on (engine seed, request id, token
    index) — never on which slot it landed in, which tick it joined, or how
    many ticks are in flight. That independence is what makes the pipelined
    (dispatch-ahead) and fused-prefill tick modes bitwise-identical to the
    sync scheduler: scheduling may shift WHEN a token is produced, never
    WHAT it is."""
    def one(rid, gen):
        return jax.random.fold_in(jax.random.fold_in(base_key, rid), gen)

    return jax.vmap(one)(rids, gens)


# Speculative tick RNG lanes: a THIRD fold_in on top of request_keys'
# (seed, rid, token_index) identity separates the three independent draws
# speculation makes per token index — the draft proposal, the acceptance
# uniform, and the bonus/correction draw. Lane keys can never collide with
# the plain path's two-fold keys (different fold depth), and rejection
# sampling stays correct because the residual draw at an index is
# independent of the acceptance uniform that rejected the proposal there.
LANE_DRAFT, LANE_ACCEPT, LANE_BONUS = 1, 2, 3


def spec_request_keys(base_key, rids, gens, lane: int):
    """Per-row speculative sampling keys:
    ``fold_in(fold_in(fold_in(base, rid), gen), lane)`` vmapped over the
    batch. Like :func:`request_keys`, the key depends only on (engine
    seed, request id, token index, lane) — never on slot placement, tick
    depth, or how many proposals earlier rounds accepted — so speculative
    sampled streams are reproducible across pipeline depths, fusion modes,
    and gamma."""
    def one(rid, gen):
        k = jax.random.fold_in(jax.random.fold_in(base_key, rid), gen)
        return jax.random.fold_in(k, lane)

    return jax.vmap(one)(rids, gens)


def select_token_rows(logits, temperature: float, top_k: int, keys,
                      top_p: float = 1.0) -> jnp.ndarray:
    """Row-wise :func:`select_token`: one key per row (request_keys) instead
    of one key per batch, same temperature/top-k/top-p filter."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    filtered = _filter_logits(logits, temperature, top_k, top_p)
    return jax.vmap(jax.random.categorical)(keys, filtered).astype(jnp.int32)


def compile_pool_tick_fn(mesh, cfg, param_shardings, batch_size: int,
                         cache_len: int, n_tokens: int, temperature: float,
                         top_k: int, top_p: float,
                         eos_token_id: Optional[int] = None,
                         read_len: Optional[int] = None,
                         chunk: Optional[int] = None,
                         donate: bool = True):
    """One continuous-batching scheduler tick as ONE compiled program with
    ON-DEVICE ACCEPTANCE: the forward, per-row sampling (request_keys),
    EOS/quota done detection, position advance, and emission masking all
    run inside the jit, and the tick returns one small packed int32 buffer
    — ``(B, n_tokens + 2)``: ``[:, :k]`` sampled tokens, ``[:, k]``
    n_emitted, ``[:, k+1]`` the done flag — so the host fetches a single
    coalesced buffer per tick instead of per-row logits/acceptance state.
    ``last_tok`` and ``done`` are device-THREADED (returned as outputs that
    feed the next tick's inputs), which is what lets the engine keep a tick
    in flight: tick N+1 can be dispatched on tick N's output futures before
    the host ever looks at tick N's packed result.

    Plain / burst (``chunk=None``)::

        tick_fn(params, cache, last_tok, done, pos, gen, quota, rids, key)
          -> (packed, cache, last_tok, done)

    ``pos``/``gen``/``quota``/``rids`` are per-row int32 vectors the host
    uploads each tick (it knows them deterministically for live rows; rows
    it parks carry ``pos = cache_len`` so their KV writes drop). ``quota``
    is the row's max_new_tokens; a row whose token hits EOS or exhausts the
    quota flips its done flag and freezes (emission masked, last_tok/pos
    held) for any remaining burst steps and for any tick already in flight.

    Fused prefill (``chunk=W``, requires ``n_tokens == 1``): the same tick
    additionally prefills ONE admitting row's next W-wide prompt chunk
    inside the same dispatch (Dynamic-SplitFuse-style) — the program runs
    B + W tokens, the rows' ``last_tok`` (the admitting row parked among
    them) followed by ``chunk_toks`` at ``chunk_pos`` (pads parked at
    ``cache_len``), takes logits at B + 1 of them, and ``emit_col`` /
    ``emit_mask`` route sampling to the admitting row's last real prompt
    column on its final chunk::

        tick_fn(params, cache, last_tok, done, pos, gen, quota, rids, key,
                chunk_toks, chunk_pos, admit_slot, emit_col, emit_mask)
          -> (packed, cache, last_tok, done)

    The cache AND the threaded last_tok/done buffers are donated
    (``donate_argnums``). Donation lets the returned pool BE the donated
    buffer (no second pool is allocated); what a tick then moves is
    ``forward_with_cache``'s doing: the pool rides its layer scan's carry
    and each layer rewrites, in place, the ``read_len`` window it reads —
    nothing of a layer's or the pool's size is copied (while the pool was
    the scan's xs / ys, a donated tick still copied every layer's K and V
    out of the pool and back: PERF.md, PR 24 / PR 25).
    ``donate=False`` opts out: the jax CPU backend implements
    donation by BLOCKING at dispatch until the donated buffer is free,
    which serializes the tick chain and defeats dispatch-ahead pipelining
    — the virtual-mesh loadgen A/B runs donation-off to measure the
    overlap; on TPU donation and async dispatch compose and both stay on.
    Returns ``(tick_fn, cache_sh, batch_sh)``.
    """
    from deepspeed_tpu.models import transformer as tf

    row_sh, cache_sh, _ = _tick_shardings(mesh, cfg, batch_size)
    k = n_tokens
    assert k >= 1, k
    donate_argnums = (1, 2, 3) if donate else ()

    @jax.named_scope(Scope.ACCEPT)
    def accept(tok, last_tok, done, gen, quota, emit_mask):
        """Shared acceptance: which rows emit this step, updated state."""
        live = (done == 0) & (emit_mask == 1)
        gen2 = jnp.where(live, gen + 1, gen)
        stop = gen2 >= quota
        if eos_token_id is not None:
            stop = stop | (tok == eos_token_id)
        done2 = jnp.where(live & stop, 1, done)
        last2 = jnp.where(live, tok, last_tok)
        return last2, done2, gen2, live.astype(jnp.int32)

    @jax.named_scope(Scope.SAMPLE)
    def sample(logits, rids, gen, base_key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = request_keys(base_key, rids, gen)
        return select_token_rows(logits, temperature, top_k, keys, top_p)

    # a layer-plan model (models/layer_plan.py) runs its own tick body; one
    # with expert layers appends its routing counters to the packed buffer
    # as TICK_STATS more columns (row 0 carries them), so they come back
    # in the tick's one fetch
    plan = cfg.layer_kinds is not None
    from deepspeed_tpu.models.layer_plan import Chunk, forward_plan_cached, stats_len

    n_stats = stats_len(cfg) if plan else 0

    def with_stats(packed, stats):
        if stats is None or not cfg.moe_num_experts:
            return packed
        extra = jnp.zeros((batch_size, n_stats), jnp.int32).at[0].set(stats)
        return jnp.concatenate([packed, extra], axis=1)

    if chunk is None:
        ones = jnp.ones((batch_size,), jnp.int32)

        def run(params, cache, last_tok, done, pos, gen, quota, rids, base_key):
            def body(carry, _):
                cache, last_tok, done, pos, gen, stats = carry
                if plan:
                    logits, cache, stats = forward_plan_cached(
                        params, cfg, last_tok, pos, cache, read_len=read_len)
                else:
                    logits, cache = tf.forward_with_cache(
                        params, cfg, last_tok[:, None], cache, pos,
                        read_len=read_len)
                    logits = logits[:, 0]
                tok = sample(logits, rids, gen, base_key)
                last2, done2, gen2, emitted = accept(
                    tok, last_tok, done, gen, quota, ones)
                pos2 = jnp.where(done == 0, pos + 1, pos)
                return (cache, last2, done2, pos2, gen2, stats), (tok, emitted)

            (cache, last_tok, done, _, _, stats), (toks, emitted) = jax.lax.scan(
                body, (cache, last_tok, done, pos, gen,
                       jnp.zeros((n_stats,), jnp.int32) if plan else None),
                None, length=k)
            packed = jnp.concatenate(
                [jnp.moveaxis(toks, 0, 1),
                 emitted.sum(axis=0, dtype=jnp.int32)[:, None],
                 done[:, None]], axis=1)
            return with_stats(packed, stats), cache, last_tok, done

        fn = _jit_cached(
            mesh, run,
            in_shardings=(param_shardings, cache_sh, row_sh, row_sh,
                          row_sh, row_sh, row_sh, row_sh, None),
            out_shardings=(row_sh, cache_sh, row_sh, row_sh),
            donate_argnums=donate_argnums,
        )
        return fn, cache_sh, row_sh

    assert k == 1, "fused-prefill ticks are single-token (burst admits " \
                   "between bursts via the separate-prefill path)"

    def run(params, cache, last_tok, done, pos, gen, quota, rids, base_key,
            chunk_toks, chunk_pos, admit_slot, emit_col, emit_mask):
        # the chunk rides beside the rows as W more tokens (B + W in all),
        # never as a W-wide row under every slot; the admitting row is
        # parked among the rows and the chunk's sampled column takes its
        # place in the logits
        stats = None
        slot = jnp.asarray(admit_slot, jnp.int32)
        ch = Chunk(chunk_toks, chunk_pos, slot, emit_col[slot])
        if plan:
            sel, cache, stats = forward_plan_cached(
                params, cfg, last_tok, pos, cache, read_len=read_len, chunk=ch)
        else:
            logits, cache = tf.forward_tick_cached(
                params, cfg, last_tok, pos, cache, ch, read_len=read_len)
            sel = jax.lax.dynamic_update_slice(
                logits[:batch_size], logits[batch_size:], (slot, 0))
        tok = sample(sel, rids, gen, base_key)
        last2, done2, gen2, emitted = accept(
            tok, last_tok, done, gen, quota, emit_mask)
        packed = jnp.concatenate(
            [tok[:, None], emitted[:, None], done2[:, None]], axis=1)
        return with_stats(packed, stats), cache, last2, done2

    fn = _jit_cached(
        mesh, run,
        in_shardings=(param_shardings, cache_sh, row_sh, row_sh,
                      row_sh, row_sh, row_sh, row_sh, None,
                      None, None, None, row_sh, row_sh),
        out_shardings=(row_sh, cache_sh, row_sh, row_sh),
        donate_argnums=donate_argnums,
    )
    return fn, cache_sh, row_sh


def compile_row_update_fn(mesh, cfg, batch_size: int, donate: bool = True):
    """Tiny jitted row update for the device-threaded tick state: admission
    sets one slot's ``last_tok``/``done`` without fetching or rebuilding the
    (possibly still in-flight) arrays — the update is dispatched against the
    current output futures and chains behind any tick already queued. Both
    operands are donated (in-place on device); ``donate`` follows the
    engine's ``donate_cache`` knob — the CPU backend blocks donated
    dispatches, and admission must stay enqueue-only in overlap
    measurements. Returns ``set_row(last_tok, done, slot, tok, flag) ->
    (last_tok, done)``.

    A model whose cache has a state pool (``kv_cache.state_spec``: recurrent
    state, which no position masks) takes and returns that pool as well, the
    admitted row of it zeroed in place: ``set_row(last_tok, done, slot, tok,
    flag, state) -> (last_tok, done, state)``."""
    row_sh, cache_sh, _ = _tick_shardings(mesh, cfg, batch_size)

    def set_row(last_tok, done, slot, tok, flag):
        return last_tok.at[slot].set(tok), done.at[slot].set(flag)

    if kv_cache.state_spec(cfg) is not None:
        state_sh = cache_sh[kv_cache.StateSpec.name]

        def set_row_and_reset(last_tok, done, slot, tok, flag, state):
            return set_row(last_tok, done, slot, tok, flag) + (kv_cache.reset_row(state, slot),)

        return jax.jit(
            set_row_and_reset,
            in_shardings=(row_sh, row_sh, None, None, None, state_sh),
            out_shardings=(row_sh, row_sh, state_sh),
            donate_argnums=(0, 1, 5) if donate else (),
        )

    return jax.jit(
        set_row,
        in_shardings=(row_sh, row_sh, None, None, None),
        out_shardings=(row_sh, row_sh),
        donate_argnums=(0, 1) if donate else (),
    )


def compile_spec_pool_tick_fn(mesh, cfg, param_shardings, batch_size: int,
                              cache_len: int, gamma: int, temperature: float,
                              top_k: int, top_p: float,
                              eos_token_id: Optional[int] = None,
                              read_len: Optional[int] = None,
                              donate: bool = True,
                              draft_cfg=None, draft_param_shardings=None):
    """Speculative continuous-batching tick: per dispatch, every active row
    proposes ``gamma`` tokens, ONE target forward over the (gamma+1)-wide
    window verifies all rows at once, and the lossless accept/correct rule
    (the on-device mirror of :func:`_accept_round`) runs inside the jit —
    per-row accept counts, the bonus token, and the rollback positions land
    in one packed int32 buffer, so the host keeps its single coalesced
    fetch per tick and ``pipeline_depth`` dispatch-ahead composes
    unchanged.

    Two drafting variants share the verify/accept machinery:

    Draft-model (``draft_cfg`` + ``draft_param_shardings`` given): a second
    param tree resident on the same mesh proposes autoregressively through
    its own pool-geometry KV cache (gamma single-token steps + one extra
    step caching the final proposal's KV, mirroring
    :func:`speculative_decode_loop`)::

        run(params, draft_params, cache, draft_cache, last_tok, done, pos,
            gen, quota, rids, run_mask, base_key)
          -> (packed, cache, draft_cache, last_tok, done, pos, gen)

    N-gram / self-drafting (``draft_cfg=None``): the host proposes
    ``drafts`` (B, gamma) from each request's own emitted context
    (inference/ngram.py) — a POINT-MASS proposal q = δ(d), for which the
    acceptance rule degenerates to ``u < p(d)`` and the residual to p with
    d's mass removed; losslessness holds for any proposal, so speculation
    needs no second model::

        run(params, cache, last_tok, done, pos, gen, quota, rids, run_mask,
            drafts, base_key)
          -> (packed, cache, last_tok, done, pos, gen)

    ``pos``/``gen`` are device-THREADED here (unlike the plain tick, where
    the host mirrors them exactly): a row advances by its own accepted
    count, which the host only learns at retire time, so the authoritative
    copies ride the tick chain and the host keeps an upper-bound mirror
    for read-geometry selection only. ``run_mask`` (1 = this row decodes
    this tick) parks rows the host cannot run (mid-prefill, quota already
    covered by in-flight ticks) without touching their threaded state.
    Parked and done rows write at position ``cache_len`` — the vector-pos
    cache scatter drops out-of-range columns, which also makes the
    quota-tail window overrun safe: columns past a row's last needed
    position drop their KV writes and their outputs are quota-clipped out
    of acceptance.

    ``packed`` is (B, gamma+4) int32: ``[:, :gamma+1]`` the emitted tokens
    (accepted prefix then bonus/correction), ``[:, gamma+1]`` n_emitted,
    ``[:, gamma+2]`` the done flag, ``[:, gamma+3]`` the accepted draft
    count (telemetry + host mirror reconciliation). Greedy mode emits the
    target argmax chain token-for-token identically to the plain tick;
    sampled mode draws from lane-separated :func:`spec_request_keys`, so
    streams are reproducible across scheduling but (like any rejection
    sampler) equal to the plain stream in distribution, not bitwise.
    Returns ``(run_fn, cache_sh, row_sh)``."""
    from deepspeed_tpu.models import transformer as tf

    row_sh, cache_sh, _ = _tick_shardings(mesh, cfg, batch_size)
    assert gamma >= 1, gamma
    B, g1 = batch_size, gamma + 1
    greedy = temperature <= 0.0
    draft_mode = draft_cfg is not None
    if draft_mode:
        _, draft_cache_sh = _decode_shardings(mesh, draft_cfg, batch_size)
    iota_g = jnp.arange(gamma, dtype=jnp.int32)
    iota_g1 = jnp.arange(g1, dtype=jnp.int32)

    def accept_round(vlogits, drafts, qstack, active, pos, gen, quota,
                     last_tok, done, rids, base_key):
        """On-device mirror of :func:`_accept_round` plus the emission/
        state bookkeeping the host loop does around it. ``qstack`` None
        means a point-mass proposal (ngram)."""
        if greedy:
            tgt = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # (B, g1)
            match = drafts == tgt[:, :gamma]
        else:
            V = vlogits.shape[-1]
            p = _filtered_probs(
                vlogits.reshape(B * g1, V), temperature, top_k, top_p
            ).reshape(B, g1, V)
            p_at = jnp.take_along_axis(
                p[:, :gamma], drafts[..., None], axis=2)[..., 0]
            if qstack is None:
                ratio = p_at  # point-mass proposal: q(d) == 1
            else:
                q_at = jnp.take_along_axis(
                    qstack, drafts[..., None], axis=2)[..., 0]
                ratio = p_at / jnp.maximum(q_at, 1e-20)

            def urow(rid, g0):
                def at(i):
                    k = jax.random.fold_in(
                        jax.random.fold_in(base_key, rid), g0 + i)
                    return jax.random.uniform(
                        jax.random.fold_in(k, LANE_ACCEPT))

                return jax.vmap(at)(iota_g)

            u = jax.vmap(urow)(rids, gen)
            match = u < jnp.minimum(1.0, ratio)
        n_acc = jnp.where(
            match.all(axis=1), gamma,
            jnp.argmin(match.astype(jnp.int32), axis=1)).astype(jnp.int32)

        rem = jnp.maximum(quota - gen, 0)
        n_take = jnp.minimum(n_acc, rem)
        if eos_token_id is not None:
            eos_mask = (drafts == eos_token_id) & (iota_g[None] < n_take[:, None])
            took_eos = eos_mask.any(axis=1)
            first_eos = jnp.where(
                took_eos, jnp.argmax(eos_mask.astype(jnp.int32), axis=1), gamma)
            n_take = jnp.minimum(n_take, first_eos + 1)
        else:
            took_eos = jnp.zeros((B,), bool)
        took_eos = took_eos & active
        n_take = jnp.where(active, n_take, 0).astype(jnp.int32)

        bonus_ok = active & ~took_eos & (n_take == n_acc) & (gen + n_take < quota)
        if greedy:
            bonus = jnp.take_along_axis(tgt, n_take[:, None], axis=1)[:, 0]
        else:
            p_b = jnp.take_along_axis(p, n_take[:, None, None], axis=1)[:, 0]
            if qstack is None:
                d_b = jnp.take_along_axis(
                    drafts, jnp.minimum(n_take, gamma - 1)[:, None], axis=1)[:, 0]
                q_b = jax.nn.one_hot(d_b, p_b.shape[-1], dtype=p_b.dtype)
            else:
                q_b = jnp.take_along_axis(
                    qstack, jnp.minimum(n_take, gamma - 1)[:, None, None],
                    axis=1)[:, 0]
            residual = jnp.maximum(p_b - q_b, 0.0)
            dist = jnp.where((n_take < gamma)[:, None], residual, p_b)
            tot = dist.sum(axis=1, keepdims=True)
            dist = jnp.where(tot > 0, dist / jnp.where(tot > 0, tot, 1.0), p_b)
            bkeys = spec_request_keys(base_key, rids, gen + n_take, LANE_BONUS)
            bonus = jax.vmap(jax.random.categorical)(
                bkeys, jnp.where(dist > 0, jnp.log(dist), -1e30)
            ).astype(jnp.int32)

        n_emit = n_take + bonus_ok.astype(jnp.int32)
        pad_drafts = jnp.concatenate(
            [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
        tok_out = jnp.where(
            iota_g1[None] < n_take[:, None], pad_drafts,
            jnp.where((iota_g1[None] == n_take[:, None]) & bonus_ok[:, None],
                      bonus[:, None], 0))
        gen2 = jnp.where(active, gen + n_emit, gen)
        done2 = jnp.where(active & (took_eos | (gen2 >= quota)), 1, done)
        if eos_token_id is not None:
            done2 = jnp.where(active & bonus_ok & (bonus == eos_token_id),
                              1, done2)
        last2 = jnp.where(active & bonus_ok, bonus, last_tok)
        # rollback-on-rejection IS the position rule: the next round's
        # window starts right after the last verified input column the row
        # consumed, so rejected drafts' KV is overwritten before any later
        # query's causal extent reaches it (windows tile contiguously)
        pos2 = jnp.where(active, pos + n_take + 1, pos)
        packed = jnp.concatenate(
            [tok_out, n_emit[:, None], done2[:, None], n_take[:, None]],
            axis=1)
        return packed, last2, done2, pos2, gen2

    if not draft_mode:
        def run(params, cache, last_tok, done, pos, gen, quota, rids,
                run_mask, drafts, base_key):
            active = (done == 0) & (run_mask == 1)
            wpos = jnp.where(active, pos, cache_len)
            seg = jnp.concatenate([last_tok[:, None], drafts], axis=1)
            vlogits, cache = tf.forward_with_cache(
                params, cfg, seg, cache, wpos, read_len=read_len)
            packed, last2, done2, pos2, gen2 = accept_round(
                vlogits, drafts, None, active, pos, gen, quota,
                last_tok, done, rids, base_key)
            return packed, cache, last2, done2, pos2, gen2

        fn = _jit_cached(
            mesh, run,
            in_shardings=(param_shardings, cache_sh, row_sh, row_sh, row_sh,
                          row_sh, row_sh, row_sh, row_sh, row_sh, None),
            out_shardings=(row_sh, cache_sh, row_sh, row_sh, row_sh, row_sh),
            donate_argnums=(1, 2, 3, 4, 5) if donate else (),
        )
        return fn, cache_sh, row_sh

    def run(params, draft_params, cache, draft_cache, last_tok, done, pos,
            gen, quota, rids, run_mask, base_key):
        active = (done == 0) & (run_mask == 1)
        wpos = jnp.where(active, pos, cache_len)

        def dbody(carry, i):
            dcache, cur = carry
            dlogits, dcache = tf.forward_with_cache(
                draft_params, draft_cfg, cur[:, None], dcache,
                jnp.where(active, pos + i, cache_len), read_len=read_len)
            lg = dlogits[:, 0]
            if greedy:
                d = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (dcache, d), d
            q = _filtered_probs(lg, temperature, top_k, top_p)
            keys = spec_request_keys(base_key, rids, gen + i, LANE_DRAFT)
            d = jax.vmap(jax.random.categorical)(
                keys, jnp.where(q > 0, jnp.log(q), -1e30)).astype(jnp.int32)
            return (dcache, d), (d, q)

        (draft_cache, dlast), ys = jax.lax.scan(
            dbody, (draft_cache, last_tok), iota_g)
        if greedy:
            drafts, qstack = jnp.moveaxis(ys, 0, 1), None
        else:
            drafts = jnp.moveaxis(ys[0], 0, 1)        # (B, gamma)
            qstack = jnp.moveaxis(ys[1], 0, 1)        # (B, gamma, V)
        # one extra draft step caches the final proposal's KV so the draft
        # context stays complete when every proposal is accepted
        _, draft_cache = tf.forward_with_cache(
            draft_params, draft_cfg, dlast[:, None], draft_cache,
            jnp.where(active, pos + gamma, cache_len), read_len=read_len)

        seg = jnp.concatenate([last_tok[:, None], drafts], axis=1)
        vlogits, cache = tf.forward_with_cache(
            params, cfg, seg, cache, wpos, read_len=read_len)
        packed, last2, done2, pos2, gen2 = accept_round(
            vlogits, drafts, qstack, active, pos, gen, quota,
            last_tok, done, rids, base_key)
        return packed, cache, draft_cache, last2, done2, pos2, gen2

    fn = _jit_cached(
        mesh, run,
        in_shardings=(param_shardings, draft_param_shardings, cache_sh,
                      draft_cache_sh, row_sh, row_sh, row_sh, row_sh,
                      row_sh, row_sh, row_sh, None),
        out_shardings=(row_sh, cache_sh, draft_cache_sh, row_sh, row_sh,
                       row_sh, row_sh),
        donate_argnums=(2, 3, 4, 5, 6, 7) if donate else (),
    )
    return fn, cache_sh, row_sh


def compile_spec_row_update_fn(mesh, cfg, batch_size: int, donate: bool = True):
    """:func:`compile_row_update_fn` for the speculative tick's WIDER
    device-threaded state: ``pos``/``gen`` ride the tick chain too (a row
    advances by its own accepted count, which only the device knows at
    dispatch time), so admission must splice them in the same
    enqueue-only way. Returns ``set_row(last_tok, done, pos, gen, slot,
    tok, flag, p, g) -> (last_tok, done, pos, gen)``."""
    row_sh, _, _ = _tick_shardings(mesh, cfg, batch_size)

    def set_row(last_tok, done, pos, gen, slot, tok, flag, p, g):
        return (last_tok.at[slot].set(tok), done.at[slot].set(flag),
                pos.at[slot].set(p), gen.at[slot].set(g))

    return jax.jit(
        set_row,
        in_shardings=(row_sh, row_sh, row_sh, row_sh, None, None, None,
                      None, None),
        out_shardings=(row_sh, row_sh, row_sh, row_sh),
        donate_argnums=(0, 1, 2, 3) if donate else (),
    )


def _filtered_probs(logits, temperature: float, top_k: int, top_p: float):
    """Normalized sampling distribution after the same temperature/top-k/
    top-p filtering select_token applies (shared _filter_logits) — the q/p
    distributions of the speculative acceptance test must match what plain
    sampling would use."""
    return jax.nn.softmax(_filter_logits(logits, temperature, top_k, top_p), axis=-1)


def _sample_rows(probs, host_rng):
    """One categorical draw per row of a (B, V) numpy prob matrix —
    vectorized inverse-CDF (no per-row Python loop)."""
    import numpy as np

    u = host_rng.random((probs.shape[0], 1))
    cum = np.cumsum(probs, axis=-1)
    cum /= cum[:, -1:]
    idx = (cum <= u).sum(axis=-1).astype(np.int32)
    return np.minimum(idx, probs.shape[1] - 1)


def _accept_round(drafts, active, lens, max_new_tokens, eos_token_id,
                  tgt=None, pdists=None, qstack=None, host_rng=None):
    """One vectorized speculative accept/correct round (VERDICT r2 weak #6:
    O(1) host work per round — every quantity below is a whole-batch numpy
    expression, no per-row Python).

    Inputs: drafts (B, gamma); active (B,) rows still generating; lens (B,)
    tokens emitted so far. Greedy mode passes ``tgt`` (B, gamma+1) argmax
    tokens; sampling mode passes ``pdists`` (B, gamma+1, V) target dists,
    ``qstack`` (B, gamma, V) draft dists, and the host rng.

    Returns (n_take, bonus, bonus_ok, took_eos):
      n_take   (B,) accepted draft tokens to append this round (0 for
               inactive rows; quota- and eos-truncated),
      bonus    (B,) the correction/extra token per row,
      bonus_ok (B,) whether the bonus token is appended,
      took_eos (B,) whether an accepted draft token was eos (row finishes).
    """
    import numpy as np

    B, gamma = drafts.shape
    greedy = tgt is not None
    if greedy:
        match = drafts == tgt[:, :gamma]
    else:
        p_at = np.take_along_axis(pdists[:, :gamma], drafts[..., None], axis=2)[..., 0]
        q_at = np.take_along_axis(qstack, drafts[..., None], axis=2)[..., 0]
        u = host_rng.random((B, gamma))
        match = u < np.minimum(1.0, p_at / np.maximum(q_at, 1e-20))
    n_acc = np.where(match.all(axis=1), gamma, (~match).argmax(axis=1)).astype(np.int32)

    rem = np.maximum(max_new_tokens - lens, 0)
    n_take = np.minimum(n_acc, rem)
    if eos_token_id is not None:
        iota = np.arange(gamma, dtype=np.int32)[None]
        eos_mask = (drafts == eos_token_id) & (iota < n_take[:, None])
        took_eos = eos_mask.any(axis=1)
        first_eos = np.where(took_eos, eos_mask.argmax(axis=1), gamma)
        n_take = np.minimum(n_take, first_eos + 1).astype(np.int32)
    else:
        took_eos = np.zeros(B, bool)
    took_eos = took_eos & active
    n_take = np.where(active, n_take, 0).astype(np.int32)

    # bonus: the target's correction at the rejection point (n_take < gamma)
    # or an extra draw past a fully-accepted block (n_take == gamma) —
    # appended only for rows not finished by quota or an accepted eos
    bonus_ok = active & ~took_eos & (n_take == n_acc) & (lens + n_take < max_new_tokens)
    if greedy:
        bonus = np.take_along_axis(tgt, n_take[:, None], axis=1)[:, 0].astype(np.int32)
    else:
        p_b = np.take_along_axis(pdists, n_take[:, None, None], axis=1)[:, 0]  # (B, V)
        q_b = np.take_along_axis(
            qstack, np.minimum(n_take, gamma - 1)[:, None, None], axis=1
        )[:, 0]
        residual = np.maximum(p_b - q_b, 0.0)
        dist = np.where((n_take < gamma)[:, None], residual, p_b)
        tot = dist.sum(axis=1, keepdims=True)
        dist = np.where(tot > 0, dist / np.where(tot > 0, tot, 1.0), p_b)
        bonus = _sample_rows(dist, host_rng)
    return n_take, bonus, bonus_ok, took_eos


def speculative_decode_loop(
    t_prefill, t_segment, d_prefill, d_decode,
    params_t, params_d, tokens, cache_t, cache_d,
    max_new_tokens: int, gamma: int, temperature: float, top_k: int,
    top_p: float, rng, eos_token_id: Optional[int] = None,
) -> jnp.ndarray:
    """Draft-model speculative decoding (lossless).

    Each round: the draft proposes ``gamma`` tokens autoregressively, the
    target verifies all of them in ONE (gamma+1)-wide segment forward, and
    the standard accept/resample rule keeps the output distribution exactly
    the target's (greedy mode: token-for-token identical to plain greedy
    decode). Rows advance by their own accepted counts — the per-row
    position generalization in models/transformer.forward_with_cache.

    The reference has no counterpart (v0.9.1 predates spec-decode serving);
    this is a capability the TPU design gets nearly for free from static
    segment shapes. t_segment/d_decode take (B,) position vectors.
    """
    import numpy as np

    if max_new_tokens <= 0:
        return tokens
    B, S = tokens.shape
    greedy = temperature <= 0.0
    host_rng = np.random.default_rng(int(jax.random.randint(rng, (), 0, 2**31 - 1)))

    logits_t, cache_t = t_prefill(params_t, tokens, cache_t)
    _, cache_d = d_prefill(params_d, tokens, cache_d)
    last_logits = logits_t[:, -1]
    if greedy:
        t0 = np.asarray(jnp.argmax(last_logits, axis=-1), np.int32)
    else:
        t0 = _sample_rows(np.asarray(_filtered_probs(last_logits, temperature, top_k, top_p)), host_rng)

    # fixed-width output buffer + per-row lengths (vectorized bookkeeping;
    # rows that finish early are padded with eos below)
    pad = eos_token_id if eos_token_id is not None else 0
    out = np.full((B, max_new_tokens), pad, np.int32)
    out[:, 0] = t0
    lens = np.ones((B,), np.int32)
    last = t0.astype(np.int32)
    pos = np.full((B,), S, np.int32)
    done = (lens >= max_new_tokens) | (
        (t0 == eos_token_id) if eos_token_id is not None else np.zeros(B, bool)
    )

    while not done.all():
        # --- draft gamma proposals; one extra step caches d_gamma's kv so
        # the draft context stays complete when every proposal is accepted
        drafts = np.zeros((B, gamma), np.int32)
        qdists = []
        cur = last
        for i in range(gamma + 1):
            logits_d, cache_d = d_decode(
                params_d, jnp.asarray(cur[:, None]), cache_d, jnp.asarray(pos + i)
            )
            if i == gamma:
                break
            if greedy:
                d = np.asarray(jnp.argmax(logits_d[:, 0], axis=-1), np.int32)
            else:
                q = np.asarray(_filtered_probs(logits_d[:, 0], temperature, top_k, top_p))
                qdists.append(q)
                d = _sample_rows(q, host_rng)
            drafts[:, i] = d
            cur = d.astype(np.int32)

        # --- verify all gamma proposals in one target forward
        seg = np.concatenate([last[:, None], drafts], axis=1)  # (B, gamma+1)
        logits_v, cache_t = t_segment(params_t, jnp.asarray(seg), cache_t, jnp.asarray(pos))
        tgt = pdists = qstack = None
        if greedy:
            tgt = np.asarray(jnp.argmax(logits_v, axis=-1), np.int32)  # (B, gamma+1)
        else:
            V = logits_v.shape[-1]
            pdists = np.asarray(
                _filtered_probs(logits_v.reshape(B * (gamma + 1), V), temperature, top_k, top_p)
            ).reshape(B, gamma + 1, V)
            qstack = np.stack(qdists, axis=1)  # (B, gamma, V)

        # --- whole-batch accept / correct (no per-row Python)
        active = ~done
        n_take, bonus, bonus_ok, took_eos = _accept_round(
            drafts, active, lens, max_new_tokens, eos_token_id,
            tgt=tgt, pdists=pdists, qstack=qstack, host_rng=host_rng,
        )
        cols = lens[:, None] + np.arange(gamma, dtype=np.int32)[None]
        valid = (np.arange(gamma)[None] < n_take[:, None]) & (cols < max_new_tokens)
        br, bi = np.nonzero(valid)
        out[br, cols[br, bi]] = drafts[br, bi]
        lens = lens + n_take
        bb = np.nonzero(bonus_ok)[0]
        out[bb, lens[bb]] = bonus[bb]
        lens = lens + bonus_ok.astype(np.int32)
        last = np.where(bonus_ok, bonus, last).astype(np.int32)
        pos = pos + np.where(active, n_take + 1, 0).astype(np.int32)
        done = done | took_eos | (lens >= max_new_tokens)
        if eos_token_id is not None:
            done = done | (bonus_ok & (bonus == eos_token_id))

    # rows that stopped at eos are already eos-padded past their length
    # (the caller's eos truncation overwrites everything past the first
    # eos with eos anyway, so plain-decode parity is preserved)
    return jnp.concatenate([tokens, jnp.asarray(out)], axis=1)


def fused_generate_fn(holder, mesh, cfg, param_shardings, batch_size: int,
                      cache_len: int, max_new_tokens: int, temperature: float,
                      top_k: int, top_p: float, read_floor: Optional[int] = None):
    """(generate_fn, cache_sharding) for the fused whole-generation program,
    memoized on ``holder`` and keyed by every trace-shaping argument — ONE
    wiring shared by the InferenceEngine and the RLHF hybrid engine so the
    cache key and builder can never drift apart. ``read_floor`` (tight-read
    bucket floor, None = full-length reads) shapes the traced program, so
    it is part of the key."""
    return cached_fn(
        holder, "fused_generate",
        (batch_size, cache_len, max_new_tokens, temperature, top_k, top_p,
         read_floor),
        lambda: compile_generate_fn(mesh, cfg, param_shardings, batch_size,
                                    cache_len, max_new_tokens, temperature,
                                    top_k, top_p, read_floor=read_floor)[:2],
    )


def _journalled(holder, family: dict, kind: str, key, value):
    """Arm the build journal (telemetry/compile_log.py) on a fresh
    ``cached_fn`` entry: a bare callable, or a tuple led by one (the
    convention every builder follows — ``(fn, cache_sharding, ...)``);
    anything else passes through. After its first dispatch the entry is
    the bare program again, so a hit runs no wrapper."""
    led = isinstance(value, tuple) and bool(value)
    fn = value[0] if led else value
    if not callable(fn):
        return value
    record = getattr(holder, "_record_build", None)
    if record is None:
        from deepspeed_tpu.telemetry.compile_log import record_build

        record = functools.partial(
            record_build, hub=lambda: getattr(holder, "telemetry", None))

    def entry(program):
        return (program,) + value[1:] if led else program

    def settle(bare):
        if family.get(key) is armed:  # not evicted or rebuilt meanwhile
            family[key] = entry(bare)

    armed = entry(record(fn, kind, key, settle=settle))
    return armed


def cached_fn(holder, kind: str, key, builder, slots: int = 4):
    """Bounded per-family memoization of compiled functions on ``holder``
    (InferenceEngine and TpuHybridEngine share this; a long-running server
    alternating shapes must not retain unbounded compiled programs).

    Hit/miss accounting rides along for telemetry: ``holder`` grows
    ``_compile_hits``/``_compile_misses`` ints (request events diff the
    miss count to tag compile-triggering requests), and a holder carrying
    an enabled ``telemetry`` hub gets per-family labeled counters. A miss
    additionally arms the build journal (telemetry/compile_log.py) on the
    fresh entry: its first dispatch — the one that pays tracing, lowering
    and the XLA compile or cache load — leaves a journal entry whatever the
    hub's state, and on a live hub a ``compile_event`` keyed
    (family=``kind``, shapes key), flagged ``recompile`` when this hub
    compiled the same key before (LRU eviction churn made visible)."""
    cache = getattr(holder, "_fn_cache", None)
    if cache is None:
        cache = holder._fn_cache = {}
    family = cache.setdefault(kind, {})
    miss = key not in family
    tele = getattr(holder, "telemetry", None)
    if miss:
        if len(family) >= slots:
            family.pop(next(iter(family)))  # evict least-recently-used
        family[key] = _journalled(holder, family, kind, key, builder())
    else:
        family[key] = family.pop(key)  # refresh recency (LRU, not FIFO)
    attr = "_compile_misses" if miss else "_compile_hits"
    setattr(holder, attr, getattr(holder, attr, 0) + 1)
    if tele is not None and tele.enabled:
        tele.registry.counter(
            "compile_cache", {"kind": kind, "outcome": "miss" if miss else "hit"}
        ).inc()
    return family[key]


def speculative_generate(cfg, params, draft, tokens, max_new_tokens: int,
                         temperature: float, top_k: int, top_p: float, rng,
                         gamma: int, max_out_tokens: Optional[int], get_fns,
                         eos_token_id: Optional[int] = None) -> jnp.ndarray:
    """Shared speculative-decoding orchestration (cache sizing with the
    verify-round overrun slack, fn lookup, cache init, loop) for BOTH the
    InferenceEngine and the RLHF hybrid engine. ``get_fns(B, cache_len) ->
    (t_prefill, t_segment, cache_sh)`` supplies the target programs;
    ``draft`` is an InferenceEngine providing its own via _spec_fns."""
    if draft.cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"draft must share the vocabulary: draft vocab "
            f"{draft.cfg.vocab_size} != target vocab {cfg.vocab_size}")
    if gamma < 1:
        raise ValueError(f"speculative.num_draft_tokens must be >= 1, got {gamma}")
    B, S = tokens.shape
    total = S + max_new_tokens + gamma + 1  # verify-round overrun slack
    cache_len = bounded_cache_len(total, max(cfg.max_seq_len, total), max_out_tokens)
    t_prefill, t_segment, cache_sh = get_fns(B, cache_len)
    d_prefill, d_decode, d_cache_sh = draft._spec_fns(B, cache_len)
    cache_t = jax.device_put(kv_cache.init(cfg, B, cache_len), cache_sh)
    cache_d = jax.device_put(kv_cache.init(draft.cfg, B, cache_len), d_cache_sh)
    return speculative_decode_loop(
        t_prefill, t_segment, d_prefill, d_decode,
        params, draft.params, tokens, cache_t, cache_d,
        max_new_tokens, gamma, temperature, top_k, top_p, rng,
        eos_token_id=eos_token_id,
    )


def bounded_cache_len(total: int, max_seq_len: int, max_out_tokens: Optional[int]) -> int:
    """KV-cache allocation: bounded by max_out_tokens, grown when the request
    needs more, never past max_seq_len."""
    if not max_out_tokens:
        return max_seq_len
    return max(total, min(max_seq_len, max_out_tokens))
