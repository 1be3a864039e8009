"""Continuous (in-flight) batching for the inference engine.

Modern serving capability BEYOND the v0.9.1 reference (its inference
engine generates one static batch at a time; continuous batching arrived
in later serving stacks): a fixed pool of sequence slots shares KV cache,
new requests are admitted into free slots while other slots keep decoding,
and finished sequences free their slot immediately — no head-of-line
blocking on the longest sequence.

TPU-shaped design: everything is static-shape, and — since PERF.md's
central finding is that host-blocked dispatch, not FLOPs, governs decode
throughput — the scheduler tick is built so device compute and host
scheduling OVERLAP instead of alternating:

- **On-device acceptance** (decoding.compile_pool_tick_fn): sampling,
  EOS/quota done detection, position advance, and per-row emission
  masking run inside the compiled tick program. Each tick returns one
  small packed ``(tokens, n_emitted, done)`` int32 buffer, fetched with a
  single coalesced device get — never per-row logits or host-side
  truncation.
- **Dispatch-ahead pipelining** (``pipeline_depth``, default 1): the
  tick program THREADS its decode state (``last_tok``/``done`` and the
  donated KV cache) through device outputs, so tick N+1 is dispatched on
  tick N's output futures BEFORE the host blocks on tick N's packed
  result. While the host parses results, admits requests, and runs the
  serving layer's scheduling, the device is already executing the next
  tick. ``pipeline_depth=0`` is the fully synchronous scheduler; token
  streams are bitwise identical in both modes (per-request rng — see
  decoding.request_keys — makes streams independent of slot/tick
  placement). The visible difference is only WHEN a token is returned:
  ``step()`` reports the results of the tick(s) it retired, which lag
  dispatch by up to ``pipeline_depth`` ticks.
- **Prefill/decode fusion** (``fused_prefill``, default on for
  single-token ticks): admission no longer dispatches a blocking B=1
  ragged prefill + cache splice. Instead one admitting row's next prompt
  chunk (bucketed fixed shapes, ``prefill_chunk`` cap) rides INSIDE the
  same tick program that decodes the active rows — Dynamic-SplitFuse
  style, one more static-shape program per (chunk bucket, read bucket)
  family — so decode ticks proceed during a long prompt's prefill. With
  fusion off (or burst ticks), admission prefills ``prompt[:-1]`` through
  the B=1 bucket program + splice WITHOUT sampling or fetching: the last
  prompt token is re-fed by the first decode tick, whose logits yield the
  first generated token, keeping every admission dispatch-only.
- **Donation**: the pool KV cache and the threaded tick state are
  ``donate_argnums`` operands of every tick program, so a tick returns
  the pool it was given instead of allocating a second one. That saves
  the allocation; the TRAFFIC is the model's (``forward_with_cache``
  carries the pool through its layer scan and rewrites in place only the
  window a tick reads — no layer-sized copy under the loop).

Bucketed KV (VERDICT r4 #9): ``cache_buckets=[(slots, len), ...]``
partitions the slots into pools with different cache lengths; admission
places each request in the smallest-length pool it fits, falling back to
longer pools when full — the static-shape TPU analogue of paged KV.
``kv_cache_bytes()`` reports the footprint for both layouts.

    eng = ContinuousBatchingEngine(model, config={"dtype": "bfloat16"},
                                   cache_buckets=[(6, 256), (2, 2048)])
    rid = eng.submit([12, 7, 99], max_new_tokens=32)
    while eng.has_work():
        eng.step()            # dispatch tick N+1, retire tick N
    out = eng.result(rid)     # prompt + generated tokens (np.int32)

``tokens_per_tick=k`` fuses k decode steps per tick into one compiled
scan (k× fewer host dispatches per token); admission then happens between
bursts. Tokens a burst computes past a row's done flag are wasted work,
counted by the ``burst_wasted_tokens`` telemetry counter.

Tight-read ticks (engine config ``kv_tight_read``, default on): every
tick attends a bucketed ACTIVE length (docs/inference.md "Cache
geometry"). Finished requests emit an ``inference_request`` event with
``kv_bytes_read`` / ``kv_bytes_per_token`` / ``kv_dtype`` /
``cache_utilization``; each ``step()`` additionally records
``tick_dispatch_ms`` / ``tick_block_ms`` / in-flight depth (histograms,
gauge, and a per-step ``serving_tick`` trace event) so the
overlap win is measurable from traces alone — ``tick_stats()`` exposes
the same accounting in-process.
"""

import functools
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import ngram
from deepspeed_tpu.inference.decoding import (
    cached_fn,
    compile_pool_tick_fn,
    compile_ragged_prefill_fn,
    compile_row_update_fn,
    compile_segment_fn,
    compile_spec_pool_tick_fn,
    compile_spec_row_update_fn,
    read_bucket,
    TICK_STATS,
)
from deepspeed_tpu.ops.pallas.mla_attention import expanded_entries
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.telemetry import compile_log
from deepspeed_tpu.telemetry.spans import host_span

# admission/bucket sizing shares the ONE bucketing rule with the tight-read
# geometry (decoding.read_bucket); the old local name stays importable
_bucket = read_bucket

# fused-prefill chunk program widths. A one-kind model's tick runs the chunk
# beside the rows (B + W tokens), where a full-width chunk costs under a
# hundredth of a tick more than a narrow one: ONE width, the pool's chunk
# cap, so one fused program a read bucket (floor None). A layer plan's chunks
# ride the flash chunk kernel (key tiles of 128): power-of-2 widths from 256
# up to the cap. The speculative pool's chunk still runs the (B, W) segment
# program under every slot, where a narrow last chunk pays: widths from 16 up.
_PLAN_CHUNK_FLOOR = 256
# a layer plan's tick counters that a ``serving_tick`` event carries, where the plan has them
_PLAN_TICK_FIELDS = ("moe_expert_layers", "moe_experts_hit", "moe_buffer_rows", "moe_filled_rows",
                     "ssm_chunk_tokens", "ssm_step_rows")
_SPEC_CHUNK_FLOOR = 16


@dataclass
class _Request:
    rid: int
    prompt: np.ndarray  # (len,) int32 — full prompt incl. any shared prefix
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    pool: Optional[int] = None
    done: bool = False
    # snapshot of the registered prefix entry (tokens/cache/bucket), taken
    # at submit time so unregister_prefix cannot strand a queued request
    prefix: Optional[dict] = None
    # device-side emission quota (gen_base + max_new_tokens; placement
    # guarantees the pool row holds prompt + max_new_tokens)
    quota: int = 0
    # recovery resume: the device ``gen`` counter starts here instead of
    # 0, so the per-token RNG keys fold_in(fold_in(base, rid), gen)
    # continue the original stream — a request re-admitted after engine
    # loss with prompt = original + emitted and gen_base = len(emitted)
    # draws its next token with the exact key the lost engine would have
    gen_base: int = 0
    # fused prefill: remaining (tokens, pos0, n_real, emits) prompt chunks
    # still to ride a tick; None/empty = decode-active
    chunks: Optional[List[tuple]] = None
    # KV-cache bytes this request's row streamed across its decode ticks
    # (host accounting at the read length each retired tick dispatched)
    kv_bytes_read: int = 0
    # called once, with no arguments, when this request's FIRST prefill work
    # is dispatched (its first fused chunk, or the admission-time separate /
    # speculative prefill), then dropped: the submitter's lifecycle mark
    # (the serving layer stamps ``ServeRequest.prefill_start_t`` on its own
    # clock). None = nobody asked.
    on_prefill_start: Optional[Callable[[], None]] = None
    # speculative accounting (spec ticks only): drafts proposed for this
    # request vs drafts its verify rounds accepted
    spec_drafted: int = 0
    spec_accepted: int = 0
    # tick-window span accumulation (span_hook): consecutive retired
    # ticks of one kind coalesce into one window span, flushed on kind
    # change / span_window_ticks / finish — host bookkeeping only, the
    # times come from clocks the tick loop already reads
    win_kind: Optional[str] = None
    win_t0: float = 0.0
    win_t1: float = 0.0
    win_ticks: int = 0
    win_tokens: int = 0
    win_drafted: int = 0
    win_accepted: int = 0


class _TickCosts(NamedTuple):
    """What one dispatch of a tick program counts that follows from its
    (pool, chunk width, read bucket) alone (``_Pool.tick_costs``)."""

    row_read_bytes: int       # KV bytes one row streams a step (``kv_bytes_read``)
    block_write_bytes: int    # ``kv_cache.rows_block_write_bytes``: 0 = the window write
    reads_to_length: bool     # ``kv_cache.rows_read_to_length``


class _TickRecord:
    """Host bookkeeping for one DISPATCHED (possibly in-flight) pool tick:
    the packed result future plus everything needed to attribute it when
    the tick is retired."""

    __slots__ = ("packed", "live", "k", "row_bytes", "fused", "spec", "t0")

    def __init__(self, packed, live, k, row_bytes, fused, spec=0):
        self.packed = packed          # device future: (B, k+2) int32
        self.live = live              # slot -> _Request live at dispatch
        self.k = k                    # burst length (1 for plain/fused)
        self.row_bytes = row_bytes    # KV bytes one row streams per step
        self.fused = fused            # carried a prefill chunk
        self.spec = spec              # speculative round: gamma (0 = plain;
        # packed is (B, gamma+4) and row_bytes is the WHOLE round's bytes)
        self.t0 = 0.0                 # dispatch time for window spans
        # (time.monotonic, set by _step_body only when a span_hook is
        # installed — zero otherwise, never read)


class _Pool:
    """One static-shape slot pool: ``n_slots`` rows of ``length`` KV."""

    def __init__(self, engine, n_slots: int, length: int):
        self.n_slots = n_slots
        self.length = length
        # the pool's companion programs journal their first dispatch
        # (telemetry/compile_log.py); as attributes they keep the wrapper
        # and its one flag test
        record_build = engine._record_build
        self.segment_fn, self.cache_sh, _ = compile_segment_fn(
            engine.mesh, engine.cfg, engine._eng.param_shardings, n_slots, length
        )
        self.segment_fn = record_build(self.segment_fn, "pool_segment",
                                       (n_slots, length))
        self.cache = jax.device_put(
            kv_cache.init(engine.cfg, n_slots, length), self.cache_sh
        )
        self.active: Dict[int, _Request] = {}       # slot -> request
        # device-THREADED tick state: the tick programs return these as
        # outputs that feed the next tick's inputs, so a tick can be
        # dispatched before the previous one's results are fetched. Free
        # slots start done=1 (never emit); admission flips a row live.
        # Placed REPLICATED over the mesh up front (the tick programs'
        # row-state sharding) so the first tick never pays a reshard.
        from jax.sharding import NamedSharding, PartitionSpec

        self.row_sh = NamedSharding(engine.mesh, PartitionSpec())
        self.last_tok_dev, self.done_dev = self.fresh_rows()
        self.set_row_fn = compile_row_update_fn(engine.mesh, engine.cfg,
                                                n_slots,
                                                donate=engine.donate_cache)
        self.set_row_fn = record_build(self.set_row_fn, "row_update",
                                       (n_slots,))
        # speculative tick state (engine.spec_gamma > 0): pos/gen join the
        # device-THREADED arrays — a spec row advances by its own accepted
        # count, which only the device knows at dispatch time — and
        # draft-model mode keeps a second KV cache with the SAME bucket
        # geometry plus its own segment program for draft prefill
        self.draft_cache = None
        if engine.spec_gamma:
            self.pos_dev, self.gen_dev = self.fresh_spec_rows()
            self.spec_set_row_fn = compile_spec_row_update_fn(
                engine.mesh, engine.cfg, n_slots,
                donate=engine.donate_cache)
            self.spec_set_row_fn = record_build(
                self.spec_set_row_fn, "spec_row_update", (n_slots,))
            if engine.spec_mode == "draft":
                deng = engine._draft_eng
                self.draft_segment_fn, self.draft_cache_sh, _ = \
                    compile_segment_fn(engine.mesh, engine.draft_cfg,
                                       deng.param_shardings, n_slots, length)
                self.draft_segment_fn = record_build(
                    self.draft_segment_fn, "pool_segment",
                    (n_slots, length, "draft"))
                self.draft_cache = jax.device_put(
                    kv_cache.init(engine.draft_cfg, n_slots, length),
                    self.draft_cache_sh)
        # ds-audit capture of the pool's companion programs (the tick
        # variants notify from _tick_fn as they are built)
        from deepspeed_tpu.analysis.program import capture

        if capture.active():
            def row_args(n=n_slots, pool=self):
                row = jax.ShapeDtypeStruct((n,), jnp.int32)
                state = pool.cache.get(kv_cache.StateSpec.name)   # a state pool rides the flip
                return (row, row, 0, 0, 0) + (() if state is None else (jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state),))

            def seg_args(n=n_slots, pool=self, eng=engine):
                def sds(a):
                    return jax.ShapeDtypeStruct(a.shape, a.dtype)

                return (jax.tree.map(sds, eng._eng.params),
                        jax.ShapeDtypeStruct((n, 8), jnp.int32),
                        jax.tree.map(sds, pool.cache),
                        jax.ShapeDtypeStruct((n,), jnp.int32))

            capture.notify_program("pool_segment", "", self.segment_fn,
                                   seg_args, meta=engine._audit_meta)
            capture.notify_program("pool_row_update", "", self.set_row_fn,
                                   row_args, meta=engine._audit_meta)
            if engine.spec_gamma:
                def spec_row_args(n=n_slots):
                    row = jax.ShapeDtypeStruct((n,), jnp.int32)
                    return (row, row, row, row, 0, 0, 0, 0, 0)

                capture.notify_program("pool_spec_row_update", "",
                                       self.spec_set_row_fn, spec_row_args,
                                       meta=engine._audit_meta)
                if engine.spec_mode == "draft":
                    def dseg_args(n=n_slots, pool=self, eng=engine):
                        def sds(a):
                            return jax.ShapeDtypeStruct(a.shape, a.dtype)

                        return (jax.tree.map(sds, eng._draft_eng.params),
                                jax.ShapeDtypeStruct((n, 8), jnp.int32),
                                jax.tree.map(sds, pool.draft_cache),
                                jax.ShapeDtypeStruct((n,), jnp.int32))

                    capture.notify_program("pool_segment", "draft",
                                           self.draft_segment_fn, dseg_args,
                                           meta=engine._draft_audit_meta)
        # host DISPATCH mirrors: the position/emission count each row will
        # have reached once every dispatched tick retires. Exact for live
        # rows (a live row advances by exactly k per burst until done);
        # rows whose finish the host has not yet observed are excluded
        # from dispatch, so the mirrors never need reconciliation.
        self.disp_pos = np.zeros(n_slots, np.int32)
        self.disp_gen = np.zeros(n_slots, np.int32)
        # fused prefill: admitted requests whose prompt chunks still need
        # ticks, FIFO — one admitting row rides each tick
        self.prefill_q: "deque[_Request]" = deque()
        self.chunk_cap = min(engine.prefill_chunk, length)
        # tick programs keyed (chunk_width, read_len): shape/sampling are
        # fixed for the engine's lifetime, so they live on the pool —
        # bounded by the (chunk bucket × read bucket) family size, never
        # evicted (an LRU consulted per tick could recompile mid-serve)
        self.tick_fns: Dict[tuple, object] = {}
        # beside each program, under its key: what a dispatch of it counts
        # off (pool, chunk, read_len) alone, worked out once as it is built
        self.tick_costs: Dict[tuple, _TickCosts] = {}

    def free_slots(self) -> List[int]:
        return [s for s in range(self.n_slots) if s not in self.active]

    def fresh_rows(self):
        """``(last_tok, done)`` as a pool starts with them, every slot free,
        placed as the tick programs take and return them (the pool's own,
        and a warm-up's throwaway pair: the programs donate them)."""
        n = self.n_slots
        return (jax.device_put(jnp.zeros(n, jnp.int32), self.row_sh),
                jax.device_put(jnp.ones(n, jnp.int32), self.row_sh))

    def fresh_spec_rows(self):
        """``(pos, gen)`` of a speculative pool, every row parked, likewise."""
        n = self.n_slots
        return (jax.device_put(jnp.full(n, self.length, jnp.int32), self.row_sh),
                jax.device_put(jnp.zeros(n, jnp.int32), self.row_sh))


class ContinuousBatchingEngine:
    """Slot-pool serving loop over the compiled pool-tick programs."""

    @compile_log.phase("pools")
    def __init__(self, model, config=None, params=None, mesh=None,
                 max_slots: Optional[int] = None, cache_len: Optional[int] = None,
                 cache_buckets: Optional[List] = None,
                 eos_token_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 tokens_per_tick: int = 1, pipeline_depth: int = 1,
                 fused_prefill: bool = True,
                 prefill_chunk: Optional[int] = None,
                 donate_cache: bool = True,
                 fetch_timeout_s: Optional[float] = None,
                 draft_model=None, draft_params=None):
        from deepspeed_tpu.inference.engine import InferenceEngine

        self._eng = InferenceEngine(model, config=config, params=params,
                                    mesh=mesh, seed=seed)
        # slot caches are written at per-row depths (ragged admission), which
        # the rolling ring's aligned-path math does not cover — the slot
        # pools run plain full/bucket-length caches; bucketing already bounds
        # the footprint (see PERF.md bucketed-KV table)
        self.cfg = self._eng._ring_off_cfg
        self.mesh = self._eng.mesh
        self._chunk_floor = (None if self.cfg.layer_kinds is None
                             else _PLAN_CHUNK_FLOOR)
        # a layer plan with expert layers: its ticks return routing counters
        self._moe_stats = (self.cfg.layer_kinds is not None
                           and self.cfg.moe_num_experts > 0)
        # ... with delta-rule layers: a state pool, reset when a row is
        # admitted, and two counters more among the routing counters
        self._state_pool = kv_cache.state_spec(self.cfg) is not None
        # ... with latent-attention layers: a latent pool, whose rows' read
        # and chunks' expansion the host counts from each tick's own lengths
        self._latent_pool = any(s.name == kv_cache.LATENT for s in kv_cache.specs(self.cfg))
        self.eos_token_id = eos_token_id
        self.temperature, self.top_k, self.top_p = temperature, top_k, top_p
        assert tokens_per_tick >= 1, tokens_per_tick
        assert pipeline_depth >= 0, pipeline_depth
        self.tokens_per_tick = tokens_per_tick
        # dispatch-ahead pipelining: how many ticks may be in flight before
        # the host blocks on the oldest packed result. 0 = fully
        # synchronous (retire every tick before returning from step()).
        self.pipeline_depth = pipeline_depth
        # fused prefill requires single-token ticks: a burst program has no
        # chunk row (admission between bursts uses the separate path)
        self.fused_prefill = fused_prefill and tokens_per_tick == 1
        self.prefill_chunk = (prefill_chunk
                              or self._eng.config.prefill_chunk_size or 128)
        # donate the KV cache + threaded state through the tick programs
        # (no per-tick cache copy in HBM). The jax CPU backend implements
        # donation by blocking at dispatch — which serializes the tick
        # chain — so virtual-mesh overlap measurements pass False here
        # (ds_loadgen --no-donate); on TPU donation and async dispatch
        # compose and this stays on.
        self.donate_cache = donate_cache
        # ONE base key: every sampled token draws from
        # fold_in(fold_in(base, rid), token_index) on device, so streams
        # are identical across pipeline depths / fusion / slot placement
        self._base_key = jax.random.PRNGKey(seed)

        # speculative pooled ticks (config speculative.enabled + .pool):
        # every tick proposes spec_gamma tokens per active row and ONE
        # target forward verifies them (decoding.compile_spec_pool_tick_fn)
        spec = self._eng.config.speculative
        if self.cfg.layer_kinds is not None and (
                (spec.enabled and spec.pool) or not fused_prefill
                or tokens_per_tick != 1):
            raise NotImplementedError(
                "a layer-plan model is served by single-token ticks with "
                "fused prefill chunks (no speculative pool ticks, bursts or "
                "separate prefill: " + (
                    "a burst or a verify round would have to roll a state "
                    "pool's recurrent state back" if self._state_pool else
                    "the plan's tick is written for one token a row and one "
                    "chunk") + ")")
        self.spec_gamma = 0
        self.spec_mode = None
        self._draft_eng = None
        self.draft_cfg = None
        if spec.enabled and spec.pool:
            if spec.mode not in ("draft", "ngram"):
                raise ValueError(
                    f"speculative.mode must be 'draft' or 'ngram', "
                    f"got {spec.mode!r}")
            if tokens_per_tick != 1:
                raise ValueError(
                    "speculative pool ticks require tokens_per_tick=1 "
                    "(the gamma-wide verify round IS the burst)")
            if spec.num_draft_tokens < 1:
                raise ValueError(
                    f"speculative.num_draft_tokens must be >= 1, "
                    f"got {spec.num_draft_tokens}")
            if spec.mode == "draft":
                if draft_model is None:
                    raise ValueError(
                        "speculative.mode='draft' needs draft_model= (a "
                        "smaller same-vocabulary model), or set "
                        "speculative.mode='ngram' for draft-free "
                        "self-drafting")
                # the draft shares the cache format (int8 KV must cover
                # both trees) and the mesh — its params are partitioned by
                # the same regex rules / annotations as the target's
                self._draft_eng = InferenceEngine(
                    draft_model,
                    config={"dtype": self._eng.config.dtype,
                            "kv_cache_dtype": self._eng.config.kv_cache_dtype,
                            "kv_tight_read": self._eng.config.kv_tight_read,
                            "kv_read_floor": self._eng.config.kv_read_floor,
                            "mesh": self._eng.config.mesh},
                    params=draft_params, mesh=self.mesh, seed=seed)
                self.draft_cfg = self._draft_eng._ring_off_cfg
                if self.draft_cfg.vocab_size != self.cfg.vocab_size:
                    raise ValueError(
                        f"draft must share the vocabulary: draft vocab "
                        f"{self.draft_cfg.vocab_size} != target vocab "
                        f"{self.cfg.vocab_size}")
            self.spec_gamma = spec.num_draft_tokens
            self.spec_mode = spec.mode
        elif draft_model is not None:
            raise ValueError(
                "draft_model= given but speculative pool ticks are off: "
                "set speculative={'enabled': True, 'pool': True} "
                "(mode='draft')")

        if cache_buckets is None:
            cache_len = min(cache_len or self.cfg.max_seq_len, self.cfg.max_seq_len)
            cache_buckets = [(max_slots if max_slots is not None else 4, cache_len)]
        else:
            assert cache_len is None, "pass cache_buckets OR cache_len, not both"
            assert max_slots is None, (
                "pass cache_buckets OR max_slots, not both (slot counts come "
                "from the buckets)")
            cache_buckets = sorted(
                ((int(s), int(l)) for s, l in cache_buckets), key=lambda sl: sl[1]
            )
            for s, l in cache_buckets:
                assert s >= 1 and 1 <= l <= self.cfg.max_seq_len, (s, l)
        # pools sorted by length: admission scans for the smallest fit
        self._pools = [_Pool(self, s, l) for s, l in cache_buckets]
        self.max_slots = sum(p.n_slots for p in self._pools)
        self.cache_len = max(p.length for p in self._pools)

        self._next_rid = 0
        self._next_pid = 0
        self._prefixes: Dict[int, dict] = {}  # prefix caching (register_prefix)
        self._pending: List[_Request] = []
        self._results: Dict[int, np.ndarray] = {}
        # dispatched-but-not-retired ticks, oldest first; each entry maps
        # pool index -> _TickRecord for one scheduler tick
        self._inflight: "deque[Dict[int, _TickRecord]]" = deque()
        # host-overhead accounting for the tick loop (tick_stats());
        # telemetry mirrors it into histograms/counters when enabled
        self._tick_stats = {"ticks": 0, "steps": 0, "dispatch_ms": 0.0,
                            "block_ms": 0.0, "tokens": 0, "wasted_tokens": 0,
                            "capacity_tokens": 0, "fused_prefill_ticks": 0,
                            "spec_drafted": 0, "spec_accepted": 0,
                            # the host ledger's rows that belong to the
                            # tick loop (docs/telemetry.md "The serving
                            # loop's ledger"): the admission loop inside
                            # dispatch_ms, _retire after its fetch, the
                            # wall time with no tick in flight, and the
                            # ticks whose result was on hand before the
                            # host asked for it
                            "admit_ms": 0.0, "attribute_ms": 0.0,
                            "inflight_empty_ms": 0.0,
                            "ticks_ready_at_retire": 0,
                            # the two kinds of tick told apart (counted at
                            # dispatch like ``ticks``; blocked ms charged
                            # where each tick retires, by its own kind)
                            "plain_ticks": 0, "block_ms_plain": 0.0,
                            "block_ms_fused": 0.0,
                            # how long the prefill queues stood when
                            # each step looked (÷ steps = mean depth)
                            "prefill_q_depth_sum": 0,
                            # what the fused ticks' chunks carried, counted
                            # where a chunk is dispatched: real prompt
                            # tokens, and the pads that filled the
                            # program's width (pad share = pad / (real + pad))
                            "prefill_chunk_tokens": 0,
                            "prefill_pad_tokens": 0,
                            # ticks whose rows' one-token KV write took
                            # the block path (kv_cache.takes_block_write,
                            # told here from the tick's read bucket), the
                            # live rows whose blocks those writes moved (a
                            # parked row or an empty slot moves nothing) and
                            # the bytes they fetched and stored: a live
                            # row's block, in and out, a leaf, a layer-step
                            # and a token step
                            "block_write_ticks": 0, "block_write_rows": 0,
                            "block_write_bytes": 0,
                            # ticks whose rows' attention read each row to
                            # its own length (kv_cache.takes_length_read),
                            # the slots that kernel fetched (whole blocks a
                            # live row, a layer and leaf) and the slots
                            # those rows hold (read / live = the over-read)
                            "length_read_ticks": 0, "row_keys_read": 0,
                            "row_keys_live": 0,
                            # programs this engine built (first dispatches,
                            # telemetry/compile_log.py) and what they cost:
                            # a window's delta is what it built
                            "programs_built": 0, "program_build_ms": 0.0}
        if self.cfg.layer_kinds is not None:
            # what those chunks' attention had to do: (query, key) pairs
            # attended in a full and in a window layer, keys a full layer read
            self._tick_stats.update(prefill_pairs_full=0,
                                    prefill_pairs_window=0, prefill_keys_full=0)
            # ... and what the flash chunk kernel did for it, summed over the
            # plan's layers: score tiles computed (a tile a query head), of
            # them masked, K/V tiles fetched (a tile a key-value head) —
            # ``layer_plan.chunk_attention_tiles``, the kernel's own walk
            from deepspeed_tpu.models.layer_plan import chunk_attention_tiles

            self._chunk_tiles = chunk_attention_tiles
            self._tick_stats.update(prefill_tiles_visited=0, prefill_tiles_masked=0,
                                    prefill_kv_tile_fetches=0)
            self._window = max(k.window for k in self.cfg.layer_kinds)
        if self._moe_stats:
            # expert routing as the ticks report it (decoding.TICK_STATS):
            # assignments made / to the experts held here, and per tick
            # the most one held expert got in a layer against the mean
            # (sum of the ratios ÷ moe_ticks = mean imbalance)
            self._tick_stats.update(
                moe_ticks=0, moe_assignments=0, moe_held_assignments=0, moe_experts_hit=0,
                moe_expert_tokens_most_sum=0, moe_expert_tokens_mean_sum=0.0,
                moe_imbalance_sum=0.0, moe_expert_layers=0)
            # ... and, where an expert layer is a layer of its own, the buffer rows its
            # grouped matmuls walked against those an assignment filled (layer_plan.ROW_STATS)
            from deepspeed_tpu.models.layer_plan import counts_rows

            self._row_stats = counts_rows(self.cfg)
            if self._row_stats:
                self._tick_stats.update(moe_buffer_rows=0, moe_filled_rows=0)
        if self._state_pool:
            # as the ticks report them (layer_plan.GDN_STATS): real tokens
            # the chunks' scans took, rows whose state a tick stepped, named
            # after the pool's mixer (gdn_* / ssm_*)
            from deepspeed_tpu.models.layer_plan import state_counters

            self._state_counters = state_counters(self.cfg)
            self._tick_stats.update(dict.fromkeys(self._state_counters, 0))
        # ... walked several times over the same weights (a looped model): every pass keeps
        # keys and values of its own, so the rows' read crosses loop_steps x the layers
        self._loop_steps = self.cfg.loop_steps
        if self._loop_steps > 1:
            # passes run (loop_steps a token step: what a later early exit would lower),
            # and of ONE layer-step's pool the positions the rows' attention fetched
            # (every slot to the read bucket) against those the live rows hold
            self._tick_stats.update(loop_passes=0, loop_kv_positions_read=0,
                                    loop_kv_positions_live=0)
        if self._latent_pool:
            # cached entries the rows' kernel read (each live row to its own
            # length, a latent layer; summed over rows and ticks), and
            # entries a chunk expanded into heads again (its row up to the
            # end of the key tile that holds the chunk's last key, a latent
            # layer: ``mla_attention.expanded_entries``)
            self._tick_stats.update(mla_row_keys=0, mla_expand_tokens=0)
        # cancelled rids, remembered so status()/result() answer precisely
        # instead of "unknown" — BOUNDED (oldest evicted past 4096): a
        # long-running server cancels routinely and must not leak an int
        # per cancellation for its lifetime. Evicted rids age back to
        # "unknown", which is also what collected results report.
        self._cancelled: "OrderedDict[int, None]" = OrderedDict()
        self._cancelled_cap = 4096
        # serving-layer enrichment point: called in _finish with
        # (rid, event dict) and may mutate/replace the event before it is
        # emitted (deepspeed_tpu/serving adds queue_ms/priority/deadline_met
        # and retags path:"serving"). None = emit the event as built.
        self.request_event_hook: Optional[Callable[[int, dict], Optional[dict]]] = None
        # request-scoped tracing (docs/telemetry.md "Request tracing"):
        # called with (rid, span_kind, t0, t1, attrs) when a coalesced
        # tick window retires — prefill_chunk / decode_window /
        # spec_verify_round, times in time.monotonic seconds. The serving
        # layer installs this ONLY when its hub is live, so the default
        # tick loop pays nothing (no clock reads, no window bookkeeping).
        # Windows coalesce up to span_window_ticks consecutive same-kind
        # ticks per request: span volume scales ~tokens/window, not
        # per-tick.
        self.span_hook: Optional[Callable[[int, str, float, float, dict], None]] = None
        self.span_window_ticks = 16
        # fault-injection hook (serving/faults.py FaultInjector): called
        # with (point, info) at "dispatch" (top of step, BEFORE any state
        # mutates), "retire" (before each packed-result fetch) and
        # "set_row" (admission row flip). The hook may raise — that IS the
        # injection; no monkeypatching. None = no injection.
        self.fault_hook: Optional[Callable[[str, dict], None]] = None
        # watchdog: a packed-result fetch in _retire exceeding this many
        # seconds raises TimeoutError (on TPU a preempted device surfaces
        # as a stuck/erroring fetch; detection is post-hoc — the fetch
        # itself cannot be interrupted from this thread). None = off.
        self.fetch_timeout_s = fetch_timeout_s
        # True once an exception escaped mid-tick: device-threaded state,
        # dispatch mirrors and in-flight results can no longer be trusted
        # to agree, so the serving layer must NOT retry step() — it
        # rebuilds instead (bitwise-safe: see docs/serving.md recovery)
        self.poisoned = False
        self._tick_index = 0  # step() calls attempted (fault-plan clock)
        # the host ledger (tick_stats() ``inflight_empty_ms``): since when
        # no tick has been in flight — stamped at the fetch that emptied
        # ``_inflight``, closed by the dispatch that fills it again — and
        # what the last step() charged to dispatch, block and attribution
        # together (the serving layer takes it off its own step's wall)
        self._inflight_empty_t = self._fetched_t = time.perf_counter()
        self.last_step_ms = 0.0
        # one memory_snapshot per engine generation: the live ops plane's
        # HBM attribution baseline (serving recovery emits the "rebuild"
        # one after re-injecting its hub into a replacement engine); the
        # enabled guard keeps telemetry-off builds from walking the trees
        if self._eng.telemetry.enabled:
            self.memory_snapshot("build")

    def _record_build(self, fn, family: str, key, **kw):
        """``compile_log.record_build`` with this engine's hub, tick index
        and ``tick_stats()`` sums, each read at the program's first
        dispatch."""
        return compile_log.record_build(
            fn, family, key, hub=lambda: self._eng.telemetry,
            tick=lambda: self._tick_index, sums=lambda: self._tick_stats, **kw)

    @property
    def telemetry(self):
        """The engine stack's ONE telemetry hub (owned by the inner
        InferenceEngine; serving recovery re-injects it into replacement
        engines so counters and the trace span generations)."""
        return self._eng.telemetry

    # -- single-pool compatibility surface (tests, introspection) --------
    @property
    def cache(self):
        assert len(self._pools) == 1, "cache is per-pool; use _pools[i].cache"
        return self._pools[0].cache

    @cache.setter
    def cache(self, value):
        assert len(self._pools) == 1
        self._pools[0].cache = value

    @property
    def _active(self) -> Dict[int, _Request]:
        """All active requests keyed by (pool-flattened) slot index."""
        out = {}
        base = 0
        for p in self._pools:
            for s, r in p.active.items():
                out[base + s] = r
            base += p.n_slots
        return out

    def kv_cache_bytes(self) -> int:
        """Total device bytes held by the slot-pool KV caches (the number
        the PERF.md bucketed-vs-fixed footprint table reports)."""
        return sum(self.kv_pool_bytes().values())

    def kv_pool_bytes(self) -> Dict[str, int]:
        """``kv_cache_bytes()`` by kind of pool (``kv_cache.specs``): a
        layer plan keeps a full-length pool and a ring of ``window``
        positions ({"full": ..., "window": ...}), with delta-rule layers
        the state pool ("state") and with latent-attention layers the
        latent pool ("latent"); a model of one kind has {"kv"}."""
        out: Dict[str, int] = {}
        for p in self._pools:
            for name, nbytes in kv_cache.pool_bytes(self.cfg, p.cache).items():
                out[name] = out.get(name, 0) + nbytes
        return out

    def hbm_components(self) -> Dict[str, int]:
        """PER-CHIP HBM attribution of everything this engine keeps
        resident: params, the slot-pool KV caches plus registered prefix
        caches (pinned KV), and the device-threaded tick state.
        Metadata-only byte math (telemetry/memory.py leaf shard shapes —
        a tensor-sharded cache counts 1/tp per chip), exact on the
        virtual mesh and TPU alike; never blocks or fetches."""
        from deepspeed_tpu.telemetry import memory as hbm

        kv = sum(hbm.tree_device_bytes(p.cache) for p in self._pools)
        kv += sum(hbm.tree_device_bytes(pre["cache"])
                  for pre in self._prefixes.values())
        tick = sum(hbm.tree_device_bytes((p.last_tok_dev, p.done_dev))
                   for p in self._pools)
        out = {"params": hbm.tree_device_bytes(self._eng.params),
               "kv_cache": kv, "tick_state": tick}
        if self.spec_gamma:
            out["tick_state"] += sum(
                hbm.tree_device_bytes((p.pos_dev, p.gen_dev))
                for p in self._pools)
            if self._draft_eng is not None:
                out["draft_params"] = hbm.tree_device_bytes(
                    self._draft_eng.params)
                out["kv_cache"] += sum(
                    hbm.tree_device_bytes(p.draft_cache)
                    for p in self._pools)
        return out

    def memory_snapshot(self, reason: str):
        """Export the current HBM attribution (``hbm_bytes{component}``
        gauges + one ``memory_snapshot`` trace event; docs/telemetry.md
        "Live ops plane"). No-op returning None with telemetry off."""
        from deepspeed_tpu.telemetry import memory as hbm

        return hbm.emit_snapshot(self._eng.telemetry, self.hbm_components(),
                                 reason)

    def _tick_arg_structs(self, pool: "_Pool", chunk: Optional[int]):
        """ShapeDtypeStruct argument tuple for one tick program — the
        ONE abstract-args builder shared by the AOT memory diagnostic
        and the ds-audit capture hook, so neither can drift from the
        real dispatch signature."""
        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        params_s = jax.tree.map(sds, self._eng.params)
        cache_s = jax.tree.map(sds, pool.cache)
        row = jax.ShapeDtypeStruct((pool.n_slots,), jnp.int32)
        args = [params_s, cache_s, row, row, row, row, row, row,
                sds(self._base_key)]
        if chunk is not None:
            cvec = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [cvec, cvec, 0, row, row]
        return tuple(args)

    def _spec_tick_arg_structs(self, pool: "_Pool"):
        """:meth:`_tick_arg_structs` for the speculative tick variants."""
        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        params_s = jax.tree.map(sds, self._eng.params)
        cache_s = jax.tree.map(sds, pool.cache)
        row = jax.ShapeDtypeStruct((pool.n_slots,), jnp.int32)
        key_s = sds(self._base_key)
        if self.spec_mode == "draft":
            return (params_s, jax.tree.map(sds, self._draft_eng.params),
                    cache_s, jax.tree.map(sds, pool.draft_cache),
                    row, row, row, row, row, row, row, key_s)
        drafts = jax.ShapeDtypeStruct((pool.n_slots, self.spec_gamma),
                                      jnp.int32)
        return (params_s, cache_s, row, row, row, row, row, row, row,
                drafts, key_s)

    def _audit_meta(self) -> dict:
        """ProgramArtifact meta for ds-audit captures from this engine
        (analysis/program/capture.py) — the inner engine's meta with the
        pool's donation knob (donate_cache gates the tick/row-update
        donations; the CPU overlap A/B runs them off) and sampler mode
        (the tick collective profile splits greedy vs sampled)."""
        return dict(self._eng._audit_meta(), donate=self.donate_cache,
                    sampled=self.temperature > 0.0)

    def _draft_audit_meta(self) -> dict:
        """Audit meta for programs over the DRAFT param tree (the draft
        segment prefill): the param-collective match set must be the
        draft's leaf shapes, not the target's."""
        from deepspeed_tpu.analysis.program.capture import param_leaf_shapes

        return dict(self._audit_meta(),
                    param_shapes=param_leaf_shapes(self._draft_eng.params))

    def _spec_audit_meta(self) -> dict:
        """Audit meta for the speculative tick: draft mode carries BOTH
        param trees, so the param-collective match set is their union."""
        meta = self._audit_meta()
        if self._draft_eng is not None:
            from deepspeed_tpu.analysis.program.capture import param_leaf_shapes

            meta["param_shapes"] = tuple(meta["param_shapes"]) + \
                param_leaf_shapes(self._draft_eng.params)
        return meta

    def analyze_program_memory(self) -> Dict[str, dict]:
        """Per-tick-program-family ``compiled.memory_analysis()`` view
        (temp/argument/output bytes) over every tick program built so
        far. EXPENSIVE — one AOT lower+compile per family (the AOT cache
        is separate from the dispatch cache), so this is an on-demand
        diagnostic (tests, prewarm reports), never the hot path. Returns
        {} per family on backends without the analysis (jax CPU)."""
        from deepspeed_tpu.telemetry import memory as hbm

        out: Dict[str, dict] = {}
        for pi, pool in enumerate(self._pools):
            for (chunk, read_len), fn in pool.tick_fns.items():
                args = (self._spec_tick_arg_structs(pool)
                        if chunk == "spec"
                        else self._tick_arg_structs(pool, chunk))
                try:
                    mem = hbm.program_memory(fn.lower(*args).compile())
                except Exception:  # noqa: BLE001 — strictly best-effort AOT
                    mem = {}
                if mem:
                    out[f"pool{pi}:len{pool.length}:chunk{chunk}:"
                        f"read{read_len}"] = mem
        return out

    # -- public API -----------------------------------------------------
    def validate_request(self, prompt_ids, max_new_tokens: int) -> np.ndarray:
        """Argument checks shared by ``submit`` and the serving layer's
        admission control (which must reject malformed requests BEFORE
        deciding whether capacity exists). Raises ValueError — a real
        error, not an assert that vanishes under ``python -O`` — and
        returns the canonicalized prompt array."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                "max_new_tokens must be >= 1 (every request emits a token)")
        if prompt.size + max_new_tokens > self.cache_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new_tokens {max_new_tokens} "
                f"exceeds the largest pool cache_len {self.cache_len}"
            )
        return prompt

    def submit(self, prompt_ids, max_new_tokens: int = 32, *,
               rid: Optional[int] = None, gen_base: int = 0,
               on_prefill_start: Optional[Callable[[], None]] = None) -> int:
        """Queue a request. ``on_prefill_start`` is called once, with no
        arguments, when the request's first prefill work is dispatched (the
        submitter's lifecycle mark). ``rid``/``gen_base`` are the RESUME surface
        (serving-layer recovery): an explicit ``rid`` preserves a lost
        request's RNG identity on a rebuilt engine, and ``gen_base``
        offsets the device generation counter so the per-token keys
        continue the original stream — submit ``prompt + emitted`` with
        ``gen_base=len(emitted)`` and the request picks up mid-stream
        bitwise-identically."""
        prompt = self.validate_request(prompt_ids, max_new_tokens)
        if gen_base < 0:
            raise ValueError("gen_base must be >= 0")
        if rid is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            if (any(r.rid == rid for r in self._pending)
                    or rid in self._results
                    or any(r.rid == rid for p in self._pools
                           for r in p.active.values())):
                raise ValueError(f"explicit rid {rid} is already in use")
            self._next_rid = max(self._next_rid, rid + 1)
        self._pending.append(_Request(rid, prompt, max_new_tokens,
                                      gen_base=gen_base,
                                      on_prefill_start=on_prefill_start))
        return rid

    def register_prefix(self, prefix_ids) -> int:
        """Prefix (prompt) caching: prefill a shared prefix ONCE and reuse
        its KV for every request submitted with ``prefix_id`` — the
        system-prompt pattern, where admission then only pays prefill for
        the per-request suffix. Returns a prefix id for submit_with_prefix.
        """
        if self.cfg.layer_kinds is not None:
            raise NotImplementedError(
                "prefix registration splices one pool of one length; a "
                "layer plan's pools have no splice yet" + (
                    ", and a state pool's recurrent state would need a "
                    "snapshot at the prefix's end" if self._state_pool else ""))
        prefix = np.asarray(prefix_ids, np.int32).reshape(-1)
        if prefix.size == 0:
            raise ValueError("empty prefix")
        if prefix.size >= self.cache_len:
            raise ValueError("prefix does not fit the cache")
        n = prefix.size
        bucket = _bucket(n, self.cache_len)
        prefill_fn = self._prefill_for_bucket(bucket)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prefix
        positions = np.full((1, bucket), bucket, np.int32)
        positions[0, :n] = np.arange(n, dtype=np.int32)
        small = kv_cache.init(self.cfg, 1, bucket)
        logits, small = prefill_fn(
            self._eng.params, toks, positions, small
        )
        pid = self._next_pid  # counter, not len(): eviction must never recycle a live id
        self._next_pid += 1
        # keep the bucket cache on device; admission splices then prefills
        # only the suffix at positions [n..)
        self._prefixes[pid] = {"tokens": prefix, "cache": small, "bucket": bucket}
        return pid

    def _require_prefix(self, prefix_id: int) -> dict:
        try:
            return self._prefixes[prefix_id]
        except KeyError:
            raise KeyError(
                f"unknown prefix id {prefix_id}: never registered or already "
                f"unregistered (live ids: {sorted(self._prefixes)})") from None

    def unregister_prefix(self, prefix_id: int):
        """Release a registered prefix's device-resident KV (a long-running
        server must bound the pinned caches; in-flight requests that
        already spliced it are unaffected)."""
        self._require_prefix(prefix_id)
        self._prefixes.pop(prefix_id)

    def submit_with_prefix(self, prefix_id: int, suffix_ids, max_new_tokens: int = 32, *,
                           on_prefill_start: Optional[Callable[[], None]] = None) -> int:
        """Queue a request whose prompt is (registered prefix + suffix);
        the prefix KV is reused, only the suffix is prefilled.
        ``on_prefill_start`` as in :meth:`submit`."""
        suffix = np.asarray(suffix_ids, np.int32).reshape(-1)
        if suffix.size == 0:
            raise ValueError("empty suffix (use submit for prefix-only prompts)")
        if max_new_tokens < 1:
            raise ValueError(
                "max_new_tokens must be >= 1 (every request emits a token)")
        pre = self._require_prefix(prefix_id)
        total = pre["tokens"].size + suffix.size
        if total + max_new_tokens > self.cache_len:
            raise ValueError(
                f"prefix {pre['tokens'].size} + suffix {suffix.size} + "
                f"max_new_tokens {max_new_tokens} exceeds cache_len {self.cache_len}"
            )
        rid = self._next_rid
        self._next_rid += 1
        req = _Request(rid, np.concatenate([pre["tokens"], suffix]), max_new_tokens,
                       on_prefill_start=on_prefill_start)
        req.prefix = pre  # snapshot: queued requests survive unregister_prefix
        self._pending.append(req)
        return rid

    def has_work(self) -> bool:
        return (bool(self._pending) or bool(self._inflight)
                or any(p.active for p in self._pools))

    def status(self, rid: int) -> str:
        """Non-destructive request state: ``"pending"`` (queued, no slot
        yet), ``"active"`` (decoding in a slot), ``"finished"`` (result
        ready, not yet collected), ``"cancelled"``, or ``"unknown"``
        (never submitted, or result already collected)."""
        if any(r.rid == rid for r in self._pending):
            return "pending"
        if any(r.rid == rid for p in self._pools for r in p.active.values()):
            return "active"
        if rid in self._results:
            return "finished"
        if rid in self._cancelled:
            return "cancelled"
        return "unknown"

    def peek(self, rid: int) -> Optional[np.ndarray]:
        """The finished result for ``rid`` WITHOUT consuming it (``result``
        pops; pollers — the serving layer — must not race the collector).
        None while the request is pending/active or the rid is unknown."""
        return self._results.get(rid)

    def result(self, rid: int) -> np.ndarray:
        try:
            return self._results.pop(rid)
        except KeyError:
            state = self.status(rid)
            detail = {
                "pending": "still queued for a slot (step() until finished)",
                "active": "still decoding (step() until finished)",
                "cancelled": "cancelled before it finished",
                "unknown": "never submitted or its result was already collected",
            }[state]
            raise KeyError(
                f"no result for request {rid}: {state} — {detail}") from None

    def cancel(self, rid: int) -> bool:
        """Cancel a request: a pending one leaves the queue, an active one
        frees its pool slot immediately — even while a tick carrying it is
        still in flight (the retired tick's row is simply not attributed;
        stale KV is position-masked on slot reuse, same as completion).
        Returns False when the rid is already finished/collected/unknown:
        too late to cancel, the caller keeps the result semantics it
        already has."""
        for i, req in enumerate(self._pending):
            if req.rid == rid:
                self._pending.pop(i)
                self._mark_cancelled(rid)
                return True
        for pool in self._pools:
            for slot, req in pool.active.items():
                if req.rid == rid:
                    pool.active.pop(slot)
                    if req.chunks:
                        try:
                            pool.prefill_q.remove(req)
                        except ValueError:
                            pass
                    self._mark_cancelled(rid)
                    return True
        return False

    def _mark_cancelled(self, rid: int):
        self._cancelled[rid] = None
        while len(self._cancelled) > self._cancelled_cap:
            self._cancelled.popitem(last=False)

    def pool_state(self) -> List[dict]:
        """Per-pool occupancy snapshot (ordered by pool length, the same
        order ``_place`` scans): ``{"length", "slots", "free"}``. The
        serving layer's admission control mirrors placement against this
        without reaching into ``_pools``."""
        return [{"length": p.length, "slots": p.n_slots,
                 "free": p.n_slots - len(p.active)} for p in self._pools]

    def finished(self) -> Dict[int, np.ndarray]:
        out, self._results = self._results, {}
        return out

    def abort_inflight(self) -> int:
        """Drop every dispatched-but-unretired tick WITHOUT fetching:
        the engine-loss path (serving recovery) counts the discarded
        ticks and abandons this engine — the tokens those ticks computed
        are regenerated bitwise by the resume RNG design, never fetched
        from a device that may be gone. Returns the number of ticks
        discarded. The engine stays ``poisoned``-marked territory: only
        call this when the engine is being abandoned."""
        lost = len(self._inflight)
        if lost:
            self._inflight_empty_t = time.perf_counter()
        self._inflight.clear()
        return lost

    def tick_stats(self) -> dict:
        """Host-overhead accounting for the tick loop: dispatch vs blocked
        milliseconds, tokens emitted / wasted past done flags.
        ``overlap_frac`` is the fraction of host-side tick-loop time NOT
        spent blocked on device results (1.0 = the device never made the
        host wait); ``block_ms_per_token``
        is the loadgen A/B headline — host-blocked ms per decoded token.
        Tick kinds: ``plain_ticks + fused_prefill_ticks == ticks`` and
        ``block_ms_plain + block_ms_fused == block_ms`` (each retired
        tick's blocked time charged to its own kind);
        ``prefill_q_depth_sum`` (÷ ``steps`` = mean) is the
        admitted-but-not-yet-prefilled requests over all pools, read once
        per ``step()``. ``block_write_ticks``: ticks dispatched on a program
        whose rows' one-token KV write took the block path (the host tells
        it from the tick's read bucket by ``kv_cache``'s own rule);
        ``block_write_rows``: the LIVE rows those ticks wrote, a row a
        token step (the kernel moves a live row's block and nothing for a
        parked row or an empty slot; ÷ (``block_write_ticks`` x slots) is
        the share of the pool's rows it touched); ``block_write_bytes``: the
        bytes those writes fetched and stored
        (``kv_cache.rows_block_write_bytes`` a live row and token step: its
        block of every leaf that goes by blocks, in and out, over its
        layer-steps).
        ``length_read_ticks``: ticks dispatched on a program whose rows'
        attention reads each row to its own length (the same way, by
        ``kv_cache.takes_length_read``); of those ticks ``row_keys_read``
        is the cached slots the kernel fetched, whole 128-slot blocks a
        live row (summed over rows and a burst's steps; a layer and leaf),
        and ``row_keys_live`` the slots those rows attend: their ratio is
        what reading by blocks costs over reading by tokens. A looped plan
        (``loop_steps`` > 1): ``loop_passes``, the passes dispatched
        (``loop_steps`` a token step); of ONE layer-step's pool,
        ``loop_kv_positions_read``, the positions the rows' attention
        fetched (every slot to the tick's read bucket, a step) and
        ``loop_kv_positions_live``, those the live rows hold; and
        ``kv_pool_bytes``, the pools as allocated, all passes' layer-steps.
        The host ledger's rows of the tick loop (docs/telemetry.md "The
        serving loop's ledger"): ``admit_ms``, the admission loop (inside
        ``dispatch_ms``); ``attribute_ms``, ``_retire`` after its fetch
        returned; ``inflight_empty_ms``, the wall time with no tick in
        flight, up to this read; ``ticks_ready_at_retire``, ticks whose
        result was on hand before the host came to fetch it."""
        s = dict(self._tick_stats)
        s["inflight_empty_ms"] = self.inflight_empty_ms()
        s["pipeline_depth"] = self.pipeline_depth
        # NOT the tokens_per_tick knob (the burst width): the observed mean
        s["mean_emitted_per_tick"] = (round(s["tokens"] / s["ticks"], 3)
                                      if s["ticks"] else 0.0)
        s["block_ms_per_token"] = (round(s["block_ms"] / s["tokens"], 4)
                                   if s["tokens"] else None)
        host = s["dispatch_ms"] + s["block_ms"]
        s["overlap_frac"] = (round(1.0 - s["block_ms"] / host, 4)
                             if host > 0 else None)
        s["spec_gamma"] = self.spec_gamma
        s["spec_mode"] = self.spec_mode
        s["spec_acceptance"] = (round(s["spec_accepted"] / s["spec_drafted"], 4)
                                if s["spec_drafted"] else None)
        if self.cfg.layer_kinds is not None:
            for name, nbytes in self.kv_pool_bytes().items():
                s["kv_pool_bytes_" + name] = nbytes
        if self._state_pool:
            s["state_pool_bytes"] = s["kv_pool_bytes_state"]
        if self._latent_pool:
            s["latent_pool_bytes"] = s["kv_pool_bytes_" + kv_cache.LATENT]
        if self._loop_steps > 1:
            s["kv_pool_bytes"] = self.kv_cache_bytes()
        return s

    def inflight_empty_ms(self) -> float:
        """Wall time so far with no tick in flight, the open stretch
        counted up to this read."""
        ms = self._tick_stats["inflight_empty_ms"]
        if not self._inflight:
            ms += (time.perf_counter() - self._inflight_empty_t) * 1000.0  # ds-lint: disable=unsynced-timing
        return ms

    def _place(self, req: _Request) -> Optional[tuple]:
        """(pool_index, slot) in the smallest-length pool that fits the
        request's full extent and has a free slot; None if all full."""
        need = req.prompt.size + req.max_new_tokens
        if req.prefix is not None:
            # the prefix KV splice writes a full bucket-length slice; the
            # pool row must hold it (dynamic_update_slice cannot clip)
            need = max(need, req.prefix["bucket"])
        for i, pool in enumerate(self._pools):
            if pool.length < need:
                continue
            free = pool.free_slots()
            if free:
                return i, free[0]
        return None

    def step(self) -> Dict[int, List[int]]:
        """One scheduler tick: admit pending into free slots (dispatch
        their prefill), dispatch one tick per pool with dispatchable rows,
        then retire in-flight ticks down to ``pipeline_depth``. Returns
        {rid: [tokens]} emitted by the RETIRED tick(s) — with
        ``pipeline_depth > 0`` a request's tokens surface up to that many
        steps after the tick that computed them; concatenating the lists
        across steps reproduces the generated stream exactly. Finished
        requests move to ``finished()``/``result()``.

        Fault surface: the ``dispatch`` fault hook fires FIRST, before
        any state mutates — an exception there leaves the engine fully
        consistent (``poisoned`` stays False, the caller may simply call
        ``step()`` again). Any exception past that point — injected or
        real, including the ``_retire`` fetch watchdog — sets
        ``poisoned``: in-flight results may be lost and the serving
        layer must rebuild rather than retry."""
        if self.fault_hook is not None:
            self.fault_hook("dispatch", {"tick": self._tick_index})
        self._tick_index += 1
        try:
            return self._step_body()
        except BaseException:
            self.poisoned = True
            raise

    def _step_body(self) -> Dict[int, List[int]]:
        emitted: Dict[int, List[int]] = {}
        t0 = time.perf_counter()
        # FIFO with skip: a request that only fits the (full) long pool
        # must not block shorter requests behind it
        still_pending = []
        with host_span("tick.admit"):
            for req in self._pending:
                placed = self._place(req)
                if placed is None:
                    still_pending.append(req)
                    continue
                self._admit(req, *placed)
        t_admitted = time.perf_counter()
        self._pending = still_pending
        stats = self._tick_stats
        stats["prefill_q_depth_sum"] += sum(len(p.prefill_q) for p in self._pools)

        recs: Dict[int, _TickRecord] = {}
        for pi, pool in enumerate(self._pools):
            with host_span("tick.dispatch.fused" if self.fused_prefill and pool.prefill_q
                           else "tick.dispatch.plain"):
                rec = (self._dispatch_spec_tick(pool) if self.spec_gamma
                       else self._dispatch_tick(pool))
            if rec is not None:
                recs[pi] = rec
        # the dispatch span is INTENTIONALLY unsynced: it measures host
        # enqueue work while the device runs ahead (the whole point of the
        # overlap); the block span in _retire ends at a real host fetch
        t_dispatched = time.perf_counter()
        dispatch_ms = (t_dispatched - t0) * 1000.0  # ds-lint: disable=unsynced-timing
        # (the ledger's rows are the HOST's wall time on purpose, like dispatch_ms)
        admit_ms = (t_admitted - t0) * 1000.0  # ds-lint: disable=unsynced-timing
        if recs:
            if self.span_hook is not None:
                # window-span clock zero for this tick's records: one
                # host clock read per step, no device traffic
                t_disp = time.monotonic()
                for r in recs.values():
                    r.t0 = t_disp
            if not self._inflight:
                # the chip has work again: the stretch with nothing in
                # flight ends on the clock read that closed the dispatch
                since = self._inflight_empty_t
                stats["inflight_empty_ms"] += (t_dispatched - since) * 1000.0
            self._inflight.append(recs)
        stats["steps"] += 1
        stats["ticks"] += len(recs)
        # emission capacity this step actually dispatched: every slot of a
        # ticked pool could emit k tokens (utilization denominators must
        # not assume one tick covers ALL pools)
        stats["capacity_tokens"] += sum(
            self._pools[pi].n_slots * r.k for pi, r in recs.items())
        n_fused = sum(1 for r in recs.values() if r.fused)
        stats["fused_prefill_ticks"] += n_fused
        stats["plain_ticks"] += len(recs) - n_fused
        stats["dispatch_ms"] += dispatch_ms
        stats["admit_ms"] += admit_ms

        # retire down to the pipeline depth; when nothing new was
        # dispatched, the remaining in-flight ticks are the drain tail
        block_ms = 0.0
        tokens0, wasted0 = stats["tokens"], stats["wasted_tokens"]
        plan0 = {name: stats[name] for name in _PLAN_TICK_FIELDS if name in stats}
        drafted0, accepted0 = stats["spec_drafted"], stats["spec_accepted"]
        attributed0 = stats["attribute_ms"]
        while self._inflight and (len(self._inflight) > self.pipeline_depth
                                  or not recs):
            block_ms += self._retire(self._inflight.popleft(), emitted)
            if not self._inflight:
                self._inflight_empty_t = self._fetched_t  # nothing in flight since that fetch returned
        stats["block_ms"] += block_ms
        attribute_ms = stats["attribute_ms"] - attributed0
        self.last_step_ms = dispatch_ms + block_ms + attribute_ms

        tele = self._eng.telemetry
        if tele.enabled:
            # tick-indexed jax.profiler window: profile_start_step counts
            # SCHEDULER TICKS here (not train steps), so a device-trace
            # capture can be pointed at the pooled-tick hot path
            tele.maybe_capture(self._tick_index)
            reg = tele.registry
            # serving dashboards read pool pressure off this gauge: cached
            # tokens across live slots / total reserved slot capacity
            reg.gauge("cache_utilization").set(self.cache_utilization())
            reg.gauge("tick_inflight_depth").set(len(self._inflight))
            n_tokens = stats["tokens"] - tokens0
            n_wasted = stats["wasted_tokens"] - wasted0
            if recs or block_ms:
                reg.histogram("tick_dispatch_ms").observe(dispatch_ms)
                reg.histogram("tick_block_ms").observe(block_ms)
                if n_wasted:
                    reg.counter("burst_wasted_tokens").inc(n_wasted)
                event = {
                    "dispatch_ms": round(dispatch_ms, 4),
                    "block_ms": round(block_ms, 4),
                    "inflight": len(self._inflight),
                    "emitted": n_tokens,
                    "wasted": n_wasted,
                    "fused_prefill": any(r.fused for r in recs.values()),
                }
                if self.spec_gamma:
                    event["spec_gamma"] = self.spec_gamma
                    event["spec_drafted"] = stats["spec_drafted"] - drafted0
                    event["spec_accepted"] = stats["spec_accepted"] - accepted0
                # what a layer plan's ticks counted, of the ticks this step retired
                event.update({name: stats[name] - was for name, was in plan0.items()})
                tele.emit("serving_tick", event)
        return emitted

    def cache_utilization(self) -> float:
        """Fraction of the reserved slot-pool KV capacity holding live
        tokens (active rows' observed extents / sum of slots × length)."""
        used = sum(min(r.prompt.size + len(r.generated), p.length)
                   for p in self._pools for r in p.active.values())
        cap = sum(p.n_slots * p.length for p in self._pools)
        return used / cap if cap else 0.0

    # -- tick dispatch / retire ------------------------------------------
    def _read_len(self, pool: _Pool, extent: int) -> Optional[int]:
        """Tight-read length covering ``extent`` cached slots (None = read
        the full pool length: tight reads off, or the bucket reached it)."""
        if not self._eng.config.kv_tight_read or extent <= 0:
            return None
        r = read_bucket(extent, pool.length,
                        self._eng.config.kv_read_floor)
        return None if r >= pool.length else r

    def _row_read_bytes(self, pool: _Pool, read_len: Optional[int], cfg=None) -> int:
        # per-chip: the pool cache shards its heads axis over the mesh's
        # tensor width, so each chip streams 1/tp of the row's window
        cfg = cfg or self.cfg
        return kv_cache.read_bytes_per_row(
            cfg, read_len if read_len is not None else pool.length,
            tp=kv_cache.shard_width(self.mesh, cfg))

    def _chunk_width(self, pool: _Pool, nreal: int) -> int:
        """Width of the fused tick program that carries ``nreal`` prompt
        tokens: the pool's chunk cap (a one-kind model: one fused program a
        read bucket), or the power-of-2 bucket from the plan's floor up."""
        if self._chunk_floor is None:
            return pool.chunk_cap
        return _bucket(nreal, pool.chunk_cap, self._chunk_floor)

    def _tick_fn(self, pool: _Pool, read_len: Optional[int],
                 chunk: Optional[int] = None):
        """The pool's compiled tick program at (chunk width, tight-read
        length). Pool-resident — bounded by the bucket family, never
        evicted."""
        key = (chunk, read_len)
        if key not in pool.tick_fns:
            fn = compile_pool_tick_fn(
                self.mesh, self.cfg, self._eng.param_shardings, pool.n_slots,
                pool.length, 1 if chunk is not None else self.tokens_per_tick,
                self.temperature, self.top_k, self.top_p,
                eos_token_id=self.eos_token_id, read_len=read_len,
                chunk=chunk, donate=self.donate_cache)[0]
            pool.tick_costs[key] = _TickCosts(
                self._row_read_bytes(pool, read_len),
                kv_cache.rows_block_write_bytes(self.cfg, pool.cache, read_len, self.mesh),
                kv_cache.rows_read_to_length(self.cfg, pool.cache, read_len, self.mesh))
            # build journal: the program's first dispatch leaves an entry
            # keyed by the full shapes key — a rebuilt engine re-compiling
            # the family is flagged recompile (the runtime view of
            # ds-lint's static recompile-hazard rule) — and then hands the
            # bare program back to the table: a steady tick runs no wrapper
            fn = pool.tick_fns[key] = self._record_build(
                fn, "pool_tick",
                (pool.length, pool.n_slots,
                 1 if chunk is not None else self.tokens_per_tick,
                 chunk, read_len),
                settle=functools.partial(pool.tick_fns.__setitem__, key))
            # ds-audit capture (zero cost without a hook): the contract
            # auditor sees every tick variant a serve actually compiles
            from deepspeed_tpu.analysis.program import capture

            if capture.active():
                variant = ("fused" if chunk is not None
                           else "burst" if self.tokens_per_tick > 1
                           else "plain")
                capture.notify_program(
                    "pool_tick", variant, fn,
                    lambda: self._tick_arg_structs(pool, chunk),
                    meta=self._audit_meta)
        return pool.tick_fns[key]

    def _dispatch_tick(self, pool: _Pool) -> Optional[_TickRecord]:
        """Dispatch one tick for ``pool`` WITHOUT waiting for anything:
        inputs come from the host dispatch mirrors plus the device-threaded
        state futures. Returns None when the pool has nothing to run.

        The host inputs go to the program as the NumPy arrays they are: the
        jitted call places them itself, and nothing here makes a
        ``device_put``. They are allocated anew every tick and NOT written
        once handed over: a transfer may still be reading them."""
        n, k = pool.n_slots, self.tokens_per_tick
        pos = np.full(n, pool.length, np.int32)   # parked rows: writes drop
        gen = np.zeros(n, np.int32)
        quota = np.zeros(n, np.int32)
        rids = np.zeros(n, np.int32)
        emit_mask = np.zeros(n, np.int32)
        live: Dict[int, _Request] = {}
        extent = 0
        for slot, req in pool.active.items():
            if req.chunks:
                continue  # mid-prefill: parked unless it rides this tick
            if pool.disp_gen[slot] >= req.quota:
                continue  # quota exhausted: result still in flight, no work
            live[slot] = req
            pos[slot] = pool.disp_pos[slot]
            gen[slot] = pool.disp_gen[slot]
            quota[slot] = req.quota
            rids[slot] = req.rid
            emit_mask[slot] = 1
            extent = max(extent, int(pool.disp_pos[slot]) + k)
        admit = pool.prefill_q[0] if (self.fused_prefill and pool.prefill_q) else None
        if not live and admit is None:
            return None

        params = self._eng.params
        if admit is not None:
            ctoks, cpos0, nreal, emits = admit.chunks[0]
            aslot = admit.slot
            self._mark_prefill_start(admit)
            W = self._chunk_width(pool, nreal)
            extent = max(extent, cpos0 + nreal)
            read_len = self._read_len(pool, extent)
            fn = self._tick_fn(pool, read_len, chunk=W)
            costs = pool.tick_costs[W, read_len]
            st = self._tick_stats
            st["prefill_chunk_tokens"] += nreal
            st["prefill_pad_tokens"] += W - nreal
            if self.cfg.layer_kinds is not None:
                st["prefill_pairs_full"] += nreal * cpos0 + nreal * (nreal + 1) // 2
                st["prefill_pairs_window"] += int(np.minimum(
                    np.arange(cpos0 + 1, cpos0 + nreal + 1), self._window).sum())
                st["prefill_keys_full"] += cpos0 + nreal
                visited, masked, fetched = self._chunk_tiles(self.cfg, W, read_len or pool.length, cpos0)
                st["prefill_tiles_visited"] += visited
                st["prefill_tiles_masked"] += masked
                st["prefill_kv_tile_fetches"] += fetched
                if self._latent_pool:
                    st["mla_expand_tokens"] += expanded_entries(cpos0 + W, read_len or pool.length)
            chunk_toks = np.zeros(W, np.int32)
            chunk_toks[:nreal] = ctoks
            chunk_pos = np.full(W, pool.length, np.int32)
            chunk_pos[:nreal] = np.arange(cpos0, cpos0 + nreal, dtype=np.int32)
            emit_col = np.zeros(n, np.int32)
            if emits:
                emit_col[aslot] = nreal - 1
                emit_mask[aslot] = 1
                quota[aslot] = admit.quota
                # resume support: the sampled first token's RNG key is
                # fold_in(rid, gen) — gen_base continues a recovered
                # request's stream at its next token index
                gen[aslot] = admit.gen_base
                rids[aslot] = admit.rid
                live[aslot] = admit
            packed, pool.cache, pool.last_tok_dev, pool.done_dev = fn(
                params, pool.cache, pool.last_tok_dev, pool.done_dev,
                pos, gen, quota, rids, self._base_key, chunk_toks,
                chunk_pos, aslot, emit_col, emit_mask)
            admit.chunks.pop(0)
            if not admit.chunks:
                pool.prefill_q.popleft()
                admit.chunks = None
                pool.disp_pos[aslot] = cpos0 + nreal  # full prompt cached
                pool.disp_gen[aslot] = admit.gen_base + 1  # the emitted first token
            rec = _TickRecord(packed, live, 1, costs.row_read_bytes, True)
            advance = 1
        else:
            read_len = self._read_len(pool, extent)
            fn = self._tick_fn(pool, read_len)
            costs = pool.tick_costs[None, read_len]
            packed, pool.cache, pool.last_tok_dev, pool.done_dev = fn(
                params, pool.cache, pool.last_tok_dev, pool.done_dev,
                pos, gen, quota, rids, self._base_key)
            rec = _TickRecord(packed, live, k, costs.row_read_bytes, False)
            advance = k
        moved = costs.block_write_bytes
        wrote = int((pos < pool.length).sum()) * advance if moved else 0   # live rows, a token step each
        self._tick_stats["block_write_ticks"] += moved > 0
        self._tick_stats["block_write_rows"] += wrote
        self._tick_stats["block_write_bytes"] += moved * wrote
        if self._latent_pool:  # a row that is not parked attends its cached entries and the one it writes
            self._tick_stats["mla_row_keys"] += int((pos[pos < pool.length] + 1).sum())
        if self._loop_steps > 1:
            st = self._tick_stats
            st["loop_passes"] += self._loop_steps * advance
            st["loop_kv_positions_read"] += n * (read_len or pool.length) * advance
            # each of the tick's steps, a live row attends what it holds and the token it writes
            st["loop_kv_positions_live"] += int(np.minimum(
                pos[pos < pool.length, None] + 1 + np.arange(advance), pool.length).sum())
        if costs.reads_to_length:
            # each of the tick's steps, a live row attends what it holds and
            # the token it writes (a row that finishes inside a burst stays
            # where it is: counted as if it went on)
            held = np.minimum(pos[pos < pool.length, None] + 1 + np.arange(advance),
                              read_len or pool.length)
            st = self._tick_stats
            st["length_read_ticks"] += 1
            st["row_keys_live"] += int(held.sum())
            st["row_keys_read"] += int((-(-held // kv_cache.BLOCK) * kv_cache.BLOCK).sum())
        # advance the dispatch mirrors for the decode rows (the admitting
        # row's were set above); quota-clamped so a burst tail never
        # over-advances a row the host can predict finishing
        for slot, req in live.items():
            if admit is not None and slot == admit.slot:
                continue
            adv = min(advance, int(req.quota) - int(pool.disp_gen[slot]))
            pool.disp_pos[slot] += adv
            pool.disp_gen[slot] += adv
        return rec

    def _spec_round_bytes(self, pool: _Pool, read_len: Optional[int]) -> int:
        """KV bytes ONE row streams per speculative round: the target
        verify reads its window once (the (gamma+1)-wide queries share a
        single cache read), plus gamma+1 draft steps each streaming the
        draft-cache window (0 extra for ngram — drafting is host-side)."""
        total = self._row_read_bytes(pool, read_len)
        if self.spec_mode == "draft":
            total += (self.spec_gamma + 1) * self._row_read_bytes(pool, read_len, self.draft_cfg)
        return total

    def _spec_tick_fn(self, pool: _Pool, read_len: Optional[int]):
        """The pool's compiled SPECULATIVE tick at tight-read length
        ``read_len`` — keyed ``("spec", read_len)`` in the same
        pool-resident table as the plain variants (same no-eviction
        rationale)."""
        key = ("spec", read_len)
        if key not in pool.tick_fns:
            kw = {}
            if self.spec_mode == "draft":
                kw = dict(
                    draft_cfg=self.draft_cfg,
                    draft_param_shardings=self._draft_eng.param_shardings)
            fn = compile_spec_pool_tick_fn(
                self.mesh, self.cfg, self._eng.param_shardings, pool.n_slots,
                pool.length, self.spec_gamma, self.temperature, self.top_k,
                self.top_p, eos_token_id=self.eos_token_id,
                read_len=read_len, donate=self.donate_cache, **kw)[0]
            fn = pool.tick_fns[key] = self._record_build(
                fn, "pool_spec_tick",
                (pool.length, pool.n_slots, self.spec_gamma,
                 self.spec_mode, read_len),
                settle=functools.partial(pool.tick_fns.__setitem__, key))
            from deepspeed_tpu.analysis.program import capture

            if capture.active():
                capture.notify_program(
                    f"pool_spec_tick_{self.spec_mode}", "", fn,
                    lambda: self._spec_tick_arg_structs(pool),
                    meta=self._spec_audit_meta)
        return pool.tick_fns[key]

    def _dispatch_spec_tick(self, pool: _Pool) -> Optional[_TickRecord]:
        """Speculative counterpart of :meth:`_dispatch_tick`: one
        gamma-verify round per pool per step, enqueue-only like the plain
        path. Fused admission rides a SEPARATE segment dispatch on the
        same step (prompt chunks never enter the spec tick program; the
        admitting row joins the decode round the step its last chunk
        dispatches), so decode rows keep speculating through a long
        prompt's prefill."""
        g, n = self.spec_gamma, pool.n_slots
        fused = False
        if self.fused_prefill and pool.prefill_q:
            admit = pool.prefill_q[0]
            ctoks, cpos0, nreal, _ = admit.chunks.pop(0)
            self._mark_prefill_start(admit)
            W = _bucket(nreal, pool.chunk_cap, _SPEC_CHUNK_FLOOR)
            seg_toks = np.zeros((n, W), np.int32)
            seg_toks[admit.slot, :nreal] = ctoks
            seg_pos = np.full(n, pool.length, np.int32)
            seg_pos[admit.slot] = cpos0
            _, pool.cache = pool.segment_fn(
                self._eng.params, seg_toks, pool.cache, seg_pos)
            fused = True
            if not admit.chunks:
                pool.prefill_q.popleft()
                admit.chunks = None  # joins the decode round below
        run_mask = np.zeros(n, np.int32)
        quota = np.zeros(n, np.int32)
        rids = np.zeros(n, np.int32)
        live: Dict[int, _Request] = {}
        extent = 0
        for slot, req in pool.active.items():
            if req.chunks:
                continue  # mid-prefill: device run_mask parks the row
            if pool.disp_gen[slot] >= req.quota:
                continue  # quota covered by in-flight rounds (lower bound
                # — the device's threaded done flag is authoritative)
            live[slot] = req
            run_mask[slot] = 1
            quota[slot] = req.quota
            rids[slot] = req.rid
            extent = max(extent, int(pool.disp_pos[slot]) + g + 1)
        if not live:
            return None
        read_len = self._read_len(pool, min(extent, pool.length))
        fn = self._spec_tick_fn(pool, read_len)
        if self.spec_mode == "draft":
            (packed, pool.cache, pool.draft_cache, pool.last_tok_dev,
             pool.done_dev, pool.pos_dev, pool.gen_dev) = fn(
                self._eng.params, self._draft_eng.params, pool.cache,
                pool.draft_cache, pool.last_tok_dev, pool.done_dev,
                pool.pos_dev, pool.gen_dev, quota, rids, run_mask,
                self._base_key)
        else:
            drafts = np.zeros((n, g), np.int32)
            order = self._eng.config.speculative.ngram_max_order
            for slot, req in live.items():
                # under dispatch-ahead the host context LAGS the device by
                # up to pipeline_depth rounds — that only lowers the
                # acceptance rate, never correctness (point-mass q)
                ctx = (np.concatenate([req.prompt,
                                       np.asarray(req.generated, np.int32)])
                       if req.generated else req.prompt)
                drafts[slot] = ngram.propose(ctx, g, order)
            (packed, pool.cache, pool.last_tok_dev, pool.done_dev,
             pool.pos_dev, pool.gen_dev) = fn(
                self._eng.params, pool.cache, pool.last_tok_dev,
                pool.done_dev, pool.pos_dev, pool.gen_dev,
                quota, rids, run_mask, drafts, self._base_key)
        # dispatch mirrors: pos becomes an UPPER bound (the device advances
        # by accepted+1 <= gamma+1, used only for read-geometry selection)
        # and gen a LOWER bound (every active round emits >= 1); _retire
        # reconciles both from the packed counts
        for slot in live:
            pool.disp_pos[slot] += g + 1
            pool.disp_gen[slot] += 1
        return _TickRecord(packed, live, g + 1,
                           self._spec_round_bytes(pool, read_len), fused,
                           spec=g)

    def _retire(self, recs: Dict[int, _TickRecord],
                emitted: Dict[int, List[int]]) -> float:
        """Retire one in-flight tick: ONE coalesced packed-buffer fetch per
        pool, then pure host attribution (no further device traffic).
        Returns the milliseconds spent blocked on the device."""
        block_ms = 0.0
        stats = self._tick_stats
        for pi, rec in recs.items():
            pool = self._pools[pi]
            if self.fault_hook is not None:
                self.fault_hook("retire", {"tick": self._tick_index,
                                           "pool": pi})
            # the result on hand before the host asks for it: the host, not
            # the device, set this tick's pace (one non-blocking query)
            stats["ticks_ready_at_retire"] += rec.packed.is_ready()
            t0 = time.perf_counter()
            with host_span("tick.retire"):
                arr = np.asarray(rec.packed)  # the single device get per tick
            self._fetched_t = t1 = time.perf_counter()
            dt = t1 - t0
            if self.fetch_timeout_s is not None and dt > self.fetch_timeout_s:
                # post-hoc watchdog: the fetch DID return, but far past
                # budget — on a preempted/unhealthy device the next one
                # may not. Poison (via step()'s wrapper) and let the
                # serving layer rebuild; the unattributed tokens are
                # regenerated bitwise on resume.
                raise TimeoutError(
                    f"tick result fetch took {dt:.3f}s "
                    f"(> fetch_timeout_s={self.fetch_timeout_s}) — device "
                    f"unhealthy, tick pipeline abandoned")
            block_ms += dt * 1000.0
            stats["block_ms_fused" if rec.fused else "block_ms_plain"] += dt * 1000.0
            with host_span("tick.attribute"):
                self._attribute(pool, rec, arr, emitted)
            stats["attribute_ms"] += (time.perf_counter() - t1) * 1000.0  # ds-lint: disable=unsynced-timing
        return block_ms

    def _attribute(self, pool: _Pool, rec: _TickRecord, arr: np.ndarray,
                   emitted: Dict[int, List[int]]):
        """The host's work on one fetched tick: its counters, each live
        row's tokens, finished rows (``tick_stats()`` ``attribute_ms``)."""
        stats = self._tick_stats
        k = rec.k
        g = rec.spec
        if self._moe_stats:
            made, held, most, layers, hit = (
                int(v) for v in arr[0, k + 2:k + 2 + TICK_STATS])
            stats["moe_experts_hit"] += hit
            stats["moe_expert_layers"] += layers   # expert layers, which need not be all the layers
            mean = held / max(1, layers * self.cfg.held_experts[1])
            stats["moe_ticks"] += 1
            stats["moe_assignments"] += made
            stats["moe_held_assignments"] += held
            stats["moe_expert_tokens_most_sum"] += most
            stats["moe_expert_tokens_mean_sum"] += mean
            if held:
                stats["moe_imbalance_sum"] += most / mean
            at = k + 2 + TICK_STATS
            if self._state_pool:
                for i, name in enumerate(self._state_counters):
                    stats[name] += int(arr[0, at + i])
                at += len(self._state_counters)
            if self._row_stats:   # a row an assignment filled is an assignment to a held expert
                stats["moe_buffer_rows"] += int(arr[0, at])
                stats["moe_filled_rows"] += held
        hook = self.span_hook
        if hook is not None:
            t_ret = time.monotonic()
            tick_kind = ("spec_verify_round" if g else
                         "prefill_chunk" if rec.fused else "decode_window")
        for slot, req in rec.live.items():
            if pool.active.get(slot) is not req:
                # cancelled / already finished while this tick was in
                # flight: the whole row-tick computed past the done
                # flag — that IS the pipelining waste, count it
                stats["wasted_tokens"] += k
                continue
            n = int(arr[slot, k])
            stats["tokens"] += n
            stats["wasted_tokens"] += k - n
            if g:
                accepted = int(arr[slot, g + 3])
                stats["spec_drafted"] += g
                stats["spec_accepted"] += accepted
                req.spec_drafted += g
                req.spec_accepted += accepted
                # reconcile the dispatch mirrors: the round really
                # advanced pos by accepted+1 (the mirror assumed
                # gamma+1) and emitted n (the mirror assumed 1)
                pool.disp_pos[slot] -= g - accepted
                pool.disp_gen[slot] += n - 1
                # rec.row_bytes is the WHOLE round's streamed bytes
                # (one gamma+1-wide target window + the draft steps)
                req.kv_bytes_read += rec.row_bytes
            else:
                # the row STREAMED k read windows whether or not it
                # accepted all k tokens (burst tails past done are
                # wasted work, not free work) — kv_bytes_read reports
                # physical HBM traffic
                req.kv_bytes_read += k * rec.row_bytes
            if hook is not None:
                # coalesce this retired tick into the request's open
                # window (flush on kind change / window cap; _finish
                # flushes the tail) — pure host arithmetic on values
                # the attribution above already fetched
                if req.win_kind is not None and req.win_kind != tick_kind:
                    self._flush_window(req)
                if req.win_kind is None:
                    req.win_kind = tick_kind
                    req.win_t0 = rec.t0
                req.win_t1 = t_ret
                req.win_ticks += 1
                req.win_tokens += n
                if g:
                    req.win_drafted += g
                    req.win_accepted += accepted
                if req.win_ticks >= self.span_window_ticks:
                    self._flush_window(req)
            if n:
                toks = [int(t) for t in arr[slot, :n]]
                req.generated.extend(toks)
                emitted.setdefault(req.rid, []).extend(toks)
            if arr[slot, k + 1]:
                req.done = True
                self._finish(pool, slot)

    def _flush_window(self, req: "_Request"):
        """Emit the request's open tick window through ``span_hook`` and
        reset the accumulator. No-op when no window is open (or the hook
        was uninstalled mid-flight)."""
        if req.win_kind is None or self.span_hook is None:
            req.win_kind = None
            return
        attrs = {"ticks": req.win_ticks, "tokens": req.win_tokens}
        if req.win_kind == "spec_verify_round":
            attrs["drafted"] = req.win_drafted
            attrs["accepted"] = req.win_accepted
        self.span_hook(req.rid, req.win_kind, req.win_t0, req.win_t1, attrs)
        req.win_kind = None
        req.win_ticks = req.win_tokens = 0
        req.win_drafted = req.win_accepted = 0

    # -- internals ------------------------------------------------------
    @staticmethod
    def _mark_prefill_start(req: _Request):
        """Prefill work for ``req`` is being dispatched: tell its submitter,
        if this is the first (the callback is dropped once called)."""
        notify, req.on_prefill_start = req.on_prefill_start, None
        if notify is not None:
            notify()

    def _prefill_for_bucket(self, bucket: int):
        """B=1 ragged prefill into a bucket-length cache (pool-agnostic)."""
        def build():
            return compile_ragged_prefill_fn(
                self.mesh, self.cfg, self._eng.param_shardings, 1, bucket
            )[0]

        return cached_fn(self, "prefill_bucket", bucket, build, slots=8)

    def _insert_for_bucket(self, bucket: int, pi: int):
        """Splice a B=1 bucket cache into pool ``pi``'s shared cache row."""
        pool = self._pools[pi]

        def build():
            from deepspeed_tpu.inference.decoding import _decode_shardings

            _, small_sh = _decode_shardings(self.mesh, self.cfg, 1)

            def insert(big, small, slot):
                return kv_cache.splice_row(big, small, slot)

            return jax.jit(
                insert,
                in_shardings=(pool.cache_sh, small_sh, None),
                out_shardings=pool.cache_sh,
                donate_argnums=(0,),
            )

        # bounded memoization keyed (bucket, pool): 8 power-of-2 buckets
        # (16 <= b <= 2048) per pool, so capacity scales with pool count
        return cached_fn(self, "insert_bucket", (bucket, pi), build,
                         slots=8 * len(self._pools))

    def _chunk_schedule(self, pool: _Pool, toks: np.ndarray,
                        start: int) -> List[tuple]:
        """Split a prompt (or prefix suffix) into the fused-prefill chunk
        stream: [(tokens, pos0, n_real, emits)] — one tick each, the final
        chunk carries the last prompt token and samples the first generated
        token from its column."""
        cap = pool.chunk_cap
        out, off, m = [], 0, int(toks.size)
        while off < m:
            take = min(cap, m - off)
            out.append((np.asarray(toks[off:off + take], np.int32),
                        start + off, take, off + take == m))
            off += take
        return out

    def _set_row(self, pool: _Pool, slot: int, tok: int, flag: int):
        """Admission-time update of one row of the device-threaded tick
        state — dispatched against the current futures, never fetched."""
        if self.fault_hook is not None:
            self.fault_hook("set_row", {"tick": self._tick_index,
                                        "slot": slot})
        if self._state_pool:
            # nothing masks a stale recurrent state: the row starts from zero
            name = kv_cache.StateSpec.name
            pool.last_tok_dev, pool.done_dev, state = pool.set_row_fn(
                pool.last_tok_dev, pool.done_dev, slot, tok, flag, pool.cache[name])
            pool.cache = dict(pool.cache, **{name: state})
            return
        pool.last_tok_dev, pool.done_dev = pool.set_row_fn(
            pool.last_tok_dev, pool.done_dev, slot, tok, flag)

    def _admit(self, req: _Request, pi: int, slot: int):
        """Place ``req`` into a slot and dispatch its prefill — NOTHING
        here blocks or fetches. Fused mode queues the prompt as chunk(s)
        riding the next tick(s); separate mode prefills ``prompt[:-1]``
        through the B=1 bucket program + splice and re-feeds the last
        prompt token on the first decode tick (whose logits produce the
        first generated token — same stream, no admission-time sample)."""
        pool = self._pools[pi]
        req.slot, req.pool = slot, pi
        # placement guarantees prompt + max_new_tokens fits the pool row;
        # the device stops at gen >= quota, and gen starts at gen_base
        # (0 for fresh requests, len(emitted) for recovery resumes) so
        # the emission budget is exactly max_new_tokens either way
        req.quota = req.gen_base + req.max_new_tokens
        pool.active[slot] = req
        start = 0
        toks = req.prompt
        if req.prefix is not None:
            pre = req.prefix
            # splice the cached prefix KV into the slot row (the prefix
            # bucket cache is NOT donated — it serves every request)
            insert_fn = self._insert_for_bucket(pre["bucket"], pi)
            pool.cache = insert_fn(pool.cache, pre["cache"], slot)
            start = pre["tokens"].size
            toks = req.prompt[start:]
        if self.spec_gamma:
            self._admit_spec(req, pool, pi, slot, toks, start)
            return
        if self.fused_prefill:
            req.chunks = self._chunk_schedule(pool, toks, start)
            pool.prefill_q.append(req)
            # flip the row live on device; last_tok is set by the emitting
            # chunk tick itself (the sampled first token)
            self._set_row(pool, slot, int(toks[-1]), 0)
            return
        m = int(toks.size)
        self._separate_prefill(pool, pi, slot, req, toks, start)
        # the first tick re-feeds the last prompt token at its own
        # position (writing its KV there — the position was not prefilled)
        # and samples the first generated token from the resulting logits
        self._set_row(pool, slot, int(toks[-1]), 0)
        pool.disp_pos[slot] = start + m - 1
        pool.disp_gen[slot] = req.gen_base

    def _separate_prefill(self, pool: _Pool, pi: int, slot: int,
                          req: _Request, toks: np.ndarray, start: int):
        """Admission-time prefill of ``toks[:-1]`` into the slot row: the
        B=1 bucket program + splice, or the shared segment program for
        prefix suffixes. Shared by the plain separate path and every
        speculative non-fused admission."""
        m = int(toks.size)
        self._mark_prefill_start(req)
        if m <= 1:
            return
        if req.prefix is not None:
            # prefill the suffix MINUS its last token through the shared
            # segment program: other rows' positions park at the pool
            # length so their KV writes drop; pad columns land at future
            # positions of THIS row, each overwritten by a real decode
            # write before it is ever attended (slot-reuse argument)
            sb = _bucket(m - 1, pool.length)
            seg_toks = np.zeros((pool.n_slots, sb), np.int32)
            seg_toks[slot, :m - 1] = toks[:m - 1]
            seg_pos = np.full(pool.n_slots, pool.length, np.int32)
            seg_pos[slot] = start
            _, pool.cache = pool.segment_fn(
                self._eng.params, seg_toks, pool.cache, seg_pos)
        else:
            b = _bucket(m - 1, pool.length)
            prefill_fn = self._prefill_for_bucket(b)
            insert_fn = self._insert_for_bucket(b, pi)
            ptoks = np.zeros((1, b), np.int32)
            ptoks[0, :m - 1] = toks[:m - 1]
            # pads park at bucket (dropped writes), real tokens 0..m-2
            positions = np.full((1, b), b, np.int32)
            positions[0, :m - 1] = np.arange(m - 1, dtype=np.int32)
            small = kv_cache.init(self.cfg, 1, b)
            _, small = prefill_fn(self._eng.params, ptoks, positions, small)
            pool.cache = insert_fn(pool.cache, small, slot)

    def _admit_spec(self, req: _Request, pool: _Pool, pi: int, slot: int,
                    toks: np.ndarray, start: int):
        """Speculative admission. The row ALWAYS prefills its tokens minus
        the last one (fused mode chunks them through the shared segment
        program, one enqueue-only chunk per step; separate mode uses the
        bucket prefill + splice) — the row's first spec round feeds the
        last prompt token and its verify logits yield the first generated
        token, so fused and separate admission produce the same stream.
        Draft mode additionally prefills the FULL prompt minus its last
        token through the draft segment program in one dispatch (prefix
        caching is target-only — the draft cache starts cold)."""
        m = int(toks.size)
        first_pos = start + m - 1
        if self.spec_mode == "draft":
            mfull = int(req.prompt.size)
            if mfull > 1:
                db = _bucket(mfull - 1, pool.length)
                dtoks = np.zeros((pool.n_slots, db), np.int32)
                dtoks[slot, :mfull - 1] = req.prompt[:mfull - 1]
                dpos = np.full(pool.n_slots, pool.length, np.int32)
                dpos[slot] = 0
                _, pool.draft_cache = pool.draft_segment_fn(
                    self._draft_eng.params, dtoks, pool.draft_cache, dpos)
        if self.fused_prefill and m > 1:
            req.chunks = self._chunk_schedule(pool, toks[:-1], start)
            pool.prefill_q.append(req)
        else:
            self._separate_prefill(pool, pi, slot, req, toks, start)
        if self.fault_hook is not None:
            self.fault_hook("set_row", {"tick": self._tick_index,
                                        "slot": slot})
        (pool.last_tok_dev, pool.done_dev, pool.pos_dev,
         pool.gen_dev) = pool.spec_set_row_fn(
            pool.last_tok_dev, pool.done_dev, pool.pos_dev, pool.gen_dev,
            slot, int(toks[-1]), 0, first_pos, int(req.gen_base))
        pool.disp_pos[slot] = first_pos
        pool.disp_gen[slot] = req.gen_base

    @compile_log.phase("precompile")
    def precompile_tick_programs(self, progress: Optional[Callable] = None) -> int:
        """Compile (and block on) the FULL tick-program family — every
        (pool, read bucket, {plain/burst, fused chunk widths}) variant a
        serve could dispatch — so first serve-time requests don't pay a
        compile per variant (dstpu_prewarm --continuous).
        Runs each program once on throwaway state. Returns the count."""
        count = 0
        for pool in self._pools:
            # enumerate the families through the SAME functions the serve
            # dispatch uses (_read_len over every reachable extent, the
            # chunk bucket over every real chunk size) — the warmed set can
            # never drift from what a live tick will request
            read_lens = sorted(
                {self._read_len(pool, e) for e in range(1, pool.length + 1)},
                key=lambda r: (r is None, r))
            if self.spec_gamma:
                count += self._precompile_spec(pool, read_lens, progress)
                continue
            chunks: List[Optional[int]] = [None]
            if self.fused_prefill:
                chunks += sorted({self._chunk_width(pool, m)
                                  for m in range(1, pool.chunk_cap + 1)})
            for rl in read_lens:
                for ch in chunks:
                    t0 = time.time()
                    fn = self._tick_fn(pool, rl, chunk=ch)
                    cache = jax.device_put(
                        kv_cache.init(self.cfg, pool.n_slots, pool.length),
                        pool.cache_sh)

                    # the KINDS of argument a served tick passes (placed
                    # row state, NumPy host inputs): the call's fast-path
                    # entry made here is the one the first served tick hits
                    zeros = functools.partial(np.zeros, pool.n_slots, np.int32)
                    parked = np.full(pool.n_slots, pool.length, np.int32)
                    args = (self._eng.params, cache, *pool.fresh_rows(),
                            parked, zeros(), zeros(), zeros(), self._base_key)
                    if ch is not None:
                        args += (np.zeros(ch, np.int32),
                                 np.full(ch, pool.length, np.int32), 0,
                                 zeros(), zeros())
                    jax.block_until_ready(fn(*args)[0])
                    count += 1
                    if progress is not None:
                        progress(f"tick(pool={pool.length}, read_len={rl}, "
                                 f"chunk={ch}) in {time.time() - t0:.1f}s")
        return count

    def _precompile_spec(self, pool: _Pool, read_lens, progress) -> int:
        """Speculative arm of :meth:`precompile_tick_programs`: the spec
        tick per read bucket (chunks never enter it — fused admission
        rides the segment program, warmed per chunk width below)."""
        count, g, n = 0, self.spec_gamma, pool.n_slots
        for rl in read_lens:
            t0 = time.time()
            fn = self._spec_tick_fn(pool, rl)
            cache = jax.device_put(
                kv_cache.init(self.cfg, n, pool.length), pool.cache_sh)

            # the kinds of argument a served round passes, as above
            zeros = functools.partial(np.zeros, n, np.int32)
            rows = (*pool.fresh_rows(), *pool.fresh_spec_rows())
            if self.spec_mode == "draft":
                dcache = jax.device_put(
                    kv_cache.init(self.draft_cfg, n, pool.length),
                    pool.draft_cache_sh)
                args = (self._eng.params, self._draft_eng.params, cache,
                        dcache, *rows, zeros(), zeros(), zeros(),
                        self._base_key)
            else:
                args = (self._eng.params, cache, *rows, zeros(), zeros(),
                        zeros(), np.zeros((n, g), np.int32), self._base_key)
            jax.block_until_ready(fn(*args)[0])
            count += 1
            if progress is not None:
                progress(f"spec_tick(pool={pool.length}, read_len={rl}, "
                         f"mode={self.spec_mode}, gamma={g}) "
                         f"in {time.time() - t0:.1f}s")
        if self.fused_prefill:
            # fused spec admission dispatches prompt chunks through the
            # shared segment program — retraces per chunk width
            for W in sorted({_bucket(m, pool.chunk_cap, _SPEC_CHUNK_FLOOR)
                             for m in range(1, pool.chunk_cap + 1)}):
                t0 = time.time()
                cache = jax.device_put(
                    kv_cache.init(self.cfg, n, pool.length), pool.cache_sh)
                _, c2 = pool.segment_fn(
                    self._eng.params, np.zeros((n, W), np.int32), cache,
                    np.full(n, pool.length, np.int32))
                jax.block_until_ready(c2)
                count += 1
                if progress is not None:
                    progress(f"spec_segment(pool={pool.length}, chunk={W}) "
                             f"in {time.time() - t0:.1f}s")
        return count

    def _finish(self, pool: _Pool, slot: int):
        # pool pressure BEFORE the pop: the event describes the state this
        # request served under (popping first reads 0.0 for the last one)
        util = self.cache_utilization()
        req = pool.active.pop(slot)
        self._flush_window(req)  # tail window span BEFORE the request
        # leaves the serving layer's engine-rid table (the hook resolves
        # the trace through it)
        self._results[req.rid] = np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)]
        )
        tele = self._eng.telemetry
        if tele.enabled:
            new = len(req.generated)
            event = {
                "request": int(req.rid),
                "path": "continuous",
                "batch": 1,
                "prompt_tokens": int(req.prompt.size),
                "new_tokens": new,
                "cache_len": pool.length,
                "kv_dtype": ("int8" if self.cfg.kv_cache_dtype == "int8"
                             else self.cfg.dtype),
                "kv_bytes_read": int(req.kv_bytes_read),
                "cache_utilization": round(util, 4),
            }
            if new:  # every token rides a pool-tick read now
                event["kv_bytes_per_token"] = round(req.kv_bytes_read / new, 1)
            if self.spec_gamma:
                event["spec_gamma"] = self.spec_gamma
                event["spec_drafted"] = int(req.spec_drafted)
                event["spec_accepted"] = int(req.spec_accepted)
            if self.request_event_hook is not None:
                event = self.request_event_hook(req.rid, event) or event
            tele.emit("inference_request", event)
