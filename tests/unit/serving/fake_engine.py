"""A host-only ContinuousBatchingEngine stand-in for jax-free serving
tests (router, policies, admission): same public surface the serving
layer drives, with a deterministic pure-function token stream that gives
REAL bitwise-resume semantics — token i of request rid is
``(rid * 1000003 + i * 101) % vocab`` regardless of which engine
instance emits it, exactly the property ``fold_in(fold_in(key, rid),
i)`` gives the real engine. So ``submit(rid=, gen_base=)`` resume, rid
partitioning, and cross-replica migration are all testable for
bitwise identity in milliseconds, no jax import anywhere."""

import time
from collections import deque
from types import SimpleNamespace

import numpy as np


def fake_token(rid: int, index: int, vocab: int) -> int:
    """The deterministic per-(rid, token-index) stream."""
    return (rid * 1000003 + index * 101) % vocab


class FakeEngine:
    """Mirrors the ``ContinuousBatchingEngine`` surface ``ServingEngine``
    uses: one pool, one token per request per tick, results keyed by
    engine rid. Fault knobs: ``fail_next_step`` raises a clean error
    before any mutation; ``poison_next_step`` raises mid-tick and marks
    the engine poisoned (the unrecoverable shape)."""

    def __init__(self, vocab_size: int = 101, cache_len: int = 64,
                 slots: int = 4, clock=time.monotonic):
        self.cfg = SimpleNamespace(vocab_size=vocab_size,
                                   max_seq_len=cache_len)
        self.cache_len = cache_len
        self.slots = slots
        self.pipeline_depth = 1
        self.fetch_timeout_s = None
        self.poisoned = False
        self.fault_hook = None
        self.request_event_hook = None
        # request tracing, mirroring the real engine: the serving layer
        # installs span_hook when its hub is live; each tick then reports
        # one window span per live request (prefill_chunk on the
        # admission tick, spec_verify_round under spec_gamma > 0, else
        # decode_window). ``clock`` should be the same injected clock the
        # ServingEngine runs on, so span times share its domain.
        self.span_hook = None
        self.clock = clock
        # the host ledger, mirrored: ``tick_cost`` = (dispatch_s, block_s,
        # attribute_s) a tick, spent by calling ``advance`` (the injected
        # clock's own) and charged to the rows the real batcher keeps; the
        # fake "device" runs only while the host is blocked on it, so no tick
        # is in flight the rest of the time
        self.tick_cost = None
        self.advance = None
        self.last_step_ms = 0.0
        self._created = clock()
        # request lifecycle, mirroring the real engine: ``submit``'s
        # ``on_prefill_start`` is called once, when the request's prefill
        # starts; ``prefill_wait_ticks`` > 0 holds an admitted request that
        # many ticks first (the real engine's one-chunk-a-tick prefill queue)
        self.prefill_wait_ticks = 0
        # spec accounting knob: gamma > 0 emulates speculative ticks —
        # the TOKEN STREAM is unchanged (still one token/request/tick, so
        # bitwise-resume invariants hold); only drafted/accepted
        # accounting and span kinds change
        self.spec_gamma = 0
        self.fail_next_step = 0        # clean failures to raise
        self.poison_next_step = False  # poison on the next tick
        self._eng = SimpleNamespace(telemetry=_DisabledTelemetry())
        self._next_rid = 0
        self._pending = []             # admitted next tick
        self._active = {}              # rid -> state dict
        self._results = {}             # rid -> full token array
        self._inflight = deque()
        self._tick_index = 0
        self._stats = {"ticks": 0, "steps": 0, "dispatch_ms": 0.0,
                       "block_ms": 0.0, "attribute_ms": 0.0, "admit_ms": 0.0,
                       "ticks_ready_at_retire": 0, "tokens": 0, "wasted": 0,
                       "capacity_tokens": 0, "spec_drafted": 0,
                       "spec_accepted": 0}
        self._prefixes = {}
        self._next_pid = 0

    # -- admission ------------------------------------------------------
    def validate_request(self, prompt_ids, max_new_tokens: int):
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.cache_len:
            raise ValueError(
                f"prompt {prompt.size} + max_new_tokens {max_new_tokens} "
                f"exceeds cache_len {self.cache_len}")
        return prompt

    def submit(self, prompt_ids, max_new_tokens: int = 32, *,
               rid=None, gen_base: int = 0, on_prefill_start=None) -> int:
        prompt = self.validate_request(prompt_ids, max_new_tokens)
        if rid is None:
            rid = self._next_rid
        else:
            rid = int(rid)
            if rid in self._active or rid in self._results or any(
                    r["rid"] == rid for r in self._pending):
                raise ValueError(f"rid {rid} already in use")
        self._next_rid = max(self._next_rid, rid + 1)
        self._pending.append({"rid": rid, "prompt": prompt,
                              "max_new": int(max_new_tokens),
                              "gen_base": int(gen_base), "emitted": [],
                              "on_prefill_start": on_prefill_start})
        return rid

    def register_prefix(self, prefix_ids) -> int:
        pid = self._next_pid
        self._next_pid += 1
        self._prefixes[pid] = np.asarray(prefix_ids, np.int32).reshape(-1)
        return pid

    def unregister_prefix(self, pid: int):
        self._prefixes.pop(pid, None)

    def submit_with_prefix(self, pid: int, suffix, max_new_tokens: int, *,
                           on_prefill_start=None) -> int:
        full = np.concatenate([self._prefixes[pid],
                               np.asarray(suffix, np.int32).reshape(-1)])
        return self.submit(full, max_new_tokens, on_prefill_start=on_prefill_start)

    # -- the tick -------------------------------------------------------
    def pool_state(self):
        return [{"length": self.cache_len, "slots": self.slots,
                 "free": self.slots - len(self._active)}]

    def has_work(self) -> bool:
        return bool(self._pending) or bool(self._active)

    def step(self):
        if self.fault_hook is not None:
            self.fault_hook("dispatch", {"tick": self._tick_index})
        self._tick_index += 1
        if self.fail_next_step > 0:
            self.fail_next_step -= 1
            raise RuntimeError("injected clean tick failure")
        if self.poison_next_step:
            self.poison_next_step = False
            self.poisoned = True
            raise RuntimeError("injected poisoned tick failure")
        # admit everything placeable, submission order
        still = []
        for req in self._pending:
            if len(self._active) < self.slots:
                req["fresh"] = True  # first tick prefills
                req["wait"] = self.prefill_wait_ticks
                self._active[req["rid"]] = req
            else:
                still.append(req)
        self._pending = still
        out = {}
        finished = []
        span_t0 = self.clock() if self.span_hook is not None else 0.0
        g = self.spec_gamma
        for rid, req in self._active.items():
            if req["wait"] > 0:
                req["wait"] -= 1
                continue
            notify = req.pop("on_prefill_start", None)
            if notify is not None:
                notify()
            idx = req["gen_base"] + len(req["emitted"])
            tok = fake_token(rid, idx, self.cfg.vocab_size)
            req["emitted"].append(tok)
            out[rid] = [tok]
            if g:
                # deterministic acceptance pattern: varies per (rid,
                # tick) so acceptance-rate math has real structure
                accepted = (rid + idx) % (g + 1)
                self._stats["spec_drafted"] += g
                self._stats["spec_accepted"] += accepted
                req["spec_drafted"] = req.get("spec_drafted", 0) + g
                req["spec_accepted"] = req.get("spec_accepted", 0) + accepted
            fresh, req["fresh"] = req["fresh"], False
            if self.span_hook is not None:
                if fresh:
                    kind, attrs = "prefill_chunk", {
                        "ticks": 1, "tokens": int(req["prompt"].size)}
                elif g:
                    kind, attrs = "spec_verify_round", {
                        "ticks": 1, "tokens": 1,
                        "drafted": g, "accepted": accepted}
                else:
                    kind, attrs = "decode_window", {"ticks": 1, "tokens": 1}
                self.span_hook(rid, kind, span_t0, self.clock(), attrs)
            if len(req["emitted"]) + req["gen_base"] >= req["max_new"] \
                    + req["gen_base"] and \
                    len(req["emitted"]) >= req["max_new"]:
                finished.append(rid)
        for rid in finished:
            req = self._active.pop(rid)
            self._results[rid] = np.concatenate(
                [req["prompt"], np.asarray(req["emitted"], np.int32)])
            self._emit_request_event(rid, req)
        if self.tick_cost is not None:
            for row, cost in zip(("dispatch_ms", "block_ms", "attribute_ms"), self.tick_cost):
                self.advance(cost)
                self._stats[row] += cost * 1000.0
            self.last_step_ms = sum(self.tick_cost) * 1000.0
        self._stats["ticks"] += 1
        self._stats["steps"] += 1
        self._stats["tokens"] += sum(len(t) for t in out.values())
        self._stats["capacity_tokens"] += self.slots
        return out

    def _emit_request_event(self, rid: int, req: dict):
        tele = self._eng.telemetry
        if not getattr(tele, "enabled", False):
            return
        event = {"request": int(rid), "path": "continuous", "batch": 1,
                 "prompt_tokens": int(req["prompt"].size),
                 "new_tokens": len(req["emitted"])}
        if self.request_event_hook is not None:
            enriched = self.request_event_hook(rid, event)
            if enriched is not None:
                event = enriched
        tele.emit("inference_request", event)

    def finished(self):
        done, self._results = self._results, {}
        return done

    def cancel(self, rid: int) -> bool:
        if rid in self._active:
            self._active.pop(rid)
            return True
        n = len(self._pending)
        self._pending = [r for r in self._pending if r["rid"] != rid]
        return len(self._pending) < n

    def abort_inflight(self) -> int:
        return 0

    # -- accounting -----------------------------------------------------
    def inflight_empty_ms(self) -> float:
        return (self.clock() - self._created) * 1000.0 - self._stats["block_ms"]

    def tick_stats(self) -> dict:
        s = dict(self._stats)
        s["inflight_empty_ms"] = self.inflight_empty_ms()
        s["pipeline_depth"] = self.pipeline_depth
        s["mean_emitted_per_tick"] = (round(s["tokens"] / s["ticks"], 3)
                                      if s["ticks"] else 0.0)
        s["block_ms_per_token"] = (round(s["block_ms"] / s["tokens"], 4)
                                   if s["tokens"] else None)
        host = s["dispatch_ms"] + s["block_ms"]
        s["overlap_frac"] = (round(1.0 - s["block_ms"] / host, 4)
                             if host > 0 else None)
        s["spec_acceptance"] = (round(s["spec_accepted"] / s["spec_drafted"], 4)
                                if s["spec_drafted"] else None)
        return s

    def hbm_components(self) -> dict:
        return {"params": 0, "kv_cache": 0}

    def memory_snapshot(self, reason: str):
        return None


class _DisabledTelemetry:
    """The inert hub shape a telemetry-off engine carries."""

    enabled = False

    def __init__(self):
        from deepspeed_tpu.telemetry.registry import MetricsRegistry

        self.registry = MetricsRegistry()

    def emit(self, kind, payload, **kw):
        return None

    def close(self):
        pass
