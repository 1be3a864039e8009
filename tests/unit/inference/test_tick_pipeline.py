"""Async serving hot path (inference/continuous.py + decoding.py tick
programs): dispatch-pipelined ticks with ON-DEVICE acceptance, prefill/
decode fusion, and donated tick state. The acceptance invariant tested
throughout: scheduling mode (pipeline depth, fused vs separate prefill,
burst width) may change WHEN a token surfaces, never WHAT it is — token
streams are bitwise identical across every mode, greedy AND sampled."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.telemetry import compile_log
from serving_toys import SMALL, built, prompts as _prompts, serve as _serve

FLOOR = 32  # a tight-read floor under the 64-slot pools: ONE crossing (32 -> the whole pool) a stream


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    model, params = built(SMALL)
    plain = deepspeed_tpu.init_inference(model, params=params,
                                         config={"dtype": "float32"})
    return model, params, plain


def _cb(setup, **kw):
    model, params, _ = setup
    cfg = {"dtype": "float32", "kv_read_floor": FLOOR}
    cfg.update(kw.pop("config", {}))
    kw.setdefault("max_slots", 3)
    kw.setdefault("cache_len", 64)
    return ContinuousBatchingEngine(model, params=params, config=cfg, **kw)


class TestPipelineParity:
    def test_pipelined_matches_sync_greedy_mixed_admission(self, setup):
        """Acceptance: bitwise token-stream parity pipelined-vs-sync under
        bucket migrations (bucketed pools) and mixed mid-flight admission,
        at depths 0 / 1 / 2."""
        subs = list(zip((0, 0, 0, 1, 3, 4), _prompts((5, 9, 3, 20, 7, 4), 1),
                        (12, 40, 8, 10, 6, 9)))
        outs = {}
        for depth in (0, 1, 2):
            cb = _cb(setup, max_slots=None, cache_len=None,
                     cache_buckets=[(2, 32), (2, 64)], pipeline_depth=depth)
            outs[depth] = _serve(cb, subs)
        for depth in (1, 2):
            for a, b in zip(outs[0], outs[depth]):
                np.testing.assert_array_equal(a, b)

    def test_pipelined_matches_sync_sampled(self, setup):
        """Sampled parity: per-request rng (request_keys) makes sampled
        streams independent of scheduling, so depth 0/1 and fused/separate
        admission all produce bitwise-identical draws."""
        subs = list(zip((0, 0, 2), _prompts((6, 11, 4), 2), (10, 10, 8)))
        variants = [
            dict(pipeline_depth=0),
            dict(pipeline_depth=1),
            dict(pipeline_depth=1, fused_prefill=False),
            dict(pipeline_depth=0, fused_prefill=False),
        ]
        outs = []
        for kw in variants:
            cb = _cb(setup, temperature=0.9, top_k=20, top_p=0.9, seed=11,
                     **kw)
            outs.append(_serve(cb, subs))
        for other in outs[1:]:
            for a, b in zip(outs[0], other):
                np.testing.assert_array_equal(a, b)
        # and the draws really are sampled (greedy run differs)
        greedy = _serve(_cb(setup, seed=11), subs)
        assert any(not np.array_equal(a, b) for a, b in zip(outs[0], greedy))

    def test_burst_pipelined_matches_sync_with_eos(self, setup):
        """Burst ticks (k decode steps per dispatch, on-device acceptance)
        at depth 1 equal depth 0, including a request EOS-finishing
        mid-burst (the waste past its done flag is masked on device)."""
        model, params, plain = setup
        prompts = _prompts((5, 9, 3), 3)
        ref = np.asarray(plain.generate(prompts[0][None, :], max_new_tokens=12))[0]
        eos = int(ref[len(prompts[0]) + 2])  # finishes mid-burst at k=4
        subs = list(zip((0, 0, 1), prompts, (12, 12, 12)))
        outs = {}
        for depth in (0, 1):
            cb = _cb(setup, tokens_per_tick=4, eos_token_id=eos,
                     pipeline_depth=depth)
            outs[depth] = _serve(cb, subs)
        for a, b in zip(outs[0], outs[1]):
            np.testing.assert_array_equal(a, b)
        assert outs[0][0][-1] == eos and len(outs[0][0]) == len(prompts[0]) + 3

    def test_fused_prefill_matches_separate_and_plain(self, setup):
        """Acceptance: fused-prefill admission (prompt chunks riding the
        decode tick) produces the same streams as separate-prefill
        admission AND as the plain engine's generate."""
        model, params, plain = setup
        prompts = _prompts((5, 13, 26, 2, 1), 4)
        refs = [np.asarray(plain.generate(p[None, :], max_new_tokens=8))[0]
                for p in prompts]
        subs = [(i % 3, p, 8) for i, p in enumerate(prompts)]
        fused = _serve(_cb(setup, fused_prefill=True), subs)
        separate = _serve(_cb(setup, fused_prefill=False), subs)
        for f, s, r in zip(fused, separate, refs):
            np.testing.assert_array_equal(f, s)
            np.testing.assert_array_equal(f, r)

    def test_long_prompt_prefills_while_others_decode(self, setup):
        """Acceptance: with fused prefill, admission never stalls decode —
        while a long prompt streams its chunks through successive ticks,
        the already-active row keeps emitting every tick."""
        model, params, plain = setup
        short, long_p = _prompts((4, 40), 5)
        cb = _cb(setup, pipeline_depth=0, prefill_chunk=16, max_slots=2)
        ref_long = np.asarray(plain.generate(long_p[None, :], max_new_tokens=8))[0]
        r_short = cb.submit(short, max_new_tokens=30)
        cb.step()
        r_long = cb.submit(long_p, max_new_tokens=8)  # 3 chunks: 16+16+8
        waiting, short_ticks = 0, 0
        for _ in range(50):
            out = cb.step()
            if r_long in out:
                break
            waiting += 1
            short_ticks += 1 if r_short in out else 0
        else:
            raise AssertionError("long request never emitted")
        # the first two chunk ticks emit nothing for the long request...
        assert waiting >= 2
        # ... but the short request decoded right through them
        assert short_ticks == waiting
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        done.update(cb.finished())
        np.testing.assert_array_equal(done[r_long], ref_long)

    def test_prefix_caching_fused_parity(self, setup):
        """Prefix splice + fused suffix chunks reproduce full-prompt
        generate exactly (and survive a concurrent decode row)."""
        model, params, plain = setup
        rs = np.random.RandomState(6)
        prefix = rs.randint(0, 128, (11,)).astype(np.int32)
        suffix = rs.randint(0, 128, (4,)).astype(np.int32)
        other = rs.randint(0, 128, (6,)).astype(np.int32)
        for depth in (0, 1):
            cb = _cb(setup, max_slots=2, pipeline_depth=depth)
            pid = cb.register_prefix(prefix)
            r_other = cb.submit(other, max_new_tokens=10)
            cb.step()
            rid = cb.submit_with_prefix(pid, suffix, max_new_tokens=6)
            done = {}
            while cb.has_work():
                cb.step()
                done.update(cb.finished())
            full = np.concatenate([prefix, suffix])
            want = np.asarray(plain.generate(full[None, :], max_new_tokens=6))[0]
            np.testing.assert_array_equal(done[rid], want)
            want_o = np.asarray(plain.generate(other[None, :], max_new_tokens=10))[0]
            np.testing.assert_array_equal(done[r_other], want_o)


class TestPipelineLifecycle:
    def test_cancel_while_tick_in_flight(self, setup):
        """Acceptance: cancelling a request whose tick is already in
        flight frees its slot; the retired tick's row for it is dropped,
        the survivor's stream is untouched, and the freed slot serves a
        fresh admission correctly (stale KV position-masked)."""
        model, params, plain = setup
        p_a, p_b, p_c = _prompts((5, 7, 6), 7)
        ref_b = np.asarray(plain.generate(p_b[None, :], max_new_tokens=20))[0]
        ref_c = np.asarray(plain.generate(p_c[None, :], max_new_tokens=5))[0]
        cb = _cb(setup, max_slots=2, pipeline_depth=1)
        ra = cb.submit(p_a, max_new_tokens=20)
        rb = cb.submit(p_b, max_new_tokens=20)
        for _ in range(3):
            cb.step()          # ticks in flight carrying both rows
        assert cb._inflight    # a tick really is in flight at depth 1
        assert cb.cancel(ra) is True
        assert cb.status(ra) == "cancelled"
        rc = cb.submit(p_c, max_new_tokens=5)  # reuses ra's slot
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        assert ra not in done  # never surfaced
        np.testing.assert_array_equal(done[rb], ref_b)
        np.testing.assert_array_equal(done[rc], ref_c)
        with pytest.raises(KeyError, match="cancelled"):
            cb.result(ra)

    def test_cancel_mid_prefill_chunks(self, setup):
        """Cancelling a request while its prompt chunks are still queued
        removes it from the prefill queue; the pool keeps serving."""
        model, params, plain = setup
        short, long_p = _prompts((4, 40), 8)
        cb = _cb(setup, max_slots=2, prefill_chunk=16, pipeline_depth=1)
        r_short = cb.submit(short, max_new_tokens=12)
        r_long = cb.submit(long_p, max_new_tokens=8)
        cb.step()  # long prompt's first chunk dispatched or queued
        assert cb.cancel(r_long) is True
        assert not cb._pools[0].prefill_q
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        want = np.asarray(plain.generate(short[None, :], max_new_tokens=12))[0]
        np.testing.assert_array_equal(done[r_short], want)

    def test_donated_ticks_do_not_alias_live_prefix_buffer(self, setup):
        """Acceptance: donation must never alias a LIVE buffer — the
        registered prefix KV is reused by every request while tick
        programs donate the pool cache around it; repeated prefix serves
        must stay bitwise stable (an aliasing bug corrupts the second)."""
        model, params, plain = setup
        rs = np.random.RandomState(9)
        prefix = rs.randint(0, 128, (9,)).astype(np.int32)
        suffix = rs.randint(0, 128, (3,)).astype(np.int32)
        cb = _cb(setup, max_slots=2, pipeline_depth=1)
        pid = cb.register_prefix(prefix)
        full = np.concatenate([prefix, suffix])
        want = np.asarray(plain.generate(full[None, :], max_new_tokens=6))[0]
        for _ in range(3):  # every serve donates the pool cache repeatedly
            rid = cb.submit_with_prefix(pid, suffix, max_new_tokens=6)
            done = {}
            while cb.has_work():
                cb.step()
                done.update(cb.finished())
            np.testing.assert_array_equal(done[rid], want)


class TestTickTelemetry:
    def test_tick_stats_and_trace_events(self, setup, tmp_path):
        """tick_stats() + registry + serving_tick trace events: dispatch/
        block spans recorded, burst waste counted (EOS mid-burst), and the
        trace alone carries the overlap breakdown."""
        model, params, plain = setup
        prompts = _prompts((5, 7), 10)
        ref = np.asarray(plain.generate(prompts[0][None, :], max_new_tokens=12))[0]
        eos = int(ref[len(prompts[0]) + 2])
        trace = tmp_path / "ticks.jsonl"
        cb = _cb(setup, max_slots=2, tokens_per_tick=4, eos_token_id=eos,
                 pipeline_depth=1,
                 config={"telemetry": {"enabled": True,
                                       "trace_file": str(trace)}})
        for p in prompts:
            cb.submit(p, max_new_tokens=12)
        starved = []   # the wall time with no tick in flight, read while rows still decode
        while cb.has_work():
            cb.step()
            if cb._inflight:
                starved.append(cb.tick_stats()["inflight_empty_ms"])
        done = cb.finished()
        stats = cb.tick_stats()
        assert stats["ticks"] > 0 and stats["steps"] >= stats["ticks"]
        assert stats["tokens"] == sum(len(v) for v in done.values()) - sum(
            len(p) for p in prompts)
        assert stats["wasted_tokens"] > 0  # EOS mid-burst wastes burst tail
        assert stats["dispatch_ms"] > 0 and stats["block_ms"] >= 0
        # pipelined: from the first dispatch to the last retire a tick was always in flight
        assert stats["pipeline_depth"] == 1 and len(starved) > 1 and starved[-1] == starved[0]
        assert stats["inflight_empty_ms"] > starved[-1] and stats["attribute_ms"] > 0
        assert 0 < stats["admit_ms"] < stats["dispatch_ms"]
        assert 0.0 <= stats["overlap_frac"] <= 1.0
        assert stats["block_ms_per_token"] is not None
        reg = cb._eng.telemetry.registry.dump()
        assert reg["counters"]["burst_wasted_tokens"] == stats["wasted_tokens"]
        assert any(k.startswith("tick_dispatch_ms") for k in reg["histograms"])
        assert reg["gauges"]["tick_inflight_depth"] == 0  # drained
        cb._eng.telemetry.close()
        events = [json.loads(l) for l in trace.read_text().splitlines()]
        ticks = [e for e in events if e["kind"] == "serving_tick"]
        assert ticks and all("dispatch_ms" in e and "block_ms" in e
                             and "emitted" in e for e in ticks)
        assert sum(e["emitted"] for e in ticks) == stats["tokens"]
        assert sum(e["wasted"] for e in ticks) == stats["wasted_tokens"]

    def test_sync_mode_keeps_nothing_in_flight(self, setup):
        """pipeline_depth=0 is the fully synchronous scheduler: step()
        retires its own tick — the in-flight queue is always empty on
        return and results never lag."""
        cb = _cb(setup, max_slots=1, pipeline_depth=0)
        rid = cb.submit(_prompts((4,), 11)[0], max_new_tokens=3)
        seen, starved = 0, [cb.tick_stats()["inflight_empty_ms"]]
        while cb.has_work():
            out = cb.step()
            seen += len(out.get(rid, []))
            assert not cb._inflight
            starved.append(cb.tick_stats()["inflight_empty_ms"])
        assert seen == 3
        # ... so the chip has nothing to run from each fetch to the next dispatch
        assert all(b > a for a, b in zip(starved, starved[1:]))


class _Watched:
    """One served window of plain and fused ticks after
    ``precompile_tick_programs`` and a warm-up request, with everything the
    cases below read noted on the way. ``as_arrays=True`` serves the same
    window in the form the dispatch had before: ``jnp.asarray`` of each host
    array, then the jitted call."""

    def __init__(self, setup, monkeypatch, as_arrays=False):
        cb = _cb(setup, temperature=0.9, top_k=20, top_p=0.9, seed=11, prefill_chunk=16)
        cb.precompile_tick_programs()
        _serve(cb, [(0, _prompts((3,), 9)[0], 2)])   # the first admission builds ``row_update``
        pool = cb._pools[0]
        self.programs = dict(pool.tick_fns)
        self.handed = []       # (the array a tick was given, its contents then)
        self.written = []      # those that read otherwise when their step ended
        self.puts = 0          # device_puts made under _dispatch_tick
        inside = [0]

        def watching(fn):
            def call(*args):
                host = [a for a in args if isinstance(a, np.ndarray)]
                self.handed += [(a, a.copy()) for a in host]
                if as_arrays:
                    args = [jnp.asarray(a) if isinstance(a, np.ndarray) else a for a in args]
                return fn(*args)
            return call

        def counting(put):
            def call(*args, **kw):
                self.puts += inside[0]
                return put(*args, **kw)
            return call

        dispatch, step = cb._dispatch_tick, cb.step

        def dispatching(p):
            inside[0] += 1
            try:
                return dispatch(p)
            finally:
                inside[0] -= 1

        def stepping():
            out = step()
            self.written += [a for a, was in self.handed if not np.array_equal(a, was)]
            return out

        for key, fn in self.programs.items():
            pool.tick_fns[key] = watching(fn)
        cb._dispatch_tick, cb.step = dispatching, stepping
        # ``jnp.asarray`` reaches ``device_put`` through the module it lives in
        monkeypatch.setattr(jax._src.api, "device_put", counting(jax._src.api.device_put))
        monkeypatch.setattr(jax, "device_put", counting(jax.device_put))
        self.sizes0 = {key: fn._cache_size() for key, fn in self.programs.items()}
        stats0, t_open = cb.tick_stats(), time.monotonic()   # the journal's clock
        subs = list(zip((0, 0, 0, 1, 3, 4), _prompts((5, 9, 3, 40, 7, 4), 1), (12, 20, 8, 10, 6, 9)))
        self.streams = _serve(cb, subs)
        monkeypatch.undo()
        stats = cb.tick_stats()
        self.plain, self.fused, self.built = (
            stats[k] - stats0[k] for k in ("plain_ticks", "fused_prefill_ticks", "programs_built"))
        self.sizes = {key: fn._cache_size() for key, fn in self.programs.items()}
        self.journalled = [e for e in compile_log.journal() if e["t"] >= t_open]


@pytest.fixture(scope="module")
def watched(setup):
    with pytest.MonkeyPatch.context() as mp:
        yield _Watched(setup, mp), _Watched(setup, mp, as_arrays=True)


@pytest.mark.parametrize("holds", ["no_device_put", "no_new_entry", "arrays_left_alone", "same_streams"])
def test_a_ticks_host_inputs_cross_inside_the_call(watched, holds):
    """A steady tick's dispatch hands its host inputs to the program as the
    NumPy arrays they are: (a) no ``device_put`` is made under
    ``_dispatch_tick`` (the form with ``jnp.asarray`` makes four a plain tick
    and eight a fused one: the counter sees them), (b) warm-up made the
    call's one entry (no program's ``_cache_size()`` grows, nothing is built),
    (c) nothing writes an array after the call, (d) the streams are those of
    the ``jnp.asarray`` form."""
    now, before = watched
    assert now.plain > 0 and now.fused > 0 and (now.plain, now.fused) == (before.plain, before.fused)
    if holds == "no_device_put":
        assert now.puts == 0
        assert before.puts == 4 * before.plain + 8 * before.fused
    elif holds == "no_new_entry":
        assert now.sizes == now.sizes0 and set(now.sizes.values()) == {1}
        assert now.built == 0 and not now.journalled
    elif holds == "arrays_left_alone":
        assert len(now.handed) == 4 * now.plain + 8 * now.fused and not now.written
        assert all(a.dtype == np.int32 for a, _ in now.handed)
    else:
        for a, b in zip(now.streams, before.streams):
            np.testing.assert_array_equal(a, b)
