"""Continuous (in-flight) batching (inference/continuous.py) — slot-pool
serving beyond the v0.9.1 reference's static-batch generate."""

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from serving_toys import SMALL, built, prompts as _prompts


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    model, params = built(SMALL)
    plain = deepspeed_tpu.init_inference(model, params=params, config={"dtype": "float32"})
    return model, params, plain


class TestContinuousBatching:
    def test_staggered_admission_matches_plain_generate(self, setup):
        """4 requests through 3 slots, one admitted mid-flight: every
        output must equal the plain engine's greedy generate."""
        model, params, plain = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=3, cache_len=64)
        prompts = _prompts((5, 9, 3, 7))
        refs = [np.asarray(plain.generate(p[None, :], max_new_tokens=8))[0]
                for p in prompts]
        rids = [cb.submit(p, max_new_tokens=8) for p in prompts[:3]]
        cb.step()
        cb.step()
        rids.append(cb.submit(prompts[3], max_new_tokens=8))  # slot reuse
        while cb.has_work():
            cb.step()
        done = cb.finished()
        for rid, want in zip(rids, refs):
            np.testing.assert_array_equal(done[rid], want)

    def test_burst_tick_matches_single_step(self, setup):
        """tokens_per_tick=4 (k decode steps fused into one compiled scan)
        must produce the SAME greedy outputs as the per-token tick,
        including a mid-flight admission and an EOS finishing mid-burst."""
        model, params, plain = setup
        prompts = _prompts((5, 9, 3, 7), seed=2)
        refs = [np.asarray(plain.generate(p[None, :], max_new_tokens=10))[0]
                for p in prompts]
        # eos chosen from request 0's stream so it finishes mid-burst
        eos = int(refs[0][len(prompts[0]) + 2])
        want = {}
        for i, r in enumerate(refs):
            gen = r[len(prompts[i]):]
            cut = np.nonzero(gen == eos)[0]
            end = cut[0] + 1 if cut.size else len(gen)
            want[i] = np.concatenate([prompts[i], gen[:end]])
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=3, cache_len=64,
                                      eos_token_id=eos, tokens_per_tick=4)
        rids = [cb.submit(p, max_new_tokens=10) for p in prompts[:3]]
        cb.step()
        rids.append(cb.submit(prompts[3], max_new_tokens=10))  # slot reuse
        while cb.has_work():
            cb.step()
        done = cb.finished()
        for i, rid in enumerate(rids):
            np.testing.assert_array_equal(done[rid], want[i])

    def test_eos_frees_slot_early(self, setup):
        """A request hitting EOS releases its slot while others continue."""
        model, params, plain = setup
        # pick an EOS id we KNOW the greedy path emits: generate once and
        # use the first generated token of prompt A as the eos id
        prompts = _prompts((4, 6), seed=1)
        probe = np.asarray(plain.generate(prompts[0][None, :], max_new_tokens=1))[0]
        eos = int(probe[-1])
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64,
                                      eos_token_id=eos)
        ra = cb.submit(prompts[0], max_new_tokens=8)
        rb = cb.submit(prompts[1], max_new_tokens=8)
        done = {}
        ticks = 0
        while cb.status(ra) in ("pending", "active"):
            cb.step()
            done.update(cb.finished())
            ticks += 1
        done.update(cb.finished())
        # finished at its very first token (admission tick + the pipelined
        # retire lag), freeing the slot while rb keeps decoding
        assert ticks <= 2 + cb.pipeline_depth
        assert ra in done
        assert len(done[ra]) == len(prompts[0]) + 1 and done[ra][-1] == eos
        assert cb.status(rb) == "active"  # unaffected by ra's early exit
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        out_b = done[rb]
        assert len(out_b) >= len(prompts[1]) + 1

    def test_queue_longer_than_slots_drains(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64)
        rids = [cb.submit(p, max_new_tokens=4) for p in _prompts((3, 4, 5, 6, 7), seed=2)]
        ticks = 0
        while cb.has_work():
            cb.step()
            ticks += 1
            assert ticks < 100, "scheduler did not drain"
        done = cb.finished()
        assert set(done) == set(rids)
        for rid in rids:
            assert len(done[rid]) >= 4

    def test_oversized_request_rejected(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=32)
        with pytest.raises(ValueError, match="cache_len"):
            cb.submit(np.arange(30, dtype=np.int32), max_new_tokens=8)

    def test_step_stream_matches_results(self, setup):
        """Concatenating step() returns per request reproduces the
        generated stream exactly (review r4: the admission tick emits two
        tokens and must return both)."""
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64)
        prompts = _prompts((4, 6, 5), seed=3)
        rids = [cb.submit(p, max_new_tokens=4) for p in prompts]
        streams = {r: [] for r in rids}
        while cb.has_work():
            for rid, toks in cb.step().items():
                streams[rid].extend(toks)
        done = cb.finished()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                np.asarray(streams[rid], np.int32), done[rid][len(p):]
            )

    def test_prefix_caching_exact_parity(self, setup):
        """register_prefix computes the shared-prefix KV once; requests
        submitted with it must match full-prompt greedy generate EXACTLY,
        even while another slot is mid-decode (no cross-slot corruption
        from the suffix segment's parked rows)."""
        model, params, plain = setup
        rs = np.random.RandomState(5)
        prefix = rs.randint(0, 128, (11,)).astype(np.int32)
        sufs = [rs.randint(0, 128, (n,)).astype(np.int32) for n in (4, 7)]
        other = rs.randint(0, 128, (6,)).astype(np.int32)

        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64)
        pid = cb.register_prefix(prefix)
        r_other = cb.submit(other, max_new_tokens=10)
        cb.step()
        cb.step()
        r0 = cb.submit_with_prefix(pid, sufs[0], max_new_tokens=6)
        cb.step()
        r1 = cb.submit_with_prefix(pid, sufs[1], max_new_tokens=6)
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        for rid, full, mnt in [(r0, np.concatenate([prefix, sufs[0]]), 6),
                               (r1, np.concatenate([prefix, sufs[1]]), 6),
                               (r_other, other, 10)]:
            want = np.asarray(plain.generate(full[None, :], max_new_tokens=mnt))[0]
            np.testing.assert_array_equal(done[rid], want)

    def test_prefix_capacity_checked(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=32)
        pid = cb.register_prefix(np.arange(20, dtype=np.int32) % 128)
        with pytest.raises(ValueError, match="cache_len"):
            cb.submit_with_prefix(pid, np.arange(8, dtype=np.int32), max_new_tokens=8)

    def test_zero_max_new_tokens_rejected(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64)
        with pytest.raises(ValueError, match="max_new_tokens"):
            cb.submit(np.arange(4, dtype=np.int32), max_new_tokens=0)
        with pytest.raises(ValueError, match="empty prompt"):
            cb.submit([], max_new_tokens=4)

    def test_unregister_prefix_releases(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64)
        p1 = cb.register_prefix(np.arange(5, dtype=np.int32))
        p2 = cb.register_prefix(np.arange(7, dtype=np.int32))
        assert p1 != p2
        cb.unregister_prefix(p1)
        assert p1 not in cb._prefixes and p2 in cb._prefixes
        p3 = cb.register_prefix(np.arange(3, dtype=np.int32))
        assert p3 not in (p1, p2)  # counter-based ids are never recycled
        with pytest.raises(KeyError):
            cb.submit_with_prefix(p1, np.arange(2, dtype=np.int32))
        with pytest.raises(KeyError, match="unknown prefix id"):
            cb.unregister_prefix(p1)  # double release fails loudly, names the id

    def test_unregister_does_not_strand_queued_request(self, setup):
        """A submit_with_prefix request still in the queue must survive
        unregister_prefix (the entry is snapshotted at submit time)."""
        model, params, plain = setup
        rs = np.random.RandomState(9)
        prefix = rs.randint(0, 128, (6,)).astype(np.int32)
        suffix = rs.randint(0, 128, (4,)).astype(np.int32)
        blockers = [rs.randint(0, 128, (3,)).astype(np.int32) for _ in range(2)]
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=2, cache_len=64)
        pid = cb.register_prefix(prefix)
        for b in blockers:  # fill both slots so the prefix request queues
            cb.submit(b, max_new_tokens=6)
        cb.step()
        rid = cb.submit_with_prefix(pid, suffix, max_new_tokens=4)
        cb.unregister_prefix(pid)  # while rid is still pending
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        full = np.concatenate([prefix, suffix])
        want = np.asarray(plain.generate(full[None, :], max_new_tokens=4))[0]
        np.testing.assert_array_equal(done[rid], want)
        with pytest.raises(ValueError, match="max_new_tokens"):
            cb.submit_with_prefix(cb.register_prefix(prefix), suffix, max_new_tokens=0)


class TestRequestLifecycle:
    """status/peek/result/cancel — the polling + cancellation surface the
    serving layer (deepspeed_tpu/serving) is built on."""

    def test_status_and_peek_across_lifecycle(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=1, cache_len=64)
        p_a, p_b = _prompts((4, 5), seed=7)
        # admission emits 1 token and the same step() decodes 1 more, so
        # max_new_tokens=4 keeps the request active past the first tick
        ra = cb.submit(p_a, max_new_tokens=4)
        rb = cb.submit(p_b, max_new_tokens=4)  # queues behind ra (1 slot)
        assert cb.status(ra) == "pending" and cb.status(rb) == "pending"
        cb.step()
        assert cb.status(ra) == "active" and cb.status(rb) == "pending"
        assert cb.peek(ra) is None  # not finished: peek stays empty
        while cb.status(ra) in ("pending", "active"):
            cb.step()
        assert cb.status(ra) == "finished"
        got = cb.peek(ra)
        assert got is not None and len(got) == len(p_a) + 4
        np.testing.assert_array_equal(cb.result(ra), got)  # peek didn't consume
        assert cb.status(ra) == "unknown"  # collected
        assert cb.status(12345) == "unknown"
        while cb.has_work():
            cb.step()
        cb.finished()

    def test_result_error_names_rid_and_state(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=1, cache_len=64)
        rid = cb.submit(_prompts((4,), seed=8)[0], max_new_tokens=4)
        with pytest.raises(KeyError, match=f"request {rid}: pending"):
            cb.result(rid)
        cb.step()  # admission + first decode: 2 of 4 tokens, still active
        with pytest.raises(KeyError, match=f"request {rid}: active"):
            cb.result(rid)
        with pytest.raises(KeyError, match="request 999: unknown"):
            cb.result(999)
        while cb.has_work():
            cb.step()
        cb.finished()

    def test_cancel_pending_and_active_frees_slot(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=1, cache_len=64)
        p_a, p_b, p_c = _prompts((4, 5, 6), seed=9)
        ra = cb.submit(p_a, max_new_tokens=8)
        rb = cb.submit(p_b, max_new_tokens=8)
        cb.step()
        assert cb.cancel(rb) is True          # pending: leaves the queue
        assert cb.status(rb) == "cancelled" and not cb._pending
        assert cb.cancel(ra) is True          # active: frees the slot NOW
        assert cb.status(ra) == "cancelled"
        assert cb.pool_state() == [{"length": 64, "slots": 1, "free": 1}]
        rc = cb.submit(p_c, max_new_tokens=2)  # freed slot is reusable
        while cb.has_work():
            cb.step()
        out = cb.finished()
        assert set(out) == {rc}
        assert len(out[rc]) == len(p_c) + 2
        assert cb.cancel(rc) is False          # already collected: too late
        with pytest.raises(KeyError, match="cancelled"):
            cb.result(ra)

    def test_cancelled_memory_is_bounded(self, setup):
        """A long-running server cancels routinely; the engine remembers
        only a bounded window of cancelled rids (evicted ones age back to
        'unknown', same as collected results)."""
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      max_slots=1, cache_len=64)
        cb._cancelled_cap = 4
        prompt = _prompts((3,), seed=10)[0]
        rids = []
        for _ in range(6):  # cancel while pending: no decode involved
            rid = cb.submit(prompt, max_new_tokens=2)
            assert cb.cancel(rid) is True
            rids.append(rid)
        assert len(cb._cancelled) == 4
        assert cb.status(rids[0]) == "unknown"   # evicted
        assert cb.status(rids[-1]) == "cancelled"


class TestBucketedKV:
    """cache_buckets (VERDICT r4 #9): slot pools with different cache
    lengths — static-shape TPU analogue of paged KV. Footprint shrinks to
    sum(slots_i * len_i); outputs must match the fixed-slot engine."""

    def test_parity_with_fixed_slots(self, setup):
        """Mixed-length requests through bucketed pools equal the plain
        engine's greedy generate (and therefore the fixed-slot engine)."""
        model, params, plain = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      cache_buckets=[(2, 32), (1, 64)])
        prompts = _prompts((5, 9, 3, 20), seed=3)
        refs = [np.asarray(plain.generate(p[None, :], max_new_tokens=8))[0]
                for p in prompts]
        rids = [cb.submit(p, max_new_tokens=8) for p in prompts]
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(done[rid], ref)

    def test_placement_smallest_fit_with_fallback(self, setup):
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      cache_buckets=[(1, 32), (1, 64)])
        short1, short2, long1 = _prompts((4, 6, 40), seed=4)
        r_short1 = cb.submit(short1, max_new_tokens=4)
        r_long = cb.submit(long1, max_new_tokens=8)   # only fits pool 1
        cb.step()
        assert cb._pools[0].active and cb._pools[1].active
        assert cb._pools[0].active[0].rid == r_short1
        assert cb._pools[1].active[0].rid == r_long
        # short pool full; a second short request falls back to... nothing
        # free -> queues; after the short request finishes it is admitted
        r_short2 = cb.submit(short2, max_new_tokens=4)
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        assert set(done) == {r_short1, r_long, r_short2}

    def test_long_request_does_not_block_short_behind_it(self, setup):
        """FIFO-with-skip: a queued long request waiting for the long pool
        must not starve short requests that fit the free short pool."""
        model, params, _ = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      cache_buckets=[(1, 32), (1, 64)])
        long_a, long_b, short = _prompts((40, 44, 4), seed=5)
        cb.submit(long_a, max_new_tokens=8)
        r_b = cb.submit(long_b, max_new_tokens=8)   # queues behind long_a
        r_s = cb.submit(short, max_new_tokens=6)    # must skip ahead
        cb.step()
        assert cb._pools[0].active[0].rid == r_s, "short request was blocked"
        assert any(r.rid == r_b for r in cb._pending)
        while cb.has_work():
            cb.step()
        assert not cb._pending

    def test_footprint_shrinks_vs_fixed(self, setup):
        """The PERF.md footprint claim: bucketed pools hold strictly fewer
        KV bytes than the same slot count at the max length."""
        model, params, _ = setup
        fixed = ContinuousBatchingEngine(model, params=params,
                                         config={"dtype": "float32"},
                                         max_slots=4, cache_len=128)
        bucketed = ContinuousBatchingEngine(model, params=params,
                                            config={"dtype": "float32"},
                                            cache_buckets=[(3, 32), (1, 128)])
        assert fixed.kv_cache_bytes() == 4 * 128 * _kv_row_bytes(model.cfg)
        assert bucketed.kv_cache_bytes() == (3 * 32 + 128) * _kv_row_bytes(model.cfg)
        assert bucketed.kv_cache_bytes() < 0.45 * fixed.kv_cache_bytes()

    def test_prefix_respects_pool_length(self, setup):
        """A prefix whose splice bucket exceeds a short pool must be placed
        in a pool that can hold the full bucket-length slice."""
        model, params, plain = setup
        cb = ContinuousBatchingEngine(model, params=params,
                                      config={"dtype": "float32"},
                                      cache_buckets=[(1, 16), (1, 64)])
        prefix, suffix = _prompts((20, 4), seed=6)
        pid = cb.register_prefix(prefix)          # bucket = 32 > short pool
        rid = cb.submit_with_prefix(pid, suffix, max_new_tokens=4)
        done = {}
        while cb.has_work():
            cb.step()
            done.update(cb.finished())
        full = np.concatenate([prefix, suffix])
        ref = np.asarray(plain.generate(full[None, :], max_new_tokens=4))[0]
        np.testing.assert_array_equal(done[rid], ref)


def _kv_row_bytes(cfg):
    """bytes of one (layer-stacked) KV row per cached position."""
    kv_heads = cfg.kv_heads
    hd = cfg.head_dim
    return 2 * cfg.num_layers * kv_heads * hd * 4  # k+v, fp32
