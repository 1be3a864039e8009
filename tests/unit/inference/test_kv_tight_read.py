"""KV-cache-centric decode geometry: tight reads (bucketed active-length
attention), bucket-migrated cache growth, int8 KV composition — token-stream
parity across every decode path plus deterministic ``kv_bytes_read``
accounting (the CPU-mesh-measurable form of the decode-bandwidth win)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.inference.decoding import (
    decode_kv_bytes,
    read_bucket,
    read_stages,
)
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.transformer.kv_cache import read_bytes_per_row as kv_read_bytes_per_row
from serving_toys import SMALL, built

FLOOR = 16  # small bucket floor so tiny test models cross several buckets


@pytest.fixture(scope="module")
def setup():
    comm.destroy()
    return built(SMALL)


def _engine(model, params, **over):
    cfg = {"dtype": "float32", "kv_read_floor": FLOOR}
    cfg.update(over)
    return deepspeed_tpu.init_inference(model, params=params, config=cfg)


def _toks(n, batch=2, seed=0):
    rs = np.random.RandomState(seed)
    return jnp.asarray(rs.randint(0, 128, (batch, n)), jnp.int32)


class TestReadGeometry:
    def test_read_stages_bucket_boundaries(self):
        # 40 decode steps from prompt 5: extents 6..45 cross 16/32/64
        assert read_stages(5, 40, 128, 16) == [(16, 11), (32, 16), (64, 13)]
        # the bucket reaching the allocation degenerates to a full read
        assert read_stages(5, 40, 32, 16) == [(16, 11), (None, 29)]
        # tight off = one full-length stage; no steps = no stages
        assert read_stages(5, 40, 128, None) == [(None, 40)]
        assert read_stages(5, 0, 128, 16) == []

    def test_stage_reads_cover_every_step(self):
        for prompt in (1, 7, 16, 33):
            j = 0
            for r, n in read_stages(prompt, 50, 256, 16):
                for _ in range(n):
                    extent = prompt + j + 1
                    assert (r if r is not None else 256) >= extent
                    if r is not None:
                        assert r == read_bucket(extent, 256, 16)
                    j += 1
            assert j == 50

    def test_row_read_bytes_int8_vs_dense(self):
        cfg = TransformerConfig(hidden_size=64, num_layers=2, num_heads=4,
                                dtype="bfloat16")
        dense = kv_read_bytes_per_row(cfg, 64)
        assert dense == 2 * 2 * 64 * 4 * 16 * 2  # K+V, L, slots, heads, hd, bf16
        cfg8 = TransformerConfig(hidden_size=64, num_layers=2, num_heads=4,
                                 dtype="bfloat16", kv_cache_dtype="int8")
        # int8 payload + 4-byte scale per (token, head)
        assert kv_read_bytes_per_row(cfg8, 64) == 2 * 2 * 64 * 4 * (16 + 4)


class TestTokenStreamParity:
    def test_tight_matches_full_across_bucket_migrations(self, setup):
        """40 new tokens from prompt 5 cross the 16->32->64 buckets: the
        fused (staged-scan) and per-token (migrating-cache) tight paths
        must reproduce the full-read streams exactly."""
        model, params = setup
        toks = _toks(5)
        want = np.asarray(_engine(model, params, kv_tight_read=False)
                          .generate(toks, max_new_tokens=40))
        for fused in (True, False):
            got = _engine(model, params, fused_generate=fused).generate(
                toks, max_new_tokens=40)
            np.testing.assert_array_equal(np.asarray(got), want)

    def test_loop_fused_burst_identical_under_int8_kv(self, setup):
        """Satellite acceptance: greedy token streams identical across the
        decode_loop / fused_generate / burst-segment (continuous) paths for
        the int8-KV tight-read cache config, fixed rng."""
        model, params = setup
        prompts = [np.arange(1, 6, dtype=np.int32), np.arange(3, 12, dtype=np.int32)]
        cfg = {"kv_cache_dtype": "int8"}
        fused = _engine(model, params, **cfg)
        loop = _engine(model, params, fused_generate=False, **cfg)
        refs = {}
        for i, p in enumerate(prompts):
            a = np.asarray(fused.generate(p[None, :], max_new_tokens=24))[0]
            b = np.asarray(loop.generate(p[None, :], max_new_tokens=24))[0]
            np.testing.assert_array_equal(a, b)
            refs[i] = a
        cb = ContinuousBatchingEngine(
            model, params=params,
            config={"dtype": "float32", "kv_cache_dtype": "int8",
                    "kv_read_floor": FLOOR},
            max_slots=2, cache_len=64, tokens_per_tick=4)
        rids = [cb.submit(p, max_new_tokens=24) for p in prompts]
        while cb.has_work():
            cb.step()
        done = cb.finished()
        for i, rid in enumerate(rids):
            np.testing.assert_array_equal(done[rid], refs[i])

    def test_ragged_tight_matches_full(self, setup):
        """attention_mask generation (per-row segment tail) under tight
        reads equals the full-read stream, left padding included."""
        model, params = setup
        rs = np.random.RandomState(3)
        toks = rs.randint(0, 128, (2, 9)).astype(np.int32)
        mask = np.ones((2, 9), np.int32)
        mask[0, :4] = 0  # left padding
        toks[0, :4] = 0
        full = _engine(model, params, kv_tight_read=False).generate(
            jnp.asarray(toks), max_new_tokens=30, attention_mask=mask)
        tight = _engine(model, params).generate(
            jnp.asarray(toks), max_new_tokens=30, attention_mask=mask)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(tight))

    def test_mixed_bucket_admission_with_tight_read(self, setup):
        """Bucketed slot pools + tight-read ticks: requests landing in
        different-length pools (and one queued past a full pool) still
        reproduce plain generate exactly."""
        model, params = setup
        plain = _engine(model, params)
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, 128, (n,)).astype(np.int32)
                   for n in (5, 9, 3, 20)]  # the 20-prompt only fits the 64 pool
        refs = [np.asarray(plain.generate(p[None, :], max_new_tokens=10))[0]
                for p in prompts]
        cb = ContinuousBatchingEngine(
            model, params=params,
            config={"dtype": "float32", "kv_read_floor": FLOOR},
            cache_buckets=[(2, 32), (2, 64)])
        rids = [cb.submit(p, max_new_tokens=10) for p in prompts]
        while cb.has_work():
            cb.step()
        done = cb.finished()
        for rid, want in zip(rids, refs):
            np.testing.assert_array_equal(done[rid], want)


class TestKvBytesAccounting:
    def _trace_events(self, path):
        with open(path) as fh:
            return [json.loads(l) for l in fh if l.strip()]

    def test_engine_event_matches_host_math(self, setup, tmp_path):
        model, params = setup
        trace = tmp_path / "trace.jsonl"
        eng = _engine(model, params, fused_generate=False,
                      telemetry={"enabled": True, "trace_file": str(trace)})
        toks = _toks(5)
        eng.generate(toks, max_new_tokens=40)
        ev = [e for e in self._trace_events(trace)
              if e["kind"] == "inference_request"][-1]
        # bounded_cache_len(45, 128, 1024) = 128: the DEFAULT config keeps
        # the full-seq-len allocation — exactly the geometry tight reads fix
        max_len = 128
        expect = 2 * decode_kv_bytes(eng.cfg, 5, 40, max_len, FLOOR)
        assert ev["kv_bytes_read"] == expect
        assert ev["kv_dtype"] == "float32"
        assert 0 < ev["cache_utilization"] <= 1.0
        assert ev["kv_bytes_per_token"] == round(expect / 2 / 39, 1)

    def test_tight_read_halves_default_config_bytes(self, setup):
        """The CPU-mesh acceptance gate: at the DEFAULT allocation (no
        max_out_tokens bound beyond max_seq_len) the tight geometry reads
        <= 0.5x the full-read bytes per decoded token."""
        model, params = setup
        cfg = _engine(model, params).cfg
        cache_len = 128  # default allocation for this model (max_seq_len)
        full = decode_kv_bytes(cfg, 8, 56, cache_len, None)
        tight = decode_kv_bytes(cfg, 8, 56, cache_len, FLOOR)
        assert tight <= 0.5 * full
        # int8 KV halves it again
        cfg8 = _engine(model, params, kv_cache_dtype="int8").cfg
        assert decode_kv_bytes(cfg8, 8, 56, cache_len, FLOOR) < tight

    def test_continuous_event_matches_simulated_ticks(self, setup, tmp_path):
        model, params = setup
        trace = tmp_path / "trace.jsonl"
        cb = ContinuousBatchingEngine(
            model, params=params,
            config={"dtype": "float32", "kv_read_floor": FLOOR,
                    "telemetry": {"enabled": True, "trace_file": str(trace)}},
            max_slots=1, cache_len=64)
        prompt = np.arange(2, 9, dtype=np.int32)  # len 7
        rid = cb.submit(prompt, max_new_tokens=12)
        while cb.has_work():
            cb.step()
        cb.finished()
        # simulate: EVERY token rides a pool tick now (the admission tick
        # itself samples token 1 — fused prefill — and each later tick
        # feeds the previous token). Tick i reads the bucket covering
        # (prompt + i) cached slots: the first tick attends exactly the
        # prompt, the last attends prompt + 11.
        expect = 0
        for i in range(12):
            extent = 7 + i
            r = read_bucket(extent, 64, FLOOR)
            expect += kv_read_bytes_per_row(cb.cfg, r if r < 64 else 64)
        ev = [e for e in self._trace_events(trace)
              if e.get("path") == "continuous" and e["request"] == rid][0]
        assert ev["kv_bytes_read"] == expect
        assert ev["new_tokens"] == 12
        assert ev["kv_bytes_per_token"] == round(expect / 12, 1)

    def test_cache_utilization_gauge(self, setup):
        model, params = setup
        cb = ContinuousBatchingEngine(
            model, params=params,
            config={"dtype": "float32", "kv_read_floor": FLOOR,
                    "telemetry": {"enabled": True, "trace_file": ""}},
            max_slots=2, cache_len=32)
        cb.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        cb.step()
        gauges = cb._eng.telemetry.registry.dump()["gauges"]
        # one slot of two holds 5-6 cached tokens out of 2*32 reserved
        assert 0 < gauges["cache_utilization"] <= 1.0
        while cb.has_work():
            cb.step()
