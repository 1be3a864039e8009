"""Rolling (ring-buffer) KV cache for uniform-sliding-window models
(Mistral): cache memory and decode reads are O(window) instead of O(total
length). Beyond the v0.9.1 reference (its inference caches are
full-length); semantics match HF Mistral's rolling cache.

Exactness argument tested here: prefill attention rides the flash band
kernel directly over the segment (never reads the ring), decode reads mask
by slot absolute positions derived mod the cache length — identical to a
full cache while nothing wraps, window-masked once it does.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.models.transformer import TransformerConfig
from serving_toys import built

W = 16


def _model(window=W, **kw):
    cfg = TransformerConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=256, pos_embedding="rope",
        norm_type="rmsnorm", use_bias=False, attn_impl="pallas",
        local_attn_windows=(window, window) if window else None, **kw)
    return built(cfg)


def _engines(window=W, **cfg_overrides):
    comm.destroy()
    model, params = _model(window)
    roll = deepspeed_tpu.init_inference(
        model, params=params, config={"dtype": "float32", **cfg_overrides})
    comm.destroy()
    full = deepspeed_tpu.init_inference(
        model, params=params,
        config={"dtype": "float32", "rolling_kv_cache": False, **cfg_overrides})
    return roll, full


class TestRingOps:
    def test_ring_degenerates_to_plain_before_wrap(self):
        from deepspeed_tpu.ops.transformer.inference_ops import softmax_context
        from deepspeed_tpu.ops.transformer.kv_cache import update_kv_cache

        B, T, H, hd = 2, 8, 2, 4
        rng = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(rng, 3)
        kc = jnp.zeros((B, T, H, hd), jnp.float32)
        vc = jnp.zeros((B, T, H, hd), jnp.float32)
        k_new = jax.random.normal(k1, (B, 5, H, hd), jnp.float32)
        v_new = jax.random.normal(k2, (B, 5, H, hd), jnp.float32)
        positions = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32)[None], (B, 5))
        k_p, v_p = update_kv_cache(kc, vc, k_new, v_new, 0, positions)
        k_r, v_r = update_kv_cache(kc, vc, k_new, v_new, 0, positions, ring=True)
        np.testing.assert_array_equal(np.asarray(k_p), np.asarray(k_r))
        q = jax.random.normal(k3, (B, 1, H, hd), jnp.float32)
        qpos = jnp.full((B, 1), 4, jnp.int32)
        a = softmax_context(q, k_p, v_p, 4, positions=qpos, local_window=jnp.int32(3))
        b = softmax_context(q, k_r, v_r, 4, positions=qpos, local_window=jnp.int32(3),
                            ring=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_ring_write_wraps_and_drops_stale(self):
        from deepspeed_tpu.ops.transformer.kv_cache import update_kv_cache

        B, T, H, hd = 1, 4, 1, 2
        kc = vc = jnp.zeros((B, T, H, hd), jnp.float32)
        # write 6 tokens into 4 slots: only the last 4 (positions 2..5) land
        k_new = jnp.arange(6, dtype=jnp.float32)[None, :, None, None] * jnp.ones((B, 6, H, hd))
        positions = jnp.arange(6, dtype=jnp.int32)[None]
        k_r, _ = update_kv_cache(kc, vc, k_new, k_new, 0, positions, ring=True)
        got = np.asarray(k_r)[0, :, 0, 0]
        # slot s holds position p with p % 4 == s, p in [2..5]
        np.testing.assert_array_equal(got, [4.0, 5.0, 2.0, 3.0])


class TestRollingGenerate:
    def test_auto_enabled_and_cache_is_window_sized(self):
        roll, full = _engines()
        assert roll.cfg.rolling_kv_cache
        assert not full.cfg.rolling_kv_cache
        assert roll._ring_cache_len(200, prompt_len=8) == W
        assert full._ring_cache_len(200, prompt_len=8) == 200

    @pytest.mark.parametrize("prompt_len,new", [(8, 40), (64, 24)],
                             ids=["wraps-in-decode", "prompt-longer-than-window"])
    def test_greedy_parity_with_full_cache(self, prompt_len, new):
        roll, full = _engines()
        toks = np.random.RandomState(0).randint(0, 128, (2, prompt_len)).astype(np.int32)
        a = np.asarray(roll.generate(toks, max_new_tokens=new))
        b = np.asarray(full.generate(toks, max_new_tokens=new))
        np.testing.assert_array_equal(a, b)

    def test_parity_per_token_loop(self):
        # the non-fused decode_loop path shares the ring fns
        roll, full = _engines(fused_generate=False)
        toks = np.random.RandomState(1).randint(0, 128, (1, 8)).astype(np.int32)
        a = np.asarray(roll.generate(toks, max_new_tokens=32))
        b = np.asarray(full.generate(toks, max_new_tokens=32))
        np.testing.assert_array_equal(a, b)
        # the compiled cache really is window-sized
        assert roll._compiled_shape == (1, W)

    def test_int8_kv_composes(self):
        roll, full = _engines(kv_cache_dtype="int8")
        assert roll.cfg.rolling_kv_cache and roll.cfg.kv_cache_dtype == "int8"
        toks = np.random.RandomState(2).randint(0, 128, (2, 8)).astype(np.int32)
        a = np.asarray(roll.generate(toks, max_new_tokens=30))
        b = np.asarray(full.generate(toks, max_new_tokens=30))
        np.testing.assert_array_equal(a, b)

    def test_hf_mistral_auto_enables(self):
        """The motivating case: a converted HF Mistral checkpoint (policy
        sets attn_impl=pallas + uniform windows) must get the rolling cache
        without any manual config."""
        import torch
        from transformers import MistralConfig, MistralForCausalLM

        torch.manual_seed(0)
        hf = MistralForCausalLM(MistralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, sliding_window=8,
            attn_implementation="eager")).eval()
        comm.destroy()
        eng = deepspeed_tpu.init_inference(hf, config={"dtype": "float32"})
        assert eng.cfg.attn_impl == "pallas"
        assert eng.cfg.rolling_kv_cache
        assert eng._ring_cache_len(64, prompt_len=4) == 8

    def test_no_window_model_stays_plain(self):
        comm.destroy()
        model, params = _model(window=None)
        eng = deepspeed_tpu.init_inference(model, params=params,
                                           config={"dtype": "float32"})
        assert not eng.cfg.rolling_kv_cache

    def test_ragged_and_continuous_paths_ring_off(self):
        roll, _ = _engines()
        assert not roll._ring_off_cfg.rolling_kv_cache
        # ragged generation works under a rolling-enabled engine
        toks = np.random.RandomState(3).randint(0, 128, (2, 10)).astype(np.int32)
        mask = np.ones((2, 10), np.float32)
        mask[1, :4] = 0
        out = np.asarray(roll.generate(toks, max_new_tokens=4, attention_mask=mask))
        assert out.shape == (2, 14)
