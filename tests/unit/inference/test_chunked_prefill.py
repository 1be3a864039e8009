"""Chunked prefill: a fixed (B, chunk) prefill program serves every prompt
length (one compile instead of one per length) with prefill memory bounded by the chunk. Token
streams must be identical to the unchunked engine."""

import jax
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel


def _model(**kw):
    cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=128, dtype="float32", **kw)
    model = TransformerModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


class TestChunkedPrefill:
    @pytest.mark.parametrize("prompt_len,chunk", [(16, 8), (13, 8), (5, 8), (8, 8)],
                             ids=["even", "ragged-tail", "prompt-lt-chunk", "exact"])
    def test_greedy_parity_with_plain(self, prompt_len, chunk):
        comm.destroy()
        model, params = _model()
        chunked = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "prefill_chunk_size": chunk})
        comm.destroy()
        plain = deepspeed_tpu.init_inference(model, params=params,
                                             config={"dtype": "float32"})
        toks = np.random.RandomState(0).randint(0, 128, (2, prompt_len)).astype(np.int32)
        a = np.asarray(chunked.generate(toks, max_new_tokens=8))
        b = np.asarray(plain.generate(toks, max_new_tokens=8))
        np.testing.assert_array_equal(a, b)

    def test_one_compile_serves_all_lengths(self):
        """The whole point: distinct prompt lengths reuse the same chunk
        program (the jit wrapper retraces per input shape; every chunk is
        the same shape)."""
        comm.destroy()
        model, params = _model()
        eng = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "prefill_chunk_size": 8,
                    "max_out_tokens": 64})
        rs = np.random.RandomState(1)
        for S in (3, 9, 17, 24):
            out = np.asarray(eng.generate(
                rs.randint(0, 128, (1, S)).astype(np.int32), max_new_tokens=4))
            assert out.shape == (1, S + 4)
        # one ragged-prefill family entry, compiled for (B=1, cache 64)
        from deepspeed_tpu.inference.decoding import cached_fn  # noqa: F401
        prefill_fn, _, _ = eng._ragged_fns_for(1, 64)
        traces = prefill_fn._cache_size() if hasattr(prefill_fn, "_cache_size") else None
        if traces is not None:
            assert traces == 1, f"chunk program retraced {traces}x"

    @pytest.mark.parametrize("side", ["right", "left"])
    def test_attention_mask_parity_with_ragged(self, side):
        """The motivating serving workload: varied-width padded batches must
        both WORK under chunking and match the unchunked ragged path."""
        comm.destroy()
        model, params = _model()
        chunked = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "prefill_chunk_size": 8})
        comm.destroy()
        plain = deepspeed_tpu.init_inference(model, params=params,
                                             config={"dtype": "float32"})
        rs = np.random.RandomState(3)
        toks = rs.randint(0, 128, (2, 20)).astype(np.int32)
        mask = np.ones((2, 20), np.float32)
        if side == "right":
            mask[1, 13:] = 0
        else:
            mask[1, :9] = 0
        a = np.asarray(chunked.generate(toks, max_new_tokens=6, attention_mask=mask))
        b = np.asarray(plain.generate(toks, max_new_tokens=6, attention_mask=mask))
        np.testing.assert_array_equal(a, b)

    def test_composes_with_int8_kv_and_windows(self):
        comm.destroy()
        model, params = _model(attn_impl="pallas", pos_embedding="rope",
                               norm_type="rmsnorm", use_bias=False,
                               num_kv_heads=2, local_attn_windows=(12, 12))
        chunked = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "prefill_chunk_size": 8,
                    "kv_cache_dtype": "int8"})
        comm.destroy()
        plain = deepspeed_tpu.init_inference(
            model, params=params,
            config={"dtype": "float32", "kv_cache_dtype": "int8",
                    "rolling_kv_cache": False})
        toks = np.random.RandomState(2).randint(0, 128, (2, 20)).astype(np.int32)
        a = np.asarray(chunked.generate(toks, max_new_tokens=6))
        b = np.asarray(plain.generate(toks, max_new_tokens=6))
        np.testing.assert_array_equal(a, b)
