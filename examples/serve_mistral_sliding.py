"""Sliding-window (Mistral-style) serving: the rolling KV cache keeps only
the last `window` positions, so generation length is unbounded at constant
cache memory, and every decode step reads O(window) cache bytes. Prefill
rides the tile-pruned flash band kernel (O(S*window) compute).
EXAMPLE_SMOKE=1 shrinks for CI."""

import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

SMOKE = os.environ.get("EXAMPLE_SMOKE") == "1"


def main():
    configure_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache
    window = 16 if SMOKE else 1024
    cfg = TransformerConfig(
        vocab_size=256 if SMOKE else 32000,
        hidden_size=64 if SMOKE else 2048,
        num_layers=2 if SMOKE else 16,
        num_heads=4 if SMOKE else 16,
        num_kv_heads=2 if SMOKE else 8,
        max_seq_len=128 if SMOKE else 8192,
        pos_embedding="rope", norm_type="rmsnorm", activation="silu_glu",
        use_bias=False, attn_impl="pallas",
        local_attn_windows=(window,) * (2 if SMOKE else 16),
        dtype="float32" if SMOKE else "bfloat16",
    )
    # (a converted HF checkpoint works the same:
    #  deepspeed_tpu.init_inference("mistralai/Mistral-7B-v0.1", ...) maps
    #  sliding_window automatically via the injection policy)
    engine = deepspeed_tpu.init_inference(TransformerModel(cfg),
                                          config={"dtype": cfg.dtype})
    assert engine.cfg.rolling_kv_cache, "rolling cache should auto-enable"

    rs = np.random.RandomState(0)
    prompt = rs.randint(0, cfg.vocab_size, (1, 8 if SMOKE else 256)).astype(np.int32)
    new = 64 if SMOKE else 4096  # generates far past the window: the ring wraps
    out = np.asarray(engine.generate(prompt, max_new_tokens=new))
    kv_slots = min(prompt.shape[1] + new, window)  # ring holds <= window positions
    print(f"generated {new} tokens with a {kv_slots}-slot ring "
          f"(window {window}); output shape {out.shape}")


if __name__ == "__main__":
    main()
