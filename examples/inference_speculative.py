"""Inference: plain, int8, and speculative decoding on one engine surface.
EXAMPLE_SMOKE=1 shrinks for CI."""

import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

SMOKE = os.environ.get("EXAMPLE_SMOKE") == "1"


def main():
    configure_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache
    if SMOKE:
        target_cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                                       num_heads=4, max_seq_len=64, dtype="float32")
        draft_cfg = TransformerConfig(vocab_size=128, hidden_size=32, num_layers=1,
                                      num_heads=4, max_seq_len=64, dtype="float32")
        new_tokens = 8
    else:
        target_cfg = TransformerModel.from_preset("gpt2-350m", dtype="bfloat16").cfg
        draft_cfg = TransformerModel.from_preset("gpt2-125m", dtype="bfloat16").cfg
        new_tokens = 64

    engine = deepspeed_tpu.init_inference(
        TransformerModel(target_cfg),
        draft_model=TransformerModel(draft_cfg),
        config={"dtype": "float32" if SMOKE else "bfloat16",
                "speculative": {"enabled": True, "num_draft_tokens": 4}},
    )
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, target_cfg.vocab_size, (2, 8)).astype(np.int32)
    out = engine.generate(prompt, max_new_tokens=new_tokens)
    print("speculative:", np.asarray(out)[:, -new_tokens:])

    # ragged prompts: HF attention_mask semantics (left padding)
    mask = np.ones_like(prompt, np.float32)
    mask[1, :3] = 0
    prompt2 = prompt.copy()
    prompt2[1, :3] = 0
    plain = deepspeed_tpu.init_inference(TransformerModel(target_cfg),
                                         config={"dtype": "float32" if SMOKE else "bfloat16"})
    out2 = plain.generate(prompt2, max_new_tokens=new_tokens, attention_mask=mask)
    print("ragged:", np.asarray(out2)[:, -new_tokens:])


if __name__ == "__main__":
    main()
