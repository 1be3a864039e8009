"""RLHF rollout+train loop on the hybrid engine (DeepSpeed-Chat step-3
shape: generate with the live policy weights, score, train on the rollouts).
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
        model = TransformerModel(TransformerConfig(
            vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, dtype="bfloat16"))
        micro_bs, prompt_len, gen_tokens, rounds = 2, 8, 4, 2
    else:
        model = TransformerModel.from_preset("gpt2-125m", dtype="bfloat16",
                                             remat=True, remat_policy="dots_saveable")
        micro_bs, prompt_len, gen_tokens, rounds = 4, 256, 128, 10

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-5}},
            "bf16": {"enabled": True},
            "hybrid_engine": {"enabled": True},
            "mesh": {"data": -1},
            "steps_per_print": 1000,
        },
    )
    import jax

    rs = np.random.RandomState(0)
    B = micro_bs * jax.device_count()
    for r in range(rounds):
        prompts = rs.randint(0, model.cfg.vocab_size, (B, prompt_len)).astype(np.int32)
        rollout = engine.generate(prompts, max_new_tokens=gen_tokens)
        batch = {"input_ids": np.asarray(rollout)}  # + rewards in a real loop
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        print(f"round {r}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main()
