"""Minimal causal-LM pretraining loop (the DeepSpeedExamples cifar/gpt
quickstart shape): build a preset model, deepspeed_tpu.initialize, train on
synthetic batches, checkpoint. Runs on any backend; defaults are sized for
one TPU chip. EXAMPLE_SMOKE=1 shrinks everything for CI."""

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
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=32, dtype="bfloat16"))
        micro_bs, seq, steps = 2, 32, 4
    else:
        model = TransformerModel.from_preset("gpt2-125m", dtype="bfloat16", remat=True)
        micro_bs, seq, steps = 8, 1024, 50

    engine, _, _, scheduler = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-4, "weight_decay": 0.01}},
            "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
            "gradient_clipping": 1.0,
            "mesh": {"data": -1},
            "steps_per_print": 10,
        },
    )
    import jax

    rs = np.random.RandomState(0)
    n_dev = jax.device_count()
    for step in range(steps):
        batch = {"input_ids": rs.randint(
            0, model.cfg.vocab_size, (micro_bs * n_dev, seq)).astype(np.int32)}
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
    print(f"final loss: {float(loss):.4f}")
    engine.save_checkpoint(os.environ.get("EXAMPLE_CKPT", "/tmp/dstpu_example_ckpt"))


if __name__ == "__main__":
    main()
