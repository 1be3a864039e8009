"""BERT-large MLM pretraining (the reference's headline benchmark task,
docs/_posts/2020-05-28-fastest-bert-training.md): masked-token batches via
labels + loss_mask. EXAMPLE_SMOKE=1 shrinks for CI."""

import os

import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

SMOKE = os.environ.get("EXAMPLE_SMOKE") == "1"


def mlm_batch(rs, B, S, vocab, mask_id=103, rate=0.15):
    ids = rs.randint(0, vocab, (B, S)).astype(np.int32)
    mask = (rs.rand(B, S) < rate).astype(np.float32)
    mask[0, 0] = 1.0
    return {
        "input_ids": np.where(mask > 0, mask_id, ids).astype(np.int32),
        "labels": ids,
        "loss_mask": mask,
        "token_type_ids": np.zeros((B, S), np.int32),
    }


def main():
    configure_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache
    if SMOKE:
        model = TransformerModel(TransformerConfig(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=32,
            dtype="bfloat16", pos_embedding="learned", type_vocab_size=2,
            embed_norm=True, norm_position="post", causal=False))
        micro_bs, seq, steps = 2, 32, 4
    else:
        model = TransformerModel.from_preset("bert-large", dtype="bfloat16", max_seq_len=128)
        micro_bs, seq, steps = 64, 128, 50

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": micro_bs,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "mesh": {"data": -1},
            "steps_per_print": 10,
        },
    )
    import jax

    rs = np.random.RandomState(0)
    B = micro_bs * jax.device_count()
    for _ in range(steps):
        loss = engine.forward(mlm_batch(rs, B, seq, model.cfg.vocab_size))
        engine.backward(loss)
        engine.step()
    print(f"final mlm loss: {float(loss):.4f}")


if __name__ == "__main__":
    main()
