"""Continuous-batching serving loop: requests of different lengths flow
through a fixed slot pool; new arrivals are admitted as others finish.
EXAMPLE_SMOKE=1 shrinks for CI."""

import os

import numpy as np

from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel
from deepspeed_tpu.utils.compile_cache import configure_compile_cache

SMOKE = os.environ.get("EXAMPLE_SMOKE") == "1"


def main():
    configure_compile_cache()  # JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache
    if SMOKE:
        cfg = TransformerConfig(vocab_size=128, hidden_size=64, num_layers=2,
                                num_heads=4, max_seq_len=64, dtype="float32")
        slots, cache_len, new_tokens = 2, 48, 6
        arrivals = [(0, 5), (0, 9), (1, 3), (4, 7)]  # (tick, prompt_len)
    else:
        cfg = TransformerModel.from_preset("gpt2-125m", dtype="bfloat16").cfg
        slots, cache_len, new_tokens = 8, 512, 64
        arrivals = [(t, 16 + 8 * (t % 5)) for t in range(0, 64, 4)]

    engine = ContinuousBatchingEngine(
        TransformerModel(cfg),
        config={"dtype": cfg.dtype},
        max_slots=slots,
        cache_len=cache_len,
    )
    rs = np.random.RandomState(0)
    queue = [(t, rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32))
             for t, n in arrivals]

    tick, completed = 0, {}
    while queue or engine.has_work():
        due = [item for item in queue if item[0] <= tick]
        queue = [item for item in queue if item[0] > tick]
        for _, prompt in due:
            rid = engine.submit(prompt, max_new_tokens=new_tokens)
            print(f"tick {tick}: admitted request {rid}")
        engine.step()
        for rid, out in engine.finished().items():
            completed[rid] = out
            print(f"tick {tick}: request {rid} done ({len(out)} tokens)")
        tick += 1

    print(f"served {len(completed)} requests in {tick} ticks "
          f"({slots} slots, cache_len {cache_len})")
    assert len(completed) == len(arrivals)


if __name__ == "__main__":
    main()
