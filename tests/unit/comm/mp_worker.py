"""Worker + shared fixtures for the 2-process DistributedTest equivalent.

TPU translation of the reference's forked-process harness
(tests/unit/common.py:277 DistributedTest, :132 forkserver + localhost
rendezvous): the parent test spawns 2 of these workers, each with 4 virtual
CPU devices; they join a jax.distributed coordinator through the SAME env
surface the dstpu launcher sets (DSTPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID,
consumed by comm._maybe_init_multi_controller), build one global 8-device
mesh, stride the dataloader per process, train, checkpoint through Orbax
multi-process save/load, and report losses for parity against the
single-process 8-device run.

Run directly:  python mp_worker.py <out_json> <ckpt_dir>
(with the DSTPU_* env set by the parent test)
"""

import json
import os
import sys

# DSTPU_TEST_MESH selects the parallelism under test: the default exercises
# cross-process DATA-parallel collectives; {"tensor": 8} exercises
# cross-process TENSOR-parallel collectives (matmul partial-sum psums over
# the process boundary) with a replicated batch both processes must feed
# identically (dataloader dp=1 path).
MESH = json.loads(os.environ.get("DSTPU_TEST_MESH", '{"data": 2, "fsdp": 4}'))
DP = MESH.get("data", 1) * MESH.get("fsdp", 1)
MICRO_BS = 2  # >1 so the tensor mesh hits batch%nprocs==0 with dp=1 — the
#               loader must NOT stride there (the engine passes
#               process_shard=False); regression for a silent wrong-data bug
GLOBAL_BS = MICRO_BS * DP
SEQ = 16
VOCAB = 64
STEPS = 2


def build_dataset():
    import numpy as np

    rs = np.random.RandomState(1234)
    return [rs.randint(0, VOCAB, (SEQ,)).astype(np.int32) for _ in range(GLOBAL_BS * STEPS)]


def collate(rows):
    import numpy as np

    return {"input_ids": np.stack(rows)}


def build_engine():
    import deepspeed_tpu

    from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

    # heads/dims divide every mesh under test (tensor up to 8)
    cfg = TransformerConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=8,
        max_seq_len=SEQ, dtype="float32",
    )
    config = {
        "train_micro_batch_size_per_gpu": MICRO_BS,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
        "mesh": MESH,
        "steps_per_print": 1000000,
    }
    return deepspeed_tpu.initialize(
        model=TransformerModel(cfg), config=config, training_data=build_dataset(),
        collate_fn=collate,
    )


def run(out_path: str, ckpt_dir: str):
    import jax

    engine, _, loader, _ = build_engine()
    assert engine.mesh.devices.size == 8, dict(engine.mesh.shape)
    losses = []
    it = iter(loader)
    for _ in range(STEPS):
        batch = next(it)
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    engine.save_checkpoint(ckpt_dir, tag="mp")
    # a fresh engine restores the trained state and reproduces the loss on
    # a fixed batch — proves Orbax multi-process save produced a loadable,
    # consistent checkpoint (not just rank-0's shards)
    engine2, _, _, _ = build_engine()
    engine2.load_checkpoint(ckpt_dir, tag="mp")
    probe = collate(build_dataset()[:GLOBAL_BS])
    l_trained = float(engine.eval_batch(probe))
    l_restored = float(engine2.eval_batch(probe))
    result = {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "losses": losses,
        "loss_trained": l_trained,
        "loss_restored": l_restored,
        "global_steps": engine.global_steps,
    }
    with open(out_path, "w") as fh:
        json.dump(result, fh)
    print("WORKER_OK", json.dumps(result), flush=True)


if __name__ == "__main__":
    # 4 virtual CPU devices per process, BEFORE the backend initializes
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)
    sys.path.insert(0, os.environ["DSTPU_REPO_ROOT"])
    run(sys.argv[1], sys.argv[2])
