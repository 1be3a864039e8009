"""Per-node process launcher.

TPU-native counterpart of the reference's ``launcher/launch.py`` (:216 main —
set rendezvous env, spawn one process per device, watch children, kill the
tree on failure :426). On TPU one JAX process drives every local chip, so a
node spawns ONE training process (per slot only when simulating hosts on
CPU), and the env speaks JAX's multi-controller dialect:

  DSTPU_COORDINATOR / DSTPU_NUM_PROCESSES / DSTPU_PROCESS_ID
  (consumed by deepspeed_tpu.comm.init_distributed →
   jax.distributed.initialize)
"""

import argparse
import os
import signal
import subprocess
import sys

from deepspeed_tpu.launcher.runner import assert_no_backend_in_parent, decode_world_info
from deepspeed_tpu.utils.logging import logger


def parse_args(args=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--world_info", type=str, required=True)
    parser.add_argument("--node_rank", type=int, required=True)
    parser.add_argument("--master_addr", type=str, required=True)
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--no_python", action="store_true")
    parser.add_argument("--module", action="store_true")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def build_child_env(args, world: dict, local_slot: int, local_index: int = None) -> dict:
    hosts = list(world)
    # global process id = processes on earlier nodes + this slot's *position*
    # (slot IDs can be sparse after --include/--exclude filtering; using the
    # raw id would collide with other nodes' ranges)
    if local_index is None:
        local_index = world[hosts[args.node_rank]].index(local_slot)
    process_id = sum(len(world[h]) for h in hosts[: args.node_rank]) + local_index
    num_processes = sum(len(s) for s in world.values())
    env = dict(os.environ)
    env.update(
        {
            "DSTPU_COORDINATOR": f"{args.master_addr}:{args.master_port}",
            "DSTPU_NUM_PROCESSES": str(num_processes),
            "DSTPU_PROCESS_ID": str(process_id),
            # reference-compat names some user scripts read
            "RANK": str(process_id),
            "LOCAL_RANK": str(local_slot),
            "WORLD_SIZE": str(num_processes),
            "MASTER_ADDR": args.master_addr,
            "MASTER_PORT": str(args.master_port),
        }
    )
    return env


def main(argv=None):
    args = parse_args(argv)
    world = decode_world_info(args.world_info)
    hosts = list(world)
    assert 0 <= args.node_rank < len(hosts), f"node_rank {args.node_rank} out of range"
    my_slots = world[hosts[args.node_rank]]

    assert_no_backend_in_parent()
    procs = []
    for idx, slot in enumerate(my_slots):
        env = build_child_env(args, world, local_slot=slot, local_index=idx)
        cmd = []
        if not args.no_python:
            cmd = [sys.executable, "-u"] + (["-m"] if args.module else [])
        cmd.append(args.user_script)
        cmd.extend(args.user_args)
        logger.info(f"launch: node {args.node_rank} slot {slot} -> {' '.join(cmd)}")
        procs.append(subprocess.Popen(cmd, env=env))

    # signal propagation + fail-fast (reference launch.py:426 sigkill_handler)
    def _terminate(signum, frame):
        for p in procs:
            if p.poll() is None:
                p.terminate()
        sys.exit(128 + signum)

    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)

    import time

    alive = list(procs)
    while alive:
        for p in list(alive):
            rc = p.poll()
            if rc is None:
                continue
            alive.remove(p)
            if rc != 0:
                logger.error(f"child {p.pid} failed with {rc}; killing node process tree")
                for q in alive:
                    q.kill()
                sys.exit(rc)
        if alive:
            time.sleep(0.2)  # poll ALL children; a blocking wait on one would
            # miss a crash in another while peers hang at the rendezvous
    sys.exit(0)


if __name__ == "__main__":
    main()
