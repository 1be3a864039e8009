"""Multi-host launch orchestrator (the ``dstpu`` command).

TPU-native counterpart of the reference's ``deepspeed`` CLI
(launcher/runner.py:376 ``main``, hostfile handling :188/:243, world-info
encoding :341, runner selection → multinode_runner.py). Differences that are
TPU-architecture, not omissions:

  - the worker unit is a *host* (one JAX process per TPU-VM worker driving
    all its local chips), not a GPU rank — so ``--num_gpus`` maps to
    process-per-host counts and ``slots=N`` in a hostfile means N hosts'
    worth only for CPU simulation;
  - rendezvous is JAX's coordinator (``jax.distributed.initialize``), so the
    launcher exports COORDINATOR_ADDRESS / PROCESS_COUNT / PROCESS_ID
    (consumed by deepspeed_tpu.comm.init_distributed) instead of
    MASTER_ADDR/RANK torch env;
  - ``--launcher tpu-pod`` builds ``gcloud compute tpus tpu-vm ssh
    --worker=all`` commands (the TPU pod analogue of pdsh); ``ssh``/``pdsh``
    runners cover self-managed clusters, and SLURM via srun.
"""

import argparse
import base64
import json
import os
import shlex
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

from deepspeed_tpu.utils.logging import logger

DLTS_HOSTFILE = "/job/hostfile"
EXPORT_ENVS = (
    "PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS", "LIBTPU_INIT_ARGS", "TPU_NAME",
    "DSTPU_ELASTIC", "DSTPU_ELASTIC_CKPT",
)


def parse_args(args=None):
    parser = argparse.ArgumentParser(
        description="dstpu launcher (reference: deepspeed CLI)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    parser.add_argument("-H", "--hostfile", type=str, default=DLTS_HOSTFILE,
                        help="hostfile: lines of '<host> slots=<n>'")
    parser.add_argument("-i", "--include", type=str, default="",
                        help="inclusion filter, e.g. 'host1,host2@host3'")
    parser.add_argument("-e", "--exclude", type=str, default="",
                        help="exclusion filter")
    parser.add_argument("--num_nodes", type=int, default=-1)
    parser.add_argument("--num_gpus", "--num_chips", type=int, default=-1,
                        help="processes per node (TPU: usually 1 per host)")
    parser.add_argument("--master_addr", type=str, default="",
                        help="coordinator address (default: first host)")
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("--launcher", type=str, default="ssh",
                        choices=("ssh", "pdsh", "slurm", "tpu-pod", "local",
                                 "openmpi", "mpich", "mvapich"))
    parser.add_argument("--tpu_name", type=str, default=os.environ.get("TPU_NAME", ""),
                        help="TPU pod slice name for --launcher tpu-pod")
    parser.add_argument("--zone", type=str, default="", help="GCP zone for tpu-pod")
    parser.add_argument("--force_multi", action="store_true")
    parser.add_argument("--elastic", action="store_true",
                        help="elastic restart: export DSTPU_ELASTIC_* env so the "
                             "user script resumes via elasticity.elastic_resume "
                             "when the chip count changed (reference ds_elastic / "
                             "elastic_agent.py membership-change restart)")
    parser.add_argument("--elastic_checkpoint_dir", type=str, default="",
                        help="checkpoint dir elastic restarts resume from")
    parser.add_argument("--no_python", action="store_true")
    parser.add_argument("--module", action="store_true", help="run script as python -m")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


# ---------------------------------------------------------------------------
# hostfile handling (reference runner.py:188 fetch_hostfile,
# :243 parse_inclusion_exclusion)
# ---------------------------------------------------------------------------

def fetch_hostfile(hostfile_path: str) -> Dict[str, int]:
    """Parse '<hostname> slots=<n>' lines; {} if the file doesn't exist."""
    if not os.path.isfile(hostfile_path):
        return {}
    resource_pool: Dict[str, int] = {}
    with open(hostfile_path) as fh:
        for line in fh:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split()
            host = parts[0]
            slots = 1
            for tok in parts[1:]:
                if tok.startswith("slots="):
                    slots = int(tok.split("=")[1])
            if host in resource_pool:
                raise ValueError(f"host {host} listed twice in hostfile")
            resource_pool[host] = slots
    return resource_pool


def _parse_filter(spec: str) -> Dict[str, Optional[List[int]]]:
    """Reference syntax (runner.py:243): hosts separated by '@', slot lists
    by ','. 'host1@host2:0,1' -> {host1: None, host2: [0, 1]} (None = all)."""
    out: Dict[str, Optional[List[int]]] = {}
    if not spec:
        return out
    for part in spec.split("@"):
        if not part:
            continue
        if ":" in part:
            host, slots = part.split(":")
            new = [int(s) for s in slots.split(",") if s != ""]
            prev = out.get(host)
            out[host] = sorted(set((prev or []) + new))
        else:
            out[part] = None
    return out


def parse_inclusion_exclusion(
    resource_pool: Dict[str, int], inclusion: str, exclusion: str
) -> Dict[str, List[int]]:
    """Apply --include/--exclude to the hostfile pool
    (reference runner.py:243). Returns {host: [slot ids]}."""
    active = {host: list(range(slots)) for host, slots in resource_pool.items()}
    inc = _parse_filter(inclusion)
    exc = _parse_filter(exclusion)
    if inc and exc:
        raise ValueError("--include and --exclude are mutually exclusive")
    if inc:
        filtered = {}
        for host, slots in inc.items():
            if host not in active:
                raise ValueError(f"included host {host} not in hostfile")
            filtered[host] = slots if slots is not None else active[host]
            bad = set(filtered[host]) - set(active[host])
            if bad:
                raise ValueError(f"included slots {bad} not available on {host}")
        return filtered
    for host, slots in exc.items():
        if host not in active:
            raise ValueError(f"excluded host {host} not in hostfile")
        if slots is None:
            del active[host]
        else:
            active[host] = [s for s in active[host] if s not in slots]
            if not active[host]:
                del active[host]
    return active


def encode_world_info(active: Dict[str, List[int]]) -> str:
    """base64 world info passed to per-node launchers (reference runner.py:341)."""
    return base64.urlsafe_b64encode(json.dumps(active).encode()).decode()


def decode_world_info(encoded: str) -> Dict[str, List[int]]:
    return json.loads(base64.urlsafe_b64decode(encoded.encode()).decode())


# ---------------------------------------------------------------------------
# command construction
# ---------------------------------------------------------------------------

def assert_no_backend_in_parent():
    """A chip belongs to one process at a time: a launcher parent that
    has initialised a JAX backend holds the chip, and the child it spawns
    then fails or hangs at its first device contact. Importing the
    package imports jax, which is harmless; *using* it here is not. Both
    launchers call this right before they spawn."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "the launcher process has initialised a JAX backend and would "
            "hold the accelerator its children need; keep device queries "
            "out of deepspeed_tpu.launcher")


def _python_exec(args) -> List[str]:
    if args.no_python:
        return []
    cmd = [sys.executable, "-u"]
    if args.module:
        cmd.append("-m")
    return cmd


def build_launch_cmd(args, active: Dict[str, List[int]], node_rank: int, master_addr: str) -> List[str]:
    """Per-node command running launcher.launch (reference launch.py spawn)."""
    world = encode_world_info(active)
    cmd = [
        sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
        f"--world_info={world}",
        f"--node_rank={node_rank}",
        f"--master_addr={master_addr}",
        f"--master_port={args.master_port}",
    ]
    if args.no_python:
        cmd.append("--no_python")
    if args.module:
        cmd.append("--module")
    cmd.append(args.user_script)
    cmd.extend(args.user_args)
    return cmd


def build_multinode_cmds(args, active: Dict[str, List[int]], master_addr: str) -> List[Tuple[str, List[str]]]:
    """(host, argv) pairs for the chosen launcher backend
    (reference multinode_runner.py PDSH/OpenMPI/Slurm get_cmd)."""
    exports = " ".join(
        f"export {k}={shlex.quote(os.environ[k])};" for k in EXPORT_ENVS if k in os.environ
    )
    cmds = []
    hosts = list(active)
    for rank, host in enumerate(hosts):
        node_cmd = build_launch_cmd(args, active, rank, master_addr)
        remote = f"{exports} cd {shlex.quote(os.getcwd())}; {' '.join(shlex.quote(c) for c in node_cmd)}"
        if args.launcher in ("ssh", "pdsh"):
            cmds.append((host, ["ssh", "-o", "StrictHostKeyChecking=no", host, remote]))
        elif args.launcher == "slurm":
            cmds.append((host, ["srun", f"--nodelist={host}", "--ntasks=1", "bash", "-c", remote]))
        elif args.launcher == "tpu-pod":
            assert args.tpu_name, "--tpu_name (or TPU_NAME env) required for tpu-pod launcher"
            gc = ["gcloud", "compute", "tpus", "tpu-vm", "ssh", args.tpu_name,
                  f"--worker={rank}", "--command", remote]
            if args.zone:
                gc.insert(5, f"--zone={args.zone}")
            cmds.append((host, gc))
    return cmds


MPI_LAUNCHERS = ("openmpi", "mpich", "mvapich")


def build_mpi_cmd(args, active: Dict[str, List[int]], master_addr: str,
                  hostfile_path: str) -> List[str]:
    """Single mpirun command spanning every host (reference
    multinode_runner.py:107 OpenMPIRunner / :160 MPICHRunner /
    :208 MVAPICHRunner). Each rank goes through launcher/mpi_shim.py,
    which maps the MPI rank env onto the DSTPU rendezvous env."""
    total = sum(len(s) for s in active.values())
    with open(hostfile_path, "w") as f:
        for host, slots in active.items():
            if args.launcher == "openmpi":
                f.write(f"{host} slots={len(slots)}\n")
            else:  # mpich / mvapich hostfile syntax
                f.write(f"{host}:{len(slots)}\n")
    exports = [k for k in EXPORT_ENVS if k in os.environ]
    if args.launcher == "openmpi":
        cmd = ["mpirun", "-n", str(total), "-hostfile", hostfile_path,
               "--allow-run-as-root"]
        for k in exports:
            cmd += ["-x", k]
    else:
        cmd = ["mpirun", "-n", str(total), "-f", hostfile_path]
        for k in exports:
            cmd += ["-genv", k, os.environ[k]]
        if args.launcher == "mvapich":
            cmd += ["-genv", "MV2_SUPPORT_DL", "1"]
    shim = [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.mpi_shim",
            f"--coordinator={master_addr}:{args.master_port}"]
    if args.no_python:
        shim.append("--no_python")
    if args.module:
        shim.append("--module")
    return cmd + shim + [args.user_script] + args.user_args


def main(argv=None):
    args = parse_args(argv)
    if args.elastic:
        # the per-process half lives in elasticity/elastic_agent.py:
        # the user script (or deepspeed_tpu.initialize via config
        # 'elasticity') reads these and calls elastic_resume when the
        # current world size differs from the checkpointed one
        os.environ["DSTPU_ELASTIC"] = "1"
        if args.elastic_checkpoint_dir:
            os.environ["DSTPU_ELASTIC_CKPT"] = args.elastic_checkpoint_dir
    resource_pool = fetch_hostfile(args.hostfile)
    if not resource_pool:
        resource_pool = {"localhost": max(1, args.num_gpus) if args.num_gpus > 0 else 1}
    active = parse_inclusion_exclusion(resource_pool, args.include, args.exclude)
    if args.num_nodes > 0:
        active = dict(list(active.items())[: args.num_nodes])
    if not active:
        raise RuntimeError("no hosts left after filtering")
    master_addr = args.master_addr or list(active)[0]

    if args.launcher in MPI_LAUNCHERS:
        import tempfile

        # NamedTemporaryFile: O_EXCL + unpredictable name (a predictable
        # /tmp path is symlink-clobberable on shared hosts), removed after
        # the launch
        tf = tempfile.NamedTemporaryFile(
            mode="w", prefix="dstpu_mpi_hostfile_", suffix=".txt", delete=False
        )
        tf.close()
        try:
            cmd = build_mpi_cmd(args, active, master_addr, tf.name)
            logger.info(f"dstpu {args.launcher} launch: {' '.join(cmd[:8])} ...")
            assert_no_backend_in_parent()
            rc = subprocess.call(cmd)
        finally:
            try:
                os.unlink(tf.name)
            except OSError:
                pass
        sys.exit(rc)

    multi_node = args.force_multi or len(active) > 1 or args.launcher == "tpu-pod"
    if not multi_node:
        cmd = build_launch_cmd(args, active, node_rank=0, master_addr="127.0.0.1")
        logger.info(f"dstpu single-node launch: {' '.join(cmd)}")
        assert_no_backend_in_parent()
        result = subprocess.call(cmd)
        sys.exit(result)

    cmds = build_multinode_cmds(args, active, master_addr)
    assert_no_backend_in_parent()
    procs = []
    for host, argv_ in cmds:
        logger.info(f"dstpu launching on {host}: {' '.join(argv_[:6])} ...")
        procs.append(subprocess.Popen(argv_))
    import time

    exit_code = 0
    try:
        alive = list(procs)
        while alive:
            for p in list(alive):
                rc = p.poll()
                if rc is None:
                    continue
                alive.remove(p)
                exit_code = exit_code or rc
                if rc != 0:  # fail fast: kill the rest (reference runner.py:543)
                    for q in procs:
                        if q.poll() is None:
                            q.terminate()
            if alive:
                time.sleep(0.5)  # poll all hosts; a sequential wait() would
                # miss a late-host crash while earlier hosts block at rendezvous
    except KeyboardInterrupt:
        for q in procs:
            if q.poll() is None:
                q.terminate()
        exit_code = 1
    sys.exit(exit_code)


if __name__ == "__main__":
    main()
