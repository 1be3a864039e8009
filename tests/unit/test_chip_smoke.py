"""chip_smoke.py and bench.py refuse to report anything without a TPU, and
chip_smoke.py's control flow is rehearsed on the CPU behind an explicit flag.

Everything here runs the scripts as subprocesses, so this session's own
backend is untouched; each gets a fresh compile-cache directory (XLA:CPU
executables must not be reloaded across processes — see conftest.py). The
four runs start together in one module fixture: they are independent, this
host has eight cores, and the fixture then costs what the longest of them
does (~30 s) instead of their sum (~60 s)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RUNS = {  # name -> (script, args, virtual CPU devices)
    "smoke-on-cpu": ("chip_smoke.py", (), 1),
    "bench-on-cpu": ("bench.py", (), 1),
    "rehearse-1": ("chip_smoke.py", ("--rehearse",), 1),
    "rehearse-4": ("chip_smoke.py", ("--rehearse", "--chips", "4"), 4),
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    # a fresh single-process environment: earlier tests of this session may
    # have left launcher rendezvous variables (DSTPU_*) in os.environ
    base = {k: v for k, v in os.environ.items() if not k.startswith("DSTPU_")}
    procs = {}
    for name, (script, args, devices) in RUNS.items():
        env = dict(base, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path_factory.mktemp(name)),
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
        procs[name] = subprocess.Popen(
            [sys.executable, os.path.join(REPO, script), *args], cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    done = {}
    try:
        for name, proc in procs.items():
            out, err = proc.communicate(timeout=300)
            done[name] = subprocess.CompletedProcess(proc.args, proc.returncode, out, err)
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return done


def _json_lines(stdout):
    return [json.loads(line) for line in stdout.splitlines() if line.startswith("{")]


def test_chip_smoke_refuses_the_cpu(runs):
    proc = runs["smoke-on-cpu"]
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr  # names what it found
    assert '"ok"' not in proc.stdout        # and prints no result


def test_bench_refuses_the_cpu(runs):
    proc = runs["bench-on-cpu"]
    assert proc.returncode != 0
    assert _json_lines(proc.stdout)[-1]["metric"] == "bench_no_tpu"
    # nothing reprinted from an earlier run, nothing measured
    assert "stale" not in proc.stdout and "tokens_per_sec" not in proc.stdout


@pytest.mark.parametrize("chips, phases", [
    (1, [("train", 1), ("serve", 1)]),
    (4, [("train", 1), ("train", 4), ("serve", 1), ("serve", 4)]),
])
def test_chip_smoke_rehearsal(runs, chips, phases):
    """--rehearse: toy model, any platform. Proves the control flow (both
    phases; with --chips 4 the sharded-vs-single comparisons and placement
    assertions on four virtual devices) and nothing about a chip: the last
    line says which platform it ran on, and that is never "tpu" here."""
    proc = runs[f"rehearse-{chips}"]
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = _json_lines(proc.stdout)
    assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                                "count": chips}}
    assert json.loads(proc.stdout.splitlines()[-1]) == lines[-1]  # the LAST line
    ran = [(l["phase"], l["chips"]) for l in lines if l.get("phase") in ("train", "serve")]
    assert ran == phases
    for l in lines:
        if l.get("phase") == "train":
            assert l["loss_last"] < l["loss_first"] and l["compiled_after_first_step"] == 0
        if l.get("phase") == "serve":
            assert l["depth0_equals_depth1"]
            assert l["worst_gap_below_reference_top"] <= l["reference_margin"]
            # the negative controls ran: against a context the engine did
            # not see, the same tokens leave the margin
            assert set(l["control_share_outside_margin"]) == {
                "prompt_permuted", "prompt_one_position_early"}
            assert min(l["control_share_outside_margin"].values()) >= l["control_share_required"]
