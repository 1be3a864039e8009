"""The routed serving runners' chip probe (``serve_routed.Runner._chip_tflops``)
is read on a TPU alone: off the chip it builds, compiles and runs nothing and
the two readings stand as ``null``; the chip's own path still runs here, at a
size a test can hold, so that a slip in it costs no chip call; and a runner
that has the probe INHERITS it, whichever file of ``runners/`` brings it."""

import glob
import importlib
import math
import os
import time
import types

import jax
import pytest

from benchmark.runners import serve_routed

RUNNERS = sorted(os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
    os.path.join(os.path.dirname(serve_routed.__file__), "[a-z]*.py")))  # every runner file, later ones too


class Bare(serve_routed.Runner):
    """A runner with no engine behind it: what ``_chip_tflops`` and the
    runner's own part of ``_measure`` read, and nothing else."""

    def __init__(self, device):
        self.ctx = dict(devices=[device])
        self.records, self.live_rows, self.live_kv = [], [], []
        self.longest_step = {False: 0.0, True: 0.0}


@pytest.fixture(autouse=True)
def small_probe(monkeypatch):
    # the committed size is the chip's (3.3 TFLOP); a test that has to FAIL on a runner that
    # measures off the chip should not take 6 s of every core to do so
    monkeypatch.setattr(serve_routed, "PROBE_N", 64)
    monkeypatch.setattr(serve_routed, "PROBE_REPEATS", 2)


def test_off_the_chip_the_probe_builds_nothing_and_both_readings_are_null():
    runner = Bare(jax.devices()[0])
    assert runner.ctx["devices"][0].platform != "tpu"
    assert runner._chip_tflops() is None and not hasattr(runner, "_probe")
    # the second half of setup(), then the runner's own part of _measure: the line's two keys
    runner.probe0 = runner._chip_tflops()
    runner.host0 = (serve_routed._host_loop_ms(), time.process_time(), time.perf_counter())
    runner.pauses = serve_routed._Pauses()
    runner.pauses.start()
    stats = {k: 0 for k in ("ticks", "capacity_tokens", "dispatch_ms", "block_ms", "tokens")}
    obs = runner._measure(True, 1.0, 0.0, 1.0, 1.0, 1.0, stats, dict(stats, ticks=3))["obs"]
    assert not runner.pauses.is_alive() and not hasattr(runner, "_probe")
    assert obs["chip_probe_tflops_before"] is None and obs["chip_probe_tflops_after"] is None
    assert obs["host_loop_ms_before"] > 0 and obs["host_loop_ms_after"] > 0   # the host's stay


def test_on_a_tpu_the_probe_measures_and_compiles_once():
    runner = Bare(types.SimpleNamespace(platform="tpu"))
    first, second = runner._chip_tflops(), runner._chip_tflops()
    for reading in (first, second):
        assert isinstance(reading, float) and math.isfinite(reading) and reading > 0
    fn, a = runner._probe
    assert a.shape == (64, 64) and fn._cache_size() == 1


@pytest.mark.parametrize("name", RUNNERS)
def test_a_runner_with_a_probe_has_the_routed_runners(name):
    runner = importlib.import_module("benchmark.runners." + name).Runner
    probe = serve_routed.Runner._chip_tflops
    assert getattr(runner, "_chip_tflops", probe) is probe   # a copy would measure off the chip again
    assert issubclass(runner, serve_routed.Runner) == hasattr(runner, "_chip_tflops")


def test_the_five_plan_runners_are_among_them():
    assert {"serve_hybrid", "serve_latent", "serve_latent_moe", "serve_looped", "serve_ssm"} <= {
        name for name in RUNNERS
        if issubclass(importlib.import_module("benchmark.runners." + name).Runner, serve_routed.Runner)}
