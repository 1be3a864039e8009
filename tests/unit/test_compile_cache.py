"""The one placement rule for the persistent XLA compile cache
(deepspeed_tpu/utils/compile_cache.py). Each case runs in fresh
interpreters (two per case, all started together): this session's own
cache config belongs to conftest.py."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_PROBE = (
    "import json, sys, jax\n"
    "from deepspeed_tpu.utils.compile_cache import configure_compile_cache\n"
    "arg = sys.argv[1] if len(sys.argv) > 1 else None\n"
    "print(json.dumps({'returned': configure_compile_cache(arg),\n"
    "                  'config': jax.config.jax_compilation_cache_dir,\n"
    "                  'min_secs': jax.config.jax_persistent_cache_min_compile_time_secs}))\n"
)

CASES = {  # name -> (JAX_COMPILATION_CACHE_DIR, argv, directory expected)
    "placed-from-outside": ("/x", (), "/x"),                  # untouched
    "unset": (None, (), os.path.join(REPO, ".jax_cache")),    # the fixed in-checkout path
    "explicit-override": ("/x", ("/y",), "/y"),               # a CLI flag's override wins
}


@pytest.fixture(scope="module")
def reports():
    base = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    procs = {}
    for name, (env_dir, argv, _) in CASES.items():
        env = dict(base, JAX_PLATFORMS="cpu")
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        procs[name] = [subprocess.Popen([sys.executable, "-c", _PROBE, *argv], cwd=REPO,
                                        env=env, stdout=subprocess.PIPE,
                                        stderr=subprocess.PIPE, text=True)
                       for _ in range(2)]
    out = {}
    for name, pair in procs.items():
        out[name] = []
        for proc in pair:
            stdout, stderr = proc.communicate(timeout=120)
            assert proc.returncode == 0, stdout + stderr
            out[name].append(json.loads(stdout.strip().splitlines()[-1]))
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_cache_placement(reports, case):
    expect = CASES[case][2]
    first, second = reports[case]
    assert first == {"returned": expect, "config": expect, "min_secs": 0.0}
    # the path is part of the cache key: a second process must land on
    # exactly the same directory, or nothing it compiles is ever found again
    assert second == first
