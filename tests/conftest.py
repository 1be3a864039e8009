"""Test harness: run everything on a virtual 8-device CPU mesh.

TPU translation of the reference's ``tests/unit/common.py`` DistributedTest
pattern: instead of forking N processes over NCCL, JAX exposes N virtual
devices in-process via ``jax_num_cpu_devices`` and tests
build real meshes/shardings over them (SURVEY.md §4).
"""

import os
import sys

# Tests run on the CPU whatever the machine holds: pin the platform and
# split the host into eight virtual devices before any backend starts.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# Persistent XLA compile cache: compiles survive the per-module
# clear_caches() below AND rerun invocations (measured ~2x on warm,
# compile-heavy modules; the build host has one CPU core, so compiles
# dominate the suite). ~MBs of machine-local artifacts; gitignored.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".pytest_jax_cache"
)
# under pytest-xdist each worker gets its OWN dir: the session-start wipe
# below would race sibling workers on a shared one, and cross-process
# entry reuse between live workers is the segfault mode it guards against
_xdist_worker = os.environ.get("PYTEST_XDIST_WORKER")
if _xdist_worker:
    _CACHE_DIR += f"-{_xdist_worker}"
# A cache written by a different jaxlib/CPU hard-aborts (SIGABRT, no
# traceback) on entry deserialization mid-suite — wipe on stamp mismatch.
import jaxlib  # noqa: E402
import platform  # noqa: E402
import shutil  # noqa: E402

_STAMP = f"{jax.__version__}|{jaxlib.__version__}|{platform.machine()}"  # kept for forensics
# The cache is SESSION-SCOPED, not cross-run: XLA:CPU executables
# deserialized from a cache written by ANOTHER process segfault on this
# jaxlib (reliably reproduced: a fully-green `pytest tests/unit/ops` run
# followed by an identical rerun on its own cache dies in device_put /
# engine.step with "Fatal Python error: Segmentation fault"; the
# jax|jaxlib|arch stamp cannot catch it because the versions match).
# Same-process re-loads — the per-module clear_caches() below recompiling
# from the entries THIS run wrote — are safe and are where the ~2x warm
# speedup actually lives, so wipe at session start and keep the dir on.
shutil.rmtree(_CACHE_DIR, ignore_errors=True)
os.makedirs(_CACHE_DIR, exist_ok=True)
with open(os.path.join(_CACHE_DIR, ".stamp"), "w") as _fh:
    _fh.write(_STAMP)
jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_comm_state():
    yield
    try:
        from deepspeed_tpu import comm

        comm.destroy()
    except Exception:
        pass


@pytest.fixture(autouse=True)
def _clear_launcher_env(monkeypatch):
    """``launcher/mpi_shim.py`` writes the rendezvous variables into
    ``os.environ`` for the script it execs; a test that drives it in-process
    leaves them in the xdist worker, and every later ``initialize`` on that
    worker then tries to join a coordinator that is not there. Cleared
    before each test, so the suite does not depend on the order it runs in,
    and after it too: a module-scoped fixture of the NEXT file (``toy_tick``
    of ``test_cache_in_carry.py``) is set up before that file's first
    function-scoped clearing, and met the variables where the two files
    shared a worker (two errors in one whole run of PR 38)."""
    names = ("DSTPU_COORDINATOR", "DSTPU_NUM_PROCESSES", "DSTPU_PROCESS_ID")
    for name in names:
        monkeypatch.delenv(name, raising=False)
    yield
    for name in names:
        os.environ.pop(name, None)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Free compiled executables between modules. A full-suite run holds
    hundreds of XLA:CPU executables in one process; the LLVM JIT has been
    observed to segfault during late-suite compiles under that accumulation
    (tests pass in isolation). Module scope keeps intra-module caching."""
    yield
    jax.clear_caches()


@pytest.fixture
def mesh8():
    """Default 8-device mesh, all devices on the fsdp axis."""
    from deepspeed_tpu import comm

    comm.destroy()
    return comm.init_distributed(mesh_shape={"data": 1, "fsdp": -1}, verbose=False)
