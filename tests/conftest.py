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
# Tests compile their CPU programs without the backend's expensive passes
# (backend optimisation level 0, LLVM's costly passes off; tracing, the HLO
# passes, partitioning and the lowered text are as ever). The toy programs
# run for milliseconds, and under the tier-1 command's six workers the suite
# is bound by CPU-seconds of compile: XLA:CPU compiles on several threads,
# six workers ask for more cores than the machine has, and most of a compile
# is LLVM's optimiser. What is tested is the JAX program; no speed is ever
# read off a CPU run. The modules whose subject IS the compiled program get
# the default level back: the table and its hook are below.
CHEAP_COMPILES = True
jax.config.update("jax_disable_most_optimizations", CHEAP_COMPILES)
# Persistent XLA compile cache, one directory per xdist worker, wiped when
# the session starts: a program compiled twice in one session (the
# per-module clear_caches() below drops the in-memory executables, and many
# modules build the same toy programs) is compiled once and loaded after.
# ~MBs of machine-local artifacts; gitignored.
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".pytest_jax_cache"
)
# per WORKER: the session-start wipe below would race sibling workers on a
# shared directory, and entries written by another live process are the
# segfault mode described next
_xdist_worker = os.environ.get("PYTEST_XDIST_WORKER")
if _xdist_worker:
    _CACHE_DIR += f"-{_xdist_worker}"
import jaxlib  # noqa: E402
import platform  # noqa: E402
import shutil  # noqa: E402

_STAMP = f"{jax.__version__}|{jaxlib.__version__}|{platform.machine()}"  # kept for forensics
# per SESSION, not across runs: XLA:CPU executables deserialized from a
# cache written by ANOTHER process segfault on this jaxlib (reliably
# reproduced: a fully-green `pytest tests/unit/ops` run followed by an
# identical rerun on its own cache dies in device_put / engine.step with
# "Fatal Python error: Segmentation fault"; a jax|jaxlib|arch stamp cannot
# catch it because the versions match). Re-loading what THIS process wrote
# is safe, so wipe at session start and keep the directory on.
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


# What compiles at the backend's DEFAULT level: node ids from the repo's root
# (a module's path covers its cases; a longer prefix names single cases),
# each with what it reads off the compiled program. This table is the one
# place that decides it, tests/benchmark/ included: no environment
# variable, option or marker.
DEFAULT_LEVEL = {
    "tests/unit/ops/test_tpu_compile.py": (
        "described-v5e compiles: the flag reaches the TPU compiler's options too, and the "
        "chip-free checks read its layouts, in-place updates, fusions and memory_analysis"
    ),
    "tests/unit/ops/test_tpu_compile_plan.py": "the same, for the layer-plan models' ticks and kernels",
    # A toy cell's timed window (1.0-1.5 s) must see a request end. Unoptimised, a tick of the
    # interpreted kernels of these three toys runs 4-14 times longer (GLM 14 -> 193 ms, Qwen3-Next
    # 26 -> 104, Granite 112 -> 788, one process alone) and ends 1, 7 and 0 requests where the
    # default level ends 38, 21 and 5: execution-bound cases, which the cheap level makes slower.
    **{
        f"tests/benchmark/{case}": "a timed window: the unoptimised tick ends one request in it or none"
        for case in (
            "test_bench_runners_cpu.py::test_runner_gives_the_contracts_object[toy-glm-longdoc-",
            "test_bench_runners_cpu.py::test_runner_gives_the_contracts_object[toy-granite-longdoc-",
            "test_bench_runners_cpu.py::test_runner_gives_the_contracts_object[toy-qwen3-next-longdoc-",
            "test_bench_glm4_moe_lite.py::test_the_variant_tool_runs_the_toy_cell_and_a_fault_is_refused[",
            "test_bench_granitemoehybrid.py::test_a_reference_without_the_decay_is_refused_by_the_toy_cells_comparison",
            "test_bench_qwen3_next.py::test_a_reference_without_the_decay_is_refused_by_the_toy_cells_comparison",
        )
    },
}


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    """Set the level a case compiles at before any of its fixtures is built
    (a module's fixtures are set up with its first case). The flag is no
    part of jit's in-memory key, so the executables of the other level are
    dropped where it turns."""
    cheap = CHEAP_COMPILES and not item.nodeid.startswith(tuple(DEFAULT_LEVEL))
    if jax.config.read("jax_disable_most_optimizations") != cheap:
        jax.clear_caches()
        jax.config.update("jax_disable_most_optimizations", cheap)


@pytest.fixture
def mesh8():
    """Default 8-device mesh, all devices on the fsdp axis."""
    from deepspeed_tpu import comm

    comm.destroy()
    return comm.init_distributed(mesh_shape={"data": 1, "fsdp": -1}, verbose=False)
