#!/usr/bin/env python
"""Fast jax-free test stage for tools/ci_check.sh: run the serving-layer
unit tests that by design never touch jax — router, scheduler policies,
fault plans, recovery log — in a plain interpreter, and PROVE it by
asserting jax never entered ``sys.modules``.

Why this exists (docs/serving.md "Fleet"): the fleet router, the
policies, and the recovery log are host-side bookkeeping; their tests
run in well under a second. Importing ``deepspeed_tpu`` normally pays
the jax import (several seconds) and would silently re-couple these
layers to the accelerator stack. This driver keeps them honest:

- ``deepspeed_tpu``, ``deepspeed_tpu.utils`` and
  ``deepspeed_tpu.telemetry`` are registered as PATH-ONLY stub packages
  (their real ``__init__``s import jax-heavy modules; the submodules the
  serving layer needs — utils/logging, telemetry/registry,
  telemetry/memory — are individually jax-free).
- pytest runs with ``--noconftest`` (the repo conftest builds a jax
  virtual mesh).
- after the run, ``"jax" in sys.modules`` is a hard failure: someone
  added an import-time jax dependency to a layer that promises not to
  have one.

Usage: python tools/ci_jaxfree_tests.py  (exit code = pytest's, or 3 if
jax leaked into the interpreter).
"""

import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# test files in the jax-free stage (serving bookkeeping + the train
# column's fault plans / recovery policy / checkpoint-integrity sidecars)
JAXFREE_TESTS = [
    "tests/unit/serving/test_router.py",
    "tests/unit/serving/test_recovery_log.py",
    "tests/unit/serving/test_policies.py",
    "tests/unit/serving/test_faults.py",
    "tests/unit/serving/test_shed_hints.py",
    "tests/unit/serving/test_scenarios.py",
    "tests/unit/serving/test_autoscaler.py",
    "tests/unit/runtime/test_train_faults.py",
    "tests/unit/runtime/test_resilience_policy.py",
    "tests/unit/runtime/test_numerics.py",
    "tests/unit/checkpoint/test_checkpoint_integrity.py",
    "tests/unit/serving/test_spans.py",
    "tests/unit/telemetry/test_timeline.py",
    "tests/unit/telemetry/test_prefill_wait_span.py",
    # ds-perf's text parsers / cost model / inventory diff are stdlib-only
    # by contract (the --diff path must run on hosts without jax)
    "tests/unit/analysis/test_perf_inventory.py",
]


def _stub_pkg(name: str, path: str):
    """Register ``name`` as a namespace-style package rooted at ``path``
    WITHOUT executing its real __init__.py — submodule imports then
    execute only the submodule file."""
    pkg = types.ModuleType(name)
    pkg.__path__ = [path]
    sys.modules[name] = pkg


def main() -> int:
    _stub_pkg("deepspeed_tpu", os.path.join(REPO, "deepspeed_tpu"))
    _stub_pkg("deepspeed_tpu.utils",
              os.path.join(REPO, "deepspeed_tpu", "utils"))
    _stub_pkg("deepspeed_tpu.telemetry",
              os.path.join(REPO, "deepspeed_tpu", "telemetry"))
    sys.path.insert(0, REPO)
    # third-party pytest entry-point plugins are the sneakiest jax
    # vector: jaxtyping's pytest11 hook imports jax at pytest STARTUP,
    # before any test runs. None of them are needed here.
    os.environ["PYTEST_DISABLE_PLUGIN_AUTOLOAD"] = "1"

    import pytest

    files = [os.path.join(REPO, f) for f in JAXFREE_TESTS]
    missing = [f for f in files if not os.path.exists(f)]
    if missing:
        print(f"ci_jaxfree_tests: missing test files: {missing}",
              file=sys.stderr)
        return 2
    # NOTE: no ``-p no:NAME`` blocks here — resolving a plugin NAME makes
    # pytest scan the pytest11 entry points, which imports jaxtyping and
    # with it jax, even under PYTEST_DISABLE_PLUGIN_AUTOLOAD. The env var
    # alone keeps third-party plugins (randomly, jaxtyping, xdist) out.
    rc = pytest.main(["--noconftest", "-q", "-p", "no:cacheprovider",
                      *files])
    if "jax" in sys.modules:
        print("ci_jaxfree_tests: FAIL — jax entered sys.modules during a "
              "stage that promises to be jax-free (an import-time jax "
              "dependency crept into serving/, utils/logging, or "
              "telemetry/registry)", file=sys.stderr)
        return 3
    print("ci_jaxfree_tests: ok — jax never imported")
    return int(rc)


if __name__ == "__main__":
    sys.exit(main())
