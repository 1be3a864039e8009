"""Benchmark orchestrator: one child process per phase, a parent that
never touches the device.

A chip belongs to one process at a time, so this parent is
**stdlib-only** — it never imports jax — and every phase, including the
first ``jax.devices()``, runs in a child (``python bench.py --child
<phase>``, implementation in ``_bench_impl.py``) under a parent-side
``communicate(timeout)`` with a process-group SIGKILL backstop. Each
child ends before the next starts, so each finds the chip free.

Protocol:

  1. Probe child (first device contact + a tiny matmul). No ``tpu``
     platform -> an error line and a non-zero exit. Nothing is ever
     reprinted from an earlier run.
  2. Primary child (headline GPT-2 training cell).
  3. Secondary phases, each under a per-phase cap, all under one global
     wall-clock budget.
  4. The headline line is printed LAST, with the suite measured in this
     run, for drivers that parse the final JSON line.

The exit code is 0 only when every phase asked for ran and returned a
result; a phase that failed, timed out or was skipped for budget is
printed as such and makes the run's exit code 1.
"""

import json
import os
import signal
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
_SENTINEL = "DSTPU_RESULT "

SECONDARIES = ("decode", "zero3_offload", "long_ctx", "serving", "bert_mlm",
               "moe_ep", "hybrid_rlhf")


def _run_child(phase, timeout_s, extra_env=None):
    """Run one bench phase in a subprocess. Returns (result_dict|None,
    err|None). The child is its own process group; on timeout the whole
    group gets SIGKILL, so a child stuck inside a blocked C call cannot
    stall the parent past ``timeout_s`` or keep holding the chip."""
    env = dict(os.environ)
    if extra_env:
        env.update(extra_env)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", phase],
        stdout=subprocess.PIPE, stderr=None, text=True,
        start_new_session=True, env=env, cwd=_ROOT,
    )
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        proc.wait()
        return None, f"killed after {timeout_s}s"
    result = None
    for line in out.splitlines():
        if line.startswith(_SENTINEL):
            try:
                result = json.loads(line[len(_SENTINEL):])
            except json.JSONDecodeError:
                pass
        elif line.strip():
            # child chatter goes to stderr so stdout stays JSON-lines-only
            print(f"[{phase}] {line}", file=sys.stderr)
    if result is None:
        return None, f"child exited rc={proc.returncode} without a result"
    return result, None


def _emit(obj):
    print(json.dumps(obj), flush=True)


def main():
    t_start = time.time()
    which = os.environ.get("DSTPU_BENCH_CONFIGS", "all")
    probe_cap = int(os.environ.get("DSTPU_BENCH_PROBE_TIMEOUT", "150"))
    primary_cap = int(os.environ.get("DSTPU_BENCH_PRIMARY_TIMEOUT", "900"))
    per_config_s = int(os.environ.get("DSTPU_BENCH_CONFIG_TIMEOUT", "240"))
    total_budget = int(os.environ.get("DSTPU_BENCH_TOTAL_BUDGET", "2100"))

    # ---- 1. probe: is there a chip at all? --------------------------------
    probe, err = _run_child("probe", probe_cap)
    if probe is None:
        _emit({"metric": "bench_probe_error", "error": err})
        return 1
    _emit(probe)
    platform = probe["extra"]["platform"]
    if platform != "tpu":
        _emit({"metric": "bench_no_tpu",
               "error": f"JAX found platform {platform!r} "
                        f"({probe['extra']['device_kind']}), not a TPU: "
                        "nothing measured"})
        return 1

    failed = []

    # ---- 2. primary -------------------------------------------------------
    primary, err = _run_child("primary", primary_cap)
    if primary is None:
        _emit({"metric": "bench_primary_error", "error": err})
        failed.append("primary")
    else:
        _emit(primary)

    # ---- 3. secondaries under one global budget ---------------------------
    suite = {}
    for name in (SECONDARIES if which != "primary" else ()):
        remaining = total_budget - (time.time() - t_start)
        if remaining < 90:
            _emit({"metric": f"bench_{name}_skipped",
                   "reason": f"global budget exhausted ({int(remaining)}s left)"})
            failed.append(name)
            continue
        # zero3_offload compiles the offload programs and moves ~4
        # bytes/param over the host link each step: it gets 2x the
        # per-config cap unless DSTPU_BENCH_ZERO3_TIMEOUT pins it
        phase_cap = int(os.environ.get("DSTPU_BENCH_ZERO3_TIMEOUT",
                                       str(2 * per_config_s))) \
            if name == "zero3_offload" else per_config_s
        cap = min(phase_cap, int(remaining))
        result, err = _run_child(name, cap,
                                 extra_env={"DSTPU_BENCH_PHASE_BUDGET": str(cap)})
        if result is None:
            _emit({"metric": f"bench_{name}_error", "error": err})
            failed.append(name)
            continue
        _emit(result)
        # a phase that returns a diagnostic line (value None / *_skipped)
        # measured nothing: printed, not recorded, and the run is not clean
        if result.get("value") is None or result["metric"].endswith("_skipped"):
            failed.append(name)
        else:
            suite[result["metric"]] = {"value": result["value"],
                                       "vs_baseline": result.get("vs_baseline")}

    # ---- 4. headline last, for last-line parsers --------------------------
    if primary is not None:
        if suite:
            primary.setdefault("extra", {})["suite"] = suite
        _emit(primary)
    if failed:
        print(f"bench: phases without a result: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        import _bench_impl

        sys.exit(_bench_impl.run_phase(sys.argv[2]))
    sys.exit(main())
