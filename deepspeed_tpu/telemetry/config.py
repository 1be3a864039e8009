"""Telemetry config block, shared verbatim by the training config
(``TpuConfig.telemetry``) and the inference config
(``InferenceConfig.telemetry``). Default off: with ``enabled: false`` the
engines behave bit-identically to a build without the telemetry layer and
no trace file is ever created.

JSON shape (see docs/telemetry.md for the full schema):

    "telemetry": {
        "enabled": true,
        "trace_file": "runs/trace.jsonl",
        "profile_start_step": 10,
        "profile_num_steps": 3
    }
"""

from dataclasses import dataclass


@dataclass
class TelemetryConfig:
    enabled: bool = False
    # JSONL destination, one event per line ("schema": 1). Written by
    # process 0 only. Relative paths resolve against the CWD.
    trace_file: str = "telemetry_trace.jsonl"
    # mirror numeric event fields into MonitorMaster writers
    # (tensorboard/csv/wandb) when any are configured
    emit_to_monitor: bool = True
    # true: block on device work at micro-step/step boundaries so the
    # forward / backward / step timers hold the device's time, not the
    # enqueue's. That buys device-inclusive phase timers and costs the
    # host's dispatch-ahead: 8.0 % of training throughput on the chip with
    # the hub on, against 0.7 % with it false (PERF.md §6, PR 24). False
    # (the default since PR 40) keeps the async pipeline; iter_ms,
    # samples/sec and MFU span whole steps and still hold.
    sync_timers: bool = False
    # per-device peak FLOP/s (in TFLOP/s) for the MFU denominator.
    # 0 = auto-detect from jax device_kind (v4/v5e/v5p/v6e table),
    # falling back to the v5e peak (197) on unknown hardware — override
    # for anything else.
    peak_tflops_per_device: float = 0.0
    # jax.profiler device-trace capture window: start at this global step
    # (0 = never) and run for profile_num_steps steps. On the serving
    # tick loop the window is TICK-indexed (the continuous engine drives
    # maybe_capture once per scheduler tick), so a capture can be pointed
    # at the pooled-tick hot path. The xplane dump lands in profile_dir
    # (default: alongside the trace file).
    profile_start_step: int = 0
    profile_num_steps: int = 1
    profile_dir: str = ""
    # size bound (bytes) on the JSONL trace file: 0 = unbounded (the
    # historical behavior); > 0 rotates the file to <trace_file>.1 once a
    # flushed write reaches the bound (one rotated generation is kept, so
    # disk stays <= ~2x the bound) and counts each rotation in the
    # trace_rotations counter. Soak runs set this; short runs never hit it.
    max_trace_bytes: int = 0
    # per-device HBM capacity override (bytes) for the hbm_headroom_bytes
    # gauge and memory_snapshot events. 0 = use the backend allocator's
    # bytes_limit when it reports one (TPU), else headroom is unknown
    # and the gauge is simply absent (the CPU virtual mesh).
    hbm_limit_bytes: int = 0
