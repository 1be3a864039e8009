"""Unified telemetry layer: labeled metrics, structured JSONL step/request
traces, MFU accounting, and jax.profiler capture hooks.

Entry points:
  - :class:`Telemetry` — per-engine hub (``TpuEngine.telemetry``,
    ``InferenceEngine.telemetry``), built from the ``telemetry`` config
    block (default off).
  - :class:`MetricsRegistry` — standalone counters/gauges/histograms/spans.
  - :class:`TraceWriter` / :func:`read_trace` — the JSONL format
    (``"schema": 1``) consumed by ``tools/ds_trace_report.py``.
  - :mod:`compile_log` — the always-on build journal (``journal()``,
    ``summary()``), hub or no hub.
"""

from deepspeed_tpu.telemetry.compile_log import CompileRecorder
from deepspeed_tpu.telemetry.config import TelemetryConfig
from deepspeed_tpu.telemetry.ops_server import OpsServer, render_prometheus
from deepspeed_tpu.telemetry.registry import MetricsRegistry, metric_key, percentile
from deepspeed_tpu.telemetry.telemetry import Telemetry
from deepspeed_tpu.telemetry.trace import SCHEMA_VERSION, TraceWriter, read_trace

# deepspeed_tpu.telemetry.memory (the HBM accountant) is deliberately NOT
# imported here: it touches jax, and this package must stay importable by
# the jax-free tools (ds_trace_report, the ops-server tests).

__all__ = [
    "Telemetry",
    "TelemetryConfig",
    "MetricsRegistry",
    "TraceWriter",
    "read_trace",
    "metric_key",
    "percentile",
    "SCHEMA_VERSION",
    "OpsServer",
    "render_prometheus",
    "CompileRecorder",
]
