"""Telemetry hub: one object per engine fanning events into every export
path — the JSONL trace file, the in-process :class:`MetricsRegistry`
(for ``summary()`` percentiles), and ``MonitorMaster`` writers
(tensorboard/csv/wandb) — plus the optional ``jax.profiler`` device-trace
capture window.

Disabled (the default) it is inert: ``emit`` returns immediately, no file
is opened, no profiler started. Engines therefore construct one
unconditionally and guard hot-path measurement (timers, host syncs) on
``telemetry.enabled`` only.
"""

import json
import os
import time
from typing import Optional

from deepspeed_tpu.telemetry.config import TelemetryConfig
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.timeline import CLOCK_SYNC_PREFIX
from deepspeed_tpu.telemetry.trace import SCHEMA_VERSION, TraceWriter
from deepspeed_tpu.utils.logging import logger

# Per-chip bf16 peaks (TFLOP/s) by jax device_kind substring; the MFU
# denominator. Override via telemetry.peak_tflops_per_device.
_DEVICE_PEAK_TFLOPS = (
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0),
    ("v5 lite", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)
_FALLBACK_PEAK_TFLOPS = 197.0  # v5e, the repo's headline bench part


def _numeric_items(payload: dict):
    for k, v in payload.items():
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            yield k, float(v)


class Telemetry:
    def __init__(self, cfg: Optional[TelemetryConfig] = None, monitor=None,
                 role: str = "train"):
        self.cfg = cfg if cfg is not None else TelemetryConfig()
        self.enabled = self.cfg.enabled
        self.role = role
        self.monitor = monitor
        self.registry = MetricsRegistry()
        self._writer = None
        self._write_warned = False
        self._profiling = False
        self._peak_flops_per_device = None
        self._compile_recorder = None
        if self.enabled and self.cfg.trace_file:
            import jax

            if jax.process_index() == 0:
                self._writer = TraceWriter(self.cfg.trace_file,
                                           max_bytes=self.cfg.max_trace_bytes)

    # ------------------------------------------------------------------
    def span(self, name: str, labels: Optional[dict] = None):
        return self.registry.span(name, labels)

    def emit(self, kind: str, payload: dict, monitor_prefix: Optional[str] = None,
             monitor_step: Optional[int] = None):
        """Fan one structured event into every export path. ``payload`` is
        flat-ish JSON (nested dicts allowed; only top-level numerics feed
        the registry/monitor). Returns the full event dict (None when
        disabled)."""
        if not self.enabled:
            return None
        event = {"role": self.role}
        event.update(payload)
        for field, value in _numeric_items(payload):
            self.registry.histogram(f"{kind}.{field}").observe(value)
        if self._writer is not None:
            try:
                rotations_before = self._writer.rotations
                self._writer.write(kind, event)
                if self._writer.rotations != rotations_before:
                    self.registry.counter("trace_rotations").inc(
                        self._writer.rotations - rotations_before)
            except OSError as e:  # telemetry must never kill the step loop
                # a transient disk hiccup must not permanently blind the
                # trace: count it, warn ONCE (not per event), and drop the
                # file handle so the NEXT emit retries through the lazy
                # reopen — while the disk stays broken each emit fails
                # into this branch again (counter grows, no log spam)
                self.registry.counter("trace_write_errors").inc()
                if not self._write_warned:
                    logger.warning(
                        f"telemetry trace write failed (will retry on the "
                        f"next event; trace_write_errors counts drops): {e}")
                    self._write_warned = True
                try:
                    self._writer.close()
                except OSError:
                    self._writer._fh = None  # force the lazy reopen anyway
        if (monitor_prefix and self.cfg.emit_to_monitor
                and self.monitor is not None and self.monitor.enabled):
            step = int(monitor_step if monitor_step is not None
                       else payload.get("step", 0))
            self.monitor.write_events(
                [(f"{monitor_prefix}/{field}", value, step)
                 for field, value in _numeric_items(payload)]
            )
        event.setdefault("schema", SCHEMA_VERSION)
        event.setdefault("kind", kind)
        return event

    # ------------------------------------------------------------------
    def compile_recorder(self):
        """The hub's side of the build journal (telemetry/compile_log.py),
        created lazily and shared across engine generations — a serving
        rebuild re-injects this hub, so the replacement engine's compiles
        are correctly flagged as recompiles."""
        if self._compile_recorder is None:
            from deepspeed_tpu.telemetry.compile_log import CompileRecorder

            self._compile_recorder = CompileRecorder(self)
        return self._compile_recorder

    # ------------------------------------------------------------------
    def peak_flops_per_device(self) -> float:
        """MFU denominator in FLOP/s per local device."""
        if self._peak_flops_per_device is None:
            tflops = self.cfg.peak_tflops_per_device
            if not tflops:
                kind = ""
                try:
                    import jax

                    kind = jax.local_devices()[0].device_kind.lower()
                except Exception:
                    pass
                tflops = next(
                    (peak for sub, peak in _DEVICE_PEAK_TFLOPS if sub in kind),
                    _FALLBACK_PEAK_TFLOPS,
                )
            self._peak_flops_per_device = tflops * 1e12
        return self._peak_flops_per_device

    # ------------------------------------------------------------------
    def start_capture(self, logdir: str):
        """Start a ``jax.profiler`` capture into ``logdir``: the ONE place
        a capture starts (``maybe_capture``'s window, ``TpuEngine.
        start_profile``, the serving loop's tick-indexed window). Host
        tracing is on and Python-frame tracing off, or the program's
        ``dstpu:`` spans (``spans.host_span``) would not be recorded and
        the host loop under the profiler would slow down. Works on a
        disabled hub (then only the JSONL event is skipped).

        Clock sync: as the capture starts, one zero-length annotation
        ``dstpu:clock_sync monotonic_ns=<n>`` goes into the xplane and a
        ``profile_window`` JSONL event carries the same reading, so a reader
        holding both files can place every JSONL span (``time.monotonic``
        seconds) on the xplane's axis (``ds_trace_timeline --xplane``)."""
        import jax.profiler

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=options)
        self._profiling = True
        now_ns = time.monotonic_ns()
        with jax.profiler.TraceAnnotation(f"{CLOCK_SYNC_PREFIX}{now_ns}"):
            pass
        self.emit("profile_window", {"event": "start", "monotonic_ns": now_ns,
                                     "logdir": os.path.abspath(logdir)})

    def stop_capture(self):
        """Stop the capture ``start_capture`` began (no-op without one)."""
        if not self._profiling:
            return
        import jax.profiler

        self._profiling = False
        jax.profiler.stop_trace()
        self.emit("profile_window", {"event": "stop",
                                     "monotonic_ns": time.monotonic_ns()})

    def maybe_capture(self, step: int):
        """Drive the configured jax.profiler window: start when ``step``
        reaches ``profile_start_step``, stop ``profile_num_steps`` later.
        Failures never propagate into the training loop."""
        cfg = self.cfg
        if not self.enabled or cfg.profile_start_step <= 0:
            return
        try:
            if not self._profiling and step == cfg.profile_start_step:
                self.start_capture(cfg.profile_dir or os.path.join(
                    os.path.dirname(os.path.abspath(cfg.trace_file or ".")),
                    "xla_trace",
                ))
            elif self._profiling and step >= cfg.profile_start_step + cfg.profile_num_steps:
                self.stop_capture()
        except Exception as e:
            logger.warning(f"telemetry profiler capture failed: {e}")
            self._profiling = False

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        """Aggregated view of everything emitted so far (counters, gauges,
        per-field histogram percentiles)."""
        return {
            "schema": SCHEMA_VERSION,
            "role": self.role,
            "metrics": self.registry.dump(),
        }

    def dump_summary(self, path: str) -> dict:
        s = self.summary()
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(s, fh, indent=2, sort_keys=True)
        return s

    def close(self):
        try:
            self.stop_capture()
        except Exception:
            self._profiling = False
        if self._writer is not None:
            self._writer.close()
