"""Wall-clock and throughput timers.

TPU-native equivalent of the reference's ``deepspeed/utils/timer.py``
(``SynchronizedWallClockTimer`` ~ timers that block on device work via
``jax.block_until_ready`` instead of cuda events; ``ThroughputTimer`` keeps the
same samples/sec + TFLOPs accounting the engine logs each ``steps_per_print``).
"""

import time

from deepspeed_tpu.utils.logging import logger

try:
    import psutil

    _PSUTIL = True
except Exception:  # pragma: no cover
    _PSUTIL = False


def _sync():
    """Block until previously dispatched device work completes (cuda-event
    analogue): execute a trivial program on the local devices — queued FIFO
    after outstanding work — and fetch the result to host. A bare
    block_until_ready on a fresh transfer would not drain compute."""
    import jax
    import jax.numpy as jnp

    try:
        float(jax.jit(lambda: jnp.zeros(()))())
    except Exception:  # pragma: no cover
        pass


class _Timer:
    def __init__(self, name: str, synchronize: bool = False):
        self.name = name
        self.synchronize = synchronize
        self.started = False
        self._start = 0.0
        self._elapsed = 0.0
        self.count = 0

    def start(self):
        if self.started:
            return
        if self.synchronize:
            _sync()
        self._start = time.time()
        self.started = True

    def stop(self, record: bool = True):
        if not self.started:
            return
        if self.synchronize:
            _sync()
        if record:
            self._elapsed += time.time() - self._start
            self.count += 1
        self.started = False

    def reset(self):
        self.started = False
        self._elapsed = 0.0
        self.count = 0

    def elapsed(self, reset: bool = True) -> float:
        """Total recorded seconds; optionally reset."""
        if self.started:
            self.stop()
            self.start()
        value = self._elapsed
        if reset:
            self._elapsed = 0.0
            self.count = 0
        return value

    def mean(self) -> float:
        return self._elapsed / max(self.count, 1)


class SynchronizedWallClockTimer:
    """A registry of named timers; ``log`` prints ms per name."""

    def __init__(self, synchronize: bool = True):
        self.timers = {}
        self.synchronize = synchronize

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name, synchronize=self.synchronize)
        return self.timers[name]

    def has(self, name: str) -> bool:
        return name in self.timers

    def log(self, names=None, normalizer: float = 1.0, reset: bool = True, memory_breakdown=False):
        assert normalizer > 0.0
        names = names if names is not None else list(self.timers)
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}")
        if parts:
            logger.info("time (ms) | " + " | ".join(parts))

    def get_mean(self, names, normalizer: float = 1.0, reset: bool = True):
        out = {}
        for name in names:
            if name in self.timers:
                t = self.timers[name]
                out[name] = (t._elapsed / max(t.count, 1)) * 1000.0 / normalizer
                if reset:
                    t.reset()
        return out


class ThroughputTimer:
    """Samples/sec (+ optional TFLOPs) over training steps, skipping warmup."""

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: int = 50, monitor_memory: bool = False,
                 synchronize: bool = False):
        self.batch_size = max(batch_size, 1)
        self.start_step = start_step
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory and _PSUTIL
        # sync at span edges so durations measure device compute, not async
        # dispatch (engine wires telemetry.sync_timers here); off by default
        # because the drain itself costs a host round-trip per micro step
        self.synchronize = synchronize
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.step_elapsed_time = 0.0
        self.last_duration = 0.0  # most recent start..stop span (telemetry)
        self._started = False
        self._start_time = 0.0

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def start(self):
        self._started = True
        if self.synchronize:
            _sync()
        self._start_time = time.time()

    def stop(self, global_step: bool, report_speed: bool = True):
        if not self._started:
            return
        self._started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
        if self.synchronize:
            _sync()
        duration = time.time() - self._start_time
        self.last_duration = duration
        if self.global_step_count >= self.start_step:
            self.total_elapsed_time += duration
            self.step_elapsed_time += duration
            if global_step and report_speed and self.global_step_count % self.steps_per_output == 0:
                logger.info(
                    f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                    f"global_step={self.global_step_count}, "
                    f"RunningAvgSamplesPerSec={self.avg_samples_per_sec():.2f}, "
                    f"CurrSamplesPerSec={self.batch_size * self.steps_per_output / self.step_elapsed_time:.2f}"
                )
                self.step_elapsed_time = 0.0

    def avg_samples_per_sec(self) -> float:
        if self.global_step_count > self.start_step and self.total_elapsed_time > 0:
            steps = self.global_step_count - self.start_step
            return self.batch_size * steps / self.total_elapsed_time
        return 0.0

    def last_samples_per_sec(self) -> float:
        """Instantaneous samples/sec of the most recent span — the
        telemetry step events report this next to the running average."""
        if self.last_duration > 0:
            return self.batch_size / self.last_duration
        return 0.0


class EngineTimers:
    """Forward/backward/step micro + global timers, mirroring the reference
    engine's ``wall_clock_breakdown`` accounting (engine.py:148)."""

    FORWARD = "fwd"
    BACKWARD = "bwd"
    BACKWARD_INNER = "bwd_inner"
    BACKWARD_REDUCE = "bwd_allreduce"
    STEP = "step"

    def __init__(self, enable: bool):
        self.enabled = enable
        self.timers = SynchronizedWallClockTimer(synchronize=enable)

    def __call__(self, name):
        return self.timers(name)

    def log(self, normalizer: float = 1.0):
        if self.enabled:
            self.timers.log(
                [self.FORWARD, self.BACKWARD, self.BACKWARD_INNER, self.BACKWARD_REDUCE, self.STEP],
                normalizer=normalizer,
            )
