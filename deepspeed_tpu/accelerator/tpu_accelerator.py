"""TPU (and CPU-simulated-TPU) implementation of the accelerator seam.

Counterpart of the reference's ``accelerator/cuda_accelerator.py`` — but backed
by ``jax.devices()`` / XLA memory stats / ``jax.profiler`` ranges instead of
torch.cuda streams and events.
"""


import jax

from deepspeed_tpu.accelerator.abstract_accelerator import Accelerator


class TpuAccelerator(Accelerator):
    _name = "tpu"

    def __init__(self):
        self._platform = jax.default_backend()

    # --- identity -------------------------------------------------------
    def device_name(self, device_index=None) -> str:
        devices = jax.devices()
        if device_index is None:
            return self._platform
        return str(devices[device_index])

    def is_available(self) -> bool:
        return len(jax.devices()) > 0

    def device_count(self) -> int:
        return jax.device_count()

    def local_device_count(self) -> int:
        return jax.local_device_count()

    def current_device(self):
        return jax.local_devices()[0]

    def current_device_name(self) -> str:
        return str(jax.local_devices()[0])

    def communication_backend_name(self) -> str:
        return "xla"

    def on_accelerator(self, array) -> bool:
        try:
            return any(d.platform != "cpu" for d in array.devices())
        except Exception:
            return False

    # --- memory ---------------------------------------------------------
    def memory_stats(self, device_index=None) -> dict:
        dev = jax.local_devices()[device_index or 0]
        stats = dev.memory_stats()
        return dict(stats) if stats else {}

    def memory_allocated(self, device_index=None) -> int:
        return self.memory_stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index=None) -> int:
        stats = self.memory_stats(device_index)
        return stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))

    def total_memory(self, device_index=None) -> int:
        return self.memory_stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index=None) -> int:
        stats = self.memory_stats(device_index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def reset_peak_memory_stats(self, device_index=None):
        return None  # XLA does not expose a reset; parity no-op

    # --- dtype / capability --------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        # fp16 compute is emulated on TPU MXU (bf16-native); supported for parity
        return True

    def supported_dtypes(self):
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    # --- RNG ------------------------------------------------------------
    def default_rng(self, seed: int):
        return jax.random.PRNGKey(seed)

    def manual_seed(self, seed: int):
        return jax.random.PRNGKey(seed)

    # --- profiler ranges (nvtx push/pop semantics: LIFO stack) ----------
    # one annotation helper for the tree: telemetry.spans.host_span, so a
    # user's ranges land in the xplane beside the program's own as
    # ``dstpu:<msg>`` and cost a flag test when no capture is on
    def range_push(self, msg: str):
        from deepspeed_tpu.telemetry.spans import host_span

        if not hasattr(self, "_range_stack"):
            self._range_stack = []
        annotation = host_span(msg)
        annotation.__enter__()
        self._range_stack.append(annotation)

    def range_pop(self):
        stack = getattr(self, "_range_stack", [])
        if stack:
            stack.pop().__exit__(None, None, None)

    def range(self, msg: str):
        from deepspeed_tpu.telemetry.spans import host_span

        return host_span(msg)

    # --- op builder dispatch -------------------------------------------
    def create_op_builder(self, op_name: str):
        builder_cls = self.get_op_builder(op_name)
        return builder_cls() if builder_cls is not None else None

    def get_op_builder(self, op_name: str):
        from deepspeed_tpu.ops.op_builder import ALL_OPS

        return ALL_OPS.get(op_name)
