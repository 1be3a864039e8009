"""Op registry (reference: ``op_builder/`` JIT-build layer, builder.py:99).

CUDA ops need nvcc JIT compilation and compatibility probing; TPU ops are
either XLA-fused jnp code (always available) or Pallas kernels (available when
a TPU backend is present). The builder surface survives so ``ds_report``-style
tooling and the accelerator's op dispatch keep working, but ``load()`` returns
a python module of jitted callables instead of a compiled extension.
"""

import importlib

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret
from deepspeed_tpu.utils.logging import logger


class OpBuilder:
    NAME = "base"
    MODULE = None  # dotted path of the python module exposing the op API

    def is_compatible(self, verbose: bool = False) -> bool:
        return True

    def load(self):
        assert self.MODULE, f"{self.NAME} has no module mapping"
        return importlib.import_module(self.MODULE)

    def builder_available(self) -> bool:
        try:
            self.load()
            return True
        except Exception as e:
            logger.warning(f"op {self.NAME} unavailable: {e}")
            return False


class PallasOpBuilder(OpBuilder):
    """Ops backed by Pallas TPU kernels; compatible on TPU backends and on CPU
    via the Pallas interpreter (used by the unit tests)."""

    def is_compatible(self, verbose: bool = False) -> bool:
        return True

    def interpret_mode(self) -> bool:
        return resolve_interpret()


class FusedAdamBuilder(OpBuilder):
    NAME = "fused_adam"
    MODULE = "deepspeed_tpu.ops.adam.fused_adam"


class CPUAdamBuilder(OpBuilder):
    NAME = "cpu_adam"
    MODULE = "deepspeed_tpu.ops.adam.cpu_adam"


class FusedLambBuilder(OpBuilder):
    NAME = "fused_lamb"
    MODULE = "deepspeed_tpu.ops.lamb.fused_lamb"


class FlashAttentionBuilder(PallasOpBuilder):
    NAME = "flash_attention"
    MODULE = "deepspeed_tpu.ops.pallas.flash_attention"


class QuantizerBuilder(OpBuilder):
    NAME = "quantizer"
    MODULE = "deepspeed_tpu.ops.quantizer"


class TransformerBuilder(OpBuilder):
    NAME = "transformer"
    MODULE = "deepspeed_tpu.ops.transformer.fused_ops"


class InferenceBuilder(OpBuilder):
    NAME = "transformer_inference"
    MODULE = "deepspeed_tpu.ops.transformer.inference_ops"


class RandomLTDBuilder(OpBuilder):
    NAME = "random_ltd"
    MODULE = "deepspeed_tpu.ops.random_ltd"


class SparseAttnBuilder(PallasOpBuilder):
    NAME = "sparse_attn"
    MODULE = "deepspeed_tpu.ops.pallas.block_sparse_attention"


class AsyncIOBuilder(OpBuilder):
    NAME = "async_io"
    MODULE = "deepspeed_tpu.ops.aio"


class UtilsBuilder(OpBuilder):
    NAME = "utils"
    MODULE = "deepspeed_tpu.ops.flatten_utils"


ALL_OPS = {
    b.NAME: b
    for b in (
        FusedAdamBuilder,
        CPUAdamBuilder,
        FusedLambBuilder,
        FlashAttentionBuilder,
        QuantizerBuilder,
        TransformerBuilder,
        InferenceBuilder,
        RandomLTDBuilder,
        SparseAttnBuilder,
        AsyncIOBuilder,
        UtilsBuilder,
    )
}
