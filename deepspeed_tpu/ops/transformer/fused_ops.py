"""Fused training-kernel surface (op registry target for 'transformer').

Reference: the csrc/transformer CUDA inventory — softmax_kernels.cu,
gelu_kernels.cu, normalize_kernels.cu, dropout_kernels.cu (SURVEY §2.4 #5).
Each maps to a jnp expression XLA fuses into its consumers.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer.transformer import (
    DeepSpeedTransformerConfig,
    DeepSpeedTransformerLayer,
    init_transformer_layer,
    transformer_layer_fwd,
)


def fused_softmax(scores, mask=None):
    """Masked softmax in fp32 accumulate (softmax_kernels.cu equivalent)."""
    if mask is not None:
        scores = scores + mask
    return jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(scores.dtype)


def fused_bias_gelu(x, bias):
    return jax.nn.gelu(x + bias, approximate=True)


def fused_bias_dropout_residual(x, bias, residual, ratio, rng):
    h = x + bias
    if ratio > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - ratio, h.shape)
        h = jnp.where(keep, h / (1.0 - ratio), 0.0).astype(h.dtype)
    return residual + h


__all__ = [
    "DeepSpeedTransformerConfig",
    "DeepSpeedTransformerLayer",
    "init_transformer_layer",
    "transformer_layer_fwd",
    "fused_softmax",
    "fused_bias_gelu",
    "fused_bias_dropout_residual",
]
