"""The flash kernel for one prefill chunk against a cached row: query and
key widths that differ from the value width, a query offset and a first
valid key that are traced scalars, the sink in the running softmax, the
window band (Pallas interpret mode)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_chunk


def plain(q, k, v, q_off, k_min, sink, window):
    W, H, dk = q.shape
    Hkv, T, dv = v.shape
    kk, vv = jnp.repeat(k, H // Hkv, axis=0), jnp.repeat(v, H // Hkv, axis=0)
    s = jnp.einsum("whd,htd->hwt", q, kk) / math.sqrt(dk)
    qpos, kpos = q_off + jnp.arange(W)[:, None], jnp.arange(T)[None, :]
    ok = (kpos <= qpos) & (kpos >= k_min)
    if window:
        ok &= qpos - kpos < window
    s = jnp.where(ok[None], s, -1e30)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(sink[:, None, None], (H, W, 1))], -1)
    p = jax.nn.softmax(s, -1)[..., :T]
    return jnp.einsum("hwt,htd->whd", jnp.where(ok[None], p, 0), vv)


CASES = {  # W, T, H, Hkv, dk, dv, q_off, k_min, window, sink
    "full-deep": (128, 1024, 8, 2, 24, 16, 300, 0, None, False),
    "full-first-chunk": (128, 512, 8, 2, 24, 16, 0, 0, None, False),
    "full-sink": (64, 256, 4, 1, 24, 16, 100, 0, None, True),
    "window-sink": (128, 256, 8, 4, 24, 16, 128, 40, 128, True),
    "window-nothing-before": (64, 192, 4, 4, 24, 16, 128, 128, 128, True),
    "window-no-sink": (256, 384, 4, 2, 24, 16, 128, 0, 128, False),
    "wide-as-published": (128, 640, 4, 2, 192, 128, 512, 0, None, True),
}


@pytest.mark.parametrize("case", CASES)
def test_chunk_kernel_matches_plain_attention(case):
    W, T, H, Hkv, dk, dv, q_off, k_min, window, has_sink = CASES[case]
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(W, H, dk), jnp.float32)
    k = jnp.asarray(rs.randn(Hkv, T, dk), jnp.float32)
    v = jnp.asarray(rs.randn(Hkv, T, dv), jnp.float32)
    sink = jnp.asarray(rs.randn(H), jnp.float32) if has_sink else None
    got = jax.jit(lambda a, b: flash_attention_chunk(q, k, v, a, b, sink, window))(
        jnp.int32(q_off), jnp.int32(k_min))  # traced scalars, as the tick passes them
    assert got.shape == (W, H, dv)
    assert np.allclose(got, plain(q, k, v, q_off, k_min, sink, window), atol=2e-5)


def test_the_sink_takes_weight_and_gives_no_value():
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(64, 2, 24), jnp.float32)
    k = jnp.asarray(rs.randn(2, 64, 24), jnp.float32)
    v = jnp.ones((2, 64, 16), jnp.float32)
    without = flash_attention_chunk(q, k, v, 0)
    with_sink = flash_attention_chunk(q, k, v, 0, sink=jnp.full((2,), 2.0))
    assert np.allclose(without, 1.0, atol=1e-5)      # the weights sum to one
    assert (np.asarray(with_sink) < 0.99).all()      # ... and to less beside the sink
