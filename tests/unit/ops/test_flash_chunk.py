"""The flash kernel for one prefill chunk against a cached row: query and
key widths that differ from the value width, a query offset and a first
valid key that are traced scalars (or a Python int offset: the static walk of
the window layers), the query heads of a key-value head walked inside one
grid step, the sink in the running softmax, the window band (Pallas
interpret mode); and the host's view of that walk."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import chunk_tiles, chunk_walk, flash_attention_chunk


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


CASES = {  # W, T, H, Hkv, dk, dv, q_off, k_min, window, sink[, q_off a Python int]
    "full-deep": (128, 1024, 8, 2, 24, 16, 300, 0, None, False),
    "full-first-chunk": (128, 512, 8, 2, 24, 16, 0, 0, None, False),
    "full-sink": (64, 256, 4, 1, 24, 16, 100, 0, None, True),
    "window-sink": (128, 256, 8, 4, 24, 16, 128, 40, 128, True),
    "window-nothing-before": (64, 192, 4, 4, 24, 16, 128, 128, 128, True),
    "window-no-sink": (256, 384, 4, 2, 24, 16, 128, 0, 128, False),
    "wide-as-published": (128, 640, 4, 2, 192, 128, 512, 0, None, True),
    "group16-sink-odd-offset": (128, 1024, 16, 1, 192, 128, 333, 0, None, True),
    "group8-window-static": (256, 384, 8, 1, 24, 16, 128, 40, 128, True, True),
    "group8-window-static-nothing-before": (256, 384, 8, 1, 24, 16, 128, 128, 128, True, True),
    "group8-window-static-all-before": (256, 384, 16, 2, 24, 16, 128, 0, 128, False, True),
    "static-no-window-cut": (256, 640, 4, 2, 24, 16, 384, 200, None, True, True),
    "static-window-wider-than-a-tile": (128, 384, 4, 2, 24, 16, 256, 130, 200, False, True),
    "group1-at-256": (128, 512, 2, 2, 256, 256, 200, 0, None, False),
    "chunk-256": (256, 1024, 4, 2, 24, 16, 600, 0, None, True),
    "chunk-1024": (1024, 2048, 4, 2, 24, 16, 700, 0, None, False),
    "chunk-1024-window-static": (1024, 1152, 2, 1, 24, 16, 128, 77, 128, True, True),
    "most-key-blocks-skipped": (128, 4096, 4, 2, 24, 16, 3000, 0, 200, True),
}


@pytest.mark.parametrize("case", CASES)
def test_chunk_kernel_matches_plain_attention(case):
    W, T, H, Hkv, dk, dv, q_off, k_min, window, has_sink, *static = CASES[case]
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(W, H, dk), jnp.float32)
    k = jnp.asarray(rs.randn(Hkv, T, dk), jnp.float32)
    v = jnp.asarray(rs.randn(Hkv, T, dv), jnp.float32)
    sink = jnp.asarray(rs.randn(H), jnp.float32) if has_sink else None
    if static:  # as a window layer of the tick passes them: the offset an int, the first key traced
        got = jax.jit(lambda b: flash_attention_chunk(q, k, v, q_off, b, sink, window))(jnp.int32(k_min))
    else:       # traced scalars, as a full layer of the tick passes them
        got = jax.jit(lambda a, b: flash_attention_chunk(q, k, v, a, b, sink, window))(
            jnp.int32(q_off), jnp.int32(k_min))
    assert got.shape == (W, H, dv)
    assert np.allclose(got, plain(q, k, v, q_off, k_min, sink, window), atol=2e-5)


@pytest.mark.parametrize("static", [False, True], ids=["traced-offset", "static-offset"])
def test_the_host_walk_visits_the_tiles_that_hold_a_pair_and_masks_those_an_edge_crosses(static):
    W, group, dk, dv = 256, 4, 24, 16
    for T, q_off, k_min, window in [(T, q_off, k_min, window)
                                    for window in (None, 128, 200)
                                    for T, q_off in ((384, 128), (640, 384), (1024, 300 if not static else 256),
                                                     (1024, 0), (1536, 1280))
                                    for k_min in (0, 40, 128, q_off)]:
        if k_min > q_off:
            continue
        qpos, kpos = q_off + np.arange(W)[:, None], np.arange(T)[None, :]
        ok = (kpos <= qpos) & (kpos >= k_min)
        if window:
            ok &= qpos - kpos < window
        tiles, fetched = chunk_walk(W, T, group, dk, dv, q_off, k_min, window, static)
        seen = np.zeros_like(ok)
        for q0, k0, rows, cols, masked in tiles:
            part = ok[q0:q0 + rows, k0:k0 + cols]
            assert part.any(), (T, q_off, k_min, window, q0, k0)          # no tile wholly masked
            assert masked == (not part.all()), (T, q_off, k_min, window, q0, k0)  # a mask iff an edge crosses
            assert not seen[q0:q0 + rows, k0:k0 + cols].any()
            seen[q0:q0 + rows, k0:k0 + cols] = True
        assert not (ok & ~seen).any(), (T, q_off, k_min, window)           # every pair in a visited tile
        # a key-value head's keys once, whole, where the walk is static; else a key block once a query block
        assert fetched == (T // 128 if static else max(len(tiles), 1))
        assert chunk_tiles(W, 2 * group, 2, T, dk, dv, q_off, k_min, window, static) == (
            2 * group * len(tiles), 2 * group * sum(t[-1] for t in tiles), 2 * fetched)


def test_the_sink_takes_weight_and_gives_no_value():
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(64, 2, 24), jnp.float32)
    k = jnp.asarray(rs.randn(2, 64, 24), jnp.float32)
    v = jnp.ones((2, 64, 16), jnp.float32)
    without = flash_attention_chunk(q, k, v, 0)
    with_sink = flash_attention_chunk(q, k, v, 0, sink=jnp.full((2,), 2.0))
    assert np.allclose(without, 1.0, atol=1e-5)      # the weights sum to one
    assert (np.asarray(with_sink) < 0.99).all()      # ... and to less beside the sink
