"""``decode_rows`` (ops/pallas/decode_attention.py), interpreted: each row's
one-token attention over the stacked pool in place, to ITS length, against
the einsum reference and against ``softmax_context``'s XLA path over the
same pool."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.decode_attention import decode_rows, decode_rows_reference
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.ops.transformer.inference_ops import softmax_context

BLOCK = kv_cache.BLOCK
LAYERS, LAYER, SCALE = 2, 1, 0.3

# name: (heads, width, allocation, read bucket, lengths)
CASES = {
    "ragged": (4, 16, 512, 384, [5, 200, 384, 77, 130]),
    "length0_among_live": (4, 16, 256, 256, [0, 9, 0, 256]),
    "length_is_size": (4, 16, 256, 256, [256, 256]),
    "at_a_block_edge": (4, 16, 384, 384, [BLOCK, 2 * BLOCK, 3 * BLOCK]),
    "one_past_a_block_edge": (4, 16, 384, 384, [BLOCK + 1, 2 * BLOCK + 1, 1]),
    "one_short_of_a_block_edge": (4, 16, 384, 384, [BLOCK - 1, 2 * BLOCK - 1]),
    "all_rows_parked": (4, 16, 256, 256, [0, 0, 0]),
    "one_live_row_among_many": (4, 16, 256, 256, [0] * 7 + [131] + [0] * 8),
    "last_row_alone": (4, 16, 256, 256, [0, 0, 0, 3]),
    "read_below_the_allocation": (4, 16, 1024, 256, [256, 1, 140]),
    "gpt2_xl_heads_25x64": (25, 64, 256, 256, [0, 200, 129]),
    "gpt2_medium_heads_16x64": (16, 64, 256, 256, [17, 0, 256]),
}


def _pools(heads, width, alloc, rows, dtype, seed=0):
    """q (B, H, x) and the stacked pools as the model keeps them, (L, B, T, H, x)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (rows, heads, width), dtype) * 2
    k, v = (jax.random.normal(key, (LAYERS, rows, alloc, heads, width), dtype) for key in ks[1:])
    return q, k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_rows_attend_to_their_own_lengths(case, dtype):
    """Against the einsum over the whole window: every live row's output, a
    row of length 0 zeros, nothing NaN, at both dtypes."""
    heads, width, alloc, size, lengths = CASES[case]
    q, k, v = _pools(heads, width, alloc, len(lengths), dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    args = (q, kv_cache.time_minor(k), kv_cache.time_minor(v), LAYER, lengths)
    got = np.asarray(decode_rows(*args, size=size, block=BLOCK, sm_scale=SCALE), np.float32)
    want = np.asarray(decode_rows_reference(*args, size=size, sm_scale=SCALE), np.float32)
    assert got.shape == q.shape and not np.isnan(got).any()
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    parked = np.asarray(lengths) == 0
    assert not got[parked].any()
    assert parked.all() or np.abs(got[~parked]).max() > 0.05


@pytest.mark.parametrize("case", ["ragged", "one_past_a_block_edge", "gpt2_xl_heads_25x64"])
def test_equals_the_xla_path_over_the_same_pool(case):
    """``softmax_context`` under the byte threshold (these pools are toys)
    contracts every row's whole window; the kernel over the same stacked
    pool hands out the same numbers for the rows that are not parked."""
    heads, width, alloc, size, lengths = CASES[case]
    q, k, v = _pools(heads, width, alloc, len(lengths), jnp.float32, seed=3)
    lengths = np.asarray(lengths, np.int32)
    pos = jnp.asarray(np.where(lengths > 0, lengths - 1, alloc), jnp.int32)   # parked: the pool's length
    want = softmax_context(q[:, None], k, v, pos, scale=SCALE, positions=pos[:, None],
                           read_len=size, layer=LAYER)[:, 0]
    got = decode_rows(q, kv_cache.time_minor(k), kv_cache.time_minor(v), LAYER,
                      jnp.where(pos < alloc, pos + 1, 0), size=size, block=BLOCK, sm_scale=SCALE)
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live], atol=2e-5, rtol=2e-5)


def test_masked_tail_and_other_layers_are_not_read():
    """NaNs planted past each row's length, in a parked row and in the other
    layer leave the output as it was: what the kernel does not need it does
    not touch (the XLA path multiplies the tail by an exact zero)."""
    heads, width, alloc, size = 4, 16, 384, 384
    lengths = jnp.asarray([130, 0, 384, 256], jnp.int32)
    q, k, v = _pools(heads, width, alloc, 4, jnp.float32, seed=5)
    run = lambda k, v: decode_rows(q, kv_cache.time_minor(k), kv_cache.time_minor(v), LAYER, lengths,
                                   size=size, block=BLOCK, sm_scale=SCALE)
    want = run(k, v)
    beyond = (jnp.arange(alloc)[None, :] >= -(-lengths[:, None] // BLOCK) * BLOCK)[None, :, :, None, None]
    other = (jnp.arange(LAYERS) != LAYER)[:, None, None, None, None]
    poison = lambda a: jnp.where(beyond | other, jnp.nan, a)
    np.testing.assert_array_equal(np.asarray(run(poison(k), poison(v))), np.asarray(want))
