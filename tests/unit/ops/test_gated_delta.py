"""The gated delta rule: the chunked scan (batched prep + the ``gdn_chunk_fwd``
kernel, interpreted here) and the rows' step (the ``gdn_step`` kernel)
against the recurrence, token by token, that defines them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import gated_delta as gd


def inputs(T, H=3, dk=16, dv=8, seed=0, g_centre=-1.0, g_spread=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (T, H, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -jnp.exp(g_centre + g_spread * jax.random.normal(ks[3], (T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dk, dv))


@pytest.mark.parametrize("T,sub", [(128, 64), (200, 64), (70, 16), (37, 64), (256, 128)],
                         ids=["divides", "does-not-divide", "small-sub", "shorter-than-sub", "sub128"])
@pytest.mark.parametrize("decay", ["mixed", "near-one", "near-zero"])
def test_chunked_scan_is_the_recurrence(T, sub, decay):
    centre = {"mixed": -1.0, "near-one": -9.0, "near-zero": 2.5}[decay]   # g ~ -0.4 / -1e-4 / -12
    args = inputs(T, seed=T, g_centre=centre, g_spread=0.5 if decay != "mixed" else 1.5)
    want_o, want_s = gd.gdn_recurrence(*args)
    got_o, got_s = jax.jit(lambda *a: gd.gdn_chunk(*a, sub=sub))(*args)
    assert np.allclose(got_o, want_o, atol=2e-4) and np.allclose(got_s, want_s, atol=2e-4)
    assert float(jnp.abs(want_o).max()) > 0.05          # ... of outputs that are not nothing


def test_a_repeated_key_at_full_strength_does_not_blow_the_inverse_up():
    q, k, v, g, beta, s = inputs(128, seed=5, g_centre=-9.0, g_spread=0.1)
    k = jnp.broadcast_to(k[:1], k.shape)                 # every token the same key
    beta = jnp.full_like(beta, 0.999)
    want_o, want_s = gd.gdn_recurrence(q, k, v, g, beta, s)
    got_o, got_s = gd.gdn_chunk(q, k, v, g, beta, s)
    assert np.allclose(got_o, want_o, atol=1e-3) and np.allclose(got_s, want_s, atol=1e-3)


def test_tokens_with_no_decay_and_no_strength_leave_the_state_as_it_was():
    q, k, v, g, beta, s = inputs(48, seed=6)
    held = jnp.arange(48) >= 29                          # a chunk's pads
    g, beta = jnp.where(held[:, None], 0.0, g), jnp.where(held[:, None], 0.0, beta)
    _, short = gd.gdn_recurrence(q[:29], k[:29], v[:29], g[:29], beta[:29], s)
    _, padded = gd.gdn_chunk(q, k, v, g, beta, s, sub=16)
    assert np.allclose(padded, short, atol=1e-5)


def test_the_rows_step_is_one_token_of_the_recurrence():
    B, H, dk, dv = 5, 4, 16, 8
    q, k, v, g, beta, _ = inputs(B, H, dk, dv, seed=7)
    g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)      # row 2 is parked
    pool = jax.random.normal(jax.random.PRNGKey(8), (3, B, H, dk, dv))
    o, new = jax.jit(gd.gdn_step_pool)(pool, 1, q, k, v, g, beta)
    assert np.array_equal(new[0], pool[0]) and np.array_equal(new[2], pool[2])   # in place
    new = new[1]
    for row in range(B):
        want_o, want_s = gd.gdn_recurrence(q[row:row + 1], k[row:row + 1], v[row:row + 1],
                                           g[row:row + 1], beta[row:row + 1], pool[1, row])
        assert np.allclose(o[row], want_o[0], atol=1e-5) and np.allclose(new[row], want_s, atol=1e-5)
    assert np.array_equal(new[2], pool[1, 2])            # exactly: nothing masks it later
