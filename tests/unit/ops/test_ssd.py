"""Mamba-2's state-space scan: the chunked scan (the ``ssd_chunk_fwd``
kernel, interpreted here) and the rows' step (the ``ssd_step`` kernel)
against the recurrence, token by token, that defines them, and the pool's
stored form and its inverse. (Both kernels compiled for a described v5e:
``tests/unit/ops/test_tpu_compile_plan.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import ssd


def inputs(T, H=4, P=16, N=32, seed=0, centre=-1.0, spread=1.0):
    """x, dt, a = dt A, B, C and a start state; ``centre`` places log(-A)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)))
    A = -jnp.exp(centre + spread * jax.random.normal(ks[2], (H,)))
    return (x, dt, dt * A, jax.random.normal(ks[3], (T, N)), jax.random.normal(ks[4], (T, N)),
            jax.random.normal(ks[5], (H, P, N)))


def chunk(x, dt, a, B, C, state, sub):
    """The chunk kernel on row 2 of layer 1 of a pool of its own; returns (y, state, pool, new pool)."""
    H, P, N = state.shape
    g = ssd.heads_per_tile(P, H)
    pool = jnp.full((2, 3, H // g, N, g * P), 7.0).at[1, 2].set(ssd.to_pool(state, g))
    T = x.shape[0]
    y, new = jax.jit(lambda *v: ssd.ssd_chunk_pool(*v, sub=sub))(
        pool, 1, 2, (dt[..., None] * x).reshape(T, H * P), a, B, C)
    return y.reshape(T, H, P), ssd.from_pool(new[1, 2], g), pool, new


@pytest.mark.parametrize("T,sub,H,P", [(128, 64, 4, 16), (200, 64, 8, 16), (70, 16, 8, 16),
                                       (37, 64, 4, 16), (300, 128, 4, 64)],
                         ids=["divides", "does-not-divide-8-heads-a-tile", "small-sub",
                              "shorter-than-sub", "two-heads-a-tile"])
@pytest.mark.parametrize("decay", ["mixed", "near-one", "near-zero"])
def test_chunked_scan_is_the_recurrence(T, sub, H, P, decay):
    centre = {"mixed": -1.0, "near-one": -9.0, "near-zero": 2.5}[decay]   # a ~ -0.4 / -1e-4 / -12
    x, dt, a, B, C, s = inputs(T, H, P, seed=T, centre=centre, spread=0.5 if decay != "mixed" else 1.5)
    want_y, want_s = ssd.ssd_recurrence(x, dt, a, B, C, s)
    got_y, got_s, pool, new = chunk(x, dt, a, B, C, s, sub)
    scale = float(jnp.abs(want_y).max())
    assert scale > 1.0                                   # ... of outputs that are not nothing
    assert np.allclose(got_y, want_y, atol=2e-5 * scale) and np.allclose(got_s, want_s, atol=2e-5 * scale)
    untouched = jnp.ones(new.shape[:2], bool).at[1, 2].set(False)
    assert np.array_equal(new[untouched], pool[untouched])   # in place: no other row, no other layer


def test_tokens_with_no_step_leave_the_state_bit_for_bit():
    x, dt, a, B, C, s = inputs(48, seed=6)
    held = jnp.arange(48) >= 29                          # a chunk's pads
    dt, a = jnp.where(held[:, None], 0.0, dt), jnp.where(held[:, None], 0.0, a)
    _, short, _, _ = chunk(x[:29], dt[:29], a[:29], B[:29], C[:29], s, 16)
    _, padded, _, _ = chunk(x, dt, a, B, C, s, 16)
    assert np.allclose(padded, short, atol=1e-5)
    _, same, _, _ = chunk(x, 0.0 * dt, 0.0 * a, B, C, s, 16)   # nothing but pads
    assert np.array_equal(same, s)


def test_the_rows_step_is_one_token_of_the_recurrence():
    R, H, P, N = 5, 8, 16, 32
    x, dt, a, B, C, _ = inputs(R, H, P, N, seed=7)
    dt, a = dt.at[2].set(0.0), a.at[2].set(0.0)          # row 2 is parked
    g = ssd.heads_per_tile(P, H)
    states = jax.random.normal(jax.random.PRNGKey(8), (3, R, H, P, N))
    pool = ssd.to_pool(states, g)
    y, new = jax.jit(ssd.ssd_step_pool)(pool, 1, (dt[..., None] * x).reshape(R, H * P), a, B, C)
    y = y.reshape(R, H, P)
    assert np.array_equal(new[0], pool[0]) and np.array_equal(new[2], pool[2])   # in place
    for row in range(R):
        want_y, want_s = ssd.ssd_recurrence(x[row:row + 1], dt[row:row + 1], a[row:row + 1],
                                            B[row:row + 1], C[row:row + 1], states[1, row])
        assert np.allclose(y[row], want_y[0], atol=1e-5)
        assert np.allclose(ssd.from_pool(new[1, row], g), want_s, atol=1e-5)
    assert np.array_equal(new[1, 2], pool[1, 2])         # exactly: nothing masks it later


@pytest.mark.parametrize("H,P,g", [(128, 64, 2), (8, 16, 8), (4, 16, 1), (6, 128, 1)])
def test_the_pool_stores_whole_lane_tiles_of_heads_transposed(H, P, g):
    assert ssd.heads_per_tile(P, H) == g
    s = jax.random.normal(jax.random.PRNGKey(1), (2, H, P, 8))
    stored = ssd.to_pool(s, g)
    assert stored.shape == (2, H // g, 8, g * P)
    assert np.array_equal(stored[1, 0, :, :P], s[1, 0].T)                # head 0, transposed
    assert np.array_equal(stored[1, 0, :, (g - 1) * P:], s[1, g - 1].T)  # ... and the tile's last beside it
    assert np.array_equal(ssd.from_pool(stored, g), s)
