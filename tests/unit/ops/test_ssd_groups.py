"""Mamba-2's scan with GROUPS of ``B`` and ``C`` (``ssm_groups`` > 1: head i
reads group i // (heads / groups)): the chunked scan and the rows' step, both
kernels interpreted here, against the recurrence that defines them, with 1, 2
and 8 groups; and a grouped call that is told every group's ``B`` and ``C``
are the same is the one-group call. (``test_ssd.py`` holds the one-group
cases; both kernels compiled for a described v5e at eight groups:
``tests/unit/ops/test_tpu_compile_plan.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import ssd


def inputs(T, H, P, N, G, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H)))
    A = -jnp.exp(-1.0 + 1.5 * jax.random.normal(ks[2], (H,)))
    return (x, dt, dt * A, jax.random.normal(ks[3], (T, G, N)), jax.random.normal(ks[4], (T, G, N)),
            jax.random.normal(ks[5], (H, P, N)))


def flat(v):
    return v.reshape(v.shape[0], -1)


# (heads, head width, groups): 16 heads of 64 are eight stored tiles; 8 heads of 16 fill ONE
# tile, which one group must own
SHAPES = [(16, 64, 1), (16, 64, 2), (16, 64, 8), (16, 16, 2), (8, 16, 1)]


@pytest.mark.parametrize("H,P,G", SHAPES, ids=lambda v: str(v))
@pytest.mark.parametrize("T,sub", [(96, 32), (50, 64)], ids=["whole-sub-chunks", "padded"])
def test_chunked_scan_with_groups_is_the_recurrence(H, P, G, T, sub):
    N = 32
    x, dt, a, B, C, s = inputs(T, H, P, N, G, seed=T + G)
    want_y, want_s = ssd.ssd_recurrence(x, dt, a, B, C, s)
    g = ssd.heads_per_tile(P, H)
    pool = jnp.full((2, 3, H // g, N, g * P), 7.0).at[1, 2].set(ssd.to_pool(s, g))
    y, new = jax.jit(lambda *v: ssd.ssd_chunk_pool(*v, sub=sub))(
        pool, 1, 2, (dt[..., None] * x).reshape(T, H * P), a, flat(B), flat(C))
    scale = float(jnp.abs(want_y).max())
    assert scale > 1.0
    assert np.allclose(y.reshape(T, H, P), want_y, atol=2e-5 * scale)
    assert np.allclose(ssd.from_pool(new[1, 2], g), want_s, atol=2e-5 * scale)
    untouched = jnp.ones(new.shape[:2], bool).at[1, 2].set(False)
    assert np.array_equal(new[untouched], pool[untouched])


@pytest.mark.parametrize("H,P,G", SHAPES, ids=lambda v: str(v))
def test_the_rows_step_with_groups_is_one_token_of_the_recurrence(H, P, G):
    R, N = 4, 32
    x, dt, a, B, C, _ = inputs(R, H, P, N, G, seed=7 + G)
    dt, a = dt.at[2].set(0.0), a.at[2].set(0.0)          # row 2 is parked
    g = ssd.heads_per_tile(P, H)
    states = jax.random.normal(jax.random.PRNGKey(8), (3, R, H, P, N))
    pool = ssd.to_pool(states, g)
    y, new = jax.jit(ssd.ssd_step_pool)(pool, 1, (dt[..., None] * x).reshape(R, H * P), a,
                                        flat(B), flat(C))
    assert np.array_equal(new[0], pool[0]) and np.array_equal(new[2], pool[2])   # in place
    for row in range(R):
        want_y, want_s = ssd.ssd_recurrence(x[row:row + 1], dt[row:row + 1], a[row:row + 1],
                                            B[row:row + 1], C[row:row + 1], states[1, row])
        assert np.allclose(y[row].reshape(H, P), want_y[0], atol=1e-5)
        assert np.allclose(ssd.from_pool(new[1, row], g), want_s, atol=1e-5)
    assert np.array_equal(new[1, 2], pool[1, 2])


def test_groups_that_agree_are_one_group_and_groups_that_differ_are_not():
    T, H, P, N = 40, 16, 64, 32
    x, dt, a, B, C, s = inputs(T, H, P, N, 1, seed=3)
    one, _ = ssd.ssd_recurrence(x, dt, a, B, C, s)
    same, _ = ssd.ssd_recurrence(x, dt, a, jnp.repeat(B, 4, axis=1), jnp.repeat(C, 4, axis=1), s)
    flat2, _ = ssd.ssd_recurrence(x, dt, a, B[:, 0], C[:, 0], s)           # (T, N): one group
    assert np.allclose(same, one, atol=1e-5) and np.allclose(flat2, one, atol=1e-6)
    _, _, _, B4, C4, _ = inputs(T, H, P, N, 4, seed=3)
    other, _ = ssd.ssd_recurrence(x, dt, a, B4, C4, s)
    # head 0 reads group 0 on both sides only if group 0 is the same draw: it is not
    assert np.abs(other - one).max() > 1.0
    # head i reads group i // 4: the last group's B and C changed, the first twelve heads' output not
    moved, _ = ssd.ssd_recurrence(x, dt, a, B4.at[:, 3].mul(2.0), C4, s)
    assert np.array_equal(moved[:, :12], other[:, :12]) and np.abs(moved[:, 12:] - other[:, 12:]).max() > 0.1
