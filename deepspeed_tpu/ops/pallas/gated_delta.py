"""The gated delta rule (Gated DeltaNet), for the serving tick.

A head keeps a state ``S`` of (key width, value width), float32. Token t,
with its key ``k_t`` and query ``q_t`` (L2-normalised by the caller, the
query scaled), value ``v_t``, decay ``alpha_t = exp(g_t)`` (``g_t <= 0``)
and write strength ``beta_t``:

    S' = alpha_t S;  delta = beta_t (v_t - S'^T k_t);  S = S' + k_t delta^T
    o_t = S^T q_t

That recurrence, token by token, is the definition (:func:`gdn_recurrence`:
the uncached forward and the tests use it). A token with ``g = 0`` and
``beta = 0`` leaves the state as it was: that is how a chunk's pad tokens
and a parked row are kept out, with no mask inside anything here.

**The rows' step** (:func:`gdn_step_pool`, the Mosaic kernel ``gdn_step``):
one token a row, every row's state read once and written once. ``o = S'^T q + (k . q) delta``, so one pass over
the state gives both contractions and a second writes ``alpha S + k
delta^T``.

**One row's prefill chunk** (:func:`gdn_chunk`): the W tokens in sub-chunks
of ``SUB``. With ``c_i`` the cumulative ``g`` inside a sub-chunk (only ever
used as differences ``c_i - c_j <= 0`` or as ``c_i`` itself, never as a
quotient of small numbers) and ``D_ij = exp(c_i - c_j)``:

    A = strictly_lower(diag(beta) (K K^T . D))
    [Wm | U] = (I + A)^-1 diag(beta) [K . exp(c) | V]        (no state in it)
    V' = U - Wm S
    O  = (Q . exp(c)) S + lower(Q K^T . D) V'
    S  = exp(c_last) S + (K . exp(c_last - c))^T V'

Everything above the line ``V' = ...`` is the same for every sub-chunk and
head and waits for no state: it is batched XLA (the inverse by products of
blocks, :func:`_unit_lower_inverse`). What does wait, four products a sub-chunk, is
the Mosaic kernel ``gdn_chunk_fwd``: a grid over value heads, the
sub-chunks walked in order, the head's state in VMEM scratch from the
first sub-chunk to the last.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
SUB = 64


def gdn_recurrence(q, k, v, g, beta, state):
    """The definition. q, k (T, H, dk); v (T, H, dv); g, beta (T, H);
    state (H, dk, dv). Returns (o (T, H, dv), state), float32."""
    def step(S, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        S = jnp.exp(g_t)[:, None, None] * S
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t, precision=HIGHEST))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    state, o = jax.lax.scan(step, state.astype(F32),
                            tuple(a.astype(F32) for a in (q, k, v, g, beta)))
    return o, state


def _step_kernel(layer_ref, s_ref, kq_ref, vab_ref, s_out_ref, o_ref, *, heads):
    del layer_ref  # read by the index maps
    for h in range(heads):
        S, kq, vab = s_ref[h], kq_ref[h], vab_ref[h]        # (dk, dv), (8, dk), (8, dv)
        v, alpha, beta, k_dot_q = vab[0:1], vab[1:2], vab[2:3], vab[3:4]
        both = alpha * jax.lax.dot(kq, S, precision=HIGHEST, preferred_element_type=F32)
        delta = beta * (v - both[0:1])                       # row 0 of kq is k, row 1 is q
        o_ref[h] = jnp.broadcast_to(both[1:2] + k_dot_q * delta, o_ref.shape[1:])
        rows = jax.lax.broadcasted_iota(jnp.int32, vab.shape, 0)
        outer = jax.lax.dot_general(kq, jnp.where(rows == 0, delta, 0.0), (((0,), (0,)), ((), ())),
                                    precision=HIGHEST, preferred_element_type=F32)
        s_out_ref[h] = alpha * S + outer


def gdn_step_pool(pool, layer, q, k, v, g, beta, *, heads: int = 8,
                  interpret: Optional[bool] = None):
    """One token a row on layer ``layer`` of the stacked pool (L, B, H, dk,
    dv), IN PLACE. q, k (B, H, dk); v (B, H, dv); g, beta (B, H). The Mosaic
    kernel ``gdn_step``, a grid step a row and
    ``heads`` heads, reads each state once and stores it once (the pool is
    aliased to the result and no other layer of it is touched). Returns (o
    (B, H, dv), pool)."""
    _, B, H, dk, dv = pool.shape
    heads = math.gcd(heads, H)
    q, k, v = (a.astype(F32) for a in (q, k, v))
    lanes = lambda a: jnp.broadcast_to(a.astype(F32)[..., None], (B, H, dv))
    kq = jnp.stack([k, q] + [jnp.zeros_like(k)] * 6, axis=2)                       # (B, H, 8, dk)
    vab = jnp.stack([v, lanes(jnp.exp(g.astype(F32))), lanes(beta), lanes((k * q).sum(-1))]
                    + [jnp.zeros_like(v)] * 4, axis=2)                             # (B, H, 8, dv)
    state = pl.BlockSpec((None, None, heads, dk, dv), lambda b, h, layer_ref: (layer_ref[0], b, h, 0, 0))
    row = lambda width: pl.BlockSpec((None, heads, 8, width), lambda b, h, layer_ref: (b, h, 0, 0))
    pool, o = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        name="gdn_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H // heads),
            in_specs=[state, row(dk), row(dv)],
            out_specs=[state, row(dv)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((B, H, 8, dv), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), pool, kq, vab)
    return o[:, :, 0], pool


def _chunk_kernel(wm_ref, u_ref, qg_ref, p_ref, kdt_ref, dec_ref, s0_ref, o_ref, s_out_ref,
                  s_scr, *, n_sub):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _load():
        s_scr[...] = s0_ref[...]

    dot = functools.partial(jax.lax.dot, precision=HIGHEST, preferred_element_type=F32)
    S = s_scr[...]
    vp = u_ref[...] - dot(wm_ref[...], S)
    o_ref[...] = (dot(qg_ref[...], S) + dot(p_ref[...], vp)).astype(o_ref.dtype)
    S = dec_ref[...] * S + dot(kdt_ref[...], vp)
    s_scr[...] = S

    @pl.when(i == n_sub - 1)
    def _store():
        s_out_ref[...] = S


def _unit_lower_inverse(A):
    """``(I + A)^-1`` for strictly lower-triangular ``A`` (..., C, C), C a
    power of two, by products alone (XLA's triangular solve inverts its
    diagonal blocks in a loop of C steps: 1.35 ms a layer and chunk on a v5e,
    a fifth of the cell's device time, PERF.md section 6, PR 34). Diagonal
    blocks of 16 by the finite series ``(I + N)(I + N^2)(I + N^4)(I + N^8)``,
    ``N = -A_block`` (``N^16 = 0``; with unit keys and ``beta <= 1`` its
    powers stay below ~6e3, where a series over all 64 would reach 1e17 and
    cancel to nothing in float32); above that by halves, ``[[X, 0], [L,
    Y]]^-1 = [[X^-1, 0], [-Y^-1 L X^-1, Y^-1]]``."""
    C = A.shape[-1]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    if C <= 16:
        inv, power = jnp.eye(C, dtype=A.dtype) - A, -A
        for _ in range(max(C.bit_length() - 2, 0)):              # N^2, N^4, ... N^(C/2)
            power = mm(power, power)
            inv = inv + mm(inv, power)
        return inv
    h = C // 2
    X, Y = _unit_lower_inverse(A[..., :h, :h]), _unit_lower_inverse(A[..., h:, h:])
    low = -mm(mm(Y, A[..., h:, :h]), X)
    return jnp.concatenate([jnp.concatenate([X, jnp.zeros_like(X)], axis=-1),
                            jnp.concatenate([low, Y], axis=-1)], axis=-2)


def gdn_chunk(q, k, v, g, beta, state, *, sub: int = SUB, interpret: Optional[bool] = None):
    """One row's W tokens from ``state``. q, k (W, H, dk); v (W, H, dv); g,
    beta (W, H) float32; state (H, dk, dv) float32. Returns (o (W, H, dv)
    float32, state). W is padded to whole sub-chunks with tokens that leave
    the state alone."""
    W, H, dk = q.shape
    dv = v.shape[-1]
    C = min(sub, max(8, 1 << (W - 1).bit_length()))       # a power of two: _unit_lower_inverse
    n = -(-W // C)
    pad = n * C - W
    heads = lambda a: jnp.pad(a.astype(F32), [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        (n, C) + a.shape[1:]).swapaxes(1, 2)              # (n, H, C, ...)
    q, k, v, g, beta = (heads(a) for a in (q, k, v, g, beta))
    c = jnp.cumsum(g, axis=-1)                            # (n, H, C)
    lower = jnp.tril(jnp.ones((C, C), bool))
    D = jnp.exp(jnp.where(lower, c[..., :, None] - c[..., None, :], -jnp.inf))
    kk = jnp.einsum("nhik,nhjk->nhij", k, k, precision=HIGHEST)
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1), beta[..., None] * kk * D, 0.0)
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(c)[..., None], v], axis=-1)
    solved = jnp.matmul(_unit_lower_inverse(A), rhs, precision=HIGHEST)
    wm, u = solved[..., :dk], solved[..., dk:]
    qg = q * jnp.exp(c)[..., None]
    P = jnp.einsum("nhik,nhjk->nhij", q, k, precision=HIGHEST) * D
    kdt = (k * jnp.exp(c[..., -1:] - c)[..., None]).swapaxes(-1, -2)        # (n, H, dk, C)
    dec = jnp.broadcast_to(jnp.exp(c[..., -1])[..., None, None], (n, H, 1, dv))

    block = lambda rows, cols: pl.BlockSpec((None, None, rows, cols), lambda h, i: (i, h, 0, 0))
    whole = pl.BlockSpec((None, dk, dv), lambda h, i: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, n_sub=n),
        name="gdn_chunk_fwd",
        grid=(H, n),
        in_specs=[block(C, dk), block(C, dv), block(C, dk), block(C, C), block(dk, C),
                  block(1, dv), whole],
        out_specs=[block(C, dv), whole],
        out_shape=[jax.ShapeDtypeStruct((n, H, C, dv), F32),
                   jax.ShapeDtypeStruct((H, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(wm, u, qg, P, kdt, dec, state.astype(F32))
    return o.swapaxes(1, 2).reshape(n * C, H, dv)[:W], state
