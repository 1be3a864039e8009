"""Mamba-2's state-space scan (the state-space-duality form), for the serving tick.

A head keeps a state ``S`` of (head width P, state width N), float32. Token
t, with its input ``x_t`` (P,), step ``dt_t > 0``, log decay ``a_t = dt_t A``
(``A < 0``, one scalar a head) and the ``B_t``, ``C_t`` (N,) every head of
its group shares (``ssm_groups`` groups of consecutive heads, each with a
``B`` and a ``C`` of its own):

    S = exp(a_t) S + (dt_t x_t) B_t^T;   y_t = S C_t

That recurrence, token by token, is the definition (:func:`ssd_recurrence`:
the uncached forward and the tests use it). A token with ``dt = 0`` has
``exp(0) = 1`` and adds nothing: it leaves the state exactly as it was,
which is how a chunk's pads, a parked row and an empty slot are kept out,
with no mask inside anything here. The skip ``D x`` is the caller's.

**How the pool stores a state** (``kv_cache.state_spec``): TRANSPOSED and
with ``LANES // P`` heads side by side, ``(N, g P)`` a group of g heads
(Granite 4.0-H: two heads of 64 in 128 lanes, 64 such pairs a row and
layer). Every per-head and per-channel quantity (the decay, ``dt x``, the
output) then lies along the lanes, as a projection gives it; only ``B`` and
``C``, which the heads of a group share, have to run down the sublanes, ONE
matrix a row and group (a group is whole stored tiles, and a kernel's block
of them lies within one group: ``layer_plan.check_plan``); and no tile of
the pool is half empty (a state stored (N, 64) would be padded to 128 lanes:
twice the pool).

**The rows' step** (:func:`ssd_step_pool`, the Mosaic kernel ``ssd_step``):
one token a row on a layer of the stacked pool, in place: every row's state
read once and written once, three vector operations an element and one
8-row product for the output.

**One row's prefill chunk** (:func:`ssd_chunk_pool`, the Mosaic kernel
``ssd_chunk_fwd``): the W tokens in sub-chunks of ``SUB``. With ``c_i`` the
cumulative ``a`` inside a sub-chunk (only ever used as differences ``c_i -
c_j <= 0`` or as ``c_i`` itself) and ``X`` the rows ``dt_t x_t``:

    Y = (C B^T . L) X + exp(c) . (C S)          L_ij = exp(c_i - c_j), i >= j
    S = exp(c_last) S + B^T (exp(c_last - c) . X)

``G = C B^T`` is one matrix for all heads of a group (batched XLA, no state in it).
The kernel is a grid over blocks of head groups, the sub-chunks walked in
order with the block's states in VMEM scratch from the first to the last;
it reads the row's states out of the stacked pool and writes them back in
place. A head's ``c`` is needed down the sublanes for ``L`` and for the two
``exp(..) .`` factors: it is picked out of the (tokens, heads) matrix by a
product with a 0/1 matrix, the one thing here that is not a product of the
mathematics.
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
LANES = 128
SUB = 256          # the published mamba_chunk_size


def heads_per_tile(head_dim: int, heads: int) -> int:
    """Heads the pool stores side by side along the lanes."""
    g = max(1, LANES // head_dim)
    return g if heads % g == 0 else 1


def ssd_recurrence(x, dt, a, B, C, state):
    """The definition. x (T, H, P); dt, a (T, H); B, C (T, G, N), head h of
    group h // (H / G) (or (T, N): one group); state (H, P, N). Returns (y
    (T, H, P), state), float32."""
    H = x.shape[1]
    B, C = (v if v.ndim == 3 else v[:, None] for v in (B, C))

    def step(S, tok):
        x_t, dt_t, a_t, b_t, c_t = tok
        b_t, c_t = (jnp.repeat(v, H // v.shape[0], axis=0) for v in (b_t, c_t))       # (H, N)
        S = jnp.exp(a_t)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, c_t, precision=HIGHEST)

    state, y = jax.lax.scan(step, state.astype(F32), tuple(v.astype(F32) for v in (x, dt, a, B, C)))
    return y, state


def _group_of(block_tiles: int, tiles_per_group: int, groups: int):
    """(rows of the grouped operands a row, block of stored tiles) -> the index
    of the block's group among the (rows x groups) matrices of ``B`` / ``C``
    laid group after group. One group: the row itself, the index map it was."""
    if groups == 1:
        return lambda r, b: r
    return lambda r, b: r * groups + b * block_tiles // tiles_per_group


def to_pool(state, g: int):
    """(..., H, P, N) as the pool stores it, (..., H / g, N, g P)."""
    *lead, H, P, N = state.shape
    s = state.reshape(*lead, H // g, g, P, N)
    return jnp.moveaxis(s, -1, -3).reshape(*lead, H // g, N, g * P)


def from_pool(stored, g: int):
    """The inverse of :func:`to_pool`."""
    *lead, T, N, W = stored.shape
    s = stored.reshape(*lead, T, N, g, W // g)
    return jnp.moveaxis(s, -3, -1).reshape(*lead, T * g, W // g, N)


def _lanes(per_head, P):
    """(..., H) -> (..., H P): a head's value on each of its lanes."""
    return jnp.repeat(per_head, P, axis=-1)


# -- the rows' step -----------------------------------------------------------

def _step_kernel(layer_ref, s_ref, dec_ref, xd_ref, b_ref, c_ref, s_out_ref, y_ref, *, tiles, width):
    del layer_ref  # read by the index maps
    b, c = b_ref[...], c_ref[...]                       # (N, width) B down the sublanes; (8, N)
    for t in range(tiles):
        at = slice(t * width, (t + 1) * width)
        S = dec_ref[:, at] * s_ref[t] + b * xd_ref[:, at]
        s_out_ref[t] = S
        y_ref[:, at] = jax.lax.dot(c, S, precision=HIGHEST, preferred_element_type=F32)[0:1]


def ssd_step_pool(pool, layer, xd, a, B, C, *, tiles: int = 16, interpret: Optional[bool] = None):
    """One token a row on layer ``layer`` of the stacked pool (L, R, T, N,
    W), IN PLACE. xd (R, H P) = dt x, the heads side by side; a (R, H) the
    log decay; B, C (R, G N), group after group. The Mosaic kernel
    ``ssd_step``, a grid step a row and ``tiles`` stored tiles of one group,
    reads each state once and stores it once (the pool is aliased to the
    result and no other layer of it is touched). Returns (y (R, H P)
    float32, pool)."""
    _, R, T, N, W = pool.shape
    H = a.shape[1]
    P = xd.shape[1] // H
    G = B.shape[1] // N
    tiles = math.gcd(tiles, T // G)
    group = _group_of(tiles, T // G, G)
    dec = _lanes(jnp.exp(a.astype(F32)), P)[:, None]                               # (R, 1, H P)
    xd = xd.astype(F32)[:, None]
    b = jnp.broadcast_to(B.astype(F32).reshape(R * G, N)[:, :, None], (R * G, N, W))
    c = jnp.broadcast_to(C.astype(F32).reshape(R * G, N)[:, None, :], (R * G, 8, N))
    state = pl.BlockSpec((None, None, tiles, N, W), lambda r, t, layer_ref: (layer_ref[0], r, t, 0, 0))
    lanes = pl.BlockSpec((None, 1, tiles * W), lambda r, t, layer_ref: (r, 0, t))
    whole = lambda rows, cols: pl.BlockSpec((None, rows, cols),
                                            lambda r, t, layer_ref: (group(r, t), 0, 0))
    pool, y = pl.pallas_call(
        functools.partial(_step_kernel, tiles=tiles, width=W),
        name="ssd_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, T // tiles),
            in_specs=[state, lanes, lanes, whole(N, W), whole(8, N)],
            out_specs=[state, lanes]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((R, 1, H * P), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), pool, dec, xd, b, c)
    return y[:, 0], pool


# -- one row's prefill chunk -----------------------------------------------------

def _chunk_kernel(at_ref, s0_ref, call_ref, crow_ref, cq_ref, g_ref, cm_ref, bt_ref, x_ref,
                  s_out_ref, y_ref, s_scr, *, n_sub, tiles, width, per_tile):
    del at_ref  # read by the index maps
    block, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _load():
        s_scr[...] = s0_ref[...]

    dot = functools.partial(jax.lax.dot, precision=HIGHEST, preferred_element_type=F32)
    call, G, Cm, BT = call_ref[...], g_ref[...], cm_ref[...], bt_ref[...]
    Q, H = call.shape
    P = width // per_tile
    lower = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    head_of_row = jax.lax.broadcasted_iota(jnp.int32, (H, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, width), 1)
    reps = -(-Q // width)             # lane tiles a row of L spans
    for t in range(tiles):
        at = slice(t * width, (t + 1) * width)
        X = x_ref[:, at]                                                    # (Q, width)
        y = col = None
        for k in range(per_tile):
            local = t * per_tile + k
            head = block * tiles * per_tile + local
            # head's c down the sublanes, the same on every lane: a product with a 0/1 matrix
            c_k = dot(call, (head_of_row == head).astype(F32))              # (Q, width)
            c_i = c_k if reps == 1 else jnp.concatenate([c_k] * reps, axis=1)
            L = jnp.exp(jnp.where(lower, c_i[:, :Q] - crow_ref[local:local + 1, :], -jnp.inf))
            y_k = dot(G * L, X)
            mine = (lane >= k * P) & (lane < (k + 1) * P)
            y = y_k if y is None else jnp.where(mine, y_k, y)
            col = c_k if col is None else jnp.where(mine, c_k, col)
        S = s_scr[t]                                                        # (N, width)
        c_last = cq_ref[:, at]                                              # (1, width)
        y_ref[:, at] = y + jnp.exp(col) * dot(Cm, S)
        S = jnp.exp(c_last) * S + dot(BT, jnp.exp(c_last - col) * X)
        s_scr[t] = S

    @pl.when(i == n_sub - 1)
    def _store():
        s_out_ref[...] = s_scr[...]


def ssd_chunk_pool(pool, layer, slot, xd, a, B, C, *, sub: int = SUB, tiles: int = 4,
                   interpret: Optional[bool] = None):
    """One row's W tokens from, and into, row ``slot`` of layer ``layer`` of
    the stacked pool (L, R, T, N, width), IN PLACE. xd (W, H P) = dt x, the
    heads side by side; a (W, H) the log decay; B, C (W, G N), group after
    group. W is padded to whole sub-chunks with tokens that leave the state
    alone. Returns (y (W, H P) float32, pool)."""
    W, H = a.shape
    P = xd.shape[1] // H
    _, _, T, N, width = pool.shape
    per_tile = width // P
    groups = B.shape[1] // N
    tiles = math.gcd(tiles, T // groups)
    group = _group_of(tiles, T // groups, groups)
    Q = min(sub, max(8, 1 << (W - 1).bit_length()))
    n = -(-W // Q)
    cut = lambda v: jnp.pad(v.astype(F32), [(0, n * Q - W)] + [(0, 0)] * (v.ndim - 1)).reshape(
        (n, Q) + v.shape[1:])
    xd, a, B, C = cut(xd), cut(a), cut(B), cut(C)
    c = jnp.cumsum(a, axis=1)                                               # (n, Q, H)
    cq = _lanes(c[:, -1:], P)                                               # (n, 1, H P)
    if groups == 1:
        G = jnp.einsum("nik,njk->nij", C, B, precision=HIGHEST)
    else:   # a (sub-chunk, group) after another: C B^T, C and B^T of each
        C, B = (v.reshape(n, Q, groups, N) for v in (C, B))
        G = jnp.einsum("nigk,njgk->ngij", C, B, precision=HIGHEST).reshape(n * groups, Q, Q)
        C = C.transpose(0, 2, 1, 3).reshape(n * groups, Q, N)
        BT = B.transpose(0, 2, 3, 1).reshape(n * groups, N, Q)
    at = jnp.stack([jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32)])
    state = pl.BlockSpec((None, None, tiles, N, width), lambda b, i, at: (at[0], at[1], b, 0, 0))
    shared = lambda rows, cols: pl.BlockSpec((None, rows, cols), lambda b, i, at: (i, 0, 0))
    grouped = lambda rows, cols: pl.BlockSpec((None, rows, cols), lambda b, i, at: (group(i, b), 0, 0))
    lanes = lambda rows: pl.BlockSpec((None, rows, tiles * width), lambda b, i, at: (i, 0, b))
    pool, y = pl.pallas_call(
        functools.partial(_chunk_kernel, n_sub=n, tiles=tiles, width=width, per_tile=per_tile),
        name="ssd_chunk_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(T // tiles, n),
            in_specs=[state, shared(Q, H),
                      pl.BlockSpec((None, tiles * per_tile, Q), lambda b, i, at: (i, b, 0)),
                      lanes(1), grouped(Q, Q), grouped(Q, N), grouped(N, Q), lanes(Q)],
            out_specs=[state, lanes(Q)],
            scratch_shapes=[pltpu.VMEM((tiles, N, width), F32)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((n, Q, H * P), F32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(at, pool, c, c.swapaxes(1, 2), cq, G, C, B.swapaxes(1, 2) if groups == 1 else BT, xd)
    return y.reshape(n * Q, H * P)[:W], pool
