"""Latent attention over the latent pool: the decoding rows' absorbed form
over the pool in place (``mla_decode``), and a prefill chunk's expansion of
its row's entries into heads (``mla_expand``, at the end of the file).

A latent layer caches ONE vector a token, ``[c | r | 0]``: the normed latent
(``rank`` wide), the one rotated key every head shares, and zeros up to whole
128-lane tiles (``ops/transformer/kv_cache.py`` says why). With ``W_UK``
folded into the query (``q'_i = q_nope_i W_UK,i^T``) a head's score against a
cached token is one dot product with that vector, ``[q'_i | q_rope_i | 0] .
[c | r | 0]``, and its output is the softmax's average of the vectors
themselves, of which the caller keeps the first ``rank`` columns and takes
them through ``W_UV``. So the pool is both the keys and the values of every
head, and a block of it brought to VMEM once serves all of them.

Grid ``(rows, time blocks)``. A row's length comes by scalar prefetch: the
blocks past it are neither fetched (the index map stays on the row's last
block, and a block whose index does not change is not fetched again) nor
computed, so a row is read to ITS length and not to the read bucket, and a
row of length 0 (parked, or an empty slot) computes nothing and returns
zeros. Online softmax in float32. Forward only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.flash_attention import _auto_block
from deepspeed_tpu.ops.pallas.interpret import resolve_interpret

NEG_INF = -1e30
BLOCK = 512    # cached tokens a grid step: 640 KB of latents at GLM-4.7-Flash's 640 stored columns
HEAD_TILE = 16  # query heads are padded to whole sublane tiles of the model's dtype


def _block(size: int) -> int:
    for b in (BLOCK, 256, 128):
        if size % b == 0:
            return b
    return size


def _kernel(layer_ref, len_ref, q_ref, kv_ref, o_ref, m_scr, l_scr, acc_scr, *, sm_scale, bt, nt):
    b, t = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(t * bt < n)
    def _compute():
        q, kv = q_ref[...], kv_ref[...]                       # (H, width), (bt, width)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        ok = t * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(kv.dtype), kv, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(t == nt - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-20)).astype(o_ref.dtype)


def mla_decode(q, pool, layer, lengths, *, size: int, sm_scale: float, interpret=None):
    """q (B, H, width): each row's one token, a head's absorbed query, its
    rotated part and zeros, as the pool lays a token out; ``pool`` (L, B, 1,
    T, width), of which layer ``layer`` (a traced scalar) is read in place;
    ``lengths`` (B,) int32: the cached tokens row b attends, ``[0,
    lengths[b])``, none past ``size`` (static: the tick's read bucket).
    Returns (B, H, width): a head's average of the cached vectors under its
    softmax (zeros for a row of length 0), in q's dtype."""
    B, H, width = q.shape
    assert pool.shape[1:3] == (B, 1) and pool.shape[4] == width, (q.shape, pool.shape)
    bt = _block(size)
    nt = size // bt
    Hp = -(-H // HEAD_TILE) * HEAD_TILE
    if Hp != H:
        q = jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))

    def kv_index(b, t, layer_ref, len_ref):
        last = jnp.maximum(len_ref[b] - 1, 0) // bt
        return layer_ref[0], b, 0, jnp.minimum(t, last), 0

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, bt=bt, nt=nt),
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nt),
            in_specs=[pl.BlockSpec((None, Hp, width), lambda b, t, *_: (b, 0, 0)),
                      pl.BlockSpec((None, None, None, bt, width), kv_index)],
            out_specs=pl.BlockSpec((None, Hp, width), lambda b, t, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((Hp, 128), jnp.float32),
                            pltpu.VMEM((Hp, 128), jnp.float32),
                            pltpu.VMEM((Hp, width), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hp, width), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32), q, pool)
    return out[:, :H]


def mla_decode_reference(q, pool, layer, lengths, *, size: int, sm_scale: float):
    """The same function by einsum over the whole window (the tests' form)."""
    kv = pool[layer, :, 0, :size].astype(jnp.float32)                      # (B, size, width)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), kv) * sm_scale
    ok = jnp.arange(size)[None, None, :] < lengths[:, None, None]
    p = jnp.where(ok, jnp.exp(s - jnp.where(ok, s, NEG_INF).max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    return jnp.einsum("bht,btw->bhw", p, kv).astype(q.dtype)


# ---------------------------------------------------------------------------
# a prefill chunk's keys and values: its row's entries through W_UKV
# ---------------------------------------------------------------------------

EXPAND_HEADS = 4   # heads a grid step expands: a block of entries fetched once serves them


def expanded_entries(end: int, size: int) -> int:
    """Entries ``mla_expand`` puts through ``W_UKV`` for a chunk whose last
    query sits at key index ``end - 1``, out of a row read to ``size``: whole
    blocks of the flash chunk kernel's key tile (the host counts with this)."""
    bt = _auto_block(size, None)
    return min(-(-end // bt) * bt, size)


def _expand_kernel(n_ref, tok_ref, wuk_ref, wuv_ref, k_ref, v_ref, *, rank, rope, bt, heads):
    @pl.when(pl.program_id(1) * bt < n_ref[0])
    def _compute():
        tok = tok_ref[...]                                    # (bt, width)
        c, r = tok[:, :rank], tok[:, rank:rank + rope]
        dn = wuk_ref.shape[-1]
        for h in range(heads):
            k_ref[h, :, :dn] = jax.lax.dot(
                c, wuk_ref[h], preferred_element_type=jnp.float32).astype(k_ref.dtype)
            k_ref[h, :, dn:] = r
            v_ref[h] = jax.lax.dot(
                c, wuv_ref[h], preferred_element_type=jnp.float32).astype(v_ref.dtype)


def mla_expand(row, wuk, wuv, end, *, rank: int, rope: int, interpret=None):
    """One row's cached entries ``row`` (T, width), each ``[c (rank) | r
    (rope) | zeros]``, into every head's keys (H, T, dn + rope) = ``[c W_UK |
    r]`` and values (H, T, dv) = ``c W_UV``, for a chunk whose keys end at
    index ``end - 1`` (a traced scalar): ``wuk`` (H, rank, dn), ``wuv`` (H,
    rank, dv). Only whole blocks of the flash chunk kernel's key tile up to
    the one that holds ``end - 1`` are expanded (:func:`expanded_entries`);
    the blocks past it are neither fetched nor computed nor WRITTEN — what
    the result holds there is undefined, and the chunk kernel, which fetches
    no key tile past its last query, never reads it."""
    T, width = row.shape
    H, _, dn = wuk.shape
    dv = wuv.shape[-1]
    bt = _auto_block(T, None)
    heads = max(g for g in range(1, EXPAND_HEADS + 1) if H % g == 0)

    def block(t, n_ref):
        return jnp.minimum(t, jnp.maximum(n_ref[0] - 1, 0) // bt)

    return pl.pallas_call(
        functools.partial(_expand_kernel, rank=rank, rope=rope, bt=bt, heads=heads),
        name="mla_expand",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // heads, T // bt),       # heads outside: a skipped step stays on its group's last block
            in_specs=[pl.BlockSpec((bt, width), lambda g, t, n: (block(t, n), 0)),
                      pl.BlockSpec((heads, rank, dn), lambda g, t, n: (g, 0, 0)),
                      pl.BlockSpec((heads, rank, dv), lambda g, t, n: (g, 0, 0))],
            out_specs=[pl.BlockSpec((heads, bt, dn + rope), lambda g, t, n: (g, block(t, n), 0)),
                       pl.BlockSpec((heads, bt, dv), lambda g, t, n: (g, block(t, n), 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((H, T, dn + rope), row.dtype),
                   jax.ShapeDtypeStruct((H, T, dv), row.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.minimum(jnp.asarray(end, jnp.int32), T).reshape(1), row, wuk, wuv)
