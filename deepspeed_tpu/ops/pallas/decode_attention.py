"""The decoding rows' one-token attention over a keyed pool in place
(``decode_rows``): each row is read to ITS length, not to the tick's read
bucket, and a parked row or an empty slot is neither fetched nor computed.

The pools come time-minor with heads and width merged, ``(L, B, H * x, T)``
(``kv_cache.time_minor``: the order the chip keeps a time-before-heads pool
of narrow heads in, so the view costs nothing). In that view ``bt`` cached
tokens of every head are ONE 2-D array ``(H * x, bt)``: a row's query laid
out block-diagonally, ``(heads, H * x)`` with head h's ``x`` numbers in
columns ``[h x, (h + 1) x)`` and zeros elsewhere, gives every head's scores
in one product, and the probabilities against the values' block in one NT
product every head's output (the diagonal blocks of a ``(heads, H * x)``
array; the rest is discarded). The matrix unit does ``heads`` times the
arithmetic the heads need, which at 16-25 heads it has to spare: the kernel
is bound by what it reads.

ONE invocation a layer, no grid: the rows that hold anything are listed
first (lengths by scalar prefetch), then their blocks stream through two
buffers a pool by DMAs the kernel issues itself, the next block (of this row
or of the next live one) in flight while this one is contracted. So a row
costs its own blocks, whole ``bt`` tokens each, and a row of length 0 costs a
scalar comparison: with a grid over (rows, blocks) every skipped block is a
grid step (~0.35 us) and every row a fetch, which at 40 slots of which 2 are
live is most of the kernel. Online softmax in float32. Forward only.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret

NEG_INF = -1e30
HEAD_TILE = 16  # query heads are padded to whole sublane tiles of the model's dtype


def _kernel(layer_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref, live_ref, k_buf, v_buf, sem,
            diag_ref, m_scr, l_scr, acc_scr, *, sm_scale, bt, x):
    rows = q_ref.shape[0]
    layer = layer_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, diag_ref.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, diag_ref.shape, 0)
    diag_ref[...] = ((col >= head * x) & (col < (head + 1) * x)).astype(diag_ref.dtype)
    o_ref[...] = jnp.zeros_like(o_ref)

    def list_live(b, n):
        live = len_ref[b] > 0

        @pl.when(live)
        def _():
            live_ref[n] = b
        return n + live.astype(jnp.int32)

    n_live = jax.lax.fori_loop(0, rows, list_live, jnp.int32(0))

    def copies(j, t, slot):
        at = (layer, live_ref[j], slice(None), pl.ds(pl.multiple_of(t * bt, bt), bt))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[slot], sem.at[1, slot]))

    def one_row(j, step):
        row = live_ref[j]
        n = len_ref[row]
        blocks = (n + bt - 1) // bt
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[row] * diag_ref[...]                          # (heads, H * x), block-diagonal

        def one_block(t, step):
            slot = step % 2
            more = t + 1 < blocks

            @pl.when(more | (j + 1 < n_live))
            def _():
                for c in copies(jnp.where(more, j, j + 1), jnp.where(more, t + 1, 0), 1 - slot):
                    c.start()

            for c in copies(j, t, slot):
                c.wait()
            k, v = k_buf[slot], v_buf[slot]                     # (H * x, bt)
            s = jax.lax.dot(q, k, preferred_element_type=jnp.float32) * sm_scale
            ok = t * bt + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
            s = jnp.where(ok, s, NEG_INF)
            m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
            return step + 1

        step = jax.lax.fori_loop(0, blocks, one_block, step)
        out = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-20) * diag_ref[...].astype(jnp.float32)
        o_ref[row] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)
        return step

    @pl.when(n_live > 0)
    def _():
        for c in copies(0, 0, 0):
            c.start()
        jax.lax.fori_loop(0, n_live, one_row, jnp.int32(0))


def decode_rows(q, k, v, layer, lengths, *, size: int, block: int, sm_scale: float,
                interpret=None):
    """q (B, H, x): each row's one token; ``k`` / ``v`` (L, B, H * x, T): the
    stacked pools time-minor, of which layer ``layer`` (a traced scalar) is
    read in place; ``lengths`` (B,) int32: the cached tokens row b attends,
    ``[0, lengths[b])``, none past ``size`` (static: the tick's read bucket,
    whole ``block``s). Returns (B, H, x) in q's dtype: softmax(q k^T
    sm_scale) v a head, float32 scores and softmax, probabilities in the
    pool's dtype before the second product; zeros for a row of length 0."""
    B, H, x = q.shape
    HX = H * x
    assert k.shape == v.shape and k.shape[1:3] == (B, HX), (q.shape, k.shape, v.shape)
    assert size % block == 0 and size <= k.shape[3], (size, block, k.shape)
    heads = -(-H // HEAD_TILE) * HEAD_TILE
    whole = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=sm_scale, bt=block, x=x),
        name="decode_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole((B, 1, HX)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole((B, 1, HX)),
            scratch_shapes=[pltpu.SMEM((B,), jnp.int32),
                            pltpu.VMEM((2, HX, block), k.dtype),
                            pltpu.VMEM((2, HX, block), v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((heads, HX), q.dtype),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, 128), jnp.float32),
                            pltpu.VMEM((heads, HX), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, HX), q.dtype),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.minimum(lengths, size).astype(jnp.int32),
      q.reshape(B, 1, HX), k, v)
    return out.reshape(B, H, x)


def decode_rows_reference(q, k, v, layer, lengths, *, size: int, sm_scale: float):
    """The same function by einsum over the whole window (the tests' form)."""
    B, H, x = q.shape
    kk, vv = (a[layer, :, :, :size].reshape(B, H, x, size).astype(jnp.float32) for a in (k, v))
    s = jnp.einsum("bhx,bhxt->bht", q.astype(jnp.float32), kk) * sm_scale
    ok = jnp.arange(size)[None, None, :] < lengths[:, None, None]
    p = jnp.where(ok, jnp.exp(s - jnp.where(ok, s, NEG_INF).max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-20)
    return jnp.einsum("bht,bhxt->bhx", p.astype(k.dtype).astype(jnp.float32), vv).astype(q.dtype)
