"""Block-sparse attention, Pallas TPU kernel.

Reference: ``deepspeed/ops/sparse_attention/`` (Triton SDD/DSD block-sparse
matmul + blocksparse softmax, matmul.py:17, softmax.py) — SURVEY.md §2.4 #12.
TPU redesign: one flash-style kernel whose kv-block loop consults a
block-level layout (from ops/sparse_attention/sparsity_config.py) held in
SMEM and skips non-attended tiles — compute scales with the number of live
blocks, the same asymptotics as the Triton SDD path.

Layout: (H, nq, nk) int32; q/k/v are (B, S, H, hd) like flash_attention.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret

NEG_INF = -1e30


def _sparse_fwd_kernel(layout_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, sm_scale, causal, bq, bk, nk):
    h, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = layout_ref[h, qi, ki] > 0

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(p, v)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-20))


def _sparse_dq_kernel(layout_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, sm_scale, causal, bq, bk, nk):
    h, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(layout_ref[h, qi, ki] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot(ds, k)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _sparse_dkv_kernel(layout_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal, bq, bk, nq):
    h, ki, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(layout_ref[h, qi, ki] > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _shapes(q, k, block):
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    b = min(block, Sq, Sk)
    assert Sq % b == 0 and Sk % b == 0
    return B, H, Sq, Sk, hd, b, Sq // b, Sk // b


def _fwd(q, k, v, layout, causal, sm_scale, block, interpret):
    B, H, Sq, Sk, hd, b, nq, nk = _shapes(q, k, block)
    o, lse = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, sm_scale=sm_scale, causal=causal, bq=b, bk=b, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, ki, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, 1), lambda bb, h, qi, ki: (bb, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, 128), jnp.float32),
            pltpu.VMEM((b, 128), jnp.float32),
            pltpu.VMEM((b, hd), jnp.float32),
        ],
        interpret=interpret,
    )(layout, q, k, v)
    return o, lse


def _bwd(causal, sm_scale, block, interpret, res, do):
    q, k, v, layout, o, lse = res
    B, H, Sq, Sk, hd, b, nq, nk = _shapes(q, k, block)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_sparse_dq_kernel, sm_scale=sm_scale, causal=causal, bq=b, bk=b, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, ki, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, ki, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, 1), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, 1), lambda bb, h, qi, ki: (bb, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, b, hd), lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((b, hd), jnp.float32)],
        interpret=interpret,
    )(layout, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_sparse_dkv_kernel, sm_scale=sm_scale, causal=causal, bq=b, bk=b, nq=nq),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, ki, qi: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, ki, qi: (bb, h, ki, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, ki, qi: (bb, h, ki, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, ki, qi: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, 1), lambda bb, h, ki, qi: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, b, 1), lambda bb, h, ki, qi: (bb, h, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, ki, qi: (bb, h, ki, 0)),
            pl.BlockSpec((1, 1, b, hd), lambda bb, h, ki, qi: (bb, h, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),
            pltpu.VMEM((b, hd), jnp.float32),
        ],
        interpret=interpret,
    )(layout, q, k, v, do, lse, delta)
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _sparse_bhsd(q, k, v, layout, causal, sm_scale, block, interpret):
    o, _ = _fwd(q, k, v, layout, causal, sm_scale, block, interpret)
    return o


def _sparse_fwd_rule(q, k, v, layout, causal, sm_scale, block, interpret):
    o, lse = _fwd(q, k, v, layout, causal, sm_scale, block, interpret)
    return o, (q, k, v, layout, o, lse)


_sparse_bhsd.defvjp(_sparse_fwd_rule, _bwd)


def block_sparse_attention(
    q,
    k,
    v,
    layout,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block: int = 128,
    interpret: Optional[bool] = None,
):
    """Block-sparse attention on (B, S, H, hd); layout (H, S/block, S/block)
    int32 from a SparsityConfig. Differentiable."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = resolve_interpret(interpret)
    layout = jnp.asarray(layout, jnp.int32)
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o = _sparse_bhsd(qt, kt, vt, layout, causal, sm_scale, block, interpret)
    return jnp.transpose(o, (0, 2, 1, 3))


def sparse_attention_reference(q, k, v, layout, block, causal=False, sm_scale=None):
    """Dense jnp reference applying the expanded block mask."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    S, Sk = q.shape[1], k.shape[1]
    mask = jnp.repeat(jnp.repeat(jnp.asarray(layout, jnp.bool_), block, axis=1), block, axis=2)  # (H,S,Sk)
    if causal:
        mask = mask & jnp.tril(jnp.ones((S, Sk), jnp.bool_))[None]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


class SparseSelfAttention:
    """Reference ``sparse_self_attention.py`` parity: config + __call__."""

    def __init__(self, sparsity_config, causal: bool = False, block_override: Optional[int] = None):
        self.config = sparsity_config
        self.causal = causal
        self.block = block_override or sparsity_config.block
        self._layout_cache = {}

    def layout(self, seq_len: int):
        if seq_len not in self._layout_cache:
            self._layout_cache[seq_len] = jnp.asarray(self.config.make_layout(seq_len), jnp.int32)
        return self._layout_cache[seq_len]

    def __call__(self, q, k, v):
        return block_sparse_attention(q, k, v, self.layout(q.shape[1]), causal=self.causal, block=self.block)
