"""Flash attention, Pallas TPU kernel (fwd + bwd).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/ds_transformer_cuda.cpp`` softmax/attention path for
training, ``csrc/transformer/inference`` softmax_context for decoding —
SURVEY.md §2.4 #5/#6). Classic FlashAttention-2 scheme:

  forward: grid (B, H, nq, nk); per q-block online softmax over kv blocks
    kept in VMEM scratch (m, l, acc persist across the sequential kv steps),
    logsumexp saved for backward.
  backward: recompute p from (q, k, lse); two kernels — dq (grid over kv
    blocks inner) and dk/dv (grid over q blocks inner) — with f32 VMEM
    accumulators, GQA head-groups reduced outside.

Layout: public API is (B, S, H, hd) (matching models/transformer.py);
kernels run (B, H, S, hd). On CPU backends the kernels run in Pallas
interpreter mode (used by unit tests); the math is identical.
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


def _blk(size: int, cap: int) -> int:
    return min(cap, size)


# Default tile cap, chosen on silicon (v5e, GPT-2 125M shapes, 2026-07-31
# microbenchmark in PERF.md): fwd+bwd per layer is 11.2 ms at 128-tiles,
# 8.1 ms for XLA attention, 5.5 ms at 512-tiles — small tiles lose to
# per-invocation grid/DMA overhead, and 512x512 f32 logits (1 MB) sit
# comfortably in VMEM.
_DEFAULT_BLOCK = 512


def supports_seq_len(size: int) -> bool:
    """True when the auto-tiler can cover a sequence of this length —
    callers that have a fallback attention path (e.g. the prefill gate in
    models/transformer.py) use this instead of duplicating the tiling rule."""
    return size <= _DEFAULT_BLOCK or size % 64 == 0


def _auto_block(size: int, cap: Optional[int]) -> int:
    """Auto tile size: ``size`` itself when it fits under the cap, else the
    largest of 512/256/128/64 that divides ``size`` (grid tiles must cover
    the sequence exactly). Longer sequences that tile by none of those get
    a loud error instead of a degenerate grid."""
    if cap is not None:
        return _blk(size, cap)
    cap = _DEFAULT_BLOCK
    if size <= cap:
        return size
    b = cap
    while b >= 64:
        if size % b == 0:
            return b
        b //= 2
    raise ValueError(
        f"flash attention auto-tiling needs the sequence length ({size}) to be "
        f"divisible by 64; pad the sequence or pass block_q/block_k explicitly")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, sm_scale, causal, bq, bk, nk, window=None):
    qi, step = pl.program_id(2), pl.program_id(3)
    if window is None:
        ki = step
        first, last = ki == 0, ki == nk - 1
    else:
        # windowed: iterate backward from the diagonal block; the grid's
        # last dim only spans the k-blocks a window-wide band can touch
        ki = (qi * bq + bq - 1) // bk - step
        first, last = step == 0, step == nk - 1  # nk = band width here

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    should_compute = True
    if causal:
        should_compute = ki * bk <= qi * bq + bq - 1
    if window is not None:
        # block touches [qpos_min - window + 1 .. qpos_max] and exists
        should_compute = (ki >= 0) & (ki * bk + bk - 1 >= qi * bq - window + 1)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0, 0]  # (bq, hd) — dots run in the input dtype (bf16 MXU
        k = k_ref[0, 0]  # path, ~4x the f32 rate) with f32 accumulation
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk) f32
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = qpos >= kpos
            if window is not None:
                ok = ok & (qpos - kpos < window)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:, :1]  # (bq, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(last)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(jnp.maximum(l, 1e-20))  # (bq, 1)


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct with varying-axis metadata when running inside a
    vma-checked shard_map (sequence-parallel Ulysses local attention)."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    try:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    except TypeError:  # pre-VMA jax: no varying-axis typing to declare
        return jax.ShapeDtypeStruct(shape, dtype)


def _band_width(window, b_outer, b_inner, n_inner):
    """Number of inner blocks a causal window of ``window`` positions can
    touch per outer block: the band spans (b_outer + window - 1) positions,
    plus one block of slack for misalignment — capped at the full grid."""
    return min(n_inner, (b_outer + window - 1 + b_inner - 1) // b_inner + 1)


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, vma=None, window=None):
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk = _auto_block(Sq, block_q), _auto_block(Sk, block_k)
    assert Sq % bq == 0 and Sk % bk == 0, f"seq lens ({Sq},{Sk}) must tile by ({bq},{bk})"
    nq, nk = Sq // bq, Sk // bk
    if window is None:
        grid = (B, H, nq, nk)
        nk_eff = nk

        def k_index(b, h, qi, ki):
            return (b, h // group, ki, 0)
    else:
        # tile pruning: only the k-blocks in the window band are visited
        # (O(S*W) compute AND DMA); the kernel walks backward from the
        # diagonal block and masks the band edges
        nk_eff = _band_width(window, bq, bk, nk)
        grid = (B, H, nq, nk_eff)

        def k_index(b, h, qi, j):
            return (b, h // group, jnp.maximum((qi * bq + bq - 1) // bk - j, 0), 0)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, nk=nk_eff,
        window=window,
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), k_index),
            pl.BlockSpec((1, 1, bk, hd), k_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            _sds((B, H, Sq, hd), q.dtype, vma),
            _sds((B, H, Sq, 1), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, sm_scale, causal, bq, bk, nk, window=None):
    qi, step = pl.program_id(2), pl.program_id(3)
    if window is None:
        ki = step
        first, last = ki == 0, ki == nk - 1
    else:
        ki = (qi * bq + bq - 1) // bk - step
        first, last = step == 0, step == nk - 1  # nk = band width here

    @pl.when(first)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    should_compute = True
    if causal:
        should_compute = ki * bk <= qi * bq + bq - 1
    if window is not None:
        should_compute = (ki >= 0) & (ki * bk + bk - 1 >= qi * bq - window + 1)

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = qpos >= kpos
            if window is not None:
                ok = ok & (qpos - kpos < window)
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[...] = dq_scr[...] + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal, bq, bk, nq, window=None, nq_total=None):
    ki, step = pl.program_id(2), pl.program_id(3)
    if window is None:
        qi = step
        first, last = qi == 0, qi == nq - 1
    else:
        # inverted band: walk the q-blocks that can see this k-block,
        # starting at the diagonal
        qi = (ki * bk) // bq + step
        first, last = step == 0, step == nq - 1  # nq = band width here

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    should_compute = True
    if causal:
        should_compute = qi * bq + bq - 1 >= ki * bk
    if window is not None:
        # band edge (q-block outside the window of this k-block) and grid
        # edge (qi walked past the last real q-block, index_map clamped)
        should_compute = (should_compute
                          & (qi * bq < ki * bk + bk + window - 1)
                          & (qi <= nq_total - 1))

    @pl.when(should_compute)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ok = qpos >= kpos
            if window is not None:
                ok = ok & (qpos - kpos < window)
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse).astype(do.dtype)  # (bq, bk)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bk, hd)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p.astype(jnp.float32) * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(last)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(causal, sm_scale, block_q, block_k, interpret, vma, window, res, do):
    q, k, v, o, lse = res
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk = _auto_block(Sq, block_q), _auto_block(Sk, block_k)
    nq, nk = Sq // bq, Sk // bk

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)  # (B,H,Sq,1)

    if window is None:
        nk_eff, nq_eff = nk, nq

        def dq_k_index(b, h, qi, ki):
            return (b, h // group, ki, 0)

        def dkv_q_index(b, h, ki, qi):
            return (b, h, qi, 0)
    else:
        nk_eff = _band_width(window, bq, bk, nk)
        nq_eff = _band_width(window, bk, bq, nq)

        def dq_k_index(b, h, qi, j):
            return (b, h // group, jnp.maximum((qi * bq + bq - 1) // bk - j, 0), 0)

        def dkv_q_index(b, h, ki, j):
            return (b, h, jnp.minimum((ki * bk) // bq + j, nq - 1), 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk,
                          nk=nk_eff, window=window),
        name="flash_bwd_dq",
        grid=(B, H, nq, nk_eff),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), dq_k_index),
            pl.BlockSpec((1, 1, bk, hd), dq_k_index),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=_sds(q.shape, q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk_full, dv_full = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk,
                          nq=nq_eff, window=window, nq_total=nq),
        name="flash_bwd_dkv",
        grid=(B, H, nk, nq_eff),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), dkv_q_index),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bq, hd), dkv_q_index),
            pl.BlockSpec((1, 1, bq, 1), dkv_q_index),
            pl.BlockSpec((1, 1, bq, 1), dkv_q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, qi: (b, h, ki, 0)),
        ],
        out_shape=[
            _sds((B, H, Sk, hd), k.dtype, vma),
            _sds((B, H, Sk, hd), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hd), jnp.float32),
            pltpu.VMEM((bk, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    if group > 1:
        dk = dk_full.reshape(B, Hkv, group, Sk, hd).sum(axis=2).astype(k.dtype)
        dv = dv_full.reshape(B, Hkv, group, Sk, hd).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, causal, sm_scale, block_q, block_k, interpret, vma, window):
    o, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, vma, window)
    return jnp.transpose(o, (0, 2, 1, 3))


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, interpret, vma, window):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, vma, window)
    return _out_and_residuals(q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, interpret, vma, window, res, do):
    return _bwd(causal, sm_scale, block_q, block_k, interpret, vma, window, *_kernel_forms(res, do))


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    vma=None,
    window: Optional[int] = None,
):
    """Flash attention on (B, S, H, head_dim) tensors (GQA via fewer KV heads).

    Differentiable (custom VJP with flash backward); runs compiled on TPU and
    interpreted on CPU backends. ``block_q``/``block_k`` default to the
    sequence length itself when <= 512, else the largest of 512/256/128/64
    dividing it (512 is the silicon-tuned cap — see ``_DEFAULT_BLOCK``);
    pass explicit values to pin. ``vma``:
    varying mesh axes to stamp on the kernel outputs when called inside a
    vma-checked ``shard_map`` (e.g. ``("sequence",)`` for the Ulysses local
    attention).

    ``window``: static sliding-window size — each query attends keys in
    ``(qpos - window, qpos]`` (Mistral-style; the reference's
    SparseSelfAttention local modes). The kernel grids only visit the
    k-blocks inside the window band, so compute AND HBM traffic are
    O(S * window) instead of O(S^2). Requires ``causal`` and equal q/k
    lengths; for best pruning pick ``block_k`` no larger than the window.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        assert causal, "sliding-window flash attention requires causal=True"
        assert q.shape[1] == k.shape[1], (
            "sliding-window flash attention requires equal q/k sequence lengths")
        # static kernel-geometry int (never a traced array): the cast
        # normalizes np.int64-style configs at trace time, no host sync
        window = int(window)  # ds-lint: disable=jit-boundary-sync
        assert window >= 1, f"window must be >= 1, got {window}"
    interpret = resolve_interpret(interpret)
    vma = tuple(vma) if vma else None
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    # kernels run (B, H, S, hd); the output comes back (B, S, H, hd) already
    return _flash_bhsd(qt, kt, vt, causal, sm_scale, block_q, block_k, interpret, vma,
                       window)


# ---------------------------------------------------------------------------
# one prefill chunk against a cached row (forward only)
# ---------------------------------------------------------------------------

def _chunk_kernel(scal_ref, sink_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  sm_scale, bq, bk, nk, window, has_sink):
    h, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q_off, k_min = scal_ref[0], scal_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo, k_lo = q_off + qi * bq, ki * bk
    should_compute = (k_lo <= q_lo + bq - 1) & (k_lo + bk - 1 >= k_min)
    if window is not None:
        should_compute = should_compute & (k_lo + bk - 1 > q_lo - window)

    @pl.when(should_compute)
    def _compute():
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk) f32
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = (kpos <= qpos) & (kpos >= k_min)
        if window is not None:
            ok = ok & (qpos - kpos < window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)  # a row all masked so far keeps l = 0
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        m, l, acc = m_scr[:, :1], l_scr[:, :1], acc_scr[...]
        if has_sink:  # the sink joins the denominator and brings no value
            sink = sink_ref[h]
            m_all = jnp.maximum(m, sink)
            corr = jnp.exp(m - m_all)
            l, acc = l * corr + jnp.exp(sink - m_all), acc * corr
        o_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def flash_attention_chunk(q, k, v, q_off, k_min=0, sink=None, window: Optional[int] = None,
                          sm_scale: Optional[float] = None, interpret: Optional[bool] = None):
    """One row's prefill chunk against that row's cached keys: q (W, H, dk)
    at key-index ``q_off + i`` for query i; k (Hkv, T, dk) and v (Hkv, T, dv)
    head-major, as a layer plan's pools keep a row (dv may differ from dk);
    returns (W, H, dv). Query i attends key j where
    ``k_min <= j <= q_off + i`` and, with ``window``, ``q_off + i - j <
    window``. ``q_off`` and ``k_min`` are traced scalars (the chunk's depth
    in its row): key tiles outside that range are neither fetched nor
    computed. ``sink`` (H,) float32: a per-head logit that joins the
    softmax's denominator and contributes no value. Forward only."""
    W, H, dk = q.shape
    Hkv, T, dv = v.shape
    group = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(dk)
    bq, bk = _auto_block(W, None), _auto_block(T, None)
    nq, nk = W // bq, T // bk
    has_sink = sink is not None
    scal = jnp.stack([jnp.asarray(q_off, jnp.int32), jnp.asarray(k_min, jnp.int32)])
    sink = (jnp.zeros((H,), jnp.float32) if sink is None else sink.astype(jnp.float32))

    def k_index(h, qi, ki, scal_ref, sink_ref):
        q_lo = scal_ref[0] + qi * bq
        lo = scal_ref[1]
        if window is not None:
            lo = jnp.maximum(lo, q_lo - window + 1)
        hi = jnp.minimum((q_lo + bq - 1) // bk, nk - 1)
        return h // group, jnp.clip(ki, jnp.minimum(jnp.maximum(lo, 0) // bk, hi), hi), 0

    def q_index(h, qi, ki, scal_ref, sink_ref):
        return h, qi, 0

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale, bq=bq, bk=bk, nk=nk, window=window,
                          has_sink=has_sink),
        name="flash_chunk_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H, nq, nk),
            in_specs=[pl.BlockSpec((None, bq, dk), q_index),
                      pl.BlockSpec((None, bk, dk), k_index),
                      pl.BlockSpec((None, bk, dv), k_index)],
            out_specs=pl.BlockSpec((None, bq, dv), q_index),
            scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, 128), jnp.float32),
                            pltpu.VMEM((bq, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((H, W, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(scal, sink, jnp.transpose(q, (1, 0, 2)), k, v)
    return jnp.transpose(out, (1, 0, 2))


def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                  window: Optional[int] = None):
    """jnp reference for parity tests."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    H, Hkv = q.shape[2], k.shape[2]
    if H != Hkv:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    S, Sk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((S, Sk), jnp.bool_))
    if window is not None:
        qp = jnp.arange(S, dtype=jnp.int32)[:, None]
        kp = jnp.arange(Sk, dtype=jnp.int32)[None, :]
        local = qp - kp < window
        mask = local if mask is None else mask & local
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# what a layer checkpoint keeps of the forward kernel
# (the section sits last so that no kernel above moves a line: a Mosaic
# call's payload carries its source lines, and with them its cache key)
# ---------------------------------------------------------------------------

from jax.ad_checkpoint import checkpoint_name  # noqa: E402

# The two residuals only the kernel can produce, by the names a remat policy
# saves them under (``save_only_these_names(*RESIDUAL_NAMES)`` is the policy
# "flash_saveable" of runtime/activation_checkpointing). q, k and v carry no
# name: the backward pass rebuilds them from the layer's input.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _out_and_residuals(q, k, v, o, lse):
    """``_flash_bhsd``'s output and its VJP residuals from the kernel's
    (B, H, S, hd) output and (B, H, S, 1) log-sum-exp. What is named, and so
    what a layer scan stacks when a policy saves it, is dense: the output as
    (B, S, H*hd) and the log-sum-exp as (B, H, S) -- the chip pads a minor
    axis of 64 to 128 lanes and one of 1 to a whole (8, 128) tile. The
    output handed on is a view of the NAMED array, so that a backward pass
    which holds the name needs the kernel for nothing."""
    B, H, S, hd = o.shape
    out = checkpoint_name(jnp.transpose(o, (0, 2, 1, 3)).reshape(B, S, H * hd),
                          RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return out.reshape(B, S, H, hd), (q, k, v, out, lse)


def _kernel_forms(res, do):
    """The residuals and the output's cotangent back in the backward
    kernels' shapes: a transpose and a trailing axis, in XLA."""
    q, k, v, out, lse = res
    B, H, S, hd = q.shape
    # the barrier makes the chip transpose the saved array in its own dtype;
    # without it the compiler first widens it to float32 for ``_bwd``'s
    # ``delta``, transposes twice the bytes, and runs the sum as a third op
    o = jax.lax.optimization_barrier(jnp.transpose(out.reshape(B, S, H, hd), (0, 2, 1, 3)))
    return (q, k, v, o, lse[..., None]), jnp.transpose(do, (0, 2, 1, 3))
