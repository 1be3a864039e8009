"""Flash attention, Pallas TPU kernel (fwd + bwd).

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/ds_transformer_cuda.cpp`` softmax/attention path for
training, ``csrc/transformer/inference`` softmax_context for decoding —
SURVEY.md §2.4 #5/#6). Classic FlashAttention-2 scheme:

  forward: per q tile an online softmax over its kv tiles (m, l, acc in
    float32), logsumexp saved for backward.
  backward: recompute p from (q, k, lse); two kernels — dq (kv tiles inner)
    and dk/dv (q tiles inner, from the diagonal on) — with f32
    accumulators, GQA head-groups reduced outside.

A GRID step is large and a TILE small (``_Walk``): the step holds a block of
rows of the outer axis and, where a head's other operands fit VMEM, their
whole sequence; a loop inside the step walks the score matrix in tiles
whose float32 intermediates fit the vector registers, and visits only tiles
that hold an unmasked pair — building a mask only where a mask's edge runs
through the tile.

Layout: public API is (B, S, H, hd) (matching models/transformer.py);
kernels run (B, H, S, hd). On CPU backends the kernels run in Pallas
interpreter mode (used by unit tests); the math is identical.
"""

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret

NEG_INF = -1e30


def _blk(size: int, cap: int) -> int:
    return min(cap, size)


# Default cap of a GRID block along an axis that is cut, chosen on silicon
# (v5e, GPT-2 125M shapes, 2026-07-31): 128-row grid steps lose to 512-row
# ones by 2x in per-step grid/DMA overhead. That says nothing of the TILE a
# step computes at a time, which is ``_TILE`` (see ``_Walk``; PERF.md §6 PR 41).
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
# the tile walk: which tiles of the score matrix a grid step visits
# ---------------------------------------------------------------------------

_TILE = 128  # rows of an outer tile, and the inner tile where a mask's edge crosses
# the inner tile where no position is masked, measured a kernel on v5e at S 1,024, hd 64 (PERF.md
# §6 PR 41): wider tiles amortise dq's per-row work and cost the other two more than they save
# (ms a call at 8 x 16 heads, 128 / 256 wide: forward 0.356 / 0.430, dq 0.438 / 0.403, dkv 0.479 / 0.528)
_WIDE = {"fwd": 128, "dq": 256, "dkv": 128}
_WHOLE = 2048, 512 << 10  # both axes this short (rows, bytes a head) and in tiles: ONE grid step a head


def _in_tiles(size):
    """Short enough, and in whole tiles: a head can be walked in one grid step."""
    return size <= _WHOLE[0] and size % _TILE == 0


def _visited(o0, to, ti, n, lo, hi):
    """The inner blocks (``ti`` wide, ``n`` of them) that hold an unmasked
    pair with outer positions ``[o0, o0 + to)``, as ``(first, end)``; a pair
    is unmasked iff ``lo <= outer - inner <= hi`` (None: unbounded). ``o0``
    is a Python int or a traced scalar (an index map's)."""
    first = 0 if hi is None else (o0 - hi) // ti
    end = n if lo is None else (o0 + to - 1 - lo) // ti + 1
    if isinstance(o0, int):
        first = max(0, min(first, n))
        return first, max(first, min(end, n))
    first = jnp.clip(first, 0, n)
    return first, jnp.clip(end, first, n)


@dataclasses.dataclass(frozen=True)
class _Walk:
    """One kernel's walk of the (Sq, Sk) score matrix. The OUTER axis (q for
    the forward and dq kernels, k for dkv) is cut into grid blocks of ``bo``
    rows and the inner into blocks of ``bi``; the grid's last axis steps
    through the inner blocks that hold an unmasked pair with the outer block,
    and no others. A step takes its outer block ``to`` rows at a time and
    walks the inner block in tiles: ``tw`` wide where no position is masked,
    ``ti`` where a mask's edge crosses (a mask is built there, and only
    there), none where every position is masked. What a pair of blocks holds
    depends only on their offset ``outer - inner``, which takes few values:
    each is a piece of straight-line code with constant masks, chosen by
    ``pl.when``. ``tiled``: a head is one grid step and its tiles are small
    (``_TILE``), the step's state in registers; else a pair of blocks is one
    tile, the state in VMEM scratch from step to step. Everything follows
    from the shape, ``causal`` and ``window``."""
    so: int
    si: int
    bo: int
    bi: int
    to: int
    ti: int
    tw: int
    lo: Optional[int]  # a pair is unmasked iff lo <= outer - inner <= hi
    hi: Optional[int]
    tiled: bool

    @classmethod
    def of(cls, sq, sk, bq, bk, causal, window, kernel="fwd"):
        tiled = (bq, bk) == (sq, sk) and _in_tiles(sq) and _in_tiles(sk)
        lo, hi = (0 if causal else None), (None if window is None else window - 1)
        so, si, bo, bi = (sq, sk, bq, bk)
        if kernel == "dkv":
            so, si, bo, bi, lo, hi = sk, sq, bk, bq, (None if hi is None else -hi), (None if lo is None else -lo)
        if not tiled:
            return cls(so, si, bo, bi, bo, bi, bi, lo, hi, False)
        wide = _WIDE[kernel]
        return cls(so, si, bo, bi, _TILE, _TILE, wide if bi % wide == 0 else _TILE, lo, hi, True)

    @property
    def n_outer(self):
        return self.so // self.bo

    def _blocks(self, ob):
        return _visited(ob * self.bo, self.bo, self.bi, self.si // self.bi, self.lo, self.hi)

    @functools.cached_property
    def _pairs(self):
        """(outer block, step) -> offset of that pair of blocks, for every
        pair the grid visits."""
        return {(ob, j): ob * self.bo - (self._blocks(ob)[0] + j) * self.bi
                for ob in range(self.n_outer) for j in range(self._blocks(ob)[1] - self._blocks(ob)[0])}

    @functools.cached_property
    def steps(self):
        """Length of the grid's last axis: the most inner blocks any outer
        block visits (a window's band; all of them without a mask)."""
        return max([j + 1 for _, j in self._pairs] or [1])

    def inner_block(self, ob, j):
        """Index map of the inner operands: the j-th block the outer block
        visits, held at its last one past the end (no new block is fetched)."""
        first, end = self._blocks(ob)
        return jnp.clip(first + j, 0, jnp.maximum(end, 1) - 1)

    def tiles(self, off):
        """The walk of one pair of blocks at offset ``off``: for each outer
        tile (its row in the block, inner tiles); an inner tile is (its row in
        the inner block, its width, d0) — d0 None where no position is masked,
        else ``outer - inner`` of the tile's first pair, for the mask."""
        out = []
        for r0 in range(0, self.bo, self.to):
            def kind(c0, width):  # 0 all masked, 1 crossed, 2 none masked
                dmin, dmax = off + r0 - c0 - width + 1, off + r0 + self.to - 1 - c0
                if (self.lo is not None and dmax < self.lo) or (self.hi is not None and dmin > self.hi):
                    return 0
                return 2 if ((self.lo is None or dmin >= self.lo)
                             and (self.hi is None or dmax <= self.hi)) else 1
            inner, c0 = [], 0
            while c0 < self.bi:
                if c0 % self.tw == 0 and kind(c0, self.tw) == 2:
                    inner.append((c0, self.tw, None))
                    c0 += self.tw
                    continue
                if fine := kind(c0, self.ti):
                    inner.append((c0, self.ti, None if fine == 2 else off + r0 - c0))
                c0 += self.ti
            out.append((r0, inner))
        return out

    def paths(self):
        """[(condition, tiles)]: the pieces of code of a grid step, each under
        the condition that this step's pair of blocks has the piece's offset.
        Offsets at which no position is masked share one piece."""
        by_off = {c: self.tiles(c) for c in sorted(set(self._pairs.values()))}
        ob, j = pl.program_id(2), pl.program_id(3)
        if self.tiled:
            # always true, and a conditional all the same: under a vma-checked shard_map
            # (Ulysses' local attention) the interpreter binds a kernel's TOP-LEVEL ref reads
            # anew, without the varying-axes cast tracing put in, and refuses them; a
            # conditional's branches it takes as they were traced
            return [(j == 0, *by_off.values())]
        first, end = self._blocks(ob)
        off, live = ob * self.bo - (first + j) * self.bi, first + j < end
        whole = [(r0, [(c0, self.tw, None) for c0 in range(0, self.bi, self.tw)])
                 for r0 in range(0, self.bo, self.to)]
        crossed = [c for c, tiles in by_off.items() if tiles != whole]
        out = [(live & (off == c), by_off[c]) for c in crossed]
        if len(crossed) < len(by_off):
            out.append((functools.reduce(lambda a, c: a & (off != c), crossed, live), whole))
        return out


def _grid_blocks(sq, sk, row_bytes, block_q, block_k):
    """(bq, bk) of a kernel's grid: an explicit block is the caller's; else a head's whole
    sequences where both are short (``_WHOLE``: VMEM holds six of them twice in the dkv kernel)
    and in tiles, so that a head is ONE grid step of straight-line code; else ``_auto_block``
    on both, and the grid steps through the blocks that hold unmasked pairs."""
    whole = all(_in_tiles(s) and s * row_bytes <= _WHOLE[1] for s in (sq, sk))
    return tuple(s if whole and cap is None else _auto_block(s, cap)
                 for s, cap in ((sq, block_q), (sk, block_k)))


def _mask(d0, shape, outer_axis, lo, hi):
    """Which pairs of a tile are unmasked: ``lo <= outer - inner <= hi``,
    ``d0`` being ``outer - inner`` of the tile's first pair."""
    diff = d0 + (jax.lax.broadcasted_iota(jnp.int32, shape, outer_axis)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - outer_axis))
    ok = None if lo is None else diff >= lo
    if hi is not None:
        ok = (diff <= hi) if ok is None else ok & (diff <= hi)
    return ok


def _flip(x):
    """(n, 1) <-> (1, n), exactly: x spread over the diagonal of an (n, n)
    tile and summed along the other axis (zeros added). The per-query float32
    statistics cross HBM as ROWS, lane-dense — a (…, S, 1) column is padded
    128-fold there and in VMEM — and a kernel whose queries lie on sublanes
    turns a tile's worth at a time."""
    n = max(x.shape)
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, x, 0.0), axis=x.shape.index(n), keepdims=True)


def _folds(sm_scale):
    """A power-of-two scale moves from the score tile onto an operand
    (exact in any binary float type); any other stays on the tile."""
    return math.frexp(sm_scale)[0] == 0.5


_NT = (((1,), (1,)), ((), ()))  # a @ b.T


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, sm_scale, walk):
    """Online softmax over the tiles ``walk`` lists for this step: float32 scores, statistics
    and accumulator, a float32 ``exp``, bfloat16 (the input dtype) into the MXU; ``lse_ref``
    takes rows where the walk is tiled, else a column as the scratch holds it."""
    j, last, carried = pl.program_id(3), walk.steps - 1, not walk.tiled
    tq, fold = walk.to, _folds(sm_scale)

    def finish(m, l, acc):
        return (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype), m + jnp.log(jnp.maximum(l, 1e-20))

    if carried:  # the online softmax's state crosses grid steps
        m_scr, l_scr, acc_scr = scratch

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    for cond, tiles in walk.paths():
        @pl.when(cond)
        def _compute(tiles=tiles):
            for r0, inner in tiles:
                rows = slice(r0, r0 + tq)
                q = q_ref[0, 0, rows]  # dots run in the input dtype (bf16 MXU
                if fold:               # path, ~4x the f32 rate) with f32 accumulation
                    q = q * sm_scale
                if carried:
                    m, l, acc = m_scr[rows, :1], l_scr[rows, :1], acc_scr[rows]
                else:
                    m, l = jnp.full((tq, 1), NEG_INF, jnp.float32), jnp.zeros((tq, 1), jnp.float32)
                    acc = jnp.zeros((tq, v_ref.shape[-1]), jnp.float32)
                for c0, width, d0 in inner:
                    k, v = k_ref[0, 0, c0:c0 + width], v_ref[0, 0, c0:c0 + width]
                    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)  # (tq, width) f32
                    if not fold:
                        s = s * sm_scale
                    ok = None if d0 is None else _mask(d0, s.shape, 0, walk.lo, walk.hi)
                    if ok is not None:
                        s = jnp.where(ok, s, NEG_INF)
                    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                    p = jnp.exp(s - m_new)
                    if ok is not None:  # a row all masked so far keeps l = 0
                        p = jnp.where(ok, p, 0.0)
                    corr = jnp.exp(m - m_new)
                    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
                    acc = acc * corr + jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                    m = m_new
                if carried:
                    m_scr[rows] = jnp.broadcast_to(m, (tq, m_scr.shape[1]))
                    l_scr[rows] = jnp.broadcast_to(l, (tq, l_scr.shape[1]))
                    acc_scr[rows] = acc
                else:  # one step a head: the log-sum-exp leaves as a row
                    o_ref[0, 0, rows], lse = finish(m, l, acc)
                    lse_ref[0, 0, :, rows] = _flip(lse)

    if carried:
        @pl.when(j == last)
        def _finalize():
            o_ref[0, 0], lse_ref[0, 0] = finish(m_scr[:, :1], l_scr[:, :1], acc_scr[...])  # lse (bq, 1)


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct with varying-axis metadata when running inside a
    vma-checked shard_map (sequence-parallel Ulysses local attention)."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    try:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    except TypeError:  # pre-VMA jax: no varying-axis typing to declare
        return jax.ShapeDtypeStruct(shape, dtype)


# batch, head and outer block independent; the last axis steps through an outer block's inner blocks in order
_GRID = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, vma=None, window=None):
    """o (B, H, Sq, hd) and the log-sum-exp (B, H, Sq)."""
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk = _grid_blocks(Sq, Sk, hd * q.dtype.itemsize, block_q, block_k)
    assert Sq % bq == 0 and Sk % bk == 0, f"seq lens ({Sq},{Sk}) must tile by ({bq},{bk})"
    walk = _Walk.of(Sq, Sk, bq, bk, causal, window)  # only the k-blocks that hold an unmasked
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, j: (b, h, qi, 0))  # pair are fetched
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, j: (b, h // group, walk.inner_block(qi, j), 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, walk=walk),
        name="flash_fwd",
        grid=(B, H, walk.n_outer, walk.steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, _stat_spec(bq, lambda b, h, qi, j: (b, h, qi, 0), walk.tiled)],
        out_shape=[
            _sds((B, H, Sq, hd), q.dtype, vma),
            _sds((B, H, 1, Sq) if walk.tiled else (B, H, Sq, 1), jnp.float32, vma),
        ],
        scratch_shapes=[] if walk.tiled else [
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=_GRID,
        interpret=interpret,
    )(q, k, v)
    return o, lse.reshape(B, H, Sq)


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
    interpreted on CPU backends. ``block_q``/``block_k`` are the GRID blocks:
    by default whole sequences of <= 1024 in tiles of 128 (a head is one grid
    step, walked in tiles: ``_Walk``), else the sequence itself when <= 512,
    else the largest of 512/256/128/64 dividing it; pass values to pin. ``vma``:
    varying mesh axes to stamp on the kernel outputs when called inside a
    vma-checked ``shard_map`` (e.g. ``("sequence",)`` for the Ulysses local
    attention).

    ``window``: static sliding-window size — each query attends keys in
    ``(qpos - window, qpos]`` (Mistral-style; the reference's
    SparseSelfAttention local modes). The kernels visit only the tiles (and
    fetch only the k-blocks) inside the window band, so compute AND HBM
    traffic are O(S * window) instead of O(S^2). Requires ``causal`` and
    equal q/k lengths; a pinned ``block_k`` prunes best no larger than the window.
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
# what a layer checkpoint keeps of the forward kernel
# ---------------------------------------------------------------------------

# The two residuals only the kernel can produce, by the names a remat policy
# saves them under (``save_only_these_names(*RESIDUAL_NAMES)`` is the policy
# "flash_saveable" of runtime/activation_checkpointing). q, k and v carry no
# name: the backward pass rebuilds them from the layer's input.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _out_and_residuals(q, k, v, o, lse):
    """``_flash_bhsd``'s output and its VJP residuals from the kernel's
    (B, H, S, hd) output and ``_fwd``'s (B, H, S) log-sum-exp. What is named, and so
    what a layer scan stacks when a policy saves it, is dense: the output as
    (B, S, H*hd) and the log-sum-exp as (B, H, S) -- the chip pads a minor
    axis of 64 to 128 lanes and one of 1 to a whole (8, 128) tile. The
    output handed on is a view of the NAMED array, so that a backward pass
    which holds the name needs the kernel for nothing."""
    B, H, S, hd = o.shape
    out = checkpoint_name(jnp.transpose(o, (0, 2, 1, 3)).reshape(B, S, H * hd),
                          RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out.reshape(B, S, H, hd), (q, k, v, out, lse)


def _kernel_forms(res, do):
    """The residuals and the output's cotangent back in the backward
    kernels' shapes: a transpose each, in XLA."""
    q, k, v, out, lse = res
    B, H, S, hd = q.shape
    # the barrier makes the chip transpose the saved array in its own dtype;
    # without it the compiler first widens it to float32 for ``_bwd``'s
    # ``delta``, transposes twice the bytes, and runs the sum as a third op
    o = jax.lax.optimization_barrier(jnp.transpose(out.reshape(B, S, H, hd), (0, 2, 1, 3)))
    return (q, k, v, o, lse), jnp.transpose(do, (0, 2, 1, 3))


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
# backward, and the host's view of the walk
# (after the prefill-chunk kernel, not beside the forward: a Mosaic call's
# payload carries its source lines, and with them its cache key, so no line
# of ``_chunk_kernel`` / ``flash_attention_chunk`` may move)
# ---------------------------------------------------------------------------

def _stat_spec(block, index, rows):
    """BlockSpec of a per-query float32 statistic (log-sum-exp, delta): of
    (B, H, 1, S) as rows, of (B, H, S, 1) as columns."""
    if not rows:
        return pl.BlockSpec((1, 1, block, 1), index)

    def as_row(*ids):
        b, h, i, _ = index(*ids)
        return b, h, 0, i
    return pl.BlockSpec((1, 1, 1, block), as_row)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *scratch, sm_scale, walk):
    j, last, carried = pl.program_id(3), walk.steps - 1, not walk.tiled
    tq, fold = walk.to, _folds(sm_scale)

    def finish(dq):
        return (dq * sm_scale if fold else dq).astype(dq_ref.dtype)

    if carried:
        dq_scr, = scratch

        @pl.when(j == 0)
        def _init():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    for cond, tiles in walk.paths():
        @pl.when(cond)
        def _compute(tiles=tiles):
            for r0, inner in tiles:
                rows = slice(r0, r0 + tq)
                q, do = q_ref[0, 0, rows], do_ref[0, 0, rows]
                if carried:
                    lse, delta, dq = lse_ref[0, 0, rows], delta_ref[0, 0, rows], dq_scr[rows]  # (tq, 1)
                else:
                    lse, delta = _flip(lse_ref[0, 0, :, rows]), _flip(delta_ref[0, 0, :, rows])
                    dq = jnp.zeros(q.shape, jnp.float32)
                if fold:
                    q = q * sm_scale
                for c0, width, d0 in inner:
                    k, v = k_ref[0, 0, c0:c0 + width], v_ref[0, 0, c0:c0 + width]
                    s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
                    if not fold:
                        s = s * sm_scale
                    if d0 is not None:
                        s = jnp.where(_mask(d0, s.shape, 0, walk.lo, walk.hi), s, NEG_INF)
                    p = jnp.exp(s - lse)
                    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)  # (tq, width)
                    ds = p * (dp - delta)
                    if not fold:
                        ds = ds * sm_scale
                    dq = dq + jax.lax.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)
                if carried:
                    dq_scr[rows] = dq
                else:
                    dq_ref[0, 0, rows] = finish(dq)

    if carried:
        @pl.when(j == last)
        def _finalize():
            dq_ref[0, 0] = finish(dq_scr[...])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *scratch, sm_scale, walk,
                stat_rows):
    """The walk's outer axis is k, and a tile is s TRANSPOSED — keys on
    sublanes, queries on lanes: p^T do and ds^T q are plain products, and the
    statistics are the rows they cross HBM as. q tiles run from the diagonal on."""
    j, last, carried = pl.program_id(3), walk.steps - 1, not walk.tiled
    tk, fold = walk.to, _folds(sm_scale)

    def finish(dk, dv):
        return (dk * sm_scale if fold else dk).astype(dk_ref.dtype), dv.astype(dv_ref.dtype)

    def stat(ref, cols):
        return ref[0, 0, :, cols] if stat_rows else _flip(ref[0, 0, cols])  # (1, width)

    if carried:
        dk_scr, dv_scr = scratch

        @pl.when(j == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

    for cond, tiles in walk.paths():
        @pl.when(cond)
        def _compute(tiles=tiles):
            for r0, inner in tiles:
                rows = slice(r0, r0 + tk)
                k, v = k_ref[0, 0, rows], v_ref[0, 0, rows]
                ks = k * sm_scale if fold else k
                dk, dv = (dk_scr[rows], dv_scr[rows]) if carried else (jnp.zeros(k.shape, jnp.float32),) * 2
                for c0, width, d0 in inner:
                    cols = slice(c0, c0 + width)
                    q, do = q_ref[0, 0, cols], do_ref[0, 0, cols]
                    s = jax.lax.dot_general(ks, q, _NT, preferred_element_type=jnp.float32)  # (tk, width)
                    if not fold:
                        s = s * sm_scale
                    if d0 is not None:
                        s = jnp.where(_mask(d0, s.shape, 0, walk.lo, walk.hi), s, NEG_INF)
                    p = jnp.exp(s - stat(lse_ref, cols)).astype(do.dtype)
                    dv = dv + jax.lax.dot(p, do, preferred_element_type=jnp.float32)  # (tk, hd)
                    dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
                    ds = p.astype(jnp.float32) * (dp - stat(delta_ref, cols))
                    if not fold:
                        ds = ds * sm_scale
                    dk = dk + jax.lax.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32)
                if carried:
                    dk_scr[rows], dv_scr[rows] = dk, dv
                else:
                    dk_ref[0, 0, rows], dv_ref[0, 0, rows] = finish(dk, dv)

    if carried:
        @pl.when(j == last)
        def _finalize():
            dk_ref[0, 0], dv_ref[0, 0] = finish(dk_scr[...], dv_scr[...])


def _bwd(causal, sm_scale, block_q, block_k, interpret, vma, window, res, do):
    q, k, v, o, lse = res  # lse (B, H, Sq), as the forward rule keeps it
    B, H, Sq, hd = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq, bk = _grid_blocks(Sq, Sk, hd * q.dtype.itemsize, block_q, block_k)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (B, H, Sq)

    def stats(rows):  # a free reshape as rows; as columns the chip re-lays them out
        return tuple(a[:, :, None, :] if rows else a[..., None] for a in (lse, delta))

    walk = _Walk.of(Sq, Sk, bq, bk, causal, window, "dq")
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, j: (b, h, qi, 0))
    stat_spec = _stat_spec(bq, lambda b, h, qi, j: (b, h, qi, 0), walk.tiled)
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, j: (b, h // group, walk.inner_block(qi, j), 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, walk=walk),
        name="flash_bwd_dq",
        grid=(B, H, walk.n_outer, walk.steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=_sds(q.shape, q.dtype, vma),
        scratch_shapes=[] if walk.tiled else [pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=_GRID,
        interpret=interpret,
    )(q, k, v, do, *stats(walk.tiled))

    walk = _Walk.of(Sq, Sk, bq, bk, causal, window, "dkv")
    stat_rows = bq % 128 == 0 or bq == Sq  # a row's block has to be whole lanes; else columns, turned
    q_index = lambda b, h, ki, j: (b, h, walk.inner_block(ki, j), 0)  # noqa: E731
    q_spec = pl.BlockSpec((1, 1, bq, hd), q_index)
    kv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, j: (b, h // group, ki, 0))
    dkv_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki, j: (b, h, ki, 0))
    dk_full, dv_full = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, walk=walk, stat_rows=stat_rows),
        name="flash_bwd_dkv",
        grid=(B, H, walk.n_outer, walk.steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec] + [_stat_spec(bq, q_index, stat_rows)] * 2,
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[
            _sds((B, H, Sk, hd), k.dtype, vma),
            _sds((B, H, Sk, hd), v.dtype, vma),
        ],
        scratch_shapes=[] if walk.tiled else [pltpu.VMEM((bk, hd), jnp.float32)] * 2,
        compiler_params=_GRID,
        interpret=interpret,
    )(q, k, v, do, *stats(stat_rows))

    if group > 1:
        dk = dk_full.reshape(B, Hkv, group, Sk, hd).sum(axis=2).astype(k.dtype)
        dv = dv_full.reshape(B, Hkv, group, Sk, hd).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk, dv


def tile_walk(sq, sk, block_q=None, block_k=None, causal=True, window=None, kernel="fwd", row_bytes=128):
    """The tiles a kernel (``"fwd"``, ``"dq"`` or ``"dkv"``) computes at this
    shape (``row_bytes``: a head's width in bytes, 64 bfloat16 by default),
    in the order it walks them: (q0, k0, rows, columns, crossed) each —
    ``crossed`` tiles build a mask, the others none; a tile not listed is
    neither fetched for nor computed. The host's view of ``_Walk``: the same
    object the kernels unroll."""
    w = _Walk.of(sq, sk, *_grid_blocks(sq, sk, row_bytes, block_q, block_k), causal, window, kernel)
    out = []
    for (ob, j), off in sorted(w._pairs.items()):
        o0, i0 = ob * w.bo, ob * w.bo - off
        for r0, inner in w.tiles(off):
            for c0, width, d0 in inner:
                tile = (i0 + c0, o0 + r0, width, w.to) if kernel == "dkv" else (o0 + r0, i0 + c0, w.to, width)
                out.append(tile + (d0 is not None,))
    return out
