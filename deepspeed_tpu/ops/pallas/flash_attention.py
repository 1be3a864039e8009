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

A GRID step is large and a TILE small (``_Walk``): the step fetches a block of
rows of the outer axis and a MAJOR block of the inner one — a head's whole
sequences where both are short, else up to ``_MAJOR`` rows — and walks that pair
of blocks in tiles whose float32 intermediates fit the vector registers: only
tiles that hold an unmasked pair, a mask built only where a mask's edge runs
through the tile; what the grid fetches and what the MXU works on are two sizes.

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


# Default cap of a GRID block where a caller pins one axis or a length has no longer divisor, chosen on silicon
# (v5e, GPT-2 125M shapes, 2026-07-31): 128-row grid steps lose to 512-row ones by 2x in per-step grid/DMA
# overhead. A head too long for one grid step takes longer blocks still (``_MAJOR``), and none of this is the
# TILE a step computes at a time (see ``_Walk``; PERF.md §6 PRs 41 and 50).
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
        return min(cap, size)
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
_WHOLE = 2048, 512 << 10  # both axes this short (rows; bytes a head, a longer head's blocks' cap too): ONE step a head


def _in_tiles(size):
    """Short enough, and in whole tiles: a head can be walked in one grid step."""
    return size <= _WHOLE[0] and size % _TILE == 0


def _edges(d0, rows, cols, lo, hi):
    """(some, every) of a ``rows`` x ``cols`` tile whose first pair has ``outer
    - inner = d0``: does it hold an unmasked pair, and no masked one? A pair is
    unmasked iff ``lo <= outer - inner <= hi`` (None: unbounded). ``d0`` is a
    Python int (a static walk's) or a traced scalar (a grid step's)."""
    dmin, dmax = d0 - cols + 1, d0 + rows - 1
    some = every = True
    if lo is not None:
        some, every = some & (dmax >= lo), every & (dmin >= lo)
    if hi is not None:
        some, every = some & (dmin <= hi), every & (dmax <= hi)
    return some, every


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
    rows and the inner into (major) blocks of ``bi``; the grid's last axis
    steps through the inner blocks that hold an unmasked pair with the outer
    block, and no others. A step takes its outer block ``to`` rows at a time
    and walks the inner block in tiles: ``tw`` wide where no position is
    masked, ``ti`` where a mask's edge crosses (a mask is built there, and
    only there), none where every position is masked. What a pair of blocks
    holds depends only on their offset ``outer - inner``, which takes few
    values: each is a piece of straight-line code with constant masks, chosen
    by ``pl.when``. Blocks that are whole ``_TILE``s are walked in tiles
    (``in_tiles``), any other pair is ONE tile. ``tiled`` says where the STATE
    lives: a head is one grid step and a row tile's state never leaves the
    registers; else it crosses VMEM scratch once a (row tile, grid step)."""
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
    in_tiles: bool = True  # else a pair of blocks is one tile

    @classmethod
    def of(cls, sq, sk, bq, bk, causal, window, kernel="fwd"):
        tiled = (bq, bk) == (sq, sk) and _in_tiles(sq) and _in_tiles(sk)
        so, si, bo, bi, lo, hi = sq, sk, bq, bk, (0 if causal else None), (None if window is None else window - 1)
        if kernel == "dkv":
            so, si, bo, bi, lo, hi = sk, sq, bk, bq, (None if hi is None else -hi), (None if lo is None else -lo)
        if bo % _TILE or bi % _TILE:  # e.g. a caller's 64-blocks, or one short block of 192 rows
            return cls(so, si, bo, bi, bo, bi, bi, lo, hi, False, False)
        rows, wide = (_TILE, _WIDE[kernel]) if tiled else _MAJOR[kernel][2:]
        to, tw = (rows if bo % rows == 0 else _TILE), (wide if bi % wide == 0 else _TILE)
        return cls(so, si, bo, bi, to, _TILE, tw, lo, hi, tiled)

    n_outer = property(lambda self: self.so // self.bo)

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
                some, every = _edges(off + r0 - c0, self.to, width, self.lo, self.hi)
                return 2 if every else int(some)
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


def _grid_blocks(sq, sk, row_bytes, block_q, block_k, kernel="fwd"):
    """(bq, bk) of a kernel's grid: an explicit block is the caller's; else a head's whole
    sequences where both are short (``_WHOLE``: VMEM holds six of them twice in the dkv kernel)
    and in tiles, so that a head is ONE grid step of straight-line code; else ``_auto_block``
    and longer, the kernel's outer axis and its inner (major) one each by ``_MAJOR``, and the
    grid steps through the pairs of blocks that hold unmasked pairs."""
    whole = all(_in_tiles(s) and s * row_bytes <= _WHOLE[1] for s in (sq, sk))
    if whole or block_q is not None or block_k is not None:
        return tuple(s if whole and cap is None else _auto_block(s, cap) for s, cap in ((sq, block_q), (sk, block_k)))
    return _major_blocks(sq, sk, row_bytes, kernel)


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
    takes rows where the walk is in tiles, else a column as the scratch holds it."""
    j, last, carried = pl.program_id(3), walk.steps - 1, not walk.tiled
    tq, fold, lanes = walk.to, _folds(sm_scale), carried and walk.in_tiles  # lanes: l is a sum a LANE

    def finish(rows, m, l, acc):  # a row tile's output, and its log-sum-exp as a row where the walk is in tiles
        o_ref[0, 0, rows], lse = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype), m + jnp.log(jnp.maximum(l, 1e-20))
        if walk.in_tiles:
            lse_ref[0, 0, :, rows] = _flip(lse)
        else:
            lse_ref[0, 0, rows] = lse

    @functools.partial(jax.jit, static_argnums=0)  # traced once a kind of tile (d0: its mask), lowered in line a tile
    def tile(d0, q, k, v, m, l, acc):
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
        psum = sum(p[:, c:c + 128] for c in range(0, p.shape[1], 128)) if lanes else jnp.sum(p, axis=-1, keepdims=True)
        return m_new, l * corr + psum, acc * corr + jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)

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
                if carried and not inner:  # a window's band misses this row tile of the pair
                    continue
                rows = slice(r0, r0 + tq)
                q = q_ref[0, 0, rows]  # dots run in the input dtype (bf16 MXU
                if fold:               # path, ~4x the f32 rate) with f32 accumulation
                    q = q * sm_scale
                if carried:  # a row of m_scr is one value 128 times: the reduction hands on a column the compiler
                    # knows for lane-replicated (one read off lane 0 is broadcast along the lanes twice a tile)
                    m, acc = jnp.max(m_scr[rows], axis=-1, keepdims=True), acc_scr[rows]
                    l = l_scr[rows] if lanes else jnp.max(l_scr[rows], axis=-1, keepdims=True)
                else:
                    m, l = jnp.full((tq, 1), NEG_INF, jnp.float32), jnp.zeros((tq, 1), jnp.float32)
                    acc = jnp.zeros((tq, v_ref.shape[-1]), jnp.float32)
                for c0, width, d0 in inner:
                    m, l, acc = tile(d0, q, k_ref[0, 0, c0:c0 + width], v_ref[0, 0, c0:c0 + width], m, l, acc)
                if carried:
                    m_scr[rows] = jnp.broadcast_to(m, (tq, m_scr.shape[1]))
                    l_scr[rows] = jnp.broadcast_to(l, (tq, l_scr.shape[1]))
                    acc_scr[rows] = acc
                else:  # one step a head
                    finish(rows, m, l, acc)

    if carried:
        @pl.when(j == last)
        def _finalize():
            for rows in (slice(r0, r0 + tq) for r0 in range(0, walk.bo, tq)):
                l = jnp.sum(l_scr[rows], axis=-1, keepdims=True) if lanes else l_scr[rows, :1]
                finish(rows, m_scr[rows, :1], l, acc_scr[rows])


def _sds(shape, dtype, vma):
    """ShapeDtypeStruct with varying-axis metadata when running inside a
    vma-checked shard_map (sequence-parallel Ulysses local attention)."""
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))


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
        out_specs=[q_spec, _stat_spec(bq, lambda b, h, qi, j: (b, h, qi, 0), walk.in_tiles)],
        out_shape=[
            _sds((B, H, Sq, hd), q.dtype, vma),
            _sds((B, H, 1, Sq) if walk.in_tiles else (B, H, Sq, 1), jnp.float32, vma),
        ],
        scratch_shapes=[] if walk.tiled else [  # m, l (a row's value, or its sum a lane, over 128 lanes), acc
            pltpu.VMEM((bq, 128), jnp.float32), pltpu.VMEM((bq, 128), jnp.float32), pltpu.VMEM((bq, hd), jnp.float32)],
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
    interpreted on CPU backends. ``block_q``/``block_k`` are the GRID blocks,
    by default chosen from the shape (``_grid_blocks``) and walked in tiles
    (``_Walk``); pass values to pin. ``vma``: varying mesh axes to stamp on the
    kernel outputs when called inside a vma-checked ``shard_map`` (e.g.
    ``("sequence",)`` for the Ulysses local attention).

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

_CHUNK_VMEM = 12 << 20  # bytes a grid step's query-side blocks and state may take (they set the query block)
# query rows that go through ONE product against a key block, as whole heads of the group stacked: a key block
# is the MXU's stationary operand, and a head's 256 rows alone do not pay for loading it (ms a call on v5e,
# 256 / 1,024 / 2,048 / 4,096 rows: MiMo's full layer 3.66 / 3.01 / 3.03 / 3.38, Qwen3-Next's 1.15 / 0.98 /
# 0.94 / 0.94, Granite's at 512 / 1,024 / 2,048 1.31 / 1.37 / 1.35: PERF.md section 6, PR 43)
_CHUNK_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class _ChunkWalk:
    """One ``flash_attention_chunk`` call's walk of its (W, T) score matrix,
    query i at key index ``q_off + i``. A grid step belongs to a KEY-VALUE
    head: a block of ``bq`` queries of each of the ``group`` query heads that
    share it, against ``bk`` of its keys, fetched once for all of them. The
    kernel's index maps, its choice of a masked or an unmasked tile and the
    host's count of both (:func:`chunk_tiles`) all ask this object.

    ``q_off`` a Python int, and both lengths short and in tiles (``straight``):
    every edge but ``k_min`` is static, so the key head's keys and values are
    ONE block and a query head is straight-line code over 128-row tiles
    against the key tiles its band holds (``_Walk.tiles``: no tile outside the
    band or above the diagonal, a mask only where an edge crosses), the state
    in registers. Else ``q_off`` is a traced scalar: the grid's last axis
    steps through the key blocks, a pair of blocks is one tile, visited and
    masked by a scalar choice a step, the state in VMEM scratch."""
    W: int
    T: int
    group: int
    bq: int
    bk: int
    window: Optional[int]
    q_off: Optional[int]  # where the walk is straight

    @classmethod
    def of(cls, W, T, group, dk, dv, itemsize, q_off, window):
        straight = (isinstance(q_off, int) and _in_tiles(W) and _in_tiles(T)
                    and T * (dk + dv) * itemsize <= 2 * _WHOLE[1])
        bq, bk = (W, T) if straight else (_auto_block(W, None), _auto_block(T, None))
        # a query row of the step: q and out of every head of the group, twice (the pipeline's two
        # buffers), and where the state crosses steps its float32 acc, m and l (lane-broadcast)
        row = group * (2 * (dk + dv) * itemsize + (0 if straight else (dv + 2 * 128) * 4))
        while bq * row > _CHUNK_VMEM and bq % (2 * _TILE) == 0:
            bq //= 2
        return cls(W, T, group, bq, bk, window, q_off if straight else None)

    @property
    def straight(self):
        return self.q_off is not None

    @property
    def grid(self):
        return self.W // self.bq, self.T // self.bk

    @property
    def hi(self):  # a pair is unmasked iff 0 <= qpos - kpos <= hi, and kpos >= k_min
        return None if self.window is None else self.window - 1

    @property
    def stacked(self):
        """Query heads of the group that a grid step stacks into one product (``_CHUNK_ROWS``)."""
        return max(n for n in range(1, self.group + 1)
                   if self.group % n == 0 and n * self.bq <= max(_CHUNK_ROWS, self.bq))

    @staticmethod
    def begun(k_lo, cols, k_min):
        """``k_min``'s part of :meth:`kind`: (some, every) key of the tile is a key of the row."""
        return k_min < k_lo + cols, k_min <= k_lo

    def kind(self, q_lo, rows, k_lo, cols, k_min):
        """(some, every) of the tile of ``rows`` queries from position ``q_lo``
        and ``cols`` keys from ``k_lo``: does it hold an unmasked pair, and no
        masked one? Python ints (the host's count) or traced scalars (a step's)."""
        (some, every), (begun, whole) = _edges(q_lo - k_lo, rows, cols, 0, self.hi), self.begun(k_lo, cols, k_min)
        return some & begun, every & whole

    def blocks(self, q_lo, k_min):
        """(first, end): the key blocks that hold an unmasked pair with the
        query block at position ``q_lo``."""
        first, end = _visited(q_lo, self.bq, self.bk, self.grid[1], 0, self.hi)
        if isinstance(q_lo, int):
            return max(first, min(k_min // self.bk, end)), end
        return jnp.maximum(first, jnp.minimum(k_min // self.bk, end)), end

    def tiles(self, qi):
        """A straight walk's query block ``qi``: for each 128-row query tile
        (its row in the block, key tiles), a key tile (its first key, its
        width, ``qpos - kpos`` of its first pair where the diagonal or the
        band's edge crosses it, else None). ``k_min`` is not in it."""
        walk = _Walk(self.W, self.T, self.bq, self.T, _TILE, _TILE, _TILE, 0, self.hi, True)
        return walk.tiles(self.q_off + qi * self.bq)

    def visits(self, q_off, k_min):
        """What the kernel does for ONE query head at these offsets (Python
        ints): the tiles it computes, (query row in the chunk, first key, rows,
        keys, whether it builds a mask) each, and the K/V tiles its key-value
        head's steps fetch."""
        tiles, fetched = [], 0
        for qi in range(self.grid[0]):
            q0 = qi * self.bq
            if self.straight:
                fetched = self.T // _TILE  # the head's keys and values once, whole
                for r0, inner in self.tiles(qi):
                    for c0, width, d0 in inner:
                        some, every = self.begun(c0, width, k_min)
                        if some:
                            tiles.append((q0 + r0, c0, _TILE, width, d0 is not None or not every))
                continue
            first, end = self.blocks(q_off + q0, k_min)
            fetched += max(end - first, 1)
            for ki in range(first, end):
                some, every = self.kind(q_off + q0, self.bq, ki * self.bk, self.bk, k_min)
                if some:
                    tiles.append((q0, ki * self.bk, self.bq, self.bk, not every))
        return tiles, fetched


def _chunk_kernel(scal_ref, sink_ref, q_ref, k_ref, v_ref, o_ref, *scratch, sm_scale, walk, has_sink):
    """Online softmax of a key-value head's query heads (``q_ref``, ``o_ref``:
    (group, bq, width)) over the key block in VMEM: float32 scores, statistics
    and accumulator, the input dtype into the MXU; the sink joins the
    denominator at a row's last tile and brings no value."""
    hk, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k_min, (nq, nk), bq, bk = scal_ref[1], walk.grid, walk.bq, walk.bk

    def cut(ok, shape, k_lo):  # keys before the row's first
        late = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1) >= k_min
        return late if ok is None else ok & late

    def tile(q, k, v, ok, m, l, acc, lanes=False):
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32) * sm_scale
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if ok is not None:  # a row all masked so far keeps l = 0
            p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m - m_new)
        if lanes:
            psum = functools.reduce(jnp.add, [p[:, c:c + 128] for c in range(0, p.shape[1], 128)])
        else:
            psum = jnp.sum(p, axis=-1, keepdims=True)
        return (m_new, l * corr + psum,
                acc * corr + jax.lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32))

    def finish(g, m, l, acc):
        if has_sink:
            sink = sink_ref[hk * walk.group + g]
            m_all = jnp.maximum(m, sink)
            corr = jnp.exp(m - m_all)
            l, acc = l * corr + jnp.exp(sink - m_all), acc * corr
        return (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)

    if walk.straight:  # every edge but k_min is static: a query head is straight-line code
        def head(g, tiles):
            edges = {}  # the masks of the diagonal and the band's edge: one a value of d0
            for r0, inner in tiles:
                rows = slice(r0, r0 + _TILE)
                q = q_ref[g, rows]
                state = (jnp.full((_TILE, 1), NEG_INF, jnp.float32), jnp.zeros((_TILE, 1), jnp.float32),
                         jnp.zeros((_TILE, v_ref.shape[-1]), jnp.float32))
                for c0, width, d0 in inner:
                    if d0 is not None and (d0, width) not in edges:
                        edges[d0, width] = _mask(d0, (_TILE, width), 0, 0, walk.hi)
                    ok, cols = edges.get((d0, width)), slice(c0, c0 + width)

                    def step(state, early, ok=ok, cols=cols, c0=c0, width=width):
                        return tile(q, k_ref[cols], v_ref[cols], cut(ok, (_TILE, width), c0) if early else ok, *state)
                    if c0 >= walk.q_off:  # the chunk's own keys: k_min is not past them
                        state = step(state, False)
                        continue
                    some, every = walk.begun(c0, width, k_min)  # a scalar choice
                    paths = [lambda s: s, functools.partial(step, early=True)]
                    if d0 is None:
                        paths.append(functools.partial(step, early=False))
                    which = some.astype(jnp.int32) + ((some & every).astype(jnp.int32) if d0 is None else 0)
                    state = jax.lax.switch(which, paths, state)
                o_ref[g, rows] = finish(g, *state)

        for n in range(nq):
            @pl.when(qi == n)
            def _block(n=n):
                jax.lax.fori_loop(0, walk.group, lambda g, c: (head(g, walk.tiles(n)), c)[1], 0)
        return

    m_scr, l_scr, acc_scr = scratch  # the state crosses the key blocks' steps

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo, k_lo = scal_ref[0] + qi * bq, ki * bk
    some, every = walk.kind(q_lo, bq, k_lo, bk, k_min)

    # l as a sum a LANE where the key block is whole lanes: whole vectors added a step, and ONE reduction
    # along lanes at the row's end (a reduction along lanes a step cost a fifth to two fifths of a call)
    lanes, sub = bk % 128 == 0, walk.stacked
    rows = sub * bq

    def block(masked):
        k, v = k_ref[...], v_ref[...]
        ok = cut(_mask(q_lo - k_lo, (bq, bk), 0, 0, walk.hi), (bq, bk), k_lo) if masked else None
        if masked and sub > 1:  # one mask for the group
            ok = jnp.broadcast_to(ok[None], (sub, bq, bk)).reshape(rows, bk)
        for part in (slice(g, g + sub) for g in range(0, walk.group, sub)):
            l = l_scr[part].reshape(rows, 128)
            m, l, acc = tile(q_ref[part].reshape(rows, -1), k, v, ok, m_scr[part].reshape(rows, 128)[:, :1],
                             l if lanes else l[:, :1], acc_scr[part].reshape(rows, -1), lanes)
            m_scr[part] = jnp.broadcast_to(m, (rows, 128)).reshape(sub, bq, 128)
            l_scr[part] = jnp.broadcast_to(l, (rows, 128)).reshape(sub, bq, 128)
            acc_scr[part] = acc.reshape(sub, bq, -1)

    pl.when(some & jnp.logical_not(every))(functools.partial(block, True))
    pl.when(some & every)(functools.partial(block, False))

    @pl.when(ki == nk - 1)
    def _finalize():
        def head(g, c):
            l = jnp.sum(l_scr[g], axis=-1, keepdims=True) if lanes else l_scr[g, :, :1]
            o_ref[g] = finish(g, m_scr[g, :, :1], l, acc_scr[g])
            return c
        jax.lax.fori_loop(0, walk.group, head, 0)


def flash_attention_chunk(q, k, v, q_off, k_min=0, sink=None, window: Optional[int] = None,
                          sm_scale: Optional[float] = None, interpret: Optional[bool] = None):
    """One row's prefill chunk against that row's cached keys: q (W, H, dk)
    at key-index ``q_off + i`` for query i; k (Hkv, T, dk) and v (Hkv, T, dv)
    head-major, as a layer plan's pools keep a row (dv may differ from dk);
    returns (W, H, dv). Query i attends key j where
    ``k_min <= j <= q_off + i`` and, with ``window``, ``q_off + i - j <
    window``; ``k_min <= q_off`` (the chunk attends its own keys). ``q_off``
    and ``k_min`` are traced scalars (the chunk's depth in its row) or, where
    the caller knows them, Python ints, which make the walk static
    (``_ChunkWalk``): either way key tiles outside that range are neither
    fetched nor computed, each is fetched once for all the query heads of its
    key-value head, and a mask is built only where an edge crosses a tile.
    ``sink`` (H,) float32: a per-head logit that joins the softmax's
    denominator and contributes no value. Forward only."""
    W, H, dk = q.shape
    Hkv, T, dv = v.shape
    group = H // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(dk)
    walk = _ChunkWalk.of(W, T, group, dk, dv, q.dtype.itemsize, q_off, window)
    bq, bk, (nq, nk) = walk.bq, walk.bk, walk.grid
    has_sink = sink is not None
    scal = jnp.stack([jnp.asarray(q_off, jnp.int32), jnp.asarray(k_min, jnp.int32)])
    sink = (jnp.zeros((H,), jnp.float32) if sink is None else sink.astype(jnp.float32))

    def k_index(hk, qi, ki, scal_ref, sink_ref):
        if walk.straight:
            return hk, 0, 0
        first, end = walk.blocks(scal_ref[0] + qi * bq, scal_ref[1])
        return hk, jnp.clip(ki, first, jnp.maximum(end, first + 1) - 1), 0  # held past the end: no new fetch

    def q_index(hk, qi, ki, scal_ref, sink_ref):
        return hk, qi, 0

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, sm_scale=sm_scale, walk=walk, has_sink=has_sink),
        name="flash_chunk_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Hkv, nq, nk),
            in_specs=[pl.BlockSpec((group, bq, dk), q_index),
                      pl.BlockSpec((None, bk, dk), k_index),
                      pl.BlockSpec((None, bk, dv), k_index)],
            out_specs=pl.BlockSpec((group, bq, dv), q_index),
            scratch_shapes=[] if walk.straight else [pltpu.VMEM((group, bq, 128), jnp.float32),
                                                     pltpu.VMEM((group, bq, 128), jnp.float32),
                                                     pltpu.VMEM((group, bq, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((H, W, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM + (20 << 20)),
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
# backward, and the host's view of the walks
# (a Mosaic call's payload carries its source lines, and with them its cache
# key: an edit above this line moves the backward kernels, and every training
# program compiles anew once; an edit above the chunk kernel does the same
# to every layer plan's fused tick)
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

    @functools.partial(jax.jit, static_argnums=0)  # traced once a kind of tile, lowered in line
    def tile(d0, q, do, k, v, lse, delta, dq):
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
        return dq + jax.lax.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    if carried:
        dq_scr, = scratch

        @pl.when(j == 0)
        def _init():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    for cond, tiles in walk.paths():
        @pl.when(cond)
        def _compute(tiles=tiles):
            for r0, inner in tiles:
                if carried and not inner:
                    continue
                rows = slice(r0, r0 + tq)
                q, do = q_ref[0, 0, rows], do_ref[0, 0, rows]
                if walk.in_tiles:
                    lse, delta = _flip(lse_ref[0, 0, :, rows]), _flip(delta_ref[0, 0, :, rows])
                else:
                    lse, delta = lse_ref[0, 0, rows], delta_ref[0, 0, rows]  # (tq, 1)
                dq = dq_scr[rows] if carried else jnp.zeros(q.shape, jnp.float32)
                if fold:
                    q = q * sm_scale
                for c0, width, d0 in inner:
                    dq = tile(d0, q, do, k_ref[0, 0, c0:c0 + width], v_ref[0, 0, c0:c0 + width], lse, delta, dq)
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

    @functools.partial(jax.jit, static_argnums=0)  # traced once a kind of tile, lowered in line
    def tile(d0, ks, v, q, do, lse, delta, dk, dv):
        s = jax.lax.dot_general(ks, q, _NT, preferred_element_type=jnp.float32)  # (tk, width)
        if not fold:
            s = s * sm_scale
        if d0 is not None:
            s = jnp.where(_mask(d0, s.shape, 0, walk.lo, walk.hi), s, NEG_INF)
        p = jnp.exp(s - lse).astype(do.dtype)
        dv = dv + jax.lax.dot(p, do, preferred_element_type=jnp.float32)  # (tk, hd)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        ds = p.astype(jnp.float32) * (dp - delta)
        if not fold:
            ds = ds * sm_scale
        return dk + jax.lax.dot(ds.astype(q.dtype), q, preferred_element_type=jnp.float32), dv

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
                if carried and not inner:
                    continue
                rows = slice(r0, r0 + tk)
                k, v = k_ref[0, 0, rows], v_ref[0, 0, rows]
                ks = k * sm_scale if fold else k
                dk, dv = (dk_scr[rows], dv_scr[rows]) if carried else (jnp.zeros(k.shape, jnp.float32),) * 2
                for c0, width, d0 in inner:
                    cols = slice(c0, c0 + width)
                    dk, dv = tile(d0, ks, v, q_ref[0, 0, cols], do_ref[0, 0, cols], stat(lse_ref, cols),
                                  stat(delta_ref, cols), dk, dv)
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
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (B, H, Sq)

    def stats(rows):  # a free reshape as rows; as columns the chip re-lays them out
        return tuple(a[:, :, None, :] if rows else a[..., None] for a in (lse, delta))

    def walk_of(kernel):  # the grid's blocks are the kernel's own: its outer axis in blocks, its inner in major blocks
        bq, bk = _grid_blocks(Sq, Sk, hd * q.dtype.itemsize, block_q, block_k, kernel)
        return bq, bk, _Walk.of(Sq, Sk, bq, bk, causal, window, kernel)

    bq, bk, walk = walk_of("dq")
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, j: (b, h, qi, 0))
    stat_spec = _stat_spec(bq, lambda b, h, qi, j: (b, h, qi, 0), walk.in_tiles)
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
    )(q, k, v, do, *stats(walk.in_tiles))

    bq, bk, walk = walk_of("dkv")
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
    w = _Walk.of(sq, sk, *_grid_blocks(sq, sk, row_bytes, block_q, block_k, kernel), causal, window, kernel)
    out = []
    for (ob, j), off in sorted(w._pairs.items()):
        o0, i0 = ob * w.bo, ob * w.bo - off
        for r0, inner in w.tiles(off):
            for c0, width, d0 in inner:
                tile = (i0 + c0, o0 + r0, width, w.to) if kernel == "dkv" else (o0 + r0, i0 + c0, w.to, width)
                out.append(tile + (d0 is not None,))
    return out


def chunk_walk(W, T, group, dk, dv, q_off, k_min=0, window=None, static=False, itemsize=2):
    """The host's view of one ``flash_attention_chunk`` call, from the object
    its kernel unrolls and its index maps ask (``_ChunkWalk``; ``static``: the
    call gave ``q_off`` as a Python int): (tiles, fetched) — the score tiles a
    QUERY head computes, (query row in the chunk, first key, rows, keys,
    masked) each, a tile not listed being neither fetched for nor computed,
    and the K/V tiles a KEY-VALUE head's grid steps fetch."""
    q_off, k_min = int(q_off), int(k_min)
    return _ChunkWalk.of(W, T, group, dk, dv, itemsize, q_off if static else None, window).visits(q_off, k_min)


@functools.lru_cache(maxsize=4096)  # a serving loop asks a tick: chunks start at few depths
def chunk_tiles(W, H, Hkv, T, dk, dv, q_off, k_min=0, window=None, static=False, itemsize=2):
    """(score tiles computed, of them masked, K/V tiles fetched) of one call
    over all its heads: :func:`chunk_walk` summed."""
    tiles, fetched = chunk_walk(W, T, H // Hkv, dk, dv, q_off, k_min, window, static, itemsize)
    return H * len(tiles), H * sum(t[-1] for t in tiles), Hkv * fetched


# ---------------------------------------------------------------------------
# the carried regime's blocks (below everything a layer plan's tick carries: see the note above the backward)
# ---------------------------------------------------------------------------

# (outer block, longest inner (MAJOR) block, rows of a tile, columns of a tile no mask crosses) of a kernel whose head
# is too long for ONE grid step. What a step fetches and the pipeline sees is a pair of long blocks; what the MXU
# and the vector unit work on is a tile of it. Read on v5e, 2026-10-03, at 2 x 32 / 8 heads of 64, S 8,192,
# causal, bfloat16, ms a call forward / dq / dkv (PERF.md section 6, PR 50; the parent's 512 x 512 blocks, each ONE
# tile with the state through scratch a tile, 19.81 / 13.35 / 16.35):
#   * blocks, in 128-row tiles: (512, 512) 12.1 / 13.0 / 17.5, (512, 1024) 9.3 / 11.6 / 15.3, (512, 2048) 8.0 /
#     10.8 / 14.2, (1024, 1024) 7.7 / 10.7 / 13.5: a row tile's state crosses scratch once every 1,024 keys, 28 of
#     a head's 64 grid steps lie past the diagonal, and ONE offset of a pair is crossed. (1024, 2048) does not
#     fit the default scoped VMEM;
#   * columns of a tile, at (1024, 1024) and 128 rows: forward 128 / 256 / 512 wide 7.7 / 8.2 / 10.5, dq 256 /
#     512 13.5 / 10.7, dkv 128 / 256 / 512 13.5 / 13.6 / 14.3;
#   * what must NOT be done: a ``fori_loop`` over the row tiles of a pair (one chain of dependent tiles an
#     iteration: 16.4 / 13.6 / 19.6, the parent's time) -- eight independent rows in ONE basic block are what
#     hides a tile's latencies; and a column read off lane 0 of the scratch (a lane broadcast twice a tile).
# A kernel's straight-line code is traced and lowered on EVERY start, tile body by tile body: 100 bodies a
# kernel (128 x 128 tiles) cost the cell's set-up 6.6 s of 37, so the tile below is 256 rows (30 / 22 / 30 bodies,
# +2.8 s): 8.02 / 10.30 / 13.73, the three 32.0 ms where the parent's were 49.5 and 128-row tiles' 31.9.
_MAJOR = {"fwd": (1024, 1024, 256, 256), "dq": (1024, 1024, 256, 512), "dkv": (1024, 1024, 256, 256)}
# (The forward keeps the softmax's denominator as a sum a LANE where the state crosses scratch and tiles are whole
# lanes: whole vectors added a tile and ONE reduction along lanes at the row's end, where a reduction a tile made
# the forward 13.3 ms for 9.3 at (512, 1024).)


def _major_blocks(sq, sk, row_bytes, kernel):
    """(bq, bk) where a head is not one grid step and the caller gave no block: on the kernel's outer and inner
    axis the longest block up to ``_MAJOR``'s that divides the axis and keeps a head's block within ``_WHOLE``'s
    bytes; from ``_DEFAULT_BLOCK`` down ``_auto_block`` (an axis no power of two from 64 up divides raises)."""
    def block(size, cap):
        while cap > _DEFAULT_BLOCK and (size % cap or cap * row_bytes > _WHOLE[1]):
            cap //= 2
        return cap if cap > _DEFAULT_BLOCK else _auto_block(size, None)

    bo, bi = map(block, (sk, sq) if kernel == "dkv" else (sq, sk), _MAJOR[kernel][:2])
    return (bi, bo) if kernel == "dkv" else (bo, bi)

