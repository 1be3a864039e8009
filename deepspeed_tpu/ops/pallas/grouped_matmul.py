"""Grouped matmul, Pallas TPU kernel: rows sorted by group against one
weight matrix a group (the experts a chip holds).

    out[r] = x[r] @ w[group of r]

The rows come sorted by group with every group padded to a whole number of
``tm``-row tiles, so a row tile belongs to ONE group and the kernel is a
plain tiled matmul whose weight block is chosen per row tile from a
prefetched table (``tile_group``). Tiles past ``num_tiles`` (the buffer is
sized for the worst routing, a tick uses a fraction of it) are skipped:
their block indices are pinned to the last block visited, so they move no
data, and their rows of the output are unspecified. A group no row tile
names is never read: what a step reads of the weights is the groups HIT.

The layout (sort, padding, ``tile_group``) is ``moe/held_experts.py``'s.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret


def _tile(size: int, cap: int) -> int:
    """``size`` itself under the cap, else the largest of cap, cap/2, ...
    512 dividing it, else the largest multiple of 128 under the cap dividing
    it (2,688 = 3 x 896: halving alone ends at 128, 21 blocks a row tile and
    weight rows of 256 bytes a DMA)."""
    if size <= cap:
        return size
    halves = [t for t in (cap, cap // 2) if t >= 512]
    for t in halves + list(range(cap - cap % 128, 0, -128)):
        if size % t == 0:
            return t
    raise ValueError(f"grouped matmul cannot tile a dimension of {size}")


def _kernel(tile_group_ref, num_tiles_ref, layer_ref, x_ref, w_ref, o_ref, acc_ref, *, nk):
    del tile_group_ref, layer_ref  # read by the index maps
    t, k = pl.program_id(0), pl.program_id(2)
    active = t < num_tiles_ref[0]

    @pl.when(active & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _accumulate():
        acc_ref[...] += jax.lax.dot(x_ref[...], w_ref[...],
                                    preferred_element_type=jnp.float32)

    @pl.when(active & (k == nk - 1))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x, w, tile_group, num_tiles, *, tm: int, layer=None,
                   interpret: Optional[bool] = None):
    """x (M, K), rows sorted by group, each group padded to a multiple of
    ``tm``; w (G, K, N), or with ``layer`` (a traced scalar) the stack
    (L, G, K, N) of which layer ``layer`` is used: the kernel fetches its
    blocks straight out of the stack, where a slice taken outside would be a
    copy of the layer's weights; tile_group (M // tm,) int32, the group of
    each row tile; num_tiles (1,) int32, the row tiles in use. Returns
    (M, N) in x's dtype; rows of tiles at or past ``num_tiles`` are
    unspecified."""
    if layer is None:
        w, layer = w[None], 0
    M, K = x.shape
    _, G, K2, N = w.shape
    assert K == K2 and M % tm == 0, (x.shape, w.shape, tm)
    tk, tn = _tile(K, 1024), _tile(N, 1024)
    mt, nt, kt = M // tm, N // tn, K // tk

    def pinned(t, idx, last, num_tiles_ref):
        # a skipped tile names the block the last tile in use ended on
        return jnp.where(t < num_tiles_ref[0], idx, last)

    def row_tile(t, num_tiles_ref):
        return jnp.minimum(t, jnp.maximum(num_tiles_ref[0] - 1, 0))

    def x_index(t, n, k, tile_group_ref, num_tiles_ref, layer_ref):
        return row_tile(t, num_tiles_ref), pinned(t, k, kt - 1, num_tiles_ref)

    def w_index(t, n, k, tile_group_ref, num_tiles_ref, layer_ref):
        return (layer_ref[0], tile_group_ref[row_tile(t, num_tiles_ref)],
                pinned(t, k, kt - 1, num_tiles_ref), pinned(t, n, nt - 1, num_tiles_ref))

    def o_index(t, n, k, tile_group_ref, num_tiles_ref, layer_ref):
        return row_tile(t, num_tiles_ref), pinned(t, n, nt - 1, num_tiles_ref)

    return pl.pallas_call(
        functools.partial(_kernel, nk=kt),
        name="moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(mt, nt, kt),
            in_specs=[pl.BlockSpec((tm, tk), x_index),
                      pl.BlockSpec((None, None, tk, tn), w_index)],
            out_specs=pl.BlockSpec((tm, tn), o_index),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(tile_group.astype(jnp.int32), num_tiles.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), x, w)
