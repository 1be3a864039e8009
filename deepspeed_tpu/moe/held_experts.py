"""An expert layer that is told which experts it holds.

The router scores every expert of the model (``E`` of them) in float32,
with a sigmoid, a token taking the ``k`` experts whose score plus selection
bias is largest (the bias decides the choice and nothing else: "noaux_tc",
DeepSeek-V3's auxiliary-loss-free balancing), or with a softmax over all
``E``, a token taking the ``k`` most probable; either way it weighs them by
their scores normalised over the chosen ``k`` (times a routed scaling
factor, where the model has one). This chip holds the contiguous
experts ``[first, first + count)`` and computes their part of the result:

    y = sum over chosen experts e that are HELD of w_e * Expert_e(h)

What the absent experts would add is left out (the chips that hold them add
it, after an exchange this layer does not make on one chip). Nothing has a
capacity and no token is dropped: the assignments to held experts are
sorted by expert into a buffer sized for the worst routing (every token
choosing as many held experts as it can), each expert's rows padded to whole
row tiles, and one grouped matmul runs over the experts that got rows: the
Pallas kernel of ``ops/pallas/grouped_matmul.py``, or, where the caller needs
a gradient (``grad=True``: the training forward), ``jax.lax.ragged_dot``,
which has one. The kernel has none; it reads a layer straight out of the
stacked experts and skips the tiles no expert got, and in the serving cell
of PR 27 a tick with it takes 21.0 ms where ``ragged_dot`` takes 32.6
(PERF.md section 6, PR 27b).
"""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.hlo_scopes import Scope


class Layout(NamedTuple):
    src: jnp.ndarray         # (M,) the token each buffer row holds; N = the zero row
    dest: jnp.ndarray        # (N, k) each assignment's buffer row; M = not held
    tile_group: jnp.ndarray  # (M // tm,) the held expert of each row tile
    num_tiles: jnp.ndarray   # (1,) row tiles in use
    counts: jnp.ndarray      # (count,) tokens each held expert got


@jax.named_scope(Scope.MOE_ROUTE)
def route(h, gate_w, gate_bias, k: int, score: str = "sigmoid", scale: float = 1.0,
          norm_eps: float = 0.0):
    """h (N, D) -> (chosen experts (N, k) int32, their weights (N, k)
    float32). Scores in float32 whatever the dtype the weights are stored
    in: ``score`` "sigmoid", chosen by score + ``gate_bias``, or "softmax"
    over ALL the experts (``gate_bias`` None: the k largest probabilities);
    either way the chosen scores are normalised over the chosen k (their sum
    plus ``norm_eps``, where a model publishes one), and then multiplied by
    ``scale`` (a model's routed scaling factor)."""
    logits = jnp.dot(h.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + gate_bias.astype(jnp.float32), k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    total = picked.sum(axis=1, keepdims=True)
    weights = picked / (total + norm_eps if norm_eps else total)
    return chosen.astype(jnp.int32), weights if scale == 1.0 else weights * scale


def buffer_rows(n_tokens: int, k: int, count: int, tm: int) -> int:
    """Rows of the sorted buffer: the most assignments the held experts can
    get (a token chooses distinct experts) plus each expert's padding."""
    worst = n_tokens * min(k, count) + count * (tm - 1)
    return -(-worst // tm) * tm


@jax.named_scope(Scope.MOE_ROUTE)
def layout(chosen, first: int, count: int, tm: int, valid=None) -> Layout:
    """Sort the assignments to held experts by expert, each expert's rows
    padded to whole ``tm``-row tiles. ``valid`` (N,) bool leaves a token's
    assignments out (a pad, a parked row)."""
    N, k = chosen.shape
    A, M = N * k, buffer_rows(N, k, count, tm)
    local = chosen.reshape(A) - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & jnp.repeat(valid, k)
    key = jnp.where(held, local, count)                       # not held sorts last
    counts = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    padded = (counts + tm - 1) // tm * tm
    ends = jnp.cumsum(counts)
    pad_ends = jnp.cumsum(padded)
    starts = jnp.concatenate([ends - counts, ends[-1:]])       # one more for key == count
    pad_starts = jnp.concatenate([pad_ends - padded, pad_ends[-1:]])
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    row = pad_starts[sorted_key] + jnp.arange(A, dtype=jnp.int32) - starts[sorted_key]
    row = jnp.where(sorted_key < count, row, M).astype(jnp.int32)
    src = jnp.full((M,), N, jnp.int32).at[row].set((order // k).astype(jnp.int32), mode="drop")
    dest = jnp.zeros((A,), jnp.int32).at[order].set(row).reshape(N, k)
    tile_group = jnp.searchsorted(pad_ends // tm, jnp.arange(M // tm, dtype=jnp.int32),
                                  side="right")
    return Layout(src, dest, jnp.minimum(tile_group, count - 1).astype(jnp.int32),
                  (pad_ends[-1:] // tm).astype(jnp.int32), counts)


def _matmul(x, w, lay: Layout, tm: int, grad: bool, layer):
    if not grad:
        from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

        return grouped_matmul(x, w, lay.tile_group, lay.num_tiles, tm=tm, layer=layer)
    sizes = (lay.counts + tm - 1) // tm * tm
    return jax.lax.ragged_dot(x, w if layer is None else w[layer], sizes.astype(jnp.int32))


def held_experts_ffn(h, chosen, weights, experts, first: int, count: int, *,
                     grad: bool = False, valid=None, tm: Optional[int] = None, layer=None):
    """The held experts' part of the layer's output, (N, D), and the tokens
    each held expert got, (count,). h (N, D); chosen/weights (N, k) from
    :func:`route`; experts {"wg", "wi": (count, D, F), "wo": (count, F, D)},
    SwiGLU: Expert(h) = (silu(h wg) * (h wi)) wo. With ``layer`` (a traced
    scalar) the experts are a stack (L, count, ...) and layer ``layer`` of
    it is used, read in place by the kernel. ``grad``: the caller will
    differentiate this (see the module's docstring)."""
    N, D = h.shape
    k = chosen.shape[1]
    if tm is None:  # whole MXU tiles for a prefill chunk, the sublane tile for decode rows
        tm = 128 if N * k >= 2048 else 16
    lay = layout(chosen, first, count, tm, valid)
    M = lay.src.shape[0]
    with jax.named_scope(Scope.MOE_EXPERTS):
        x = jnp.take(jnp.concatenate([h, jnp.zeros((1, D), h.dtype)]), lay.src, axis=0)
        act = (jax.nn.silu(_matmul(x, experts["wg"], lay, tm, grad, layer))
               * _matmul(x, experts["wi"], lay, tm, grad, layer))
        y = _matmul(act, experts["wo"], lay, tm, grad, layer)
        mine = (lay.dest < M)[:, :, None]                      # rows of unused tiles hold anything
        y = jnp.take(y, jnp.minimum(lay.dest, M - 1).reshape(-1), axis=0).reshape(N, k, D)
        out = jnp.where(mine, y.astype(jnp.float32) * weights[:, :, None], 0.0).sum(axis=1)
    return out.astype(h.dtype), lay.counts
