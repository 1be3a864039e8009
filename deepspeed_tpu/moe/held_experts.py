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
capacity and no token is dropped: the assignments to held experts are sorted
by expert into a buffer, each expert's rows padded to whole row tiles, and
one grouped matmul runs over the experts that got rows. A tick (``grad=False``)
sizes the buffer for the worst routing (every token choosing as many held
experts as it can), takes the Pallas kernel of ``ops/pallas/grouped_matmul.py``, which reads a layer
straight out of the stack and skips the tiles no expert got (21.0 ms a tick where ``ragged_dot`` takes 32.6:
PERF.md section 6, PR 27b) but has no gradient, and comes back by assignment: a token's k rows gathered with
the assignment axis leading, then summed (:func:`_combine`). Training (``grad=True``) takes ``ragged_dot`` over
a bucket of twice the even share (the whole buffer when the routing overflows it); back by rows (:func:`_rows_ffn`).
"""

import functools
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


def held_experts_ffn(h, chosen, weights, experts, first: int, count: int, *, grad: bool = False,
                     valid=None, tm: Optional[int] = None, layer=None, n_experts: int = 0):
    """The held experts' part of the layer's output, (N, D), and the tokens each
    held expert got, (count,). h (N, D); chosen/weights (N, k) from :func:`route`;
    experts {"wg", "wi": (count, D, F), "wo": (count, F, D)}, SwiGLU (no "wg": relu(.)^2,
    :func:`_hidden`); with ``layer`` (a traced scalar) a stack (L, count, ...) whose layer
    the kernel reads in place. ``grad``: the caller differentiates this (``n_experts``: the router's)."""
    N, D = h.shape
    k = chosen.shape[1]
    if tm is None:
        tm = row_tile(N, k)
    lay = layout(chosen, first, count, tm, valid)
    # every line from `_matmul` to `row_tile` keeps its NUMBER: the grouped matmuls' payloads carry their call stack's
    if grad:
        return _trained_ffn(h, chosen, weights, experts, lay, first, tm, layer, n_experts)
    with jax.named_scope(Scope.MOE_EXPERTS):
        x = jnp.take(jnp.concatenate([h, jnp.zeros((1, D), h.dtype)]), lay.src, axis=0)
        # the experts' hidden activation, by the form their parameters have
        act = _hidden(x, experts, functools.partial(_matmul, lay=lay, tm=tm, grad=grad, layer=layer))
        y = _matmul(act, experts["wo"], lay, tm, grad, layer)
        # the way back to the tokens is a sum over a LEADING assignment axis (:func:`_combine`, last in
        # this file): (N, k, D) lays k on the sublanes, which the chip can only do by a padded copy of it all
        out = _combine(y, lay.dest, weights)
    return out.astype(h.dtype), lay.counts


def bucket_rows(n_tokens: int, k: int, count: int, n_experts: int, tm: int) -> int:
    """Rows of the bucket the training path tries first: twice the held
    experts' even share of the assignments plus each expert's padding."""
    even = -(-n_tokens * k * count // n_experts)
    return -(-(2 * even + count * (tm - 1)) // tm) * tm


def _rows_ffn(rows: int, first: int, tm: int, h, chosen, weights, experts, lay: Layout):
    """The layer over the sorted buffer's first ``rows`` rows, which hold every
    assignment whenever ``lay.num_tiles * tm <= rows`` (each expert's padded
    rows are packed from row 0). The way back is by rows too: a row adds its
    result, times its weight, to its token, so nothing here is sized by the
    N * k assignments, most of them not held."""
    N, D = h.shape
    k = chosen.shape[1]
    src = lay.src[:rows]
    x = jnp.take(jnp.concatenate([h, jnp.zeros((1, D), h.dtype)]), src, axis=0)
    act = _hidden(x, experts, functools.partial(_matmul, lay=lay, tm=tm, grad=True, layer=None))
    y = _matmul(act, experts["wo"], lay, tm, True, None).astype(jnp.float32)
    # a row's weight: its token's weight for the expert whose tile the row lies in
    expert = first + jnp.repeat(lay.tile_group[:rows // tm], tm)
    theirs = jnp.take(jnp.concatenate([chosen, jnp.full((1, k), -1, chosen.dtype)]), src, axis=0)
    w = jnp.take(jnp.concatenate([weights, jnp.zeros((1, k), weights.dtype)]), src, axis=0)
    w = jnp.where(theirs == expert[:, None], w, 0.0).sum(axis=1)
    y = jnp.where((src < N)[:, None], y * w[:, None], 0.0)     # rows of unused tiles hold anything
    return jnp.zeros((N + 1, D), jnp.float32).at[src].add(y)[:N].astype(h.dtype)


def _choice(bucket: int, first: int, tm: int, lay: Layout):
    """(the routing's padded rows end inside the bucket, the layer over the
    bucket, the layer over the whole buffer): ``jax.lax.cond``'s first three."""
    def whole(h, chosen, weights, experts, lay):
        with jax.named_scope(Scope.MOE_EXPERTS_WHOLE):
            return _rows_ffn(lay.src.shape[0], first, tm, h, chosen, weights, experts, lay)

    return lay.num_tiles[0] * tm <= bucket, functools.partial(_rows_ffn, bucket, first, tm), whole


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bucketed_ffn(bucket: int, first: int, tm: int, h, chosen, weights, experts, lay: Layout):
    """:func:`_rows_ffn` over the first ``bucket`` rows where the routing's
    padded rows end inside them, over the whole buffer where they do not:
    both compiled, one chosen on the device from the counts, each call."""
    return jax.lax.cond(*_choice(bucket, first, tm, lay), h, chosen, weights, experts, lay)


def _bucketed_fwd(bucket, first, tm, *operands):
    return _bucketed_ffn(bucket, first, tm, *operands), operands


def _bucketed_bwd(bucket, first, tm, operands, g):
    """One branch around the chosen body's own backward. A differentiated
    ``cond`` has each branch return both branches' residuals, the other's as
    zeros of its shapes: on the chip 4.5 ms and 3.3 GB a layer more than this
    (PERF.md section 6, PR 49). Under a checkpoint the replayed forward is
    dead code, so the products run as often as they did."""
    h, chosen, weights, experts, lay = operands
    fits, *bodies = _choice(bucket, first, tm, lay)

    def back(body):
        return lambda h, weights, experts, g: jax.vjp(
            lambda h, weights, experts: body(h, chosen, weights, experts, lay), h, weights, experts)[1](g)

    dh, dw, de = jax.lax.cond(fits, *map(back, bodies), h, weights, experts, g)
    return dh, None, dw, de, None


_bucketed_ffn.defvjp(_bucketed_fwd, _bucketed_bwd)


def _trained_ffn(h, chosen, weights, experts, lay: Layout, first: int, tm: int, layer, n_experts: int):
    """``held_experts_ffn`` for a caller that differentiates it: the layer over
    a bucket of the sorted buffer (:func:`bucket_rows`), over the whole buffer
    only when the routing overflows the bucket, or when the bucket is no
    smaller (half the experts held, or ``n_experts`` not said: a model held
    whole). Nothing has a capacity and no token is dropped either way."""
    if layer is not None:
        experts = {n: w[layer] for n, w in experts.items()}
    count, M = lay.counts.shape[0], lay.src.shape[0]
    bucket = bucket_rows(h.shape[0], chosen.shape[1], count, n_experts or count, tm)
    with jax.named_scope(Scope.MOE_EXPERTS):
        body = functools.partial(_rows_ffn, M) if bucket >= M else functools.partial(_bucketed_ffn, bucket)
        return body(first, tm, h, chosen, weights, experts, lay), lay.counts


def _hidden(x, experts, matmul):
    """The experts' hidden activation from the form of their parameters: with
    a gate's matrix ``wg`` SwiGLU, ``silu(x wg) * (x wi)``; without one the
    un-gated squared ReLU, ``relu(x wi)^2`` (two matrices an expert)."""
    if "wg" in experts:
        return jax.nn.silu(matmul(x, experts["wg"])) * matmul(x, experts["wi"])
    return jnp.square(jax.nn.relu(matmul(x, experts["wi"])))


def row_tile(n_tokens: int, k: int) -> int:
    """Rows of a tile of the sorted buffer: whole MXU tiles (128) where a
    prefill chunk rides the tick, the sublane tile (16) for decode rows alone.
    ``n_tokens * k >= 2048`` said "a chunk" while no model chose more than ten
    experts a token; at top-22, 128 decode rows make 2,816 assignments, of
    which an expert is expected to get 5.5, and a 128-row tile an expert hit
    is twenty-three parts padding. So the tokens themselves must fill two MXU
    tiles as well (every tick of the plans served before this rule keeps its
    tile: those over 2,048 assignments hold 288 tokens and more). A rule on
    the assignments EXPECTED on held experts (``n k count / E``) alone cannot
    keep them: PERF.md section 6, PR 51."""
    return 128 if n_tokens * k >= 2048 and n_tokens >= 256 else 16


def _combine(y, dest, weights):
    """The way back from the sorted buffer to the tokens: y (M, D) the buffer's rows, dest (N, k)
    each assignment's row (M: not held), weights (N, k) float32 -> (N, D) float32, a token's k rows
    times their weights, summed. The assignment axis LEADS: the gather by ``dest.T`` writes (k, N, D)
    itself and the sum runs over whole (N, D) planes, which the compiler fuses into one pass over the
    rows. (N, k, D) puts k on the sublanes of an (8, 128) tile: at top-10 the chip wrote the gathered
    rows out in float32, copied them into a layout padded to 16 and summed the copy, 1.42 ms a layer
    of the Granite cell's fused tick where this takes 0.12 (PERF.md section 6, PR 52)."""
    M = y.shape[0]
    rows = jnp.take(y, jnp.minimum(dest, M - 1).T, axis=0)
    mine = (dest < M).T[:, :, None]                            # rows of unused tiles hold anything
    return jnp.where(mine, rows.astype(jnp.float32) * weights.T[:, :, None], 0.0).sum(axis=0)
