"""The KV cache's data format: the ONE module that knows the order of a
pool's axes, its dtype forms, its bytes and how its heads are sharded.

A cache is a tree of pools the slot manager carries and donates whole:
``{"k", "v"}`` (one kind of layer: pool "kv") or ``{"full": {"k", "v"},
"window": {"k", "v"}}`` (a layer plan). A leaf is one stacked array,
``(L, B, T, kv_heads, width)`` (time before heads) or ``(L, B, kv_heads, T,
width)`` (heads before time), or int8 (``kv_cache_dtype="int8"``) the pair
``{"q8": payload, "s": float32 per-token-per-head scales}`` of such arrays.
A plan with delta-rule or state-space layers has one pool more, ``"state": {"s", "conv"}``
(:class:`StateSpec`), which has NO time axis: a row's recurrent state ``(L,
B, heads, key width, value width)`` float32 and the last taps - 1 inputs of
its convolution ``(L, B, taps - 1, channels)`` are read whole and rewritten
whole every token, never grow, have no read bucket and, since nothing masks
a stale state, are zeroed when a request takes the row (:func:`reset_row`).
A plan's latent-attention layers keep the pool ``"latent": {"c"}``: ONE leaf
``(L, B, 1, T, stored width)``, a token's latent and its one rotated key,
which are the keys AND the values of every head (:func:`latent_width`); it
has a time axis like any keyed pool and is written, read, grown and masked
like one, as a pool of one head.

Three parts: the spec (:func:`specs` chooses the order), what the host
knows (init, length, bytes, sharding, growth, splice) and the device-side
window and write. Attention is not format: ``softmax_context`` and
``layer_plan._attend_cached`` contract the windows handed out here, each in
its pool's order. The configuration is duck-typed (``ops`` is below ``models``).
"""

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from deepspeed_tpu.ops.pallas.interpret import resolve_interpret


class PoolSpec(NamedTuple):
    name: str              # "kv" (one kind of layer) / "full" / "window" / "latent"
    layers: int
    kv_heads: int
    k_width: int
    v_width: int           # 0: a latent pool, whose one leaf "c" (k_width wide) is keys and values
    ring: Optional[int]    # slots of a ring (position p in slot p mod ring); None: the allocation
    heads_first: bool      # the order of a leaf's (time, heads) axes
    int8: bool             # {"q8", "s"} leaves

    @property
    def time_axis(self) -> int:
        return 3 if self.heads_first else 2

    @property
    def heads_axis(self) -> int:
        return 2 if self.heads_first else 3

    def shape(self, batch: int, length: int, width: int) -> tuple:
        T = self.ring or length
        mid = (self.kv_heads, T) if self.heads_first else (T, self.kv_heads)
        return (self.layers, batch) + mid + (width,)


class StateSpec(NamedTuple):
    """The state pool of a plan's delta-rule or state-space layers."""
    layers: int
    heads: int             # value heads (state-space: lane tiles of heads): a (k_width, v_width) state each
    k_width: int
    v_width: int
    tail: int              # convolution taps - 1: the inputs kept
    channels: int          # ... of this many channels

    name = "state"

    def shapes(self, batch: int, dtype) -> dict:
        """{leaf: (shape, dtype)}: the state in float32, the tail in the model's dtype."""
        return {"s": ((self.layers, batch, self.heads, self.k_width, self.v_width), jnp.float32),
                "conv": ((self.layers, batch, self.tail, self.channels), dtype)}


def _is_plan(cfg) -> bool:
    return getattr(cfg, "layer_kinds", None) is not None


def refuse_unserved(cfg):
    """A plan with a short-convolution kind trains and is not served: say so
    where a cache or a tick is asked of it."""
    if _is_plan(cfg) and any(k.mixer == "conv" for k in cfg.layer_kinds):
        raise NotImplementedError(
            "a layer plan with a short-convolution kind trains (forward_plan) and is not served "
            "yet: the state pool has no row that is a convolution's tail and nothing else "
            "(kv_cache.StateSpec, layer_plan.forward_plan_cached)")


def state_spec(cfg) -> Optional[StateSpec]:
    """The state pool of ``cfg``'s cache; None where no layer keeps one."""
    refuse_unserved(cfg)
    kinds = [k for k in cfg.plan if k.pool == "state"] if _is_plan(cfg) else []
    n = len(kinds)
    if not n:
        return None
    if kinds[0].mixer == "ssm":
        # a state-space head's state, stored transposed (state width down, head width along
        # the lanes) with as many heads side by side as fill the 128 lanes: ops/pallas/ssd.py
        from deepspeed_tpu.ops.pallas.ssd import heads_per_tile

        g = heads_per_tile(cfg.ssm_head_dim, cfg.ssm_heads)
        inner = cfg.ssm_heads * cfg.ssm_head_dim
        return StateSpec(n, cfg.ssm_heads // g, cfg.ssm_state, g * cfg.ssm_head_dim,
                         cfg.ssm_conv - 1, inner + 2 * cfg.ssm_groups * cfg.ssm_state)
    channels = 2 * cfg.gdn_key_heads * cfg.gdn_key_dim + cfg.gdn_value_heads * cfg.gdn_value_dim
    return StateSpec(n, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim,
                     cfg.gdn_conv - 1, channels)


LATENT = "latent"
LANES = 128


def latent_width(cfg) -> int:
    """Stored columns of a latent pool's token: the latent (``mla_kv_rank``)
    and the shared rotated key (``mla_rope_dim``) side by side, zero-padded
    to whole 128-lane tiles. GLM-4.7-Flash: 512 + 64 = 576 -> 640, 1,280
    bytes a token and layer in bfloat16 where the numbers themselves are
    1,152. The chip holds no less either way it is cut: a leaf 576 wide is
    padded to 640 lanes or kept time-minor, and of two leaves the 64-wide
    one pads to 128; time-minor is an order the rows' kernel (``mla_decode``,
    which wants a block of tokens as a (tokens, width) matrix) could only
    read through a copy of the pool, and the block write chooses its path
    by ``width % 128``. One leaf of whole tiles is one DMA a block, one
    product for all the scores, and the write path of any other pool."""
    return -(-(cfg.mla_kv_rank + cfg.mla_rope_dim) // LANES) * LANES


def specs(cfg) -> Tuple[PoolSpec, ...]:
    """The pools of ``cfg``'s cache. The ONE place that chooses a layout,
    and it chooses as each model body was written. One kind of layer, time
    before heads: GPT-2 XL's 25 heads of 64 stay time-minor on the chip, and
    heads first a token-sized update re-laid out the whole pool, two pool
    copies a tick (PR 25). A layer plan, heads before time: at MiMo's 4-8
    heads of 192 / 128 a row's keys of one head are the (T, width) matrix
    the contractions and the chunk kernel want, and time first the compiler
    copied the whole value pool into and out of every tick (PR 27). A plan
    walked ``loop_steps`` times keeps every pass's keys and values apart: a
    keyed pool has ``loop_steps`` x its kinds' layers. A choice
    from the shapes alone, one order for both bodies, is ROADMAP Queue 1
    item 3's to make, here. A rolling one-kind cache is a ring as long as
    its allocation: ``ring`` stays None, the write takes ``ring=True``."""
    if not _is_plan(cfg):
        return (PoolSpec("kv", cfg.num_layers, cfg.kv_heads, cfg.head_dim, cfg.head_dim, None,
                         heads_first=False, int8=cfg.kv_cache_dtype == "int8"),)
    refuse_unserved(cfg)
    pools = {}
    for kind in cfg.plan:
        if kind.pool in ("state", None):   # no keys, no time axis: state_spec(); no mixer, no row
            continue
        n = pools[kind.pool].layers if kind.pool in pools else 0
        if kind.pool == LATENT:   # one head, one leaf: latent_width()
            pools[LATENT] = PoolSpec(LATENT, n + 1, 1, latent_width(cfg), 0, None,
                                     heads_first=True, int8=False)
            continue
        pools[kind.pool] = PoolSpec(kind.pool, n + 1, kind.kv_heads, cfg.head_dim,
                                    cfg.v_head_dim, kind.window or None,
                                    heads_first=True, int8=False)
    # a looped plan keeps keys and values a pass: layer i of pass t at t x (layers a pass) + i
    return tuple(s._replace(layers=cfg.loop_steps * s.layers) for s in pools.values())


# -- what the host knows ----------------------------------------------------

def _build(cfg, leaf, state_leaf):
    """``cfg``'s cache tree with ``leaf(spec, width, dtype)`` in every KV
    array's place and ``state_leaf(name)`` in the state pool's."""
    def component(spec, width):
        if spec.int8:
            return {"q8": leaf(spec, width, jnp.int8), "s": leaf(spec, 1, jnp.float32)}
        return leaf(spec, width, cfg.jnp_dtype)

    pools = {s.name: ({"k": component(s, s.k_width), "v": component(s, s.v_width)}
                      if s.v_width else {"c": component(s, s.k_width)})
             for s in specs(cfg)}
    if state_spec(cfg) is not None:
        pools[StateSpec.name] = {name: state_leaf(name) for name in ("s", "conv")}
    return pools if _is_plan(cfg) else pools["kv"]


def _pools(cfg, cache):
    """[(spec, its subtree)]; of a one-kind cache any part will do (one spec)."""
    if not _is_plan(cfg):
        return [(specs(cfg)[0], cache)]
    return [(s, cache[s.name]) for s in specs(cfg)]


def init(cfg, batch_size: int, length: int):
    """The zeroed cache; a ring pool is ``ring`` long whatever ``length``
    is, and the state pool has no length."""
    state = state_spec(cfg)
    return _build(cfg, lambda spec, width, dtype: jnp.zeros(
        spec.shape(batch_size, length, width), dtype),
        lambda name: jnp.zeros(*state.shapes(batch_size, cfg.jnp_dtype)[name]))


def alloc_len(cfg, cache) -> int:
    """Allocated length of the time axis (of a plan's pools, the full pool's
    or the latent pool's, which are as long as each other: the slot manager
    reads a row's room off it)."""
    return next(jax.tree.leaves(sub)[0].shape[spec.time_axis]
                for spec, sub in _pools(cfg, cache) if spec.ring is None)


def grow(cfg, cache, new_len: int):
    """``cache`` zero-padded along time to ``new_len`` (a ring keeps its
    length, the state pool has none). Traced: the caller jits it."""
    more = new_len - alloc_len(cfg, cache)
    grown = {StateSpec.name: cache[StateSpec.name]} if state_spec(cfg) is not None else {}
    for spec, sub in _pools(cfg, cache):
        widths = [(0, 0)] * 5
        widths[spec.time_axis] = (0, more)
        grown[spec.name] = sub if spec.ring else jax.tree.map(lambda a: jnp.pad(a, widths), sub)
    return grown if _is_plan(cfg) else grown["kv"]


def splice_row(big, small, slot):
    """The one-row cache ``small`` over the first slots of row ``slot`` of
    ``big`` (the prefix splice; staler entries beyond stay, causally masked
    until real writes reach them). Either order, dense or int8. Traced."""
    return jax.tree.map(
        lambda b, sm: jax.lax.dynamic_update_slice(b, sm.astype(b.dtype), (0, slot, 0, 0, 0)),
        big, small)


def reset_row(state, slot):
    """The state pool ``state`` (``cache["state"]``) with row ``slot``
    zeroed in every layer, in place: what a request admitted to the row
    starts from. Traced: ``decoding.compile_row_update_fn`` jits it beside
    the row's flip."""
    def zero(leaf):
        blank = jnp.zeros((leaf.shape[0], 1) + leaf.shape[2:], leaf.dtype)
        return jax.lax.dynamic_update_slice(leaf, blank, (0, slot) + (0,) * (leaf.ndim - 2))

    return jax.tree.map(zero, state)


def pool_bytes(cfg, cache) -> dict:
    out = {spec.name: sum(leaf.nbytes for leaf in jax.tree.leaves(sub))
           for spec, sub in _pools(cfg, cache)}
    if state_spec(cfg) is not None:
        out[StateSpec.name] = sum(leaf.nbytes for leaf in jax.tree.leaves(cache[StateSpec.name]))
    return out


def state_bytes_per_row(cfg) -> int:
    """Bytes of ONE row of the state pool over its layers (0 without one):
    what a row's step reads, and writes back."""
    state = state_spec(cfg)
    if state is None:
        return 0
    return sum(math.prod(shape[2:]) * shape[0] * jnp.dtype(dtype).itemsize
               for shape, dtype in state.shapes(1, cfg.jnp_dtype).values())


def read_bytes_by_pool(cfg, read_len: int) -> dict:
    """{pool: HBM bytes ONE row's attention streams from it in a decode step
    that attends ``read_len`` slots}: K and V across its layers (a ring is
    read whole and no further), int8 as payload + a float32 scale a token
    and head; the state pool is read whole whatever ``read_len`` is; a
    latent pool's one leaf at its stored width (:func:`latent_width`). What
    the compiled read touches, so tests assert it."""
    item = jnp.dtype(cfg.jnp_dtype).itemsize
    out = {StateSpec.name: state_bytes_per_row(cfg)} if state_spec(cfg) is not None else {}
    for s in specs(cfg):
        per_head = (s.k_width + s.v_width) * (1 if s.int8 else item) + (2 * 4 if s.int8 else 0)
        out[s.name] = s.layers * min(s.ring or read_len, read_len) * s.kv_heads * per_head
    return out


def read_bytes_per_row(cfg, read_len: int, tp: int = 1) -> int:
    """:func:`read_bytes_by_pool` summed (``kv_bytes_read``, the roofline
    math); ``tp`` (:func:`shard_width`) makes it PER-CHIP: a chip streams its
    head shard only, which is what bounds a bandwidth-limited decode step."""
    assert all(s.kv_heads % tp == 0 for s in specs(cfg)), (specs(cfg), tp)
    return sum(read_bytes_by_pool(cfg, read_len).values()) // tp


def _tensor_split(cfg, mesh) -> int:
    """The ONE rule of where ``tensor`` goes: on the heads axis when the
    heads divide its width evenly (returned), else nowhere (0: every chip
    reads full rows). A layer plan's pools differ in heads: they stay whole."""
    t = 1 if mesh is None else int(mesh.shape.get("tensor", 1))
    return t if not _is_plan(cfg) and cfg.kv_heads % t == 0 else 0


def shard_width(mesh, cfg) -> int:
    """How many ways the heads axis is ACTUALLY split on this mesh."""
    return _tensor_split(cfg, mesh) or 1


def partition_spec(cfg, mesh, batch_axes):
    """The cache tree of ``PartitionSpec``s: ``batch_axes`` on the batch
    axis, ``tensor`` on the HEADS axis of each leaf's own order or nowhere."""
    def leaf(spec, width, dtype):
        axes = [None, batch_axes, None, None, None]
        if _tensor_split(cfg, mesh):
            axes[spec.heads_axis] = "tensor"
        return PartitionSpec(*axes)

    state = state_spec(cfg)
    return _build(cfg, leaf, lambda name: PartitionSpec(
        None, batch_axes, *[None] * (len(state.shapes(1, cfg.jnp_dtype)[name][0]) - 2)))


def spans_chips(mesh) -> bool:
    """Whether a program built on ``mesh`` has its pools on more than one
    chip (split by rows, by heads, or held whole by each)."""
    return mesh is not None and mesh.size > 1


def rows_block_write_bytes(cfg, cache, read_len: Optional[int], mesh=None) -> int:
    """The host's side of :func:`takes_block_write`: the bytes the rows'
    block write fetches and stores for ONE live row (a parked row or an empty
    slot moves nothing) in ONE token step of a program built on ``mesh`` that
    writes one token a row into ``cache`` at read bucket ``read_len`` (None:
    the allocation); 0 where no leaf takes the block path. Off static shapes:
    the row's block of :func:`block_slots` slots, all heads, in and out,
    summed over the leaves that go by blocks and their layer-steps
    (``tick_stats()``'s ``block_write_bytes``, a live row and a token step)."""
    def one(spec, leaf):
        size = spec.ring or read_len or leaf.shape[spec.time_axis]
        if not takes_block_write(size, _row_bytes(leaf, size, spec.heads_first),
                                 _takes_ragged(leaf, spec.heads_first), leaf.shape[4] % LANES != 0):
            return 0
        return 2 * leaf.shape[0] * _row_bytes(leaf, block_slots(leaf), spec.heads_first)

    if spans_chips(mesh):
        return 0
    return sum(one(spec, leaf) for spec, sub in _pools(cfg, cache) for leaf in jax.tree.leaves(sub))


def rows_write_by_blocks(cfg, cache, read_len: Optional[int], mesh=None) -> bool:
    """Whether that program takes the block path in any leaf
    (``tick_stats()``'s ``block_write_ticks``)."""
    return rows_block_write_bytes(cfg, cache, read_len, mesh) > 0


def rows_read_to_length(cfg, cache, read_len: Optional[int], mesh=None) -> bool:
    """The host's side of :func:`takes_length_read`: whether a program built
    on ``mesh`` whose rows attend one token each at read bucket ``read_len``
    (None: the allocation) reads each row of ``cache`` to its own length
    (``tick_stats()``'s ``length_read_ticks``). What ``softmax_context`` sees
    in its arguments the host reads off the configuration: one kind of
    layer, no ALiBi, no local window, no ring, as many key heads as query
    heads, a dense pool."""
    if _is_plan(cfg) or spans_chips(mesh) or specs(cfg)[0].int8:
        return False
    masked_only = (cfg.pos_embedding != "alibi" and cfg.uniform_window is None
                   and not cfg.varying_windows and not cfg.rolling_kv_cache)
    return takes_length_read(cache["k"], read_len, tokens=1, heads=cfg.num_heads,
                             masked_only=masked_only)


# -- on the device: window and write of one stacked array, both orders -------
#                   time before heads                 heads before time
#   rows' window    (B, size, H, x)                   (B, H, size, x)
#   one row's       (1, size, H, x), from slot 0      (H, size, x), from ``start``
#   rows write      new (B, S, H, x), cols (B, S)     new (B, H, x), cols (B,)
#   chunk write     new (1, S, H, x), cols (1, S)     new (W, H, x), cols (W,)
#
# Tokens come as the model body of that order produces them, windows go out
# as its attention contracts them. A column outside [0, size) drops its
# token: the ONE drop rule of every write.

def window(pool, layer, size, *, heads_first: bool, slot=None, start=0):
    """``size`` slots (static) of ``[layer]`` straight out of the stacked
    pool, one ``dynamic_slice``: every row's first ``size``, or with
    ``slot`` (i32 scalar) that ONE row's from ``start`` on."""
    if not heads_first:
        assert isinstance(start, int) and start == 0
        _, B, T, H, x = pool.shape
        first, rows = (0, B) if slot is None else (slot, 1)
        return jax.lax.dynamic_slice(
            pool, (layer, first, 0, 0, 0), (1, rows, size or T, H, x))[0]
    _, B, H, _, x = pool.shape
    if slot is None:
        return jax.lax.dynamic_slice(pool, (layer, 0, 0, 0, 0), (1, B, H, size, x))[0]
    return jax.lax.dynamic_slice(pool, (layer, slot, 0, start, 0), (1, 1, H, size, x))[0, 0]


def write(pool, layer, new, cols, size, *, heads_first: bool, slot=None, start=0):
    """``new`` into slots ``cols`` of ``[layer]``, in place: one token a row,
    or with ``slot`` W tokens into that ONE row at ``start + cols``. One
    token a row of a window that is long enough goes into the row's own time
    block (:func:`_write_blocks`, by :func:`takes_block_write`). Every other
    write goes through the window :func:`window` reads: slice, select and
    update fuse into one pass over it; several tokens a row are laid out
    along it by a one-hot contraction (exact: one term a slot), never
    scattered token by token, so no index is dynamic along the time axis."""
    size = size or pool.shape[3 if heads_first else 2]
    one_token = slot is None and (heads_first or new.shape[1] == 1) and not _split_over_chips
    if one_token and takes_block_write(size, _row_bytes(pool, size, heads_first),
                                       _takes_ragged(pool, heads_first), pool.shape[4] % LANES != 0):
        return _write_blocks(pool, layer, new[:, :, None] if heads_first else new,
                             cols.reshape(-1), size, heads_first)
    if not heads_first:
        merged = _place(window(pool, layer, size, heads_first=False, slot=slot), new, cols)
        return jax.lax.dynamic_update_slice(
            pool, merged[None], (layer, 0 if slot is None else slot, 0, 0, 0))
    merged = window(pool, layer, size, heads_first=True, slot=slot, start=start)
    if slot is None:
        hit = cols[:, None] == jnp.arange(size, dtype=cols.dtype)[None, :]
        merged = jnp.where(hit[:, None, :, None], new.astype(pool.dtype)[:, :, None, :], merged)
        return jax.lax.dynamic_update_slice(pool, merged[None], (layer, 0, 0, 0, 0))
    hit = jnp.arange(size, dtype=cols.dtype)[:, None] == cols[None, :]          # (size, W)
    placed = jnp.einsum("rs,shx->hrx", hit.astype(pool.dtype), new.astype(pool.dtype),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32).astype(pool.dtype)
    merged = jnp.where(hit.any(axis=1)[None, :, None], placed, merged)
    return jax.lax.dynamic_update_slice(pool, merged[None, None], (layer, slot, 0, start, 0))


# The rows' one-token write, by blocks: the smallest extent along time that
# the chip updates in place, which follows the leaf's layout there
# (``block_slots``). A leaf whose width is not whole lanes (GPT-2's 25 x 64,
# MiMo's 192-wide keys) is kept TIME-minor: time runs along the 128 lanes, a
# token is one lane of every tile of its block, and the block is BLOCK slots
# (PR 25, PR 32). A leaf whose width IS whole 128-lane tiles (Ouro's and
# MiMo's values' 128, Qwen3-Next's 256, the latent pool's 640) is kept as it
# is written, time down the sublanes: its block is ONE packed sublane tile,
# 16 slots of bfloat16, 8 of float32, 32 of int8 (PR 45). BLOCK is also the
# block of the rows' read by length and the unit of WHICH programs write by
# blocks (``takes_block_write``), whatever the leaf's own block is.
BLOCK = 128
# The ONE rule of which path a rows' write takes, over static shapes: by
# blocks iff one row's window of one leaf holds this many bytes, and of a
# TIME-minor leaf HALF of them. Measured on a v5e (PERF.md section 6, PR 54,
# call 1; us a call = a layer and leaf, its columns' arithmetic and tokens'
# transpose with it): the window path moves every row's window twice at the
# chip's bandwidth, whatever the rows hold (gpt2-medium's 40 rows of 16 x 64
# bf16: 34.0 / 66.1 / 129.1 / 254.3 for 128 / 256 / 512 / 1,024 slots; GPT-2
# XL's 16 of 25 x 64: 22.1 / 41.8 / 82.1 / 159.7); the time-minor kernel
# moves the LIVE rows' blocks, whatever the window's length: gpt2-medium 2.2
# with no row live, 4.0 with 1, 4.5 with 2, 9.7 with 8, 35.5 with all 40
# (0.85 a row; PR 32's grid step a row cost 48.8 whatever the rows held), XL
# 4.2 with 1, 12.9 with 8, 22.7 with all 16 (PR 32's: 28.6); MiMo's keys, 32
# rows: the 128-slot ring of 8 x 192 41.2 with all live, 38.8 with 30, 7.5
# with 4 (window 40.0), the full pool's 4 x 192 at 256 slots 21.9 (window
# 39.8). So with EVERY row live a 128-slot window costs the same either way
# (+3-4 %) and every parked row is 0.85 us saved; the smallest row measured,
# gpt2-medium's 128 slots (256 KiB), is half the constant. Below it nothing
# was measured and toy models keep the XLA program they lower to.
# The rows' READ by length (``takes_length_read``, PR 39) stands on the
# constant itself and above one block, as it did: at gpt2-medium's 40 rows
# of 256 slots 0.23 ms over all layers against the window's 1.78 with 2 rows
# live, 1.88 against 1.78 only when all 40 fill the window; where EVERY row
# is as long as the read bucket it loses 5-20 % (XL 1,024: 7.77 against
# 6.98), which no static shape can tell. A lane-aligned leaf's blocks are an
# eighth of a time-minor leaf's bytes and its call cheaper still (Ouro: 4.4 us
# against 24-27, PR 45); the threshold was not measured again for them.
BLOCK_WRITE_MIN_ROW_BYTES = 1 << 19


_split_over_chips = False   # True while a program whose pools span several chips is traced


def split_over_chips(fn):
    """``fn``, traced knowing that its pools are split over a mesh of more
    than one chip. The block write is a Mosaic kernel, which the partitioner
    cannot split (and a row's block is another chip's as often as not when
    the BATCH axis is split): such a program keeps the window path, which
    splits by rows and by heads. ``write`` sees shapes, not shardings: the
    programs' builder (``decoding.py``) says so here."""
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        global _split_over_chips
        before, _split_over_chips = _split_over_chips, True
        try:
            return fn(*args, **kwargs)
        finally:
            _split_over_chips = before
    return traced


def takes_block_write(size: int, row_bytes: int, ragged: bool = False, time_minor: bool = False) -> bool:
    """Whether one token a row goes into its row's block (True) or through
    the whole window (False), for a window of ``size`` slots that holds
    ``row_bytes`` a row of one leaf. Of a leaf kept as written a window no
    longer than ``BLOCK`` stays a window; a ``time_minor`` leaf's, which IS its
    block, goes too (the kernel moves the LIVE rows' blocks), from half the
    bytes on. One that is not whole ``BLOCK``s: only where ``ragged``."""
    least = BLOCK_WRITE_MIN_ROW_BYTES // 2 if time_minor else BLOCK_WRITE_MIN_ROW_BYTES
    return (size > BLOCK or time_minor) and (ragged or size % BLOCK == 0) and row_bytes >= least


def _takes_ragged(pool, heads_first: bool = True) -> bool:
    """Whether the block write may be handed a window of ``pool`` that is not
    whole ``BLOCK``s. A leaf whose width is whole 128-lane tiles goes to the
    kernel as it is written, in blocks of one sublane tile of slots
    (:func:`block_slots`): a 320-slot window is twenty whole blocks of
    bfloat16, and a window that is not whole blocks of its own (300 slots)
    only has a last block that reaches past it, inside the allocation, whose
    slots past the window drop their token. What the guard still keeps on the
    window path: a time-minor leaf, which would need part of a lane tile, and
    a lane-aligned leaf whose ALLOCATION is not whole blocks of its own (300
    slots of bfloat16: the kernel's own copy of the last block would reach
    past the leaf)."""
    return pool.shape[4] % LANES == 0 and pool.shape[3 if heads_first else 2] % block_slots(pool) == 0


def _row_bytes(pool, size: int, heads_first: bool) -> int:
    """Bytes of ONE row's ``size`` slots of one layer of a leaf."""
    heads, width = pool.shape[2 if heads_first else 3], pool.shape[4]
    return size * heads * width * pool.dtype.itemsize


def takes_length_read(pool, size: Optional[int], *, tokens: int, heads: int,
                      masked_only: bool) -> bool:
    """Whether the rows' attention over the first ``size`` slots (None: all)
    of a time-before-heads leaf ``pool`` goes through the kernel that reads
    each row to ITS length in whole BLOCKs (``ops/pallas/decode_attention.py``;
    True) or contracts every row's whole window (``_masked_attention``,
    False). The ONE rule, over what a trace can see: ``tokens`` a row with
    per-row depths is 1; ``masked_only``, the causal mask is all there is (no
    ALiBi, no local window, no ring); the pool is dense (the int8 pair is
    dequantized where it is read) with a key head a query head (``heads``)
    and is kept time-minor on the chip (a width of whole 128-lane tiles is
    not: its transpose would be a copy); the window is whole blocks and more
    than one (a 128-slot read IS its block, and the XLA form reads it at the
    chip's bandwidth), and a row's window of one leaf holds
    ``BLOCK_WRITE_MIN_ROW_BYTES`` (the block write's threshold: below it
    nothing was measured, and toy models keep the XLA program); its pools are
    on one chip (a Mosaic kernel is not partitioned)."""
    if _split_over_chips or isinstance(pool, dict) or not masked_only or tokens != 1:
        return False
    size = size or pool.shape[2]
    return (pool.shape[3] == heads and pool.shape[4] % LANES != 0 and size % BLOCK == 0
            and takes_block_write(size, _row_bytes(pool, size, heads_first=False)))


def time_minor(pool):
    """A time-before-heads leaf ``(L, B, T, H, x)`` as ``(L, B, H * x, T)``:
    the order the chip keeps it in where ``x`` is not whole lanes, so the
    view is a bitcast in the compiled tick (``test_tpu_compile.py``). A
    128-token block of it is every head's keys as one 2-D array."""
    L, B, T, H, x = pool.shape
    return pool.transpose(0, 1, 3, 4, 2).reshape(L, B, H * x, T)


def block_slots(pool) -> int:
    """Slots along time of the block the rows' one-token write fetches and
    stores in leaf ``pool``: the smallest extent along time that the chip
    updates in place, read off the leaf's layout. A leaf whose width is whole
    128-lane tiles is kept as it is written, time down the sublanes: one
    packed sublane tile, 16 slots of bfloat16, 8 of float32, 32 of int8 (time
    before heads a slot is whole tiles by itself; no cell has such a leaf,
    and it takes the same block). Any other leaf is kept TIME-minor, a token
    one lane of every tile of its block: ``BLOCK``."""
    return BLOCK if pool.shape[4] % LANES else 32 // pool.dtype.itemsize


def _write_blocks(pool, layer, token, cols, size, heads_first):
    """Each row's one token (``token`` holds one slot along time, ``cols``
    (B,)) into the block of :func:`block_slots` slots that holds its column,
    in place: ONE kernel call that fetches the block of every head of each
    row whose token LANDS, selects the token in where the slot is the
    column's, and stores the block back (``kv_block_write``; the pool is
    aliased to the result and nothing else of it is touched). A column outside
    ``[0, size)`` (a parked row, an empty slot) gets offset -1: its row is
    neither fetched nor stored, in either layout (PR 45, PR 54).

    The kernel has to see the pool in the order the chip keeps it, or the
    compiler copies the pool in and out: a leaf whose width is whole 128-lane
    tiles as it is written (:func:`_write_tile_blocks`), any other TIME-minor
    (:func:`_write_lane_blocks`). Unrolled XLA ops (a ``dynamic_slice``,
    select and ``dynamic_update_slice`` a row) compile in place too, but the
    chip runs each update as a copy of the block's separate 2 KB tiles, 7.7 us
    a row and leaf at XL: no faster than the window (PERF.md section 6, PR 32)."""
    slots = block_slots(pool)
    first = jnp.clip(cols // slots, 0, -(-size // slots) - 1).astype(jnp.int32)
    # a window that is not whole blocks: its last block reaches past it, and a column there drops too
    offset = jnp.where((cols >= 0) & (cols < size), cols - first * slots, -1).astype(jnp.int32)
    by_layout = _write_lane_blocks if pool.shape[4] % LANES else _write_tile_blocks
    out = by_layout(pool, (jnp.asarray(layer, jnp.int32).reshape(1), first, offset),
                    token.astype(pool.dtype), 3 if heads_first else 2)
    # one value for every later reader: a chunk's write that read the kernel's result and updated
    # its transpose was given a copy of the pool (the fused tick, compiled for a described v5e)
    return jax.lax.optimization_barrier(out)


def _write_lane_blocks(pool, scalars, token, time):
    """A TIME-minor leaf (GPT-2's ``(L, B, T, H, 64)``, MiMo's keys ``(L, B,
    H, T, 192)``; ``time`` its time axis): a token is one lane of every tile
    of its ``BLOCK`` slots. The leaf goes to the kernel as its ``(L, B, H, x,
    T)`` transpose, which IS that memory order and costs nothing (no copy in
    the compiled ticks: ``tests/unit/ops/test_tpu_compile.py``,
    ``test_tpu_compile_plan.py``), and the kernel moves the blocks of the LIVE
    rows only (:func:`_lane_blocks_call`, at the end of this file, where a new
    kernel moves no other kernel's lines: one invocation a layer and leaf, the
    rows whose token lands listed first, their blocks through as many VMEM
    buffers as ``_TILE_WRITE_VMEM_BYTES`` holds, never more than the rows, by
    DMAs of its own; a parked row or an empty slot costs a scalar comparison,
    and a call with no live row moves nothing). The tokens go in as one column
    a row with the rows along the lanes, 262 KB a call at gpt2-medium, where
    PR 32's form (a pipelined grid step a row, which fetched and stored EVERY
    row's block beside the row's token blown up to a block in HBM, 10 MB a
    call) cost 48.8 us a call at gpt2-medium's 40 rows whatever they held.

    On a v5e (PERF.md section 6, PR 54, call 1; the table above
    ``BLOCK_WRITE_MIN_ROW_BYTES``), us a call with its columns' arithmetic
    and its tokens' transpose: gpt2-medium 2.2 with no row live, 4.0 with
    one, 4.5 with two, 9.7 with eight, 35.5 with all 40: 2.2 a call, 1.8 the
    first row's two dependent DMAs, 0.85 a row after it (262 KB in and out
    at 620 GB/s); GPT-2 XL's 16 rows of 410 KB 22.7 with every row live
    where the grid step a row cost 28.6; MiMo's keys (32 rows, 4 heads of
    192, 196 KB) 23.4 against 34.3. The ring's depth does not matter
    between 2 and 8 MiB (medium, all 40 live: 35.2 / 35.5 / 35.7 us at 2 / 4
    / 8 MiB; 39.8 at 1 MiB, four buffers): the lane-aligned kernel's budget
    serves both. In the chat cell, 1.3 rows of 40 live, the rows' write is
    48 calls x ~4.2 us = 0.2 ms a tick where it was 2.3. With every row
    live a 128-slot window costs the window path's time (+3-4 %): the rule
    (``takes_block_write``) sends it here for the rows that are not."""
    block_bytes = BLOCK * math.prod(pool.shape[2:]) // pool.shape[time] * pool.dtype.itemsize
    ring = max(min(_TILE_WRITE_VMEM_BYTES // block_bytes, pool.shape[1]), 1)
    return _lane_blocks_call(*scalars, pool, token, time=time, ring=ring, interpret=resolve_interpret())


# VMEM the rows' blocks of a lane-aligned leaf may hold in one grid step of the block write
_TILE_WRITE_VMEM_BYTES = 4 << 20


def _write_tile_blocks(pool, scalars, token, time):
    """A leaf whose width is whole 128-lane tiles, kept as it is written
    (Ouro's ``(L, B, H, T, 128)``, MiMo's values, the latent pool's 640
    columns; ``time`` its time axis): the block is one packed sublane tile of
    slots, 64 KiB of Ouro's 16 heads where 128 slots were 512. The token goes
    in as it is, one slot of time, and is broadcast along the block inside
    the select; the kernel drives its own DMAs, every row's fetch in flight at
    once in one grid step (as many rows a step as ``_TILE_WRITE_VMEM_BYTES``
    holds), and a row that drops its token is neither fetched nor stored. On
    a v5e at Ouro's shapes a call costs 4.2 us where a pipelined grid step a
    row over the same blocks cost 7.6 and the 128-slot block 24-27 (PERF.md
    section 6, PR 45)."""
    rows, slots = pool.shape[1], block_slots(pool)
    block_bytes = slots * math.prod(pool.shape[2:]) // pool.shape[time] * pool.dtype.itemsize
    fits = max(_TILE_WRITE_VMEM_BYTES // block_bytes, 1)
    step = max(n for n in range(1, rows + 1) if rows % n == 0 and n <= fits)
    return _tile_blocks_call(*scalars, pool, token, time=time, step=step, interpret=resolve_interpret())


@functools.partial(jax.jit, static_argnames=("time", "step", "interpret"))
def _tile_blocks_call(layer, first, offset, pool, token, *, time, step, interpret):
    """:func:`_write_tile_blocks`' kernel call, ``step`` rows a grid step. A
    ``jit`` of its own so that it is traced ONCE a shape and process: the
    kernel's three loops cost a trace 15 ms a call site, 3.7 s of the GLM
    cell's warm set-up over its 18 tick programs (PERF.md section 6, PR 45);
    the enclosing program inlines it."""
    rows, slots = pool.shape[1], block_slots(pool)
    block = tuple(slots if a == time else n for a, n in enumerate(pool.shape) if a > 1)

    def kernel(layer_ref, first_ref, offset_ref, pool_ref, token_ref, out_ref, buf, sem):
        slot = jax.lax.broadcasted_iota(jnp.int32, block, time - 2)
        first_row = pl.program_id(0) * step

        def each_live_row(do):       # a loop, not ``step`` copies of its body: a call lowers in 0.1 s, not 1
            def one(r, carry):
                row = first_row + r
                pl.when(offset_ref[row] >= 0)(functools.partial(do, r, row))
                return carry
            jax.lax.fori_loop(0, step, one, 0)

        def blocks_of(ref, row):
            at = [layer_ref[0], row, slice(None), slice(None), slice(None)]
            at[time] = pl.ds(pl.multiple_of(first_ref[row] * slots, slots), slots)
            return ref.at[tuple(at)]

        def fetch(r, row):
            pltpu.make_async_copy(blocks_of(pool_ref, row), buf.at[r], sem.at[r]).start()

        def select_and_store(r, row):
            pltpu.make_async_copy(blocks_of(pool_ref, row), buf.at[r], sem.at[r]).wait()
            buf[r] = jnp.where(slot == offset_ref[row], token_ref[r], buf[r])
            pltpu.make_async_copy(buf.at[r], blocks_of(out_ref, row), sem.at[r]).start()

        def stored(r, row):
            pltpu.make_async_copy(buf.at[r], blocks_of(out_ref, row), sem.at[r]).wait()

        each_live_row(fetch)
        each_live_row(select_and_store)
        each_live_row(stored)

    return pl.pallas_call(
        kernel, name="kv_block_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows // step,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((step,) + token.shape[1:], lambda g, *_: (g, 0, 0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((step,) + block, pool.dtype),
                            pltpu.SemaphoreType.DMA((step,))]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(layer, first, offset, pool, token)


def _place(win, new, cols):
    """Time before heads: ``win`` (B, R, H, x) with ``new`` (B, S, H, x) at
    columns ``cols`` (B, S), distinct within a row. The TPU keeps this pool
    time-minor, where a token-sized scatter or ``dynamic_update_slice``
    makes the compiler re-lay out the whole pool. A single token needs no
    contraction, and without one slice, select and update fuse in place."""
    hit = cols[:, None, :] == jnp.arange(win.shape[1], dtype=cols.dtype)[None, :, None]
    placed = new.astype(win.dtype)  # S == 1: the one token, wherever its column hits
    if new.shape[1] > 1:
        acc = jnp.int32 if jnp.issubdtype(placed.dtype, jnp.integer) else jnp.float32
        placed = jnp.einsum("brs,bshx->brhx", hit.astype(placed.dtype), placed,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=acc).astype(win.dtype)
    return jnp.where(hit.any(-1)[:, :, None, None], placed, win)


# -- on the device: time before heads, S tokens a row, dense or int8 --------

def quantize_kv(x):
    """Per-token-per-head symmetric int8 quantization of (B, S, H, hd)
    keys/values (the int8 write; scales keep the trailing dim)."""
    a = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(cache_component, dtype):
    """{"q8","s"} component -> dense in ``dtype``. Under jit convert and
    multiply fuse into the attention read: HBM traffic is payload + scales."""
    return (cache_component["q8"].astype(jnp.float32) * cache_component["s"]).astype(dtype)


def kv_window(cache_component, read_len: Optional[int] = None, layer=None, slot=None):
    """First ``read_len`` (static; default all) slots of a component (dense
    or int8 pair) as one layer's (B, read_len, H, x): of one layer's
    (B, T, H, x) cache (``layer`` None), or straight out of ``[layer]`` of
    the stacked pool (:func:`window`); with ``slot`` ONE row's, (1,
    read_len, H, x). The window is no longer than what attention reads (the
    tight-read geometry: the bucketed active length, not the allocation)."""
    def one(c):
        if layer is None:
            return c if read_len is None else c[:, :read_len]
        return window(c, layer, read_len, heads_first=False, slot=slot)

    return jax.tree.map(one, cache_component)


def _write_columns(T, new_shape, pos, positions, ring):
    """Cache column (B, S) each new token lands in (outside the cache: dropped)."""
    B, S = new_shape[:2]
    if positions is None:
        positions = jnp.reshape(pos, (-1, 1)) + jnp.arange(S, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (B, S))
    if not ring:
        return positions
    # ring: slot = absolute position mod cache length. Stale tokens of an
    # over-long segment (more new tokens than slots) drop instead of
    # colliding: only its last T positions land, later tokens must win.
    assert jnp.ndim(pos) == 0, "ring cache writes need the aligned (scalar-pos) path"
    return jnp.where(positions >= pos + S - T, positions % T, T)


def update_kv_cache(k_cache, v_cache, k_new, v_new, pos, positions=None, ring=False,
                    layer=None, write_len=None, slot=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write S new keys/values (B, S, H, hd) into (B, T, H, hd) caches (or
    int8 {"q8","s"} components: the write quantizes per token and head).

    ``pos`` python int: static offset (the prefill program). ``pos`` scalar:
    contiguous write at ``pos``. ``pos`` (B,) with ``positions`` (B, S):
    each row's segment at its own depth (speculative verify / draft, the
    serving tick); columns >= T drop, matching ``softmax_context``'s mask.
    ``ring``: positions wrap modulo the cache length (scalar ``pos`` +
    ``positions``). ``layer`` (i32 scalar): the caches are the stacked
    (L, B, T, H, hd) pool the layer scan carries, updated in place at
    ``[layer]``; ``write_len`` (static, the step's ``read_len``) then bounds
    the slots touched: columns at or beyond it drop too, which loses nothing
    because ``read_len`` covers every live position. ``slot`` (with
    ``layer``): the tokens are ONE row's, (1, S, H, hd) at ``positions``
    (1, S), and only that row of the pool is touched (a tick's chunk)."""
    def component(cache, new):
        if isinstance(pos, int) and not ring:
            new = new.astype(cache.dtype)  # the S tokens and nothing else
            if layer is None:
                return jax.lax.dynamic_update_slice(cache, new, (0, pos, 0, 0))
            return jax.lax.dynamic_update_slice(cache, new[None], (layer, 0, pos, 0, 0))
        cols = _write_columns(cache.shape[-3], new.shape, pos, positions, ring)
        if layer is None:
            return _place(cache, new, cols)
        return write(cache, layer, new, cols, write_len, heads_first=False, slot=slot)

    def write_one(cache, new):
        if isinstance(cache, dict):
            q, s = quantize_kv(new)
            return {"q8": component(cache["q8"], q), "s": component(cache["s"], s)}
        return component(cache, new)

    return write_one(k_cache, k_new), write_one(v_cache, v_new)


def traced_over_chips() -> bool:
    """Whether the program being traced holds its pools on more than one chip
    (:func:`split_over_chips`): all that a traced function sees of its mesh."""
    return _split_over_chips


@functools.partial(jax.jit, static_argnames=("time", "ring", "interpret"))
def _lane_blocks_call(layer, first, offset, pool, token, *, time, ring, interpret):
    """:func:`_write_lane_blocks`' kernel call: ONE invocation, no grid step a
    row. The rows whose offset is not negative are listed first (SMEM); their
    blocks ``(H, x, BLOCK)`` of the ``(L, B, H, x, T)`` transpose go through a
    ring of ``ring`` VMEM buffers by DMAs the kernel issues itself: up to
    ``ring`` fetches in flight at the start, then a row's select and the start
    of its store, then the wait for the store ``ring // 2`` rows back, whose
    buffer takes the next fetch. The tokens come as ONE ``(H, x, 128)`` tile a
    128 rows with the ROWS on the lanes (a small transpose outside the kernel);
    a live row's column is picked out by a lane mask and a lane maximum (one
    term is not the fill: exact in every dtype, -0.0 included) and broadcast
    along the block's lanes inside the select. A ``jit`` of its own and loops,
    not copies of a body, for :func:`_tile_blocks_call`'s reasons."""
    order = [a for a in range(5) if a != time] + [time]
    lead = pool.transpose(order)                                     # (L, B, H, x, T): a bitcast on the chip
    rows, block = pool.shape[1], lead.shape[2:4] + (BLOCK,)
    tiles = -(-rows // LANES)
    by_lane = jnp.pad(token.reshape((rows,) + block[:2]), ((0, tiles * LANES - rows), (0, 0), (0, 0)))
    by_lane = by_lane.reshape((tiles, LANES) + block[:2]).transpose(0, 2, 3, 1)
    lag = ring // 2

    def kernel(layer_ref, first_ref, offset_ref, pool_ref, token_ref, out_ref, live_ref, buf, sem):
        lane = jax.lax.broadcasted_iota(jnp.int32, block, 2)

        def list_live(row, n):
            live = offset_ref[row] >= 0

            @pl.when(live)
            def _():
                live_ref[n] = row
            return n + live.astype(jnp.int32)

        n_live = jax.lax.fori_loop(0, rows, list_live, jnp.int32(0))

        def block_of(ref, j):
            row = live_ref[j]
            return ref.at[layer_ref[0], row, :, :, pl.ds(pl.multiple_of(first_ref[row] * BLOCK, BLOCK), BLOCK)]

        def fetch(j):       # the j-th live row's block into its buffer of the ring
            return pltpu.make_async_copy(block_of(pool_ref, j), buf.at[j % ring], sem.at[0, j % ring])

        def store(j):
            return pltpu.make_async_copy(buf.at[j % ring], block_of(out_ref, j), sem.at[1, j % ring])

        def each(lo, hi, do):
            jax.lax.fori_loop(lo, hi, lambda j, carry: do(j) or carry, 0)

        def select_and_store(j):
            row = live_ref[j]
            fetch(j).wait()
            mine = jnp.where(lane == row % LANES, token_ref[row // LANES].astype(jnp.float32), -jnp.inf)
            column = jnp.max(mine, axis=2, keepdims=True).astype(buf.dtype)        # (H, x, 1)
            buf[j % ring] = jnp.where(lane == offset_ref[row], column, buf[j % ring])
            store(j).start()

            @pl.when(j >= lag)      # the store ``lag`` rows back has had its time: its buffer takes the next fetch
            def _():
                store(j - lag).wait()

                @pl.when(j - lag + ring < n_live)
                def _():
                    fetch(j - lag + ring).start()

        each(0, jnp.minimum(n_live, ring), lambda j: fetch(j).start())
        each(0, n_live, select_and_store)
        each(jnp.maximum(n_live - lag, 0), n_live, lambda j: store(j).wait())

    out = pl.pallas_call(
        kernel, name="kv_block_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(by_lane.shape, lambda g, *_: (0, 0, 0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SMEM((rows,), jnp.int32),
                            pltpu.VMEM((ring,) + block, pool.dtype),
                            pltpu.SemaphoreType.DMA((2, ring))]),
        out_shape=jax.ShapeDtypeStruct(lead.shape, lead.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(layer, first, offset, lead, by_lane)
    return out.transpose([order.index(a) for a in range(5)])
