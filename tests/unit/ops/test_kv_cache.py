"""The KV cache's format behind one module (``ops/transformer/kv_cache.py``):
what the host knows of every layout that exists (shapes, dtypes, bytes,
sharding, growth) and the device-side window and write, the same cases run
against both orders of (time, heads)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from deepspeed_tpu import comm
from deepspeed_tpu.inference.decoding import _decode_shardings
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.parallel.partition import kv_shard_width

PLAN_KINDS = (LayerKind("dense_full", kv_heads=1, rope_theta=1e7, ffn="dense", ffn_size=96),
              LayerKind("moe_window", kv_heads=2, window=8, rope_theta=1e4, sink=True, ffn="moe",
                        ffn_size=32),
              LayerKind("moe_full", kv_heads=1, rope_theta=1e7, ffn="moe", ffn_size=32))


def plan_config(**over):
    base = dict(vocab_size=97, hidden_size=64, num_layers=5, num_heads=4, head_size=24,
                v_head_size=16, rope_dim=8, pos_embedding="rope", norm_type="rmsnorm",
                activation="silu_glu", tie_embeddings=False, use_bias=False, dtype="float32",
                max_seq_len=128, layer_kinds=PLAN_KINDS, layer_plan=(0, 1, 1, 1, 2),
                moe_num_experts=16, moe_top_k=4, moe_experts_held=(4, 8))
    return TransformerConfig(**dict(base, **over))


def one_kind(**over):
    base = dict(vocab_size=64, hidden_size=64, num_layers=2, num_heads=4, max_seq_len=64,
                dtype="bfloat16")
    return TransformerConfig(**dict(base, **over))


LAYOUTS = {
    "dense": one_kind,
    "int8": lambda: one_kind(kv_cache_dtype="int8"),
    "grouped": lambda: one_kind(num_kv_heads=2),
    "plan": plan_config,
}


@pytest.fixture(params=sorted(LAYOUTS))
def cfg(request):
    return LAYOUTS[request.param]()


def pools_of(cfg, cache):
    """{pool name: (spec, its {"k", "v"} subtree)} of a whole cache."""
    specs = kv_cache.specs(cfg)
    if len(specs) == 1 and specs[0].name == "kv":
        return {"kv": (specs[0], cache)}
    return {s.name: (s, cache[s.name]) for s in specs}


# -- the spec and what the host knows ------------------------------------

def test_specs_name_the_pools_and_choose_the_order():
    (kv,) = kv_cache.specs(one_kind(num_kv_heads=2, kv_cache_dtype="int8"))
    assert kv == kv_cache.PoolSpec("kv", 2, 2, 16, 16, None, heads_first=False, int8=True)
    assert (kv.time_axis, kv.heads_axis) == (2, 3)
    full, window = kv_cache.specs(plan_config())
    assert full == kv_cache.PoolSpec("full", 2, 1, 24, 16, None, heads_first=True, int8=False)
    assert window == kv_cache.PoolSpec("window", 3, 2, 24, 16, 8, heads_first=True, int8=False)
    assert (full.time_axis, full.heads_axis) == (3, 2)


def test_init_gives_the_specs_shapes_and_dtypes(cfg):
    B, T = 3, 32
    cache = kv_cache.init(cfg, B, T)
    for name, (spec, sub) in pools_of(cfg, cache).items():
        assert set(sub) == {"k", "v"}
        for part, width in (("k", spec.k_width), ("v", spec.v_width)):
            shape = spec.shape(B, T, width)
            assert shape[0] == spec.layers and shape[1] == B and shape[-1] == width
            assert shape[spec.time_axis] == (spec.ring or T)
            assert shape[spec.heads_axis] == spec.kv_heads
            leaf = sub[part]
            if spec.int8:
                assert set(leaf) == {"q8", "s"}
                assert leaf["q8"].shape == shape and leaf["q8"].dtype == jnp.int8
                assert leaf["s"].shape == shape[:-1] + (1,) and leaf["s"].dtype == jnp.float32
            else:
                assert leaf.shape == shape and leaf.dtype == cfg.jnp_dtype
    assert kv_cache.alloc_len(cfg, cache) == T
    assert not any(np.asarray(leaf, np.float32).any() for leaf in jax.tree.leaves(cache))


def test_pool_bytes_are_the_arrays_bytes(cfg):
    cache = kv_cache.init(cfg, 3, 32)
    by_pool = kv_cache.pool_bytes(cfg, cache)
    assert set(by_pool) == {s.name for s in kv_cache.specs(cfg)}
    assert sum(by_pool.values()) == sum(leaf.nbytes for leaf in jax.tree.leaves(cache))
    for name, (_, sub) in pools_of(cfg, cache).items():
        assert by_pool[name] == sum(leaf.nbytes for leaf in jax.tree.leaves(sub))


def test_read_bytes_are_the_values_the_older_tests_pin():
    # tests/unit/inference/test_kv_tight_read.py: K+V, layers, slots, heads, head_dim, bf16
    assert kv_cache.read_bytes_per_row(one_kind(), 64) == 2 * 2 * 64 * 4 * 16 * 2
    # ... and the int8 payload + a 4-byte scale per (token, head)
    assert kv_cache.read_bytes_per_row(one_kind(kv_cache_dtype="int8"), 64) == 2 * 2 * 64 * 4 * (16 + 4)
    assert kv_cache.read_bytes_per_row(one_kind(), 64, tp=2) == 2 * 2 * 64 * 2 * 16 * 2
    assert kv_cache.read_bytes_by_pool(one_kind(num_kv_heads=2), 64) == {"kv": 2 * 2 * 64 * 2 * 16 * 2}
    # tests/benchmark/test_bench_mimo_v2.py: MiMo-V2.5 at the benchmark's cut, 16,896 slots read
    mimo = plan_config(
        num_layers=7, num_heads=64, head_size=192, v_head_size=128, rope_dim=64, dtype="bfloat16",
        layer_kinds=(LayerKind("dense_full", kv_heads=4, ffn="dense"),
                     LayerKind("moe_window", kv_heads=8, window=128, sink=True, ffn="moe"),
                     LayerKind("moe_full", kv_heads=4, ffn="moe")),
        layer_plan=(0, 1, 1, 1, 1, 1, 2))
    assert kv_cache.read_bytes_by_pool(mimo, 16896) == {"full": 5120 * 16896,
                                                       "window": 5 * 8 * 320 * 2 * 128}
    assert kv_cache.read_bytes_per_row(mimo, 16896) == 5120 * 16896 + 5 * 8 * 320 * 2 * 128
    # a ring is read whole and no further; a read shorter than the ring reads that much
    assert kv_cache.read_bytes_by_pool(plan_config(), 4)["window"] == 3 * 4 * 2 * 40 * 4


@pytest.mark.parametrize("name, tensor, width", [
    ("dense", 2, 2), ("int8", 2, 2), ("grouped", 2, 2), ("dense", 4, 4),
    ("grouped", 4, 1),       # 2 heads do not split 4 ways: the cache replicates
    ("three_heads", 2, 1),   # nor 3 heads 2 ways
    ("plan", 2, 1),          # a plan's pools differ in heads: whole on every chip
    ("dense", 1, 1),
])
def test_tensor_sits_on_the_heads_axis_and_shard_width_counts_its_shards(name, tensor, width):
    comm.destroy()
    cfg = (one_kind(hidden_size=48, num_heads=3) if name == "three_heads" else LAYOUTS[name]())
    mesh = comm.build_mesh({"data": 1, "tensor": tensor}, devices=jax.devices()[:tensor])
    try:
        cache = kv_cache.init(cfg, 2, 16)
        pspecs = kv_cache.partition_spec(cfg, mesh, ("data", "fsdp"))
        assert jax.tree.structure(pspecs) == jax.tree.structure(cache)
        _, cache_sh = _decode_shardings(mesh, cfg, 2)   # what every decode program is built with
        assert jax.tree.leaves(cache_sh) == [NamedSharding(mesh, p) for p in jax.tree.leaves(pspecs)]
        assert kv_cache.shard_width(mesh, cfg) == kv_shard_width(mesh, cfg) == width
        for _, (spec, sub) in pools_of(cfg, cache).items():
            sharded = pools_of(cfg, cache_sh)[spec.name][1]
            for leaf, sh in zip(jax.tree.leaves(sub), jax.tree.leaves(sharded)):
                on_tensor = [axis for axis, names in enumerate(sh.spec) if names == "tensor"]
                assert on_tensor in ([], [spec.heads_axis])
                pieces = [n // m for n, m in zip(leaf.shape, sh.shard_shape(leaf.shape))]
                assert pieces[spec.heads_axis] == width          # the shards it really has
                assert all(p == 1 for a, p in enumerate(pieces) if a != spec.heads_axis)
    finally:
        comm.destroy()


def test_growth_pads_the_time_axis_and_nothing_else(cfg):
    cache = jax.tree.map(lambda leaf: jnp.ones_like(leaf), kv_cache.init(cfg, 2, 8))
    grown = kv_cache.grow(cfg, cache, 16)
    assert jax.tree.structure(grown) == jax.tree.structure(cache)
    assert kv_cache.alloc_len(cfg, grown) == 16
    for name, (spec, sub) in pools_of(cfg, grown).items():
        for new, old in zip(jax.tree.leaves(sub), jax.tree.leaves(pools_of(cfg, cache)[name][1])):
            if spec.ring:   # a ring keeps its length
                assert new.shape == old.shape
                continue
            want = list(old.shape)
            want[spec.time_axis] = 16
            assert list(new.shape) == want and new.dtype == old.dtype
            head, tail = np.split(np.asarray(new, np.float32), [8], axis=spec.time_axis)
            assert (head == 1).all() and (tail == 0).all()


@pytest.mark.parametrize("name", ["dense", "int8", "grouped"])   # a plan's pools have no splice yet
def test_splice_lays_a_one_row_cache_over_the_first_slots_of_its_row(name):
    cfg = LAYOUTS[name]()
    big = kv_cache.init(cfg, 3, 16)
    small = jax.tree.map(lambda leaf: jnp.ones_like(leaf), kv_cache.init(cfg, 1, 4))
    out = jax.jit(kv_cache.splice_row)(big, small, jnp.int32(1))
    for leaf in jax.tree.leaves(out):
        a = np.asarray(leaf, np.float32)
        assert (a[:, 1, :4] == 1).all() and a.sum() == a[:, 1, :4].size


# -- window and write: the same cases against both orders -----------------

L, B, H, T, X = 3, 4, 2, 16, 8


def pool_of(heads_first, seed=0):
    spec = kv_cache.PoolSpec("p", L, H, X, X, None, heads_first, False)
    values = np.random.RandomState(seed).normal(size=spec.shape(B, T, X)).astype(np.float32)
    return jnp.asarray(values), spec


def by_time(pool, spec):
    """A pool, or a window of it, as numpy (..., T, H, x)."""
    a = np.asarray(pool)
    return np.swapaxes(a, -3, -2) if spec.heads_first else a


def rows_tokens(new, cols, heads_first):
    """(B, H, x) tokens at (B,) columns, as that order's body hands them over."""
    return (new, cols) if heads_first else (new[:, None], cols[:, None])


def chunk_tokens(new, cols, heads_first):
    """(W, H, x) tokens at (W,) columns of one row."""
    return (new, cols) if heads_first else (new[None], cols[None])


@pytest.mark.parametrize("heads_first", [False, True], ids=["time_first", "heads_first"])
class TestBothOrders:
    def test_windows_are_the_first_slots_of_every_row_or_of_one(self, heads_first):
        pool, spec = pool_of(heads_first)
        whole = by_time(pool, spec)                                    # (L, B, T, H, x)
        rows = kv_cache.window(pool, jnp.int32(1), 8, heads_first=heads_first)
        np.testing.assert_array_equal(by_time(rows, spec), whole[1, :, :8])
        row = kv_cache.window(pool, jnp.int32(2), 8, heads_first=heads_first, slot=jnp.int32(3))
        np.testing.assert_array_equal(by_time(row, spec).reshape(8, H, X), whole[2, 3, :8])

    @pytest.mark.parametrize("size", [8, T])
    def test_rows_write_round_trips_and_drops_outside_the_window(self, heads_first, size):
        pool, spec = pool_of(heads_first)
        new = np.random.RandomState(1).normal(size=(B, H, X)).astype(np.float32)
        cols = np.asarray([0, 7, 8, T], np.int32)    # in, the window's last slot, past 8, parked
        toks, at = rows_tokens(jnp.asarray(new), jnp.asarray(cols), heads_first)
        out = jax.jit(lambda p: kv_cache.write(p, jnp.int32(1), toks, at, size,
                                               heads_first=heads_first))(pool)
        want = by_time(pool, spec).copy()
        for b, c in enumerate(cols):
            if c < size:
                want[1, b, c] = new[b]
        np.testing.assert_array_equal(by_time(out, spec), want)
        back = kv_cache.window(out, jnp.int32(1), size, heads_first=heads_first)
        np.testing.assert_array_equal(by_time(back, spec), want[1, :, :size])

    def test_chunk_write_lays_w_tokens_into_one_row(self, heads_first):
        pool, spec = pool_of(heads_first)
        W = 6
        new = np.random.RandomState(2).normal(size=(W, H, X)).astype(np.float32)
        cols = np.asarray([2, 3, 4, 5, 8, 9], np.int32)     # the last two fall outside 8 slots
        toks, at = chunk_tokens(jnp.asarray(new), jnp.asarray(cols), heads_first)
        out = jax.jit(lambda p: kv_cache.write(p, jnp.int32(2), toks, at, 8,
                                               heads_first=heads_first, slot=jnp.int32(1)))(pool)
        want = by_time(pool, spec).copy()
        want[2, 1, 2:6] = new[:4]
        np.testing.assert_allclose(by_time(out, spec), want, rtol=0, atol=0)
        row = kv_cache.window(out, jnp.int32(2), 8, heads_first=heads_first, slot=jnp.int32(1))
        np.testing.assert_array_equal(by_time(row, spec).reshape(8, H, X), want[2, 1, :8])

    def test_a_write_touches_no_other_layer_and_no_other_row(self, heads_first):
        pool, spec = pool_of(heads_first)
        toks, at = chunk_tokens(jnp.ones((2, H, X)), jnp.asarray([0, 1], jnp.int32), heads_first)
        out = kv_cache.write(pool, jnp.int32(0), toks, at, 4, heads_first=heads_first,
                             slot=jnp.int32(2))
        changed = np.argwhere((by_time(out, spec) != by_time(pool, spec)).any(axis=(-1, -2)))
        assert sorted(map(tuple, changed)) == [(0, 2, 0), (0, 2, 1)]


# -- the rows' one-token write by blocks: the window path's bits, every case --

BIG_T = 512


def big_pool(heads_first, dtype, batch, seed=0, heads=5, width=X):
    spec = kv_cache.PoolSpec("p", L, heads, width, width, None, heads_first, False)
    values = np.random.RandomState(seed).normal(size=spec.shape(batch, BIG_T, width)) * 20
    return jnp.asarray(values, dtype), spec


def both_paths(monkeypatch, build):
    """``build()`` -> (jitted fn, its args); run and lowered on the window
    path (the constant out of reach) and on the block path (at zero)."""
    out = {}
    for path, least in (("window", 1 << 60), ("block", 0)):
        monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", least)
        fn, args = build()
        out[path] = (jax.tree.map(np.asarray, fn(*args)), fn.lower(*args).as_text())
    return out["window"], out["block"]


def _rows_columns(rows, reach):
    """The columns of a call's eight rows. ``mixed``: 0, 127, 128, the
    window's last slot, one past it (dropped), the pool's length (a parked
    row), a negative one, and rows in different blocks in one call; ``none``:
    every row parked, below 0 or past the window (the kernel moves nothing);
    ``one`` / ``few``: one or a scattered three live among parked rows;
    ``all``: every row live."""
    parked = [BIG_T, -1, reach, BIG_T + 7, -5, BIG_T, reach, -1]
    return np.asarray({
        "mixed": [0, 127, 128 % reach, reach - 1, reach, BIG_T, -1, 300 % reach],
        "none": parked,
        "one": parked[:5] + [reach - 1] + parked[6:],
        "few": [parked[0], 127, parked[2], parked[3], 0, parked[5], reach - 1, parked[7]],
        "all": [0, 127, 128 % reach, reach - 1, 1, 300 % reach, 64, 126],
    }[rows], np.int32)


# every order, window (a 128-slot one IS its block: PR 54) and dtype with the mixed rows; the rows that
# are none / one / a few / all live, and the cells' two time-minor shapes (GPT-2's time before heads at
# 64 wide, MiMo's keys heads first at 192 wide), in bfloat16 at the shortest and the whole window
BLOCK_WRITES = [(hf, size, dtype, "mixed", 5, X)
                for dtype in (jnp.float32, jnp.bfloat16, jnp.int8) for size in (128, 256, 512, None)
                for hf in (False, True)]
BLOCK_WRITES += [(hf, size, jnp.bfloat16, rows, 5, X) for rows in ("none", "one", "few", "all")
                 for size in (128, None) for hf in (False, True)]
BLOCK_WRITES += [(hf, size, jnp.bfloat16, rows, 2, width) for hf, width in ((False, 64), (True, 192))
                 for size in (128, None) for rows in ("mixed", "few")]


@pytest.mark.parametrize(
    "heads_first, size, dtype, rows, heads, width", BLOCK_WRITES,
    ids=["-".join(["heads_first" if hf else "time_first", f"size{size or 'full'}", jnp.dtype(dtype).name, rows,
                   f"{heads}x{width}"]) for hf, size, dtype, rows, heads, width in BLOCK_WRITES])
def test_block_write_leaves_the_window_writes_bits(monkeypatch, heads_first, size, dtype, rows, heads, width):
    """The kernel's pool is the window write's, bit for bit (``_rows_columns``:
    which rows are live), and the kernel touches nothing but the live rows'
    slots."""
    reach = size or BIG_T
    cols = _rows_columns(rows, reach)
    pool, spec = big_pool(heads_first, dtype, len(cols), heads=heads, width=width)
    new = jnp.asarray(np.random.RandomState(1).normal(size=(len(cols), heads, width)) * 20, dtype)
    toks, at = rows_tokens(new, jnp.asarray(cols), heads_first)

    def build():
        return jax.jit(lambda p: kv_cache.write(p, jnp.int32(1), toks, at, size,
                                                heads_first=heads_first)), (pool,)

    (window, window_text), (block, block_text) = both_paths(monkeypatch, build)
    assert window_text != block_text                    # two programs ...
    np.testing.assert_array_equal(block.view(np.uint8), window.view(np.uint8))   # ... one pool
    want = by_time(pool, spec).copy()
    for b, c in enumerate(cols):
        if 0 <= c < reach:
            want[1, b, c] = np.asarray(new)[b]
    np.testing.assert_array_equal(by_time(block, spec), want)


@pytest.mark.parametrize("name", ["dense", "int8", "grouped"])
@pytest.mark.parametrize("ring", [False, True], ids=["rows", "ring"])
@pytest.mark.parametrize("length", [256, 128], ids=["len256", "len128"])
def test_block_write_through_update_kv_cache_dense_int8_and_a_ring(monkeypatch, name, ring, length):
    """The one-kind body's entry: rows at their own depths (the tick; the
    last row is parked), and a rolling cache's scalar-depth step whose
    columns wrap; an int8 pool is written component by component (``q8`` and
    ``s``); a cache of ONE block (PR 54: its window goes to the kernel)."""
    cfg = LAYOUTS[name]()
    cache = jax.tree.map(
        lambda a: jnp.asarray(np.random.RandomState(2).randint(-90, 90, a.shape), a.dtype),
        kv_cache.init(cfg, 4, length))
    new = jax.random.normal(jax.random.PRNGKey(0), (4, 1, cfg.kv_heads, cfg.head_dim), jnp.float32)
    pos = jnp.int32(300) if ring else jnp.asarray([0, 127, 128 % length, length], jnp.int32)

    def build():
        def step(k, v):
            return kv_cache.update_kv_cache(k, v, new, new * 2, pos,
                                            None if ring else pos[:, None], ring=ring,
                                            layer=jnp.int32(1), write_len=None)
        return jax.jit(step), (cache["k"], cache["v"])

    (window, window_text), (block, block_text) = both_paths(monkeypatch, build)
    assert window_text != block_text
    for got, want in zip(jax.tree.leaves(block), jax.tree.leaves(window)):
        np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))
    assert any((a != np.asarray(b)).any() for a, b in zip(jax.tree.leaves(block),
                                                         jax.tree.leaves(cache)))


def lane_pool(heads_first, dtype, batch, T, width=128, heads=2):
    """A leaf whose width is whole lane tiles, values in [1, 2) (int8: [0, 100)), and the rows'
    tokens in [-2, -1) (int8: [-100, 0)): a written element differs from what it replaced."""
    spec = kv_cache.PoolSpec("p", L, heads, width, width, None, heads_first, False)
    rs = np.random.RandomState(7)
    whole = jnp.issubdtype(dtype, jnp.integer)
    draw = (lambda shape: rs.randint(0, 100, shape)) if whole else (lambda shape: 1 + rs.rand(*shape))
    pool = jnp.asarray(draw(spec.shape(batch, T, width)), dtype)
    new = jnp.asarray(-1 - draw((batch, heads, width)), dtype)
    return pool, new, spec


@pytest.mark.parametrize("heads_first", [True, False], ids=["heads_first", "time_first"])
@pytest.mark.parametrize("T,size", [(320, 256), (320, None), (320, 300), (300, None), (300, 256)],
                         ids=["256of320", "320", "300of320", "300", "256of300"])
@pytest.mark.parametrize("dtype,slots", [(jnp.bfloat16, 16), (jnp.float32, 8), (jnp.int8, 32)],
                         ids=["bfloat16", "float32", "int8"])
def test_block_write_of_a_lane_aligned_leaf_moves_one_sublane_tile_and_the_windows_bits(
        monkeypatch, heads_first, T, size, dtype, slots):
    """A leaf kept as written (width of whole lanes): the block is one packed
    sublane tile of slots, the token goes in as it is, and the pool is the
    window path's bit for bit. Columns at a tile's first and last slot and
    either side of it, the window's last slot, one past it (dropped; in
    300-of-320 the slot is in the last block's reach), the allocation (a
    parked row) and a negative one; a window of 300 slots is no multiple of
    any dtype's block: of 320 its last block reaches past the window, inside
    the allocation; an ALLOCATION of 300 keeps the window path (the guard
    ``_takes_ragged`` is still for), while its 256-slot window goes by blocks."""
    reach = size or T
    cols = np.asarray([0, 15, 16, 17, 127, 128, reach - 1, reach, T, -1], np.int32)
    pool, new, spec = lane_pool(heads_first, dtype, len(cols), T)
    by_blocks = T % slots == 0 or reach % kv_cache.BLOCK == 0
    assert kv_cache.block_slots(pool) == slots and kv_cache._takes_ragged(pool, heads_first) == (T % slots == 0)
    toks, at = rows_tokens(new, jnp.asarray(cols), heads_first)
    calls, kernel = [], kv_cache._write_blocks
    monkeypatch.setattr(kv_cache, "_write_blocks", lambda *a: calls.append(a[2].shape) or kernel(*a))

    def build():
        return jax.jit(lambda p: kv_cache.write(p, jnp.int32(1), toks, at, size,
                                                heads_first=heads_first)), (pool,)

    (window, window_text), (block, block_text) = both_paths(monkeypatch, build)
    assert (window_text != block_text) == by_blocks
    token = (len(cols), 2, 1, 128) if heads_first else (len(cols), 1, 2, 128)      # as it is
    assert set(calls) == ({token} if by_blocks else set())
    np.testing.assert_array_equal(block.view(np.uint8), window.view(np.uint8))
    changed = (by_time(block, spec) != by_time(pool, spec))        # (L, B, T, H, x)
    for b, c in enumerate(cols):
        hit = changed[1, b].sum(axis=(-1, -2))                     # elements a slot of this row
        want = np.zeros(T, int)
        if 0 <= c < reach:
            want[c] = 2 * 128                                      # heads x width, and nothing else
        np.testing.assert_array_equal(hit, want)
    assert not changed[0].any()


def block_write_call(fn, *args):
    """(grid, block shapes, operand shapes, scratch shapes, aliases) of the
    one ``kv_block_write`` equation in ``fn``'s jaxpr."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "kv_block_write":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from find(sub)

    (eqn,) = find(jax.make_jaxpr(fn)(*args).jaxpr)
    grid = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(d, "block_size", None) for d in m.block_shape) for m in grid.block_mappings]
    return (grid.grid, blocks, [v.aval.shape for v in eqn.invars],
            [a.shape for a in grid.scratch_avals], eqn.params["input_output_aliases"])


@pytest.mark.parametrize("case,shape,heads_first,want", [
    # GPT-2's pool (time before heads, 64 wide), MiMo's keys (heads first, 192 wide) and an int8
    # pool's scales: TIME-minor on the chip. Recorded anew in PR 54 (PR 32's call was a grid step a
    # row beside the token broadcast to a block, (4, 5, 64, 128)): ONE step, the (L, B, H, x, T)
    # transpose left where it is, the tokens as one (H, x, 128) tile with the rows on the lanes, and
    # for scratch the list of live rows, a ring of 128-slot blocks (all four rows' fit) and the
    # DMAs' semaphores, a fetch's and a store's a buffer
    ("gpt2", (3, 4, 512, 5, 64), False,
     ((1,), [(3, 4, 5, 64, 512), (1, 5, 64, 128), (3, 4, 5, 64, 512)],
      [(1,), (4,), (4,), (3, 4, 5, 64, 512), (1, 5, 64, 128)], [(4,), (4, 5, 64, 128), (2, 4)])),
    ("mimo_keys", (3, 4, 2, 512, 192), True,
     ((1,), [(3, 4, 2, 192, 512), (1, 2, 192, 128), (3, 4, 2, 192, 512)],
      [(1,), (4,), (4,), (3, 4, 2, 192, 512), (1, 2, 192, 128)], [(4,), (4, 2, 192, 128), (2, 4)])),
    ("int8_scales", (3, 4, 512, 5, 1), False,
     ((1,), [(3, 4, 5, 1, 512), (1, 5, 1, 128), (3, 4, 5, 1, 512)],
      [(1,), (4,), (4,), (3, 4, 5, 1, 512), (1, 5, 1, 128)], [(4,), (4, 5, 1, 128), (2, 4)])),
    # whole lanes: the leaf as written and left where it is (the kernel copies the rows' blocks of
    # one sublane tile itself, all rows in one grid step), the token as it is
    ("ouro", (3, 4, 2, 320, 128), True,
     ((1,), [(3, 4, 2, 320, 128), (4, 2, 1, 128), (3, 4, 2, 320, 128)],
      [(1,), (4,), (4,), (3, 4, 2, 320, 128), (4, 2, 1, 128)], [(4, 2, 16, 128), (4,)])),
    ("latent", (3, 4, 1, 512, 640), True,
     ((1,), [(3, 4, 1, 512, 640), (4, 1, 1, 640), (3, 4, 1, 512, 640)],
      [(1,), (4,), (4,), (3, 4, 1, 512, 640), (4, 1, 1, 640)], [(4, 1, 16, 640), (4,)])),
    ("whole_lanes_time_first", (3, 4, 512, 5, 128), False,
     ((1,), [(3, 4, 512, 5, 128), (4, 1, 5, 128), (3, 4, 512, 5, 128)],
      [(1,), (4,), (4,), (3, 4, 512, 5, 128), (4, 1, 5, 128)], [(4, 16, 5, 128), (4,)])),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_kernels_call_follows_the_leafs_layout(monkeypatch, case, shape, heads_first, want):
    """The ``kv_block_write`` equation's grid, block shapes, operand shapes
    and scratch, read off the jaxpr: a time-minor leaf's (PR 54) has no grid
    step a row and no token blown up to a block; a lane-aligned leaf's block
    (the kernel's buffer) is ``block_slots`` high and its token one slot."""
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    pool = jnp.zeros(shape, jnp.bfloat16)
    rows, heads = shape[1], shape[2 if heads_first else 3]
    toks, at = rows_tokens(jnp.ones((rows, heads, shape[4])), jnp.arange(rows, dtype=jnp.int32), heads_first)
    *call, aliases = block_write_call(
        lambda p: kv_cache.write(p, jnp.int32(1), toks, at, None, heads_first=heads_first), pool)
    assert tuple(call) == want
    assert aliases == ((3, 0),)                                    # the pool, in place


@pytest.mark.parametrize("heads_first", [False, True], ids=["time_first", "heads_first"])
def test_a_time_minor_leafs_live_rows_go_through_as_many_buffers_as_the_budget_holds(monkeypatch, heads_first):
    """Eight rows of which six are live, their 128-slot blocks 20 KiB each:
    a ring of eight buffers under the budget (never more than the rows), of
    three at 60 KiB (the store one row back is waited for before its buffer
    takes the fourth row's fetch), of two, and of one where not even one fits
    (fetch, select, store, a row at a time); the pool is the same every way,
    and the window write's."""
    pool, spec = big_pool(heads_first, jnp.float32, 8)
    cols = jnp.asarray([0, 127, 128, BIG_T, 511, -1, 300, 129], jnp.int32)
    new = jnp.asarray(np.random.RandomState(1).normal(size=(8, 5, X)), jnp.float32)
    toks, at = rows_tokens(new, cols, heads_first)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 1 << 60)
    window = np.asarray(kv_cache.write(pool, jnp.int32(2), toks, at, None, heads_first=heads_first))
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    a_block = 128 * 5 * X * 4
    for budget, ring in ((kv_cache._TILE_WRITE_VMEM_BYTES, 8), (3 * a_block, 3), (2 * a_block + 1, 2), (100, 1)):
        monkeypatch.setattr(kv_cache, "_TILE_WRITE_VMEM_BYTES", budget)
        write = lambda p: kv_cache.write(p, jnp.int32(2), toks, at, None, heads_first=heads_first)   # traced anew
        grid, _, _, scratch, _ = block_write_call(write, pool)
        assert grid == (1,) and scratch == [(8,), (ring, 5, X, 128), (2, ring)]
        np.testing.assert_array_equal(np.asarray(write(pool)).view(np.uint8), window.view(np.uint8))
    assert int((window != np.asarray(pool)).sum()) == 6 * 5 * X


def test_a_lane_aligned_leafs_rows_go_as_many_a_grid_step_as_the_budget_holds(monkeypatch):
    """Six rows whose blocks are 8 KiB each: all in one step under the
    budget, three a step at 24 KiB, one a step where not even one fits; the
    pool is the same every way."""
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    pool, new, spec = lane_pool(True, jnp.bfloat16, 6, 256)
    cols = jnp.asarray([0, 17, 256, 255, -1, 131], jnp.int32)
    outs = []
    for budget, steps in ((kv_cache._TILE_WRITE_VMEM_BYTES, 1), (3 * 8192, 2), (5 * 8192, 2), (100, 6)):
        monkeypatch.setattr(kv_cache, "_TILE_WRITE_VMEM_BYTES", budget)
        write = lambda p: kv_cache.write(p, jnp.int32(0), new, cols, None, heads_first=True)   # traced anew
        grid, _, _, scratch, _ = block_write_call(write, pool)
        assert grid == (steps,) and scratch[0] == (6 // steps, 2, 16, 128)
        outs.append(np.asarray(write(pool)))
    for out in outs[1:]:
        np.testing.assert_array_equal(out.view(np.uint8), outs[0].view(np.uint8))
    assert int((outs[0] != np.asarray(pool)).sum()) == 4 * 2 * 128        # four rows land


@pytest.mark.parametrize("case", ["size128_of_whole_lanes", "size64", "not_whole_blocks", "three_tokens_a_row",
                                  "one_rows_chunk", "pools_span_chips"])
def test_writes_the_rule_leaves_alone_lower_to_the_window_paths_text(monkeypatch, case):
    """A window shorter than a block, a 128-slot window of a leaf kept as
    written (its block is a sublane tile: the window stays; a TIME-minor
    leaf's 128-slot window, this test's ``size128`` until PR 54, goes to the
    kernel now and is a case of
    ``test_block_write_leaves_the_window_writes_bits``), one that is not whole
    blocks, several tokens a row, the chunk's one-row write, and a program
    whose pools are split over several chips (the partitioner cannot split
    the kernel): the text the parent's ``write`` lowers to, with the constant
    at zero."""
    pool, _ = big_pool(False, jnp.float32, 4, width=128 if case == "size128_of_whole_lanes" else X)
    size = {"size128_of_whole_lanes": 128, "size64": 64, "not_whole_blocks": 200}.get(case, 256)
    S = 3 if case in ("three_tokens_a_row", "one_rows_chunk") else 1
    rows = 1 if case == "one_rows_chunk" else 4
    new = jnp.ones((rows, S, 5, pool.shape[4]))
    cols = jnp.arange(rows * S, dtype=jnp.int32).reshape(rows, S)
    slot = jnp.int32(2) if case == "one_rows_chunk" else None

    def build():
        fn = lambda p: kv_cache.write(p, jnp.int32(1), new, cols, size, heads_first=False, slot=slot)
        return jax.jit(kv_cache.split_over_chips(fn) if case == "pools_span_chips" else fn), (pool,)

    (window, window_text), (block, block_text) = both_paths(monkeypatch, build)
    assert window_text == block_text
    np.testing.assert_array_equal(block, window)


def test_the_rule_reads_static_shapes_and_the_host_reads_the_same_rule(monkeypatch):
    """One rule for every pool: a window longer than a block, of whole
    blocks, whose row holds the constant's bytes or more. The benchmark's
    cells: every read bucket above 128 slots of GPT-2 XL's 16 rows (0.82 MB a
    row at 256 slots) and of gpt2-medium's 40 (0.52 MB) goes by blocks, and
    MiMo's full pool from a 512-slot read on; a toy model's rows never do."""
    least = kv_cache.BLOCK_WRITE_MIN_ROW_BYTES
    xl, medium, mimo_k = 25 * 64 * 2, 16 * 64 * 2, 4 * 192 * 2
    assert kv_cache.takes_block_write(1024, 1024 * xl) and kv_cache.takes_block_write(256, 256 * xl)
    assert kv_cache.takes_block_write(256, 256 * medium)
    assert kv_cache.takes_block_write(512, 512 * mimo_k) and not kv_cache.takes_block_write(256, 256 * mimo_k)
    assert not kv_cache.takes_block_write(128, 1 << 40) and not kv_cache.takes_block_write(1000, 1 << 40)
    assert kv_cache.takes_block_write(256, least) and not kv_cache.takes_block_write(256, least - 1)
    assert not kv_cache.takes_block_write(512, 512 * 4 * 16 * 4)     # this file's toy pool
    # a TIME-minor leaf (PR 54: the kernel moves the live rows' blocks alone): its 128-slot window too,
    # from half the bytes on, which is gpt2-medium's 128-slot row (256 KiB); MiMo's keys from 256 slots
    # of the full pool (4 heads) and in the window layers' 128-slot ring (8 heads); never part of a block
    minor = lambda size, slot_bytes: kv_cache.takes_block_write(size, size * slot_bytes, time_minor=True)
    assert minor(128, medium) and minor(128, xl) and minor(1024, medium) and 128 * medium == least // 2
    assert minor(256, mimo_k) and not minor(128, mimo_k) and minor(128, 2 * mimo_k)
    assert not minor(64, 1 << 30) and not minor(200, 1 << 30) and not minor(128, least // 2 // 128 - 1)
    assert not kv_cache.takes_block_write(512, 512 * 4 * 16 * 4, time_minor=True)
    # the host's side, from a cache's own leaves: any leaf by blocks; a ring never; several chips never
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 256 * 4 * 16 * 2)
    cfg = one_kind(max_seq_len=512)
    cache = kv_cache.init(cfg, 2, 512)
    assert kv_cache.rows_write_by_blocks(cfg, cache, None)
    assert kv_cache.rows_write_by_blocks(cfg, cache, 256)
    assert kv_cache.rows_write_by_blocks(cfg, cache, 128)         # time-minor: half the bytes will do
    assert not kv_cache.rows_write_by_blocks(cfg, cache, 64)
    # ... and its bytes are ONE live row's: a block of 128 slots, in and out, K and V, two layers
    assert kv_cache.rows_block_write_bytes(cfg, cache, 128) == 2 * 2 * 2 * 128 * 4 * 16 * 2
    assert kv_cache.rows_block_write_bytes(cfg, cache, None) == kv_cache.rows_block_write_bytes(cfg, cache, 128)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 256 * 4 * 16 * 2 + 2)
    assert kv_cache.rows_write_by_blocks(cfg, cache, 256) and not kv_cache.rows_write_by_blocks(cfg, cache, 128)
    plan = plan_config(max_seq_len=512)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 256 * 1 * 16 * 4)
    assert kv_cache.rows_write_by_blocks(plan, kv_cache.init(plan, 2, 512), 256)   # the full pool
    assert kv_cache.rows_write_by_blocks(plan, kv_cache.init(plan, 2, 512), 128)   # its values, 16 wide
    assert not kv_cache.rows_write_by_blocks(plan, kv_cache.init(plan, 2, 512), 64)
    two = comm.build_mesh({"data": 1, "tensor": 2}, devices=jax.devices()[:2])
    one = comm.build_mesh({"data": 1, "tensor": 1}, devices=jax.devices()[:1])
    assert kv_cache.spans_chips(two) and not kv_cache.spans_chips(one) and not kv_cache.spans_chips(None)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    assert kv_cache.rows_write_by_blocks(cfg, cache, None, one)
    assert not kv_cache.rows_write_by_blocks(cfg, cache, None, two)


def test_heads_first_row_window_starts_where_it_is_told():
    pool, spec = pool_of(True)   # time before heads reads a row from slot 0
    row = kv_cache.window(pool, jnp.int32(0), 4, heads_first=True, slot=jnp.int32(1),
                          start=jnp.int32(6))
    np.testing.assert_array_equal(by_time(row, spec), by_time(pool, spec)[0, 1, 6:10])


def test_heads_first_chunk_write_through_a_window_that_starts_mid_row():
    pool, spec = pool_of(True)
    new = np.random.RandomState(3).normal(size=(3, H, X)).astype(np.float32)
    out = kv_cache.write(pool, jnp.int32(1), jnp.asarray(new), jnp.asarray([0, 1, 4], jnp.int32), 4,
                         heads_first=True, slot=jnp.int32(0), start=jnp.int32(5))
    want = by_time(pool, spec).copy()
    want[1, 0, 5:7] = new[:2]                       # column 4 is outside the 4-slot window
    np.testing.assert_array_equal(by_time(out, spec), want)


def test_int8_write_quantises_and_the_read_dequantises():
    cfg = one_kind(kv_cache_dtype="int8", dtype="float32")
    cache = kv_cache.init(cfg, 2, 8)
    new = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 4, 16), jnp.float32)
    pos = jnp.asarray([3, 8], jnp.int32)            # the second row is parked: nothing lands
    k, v = kv_cache.update_kv_cache(cache["k"], cache["v"], new, new * 2, pos, pos[:, None],
                                    layer=jnp.int32(1), write_len=8)
    back = np.array(kv_cache.dequantize_kv(kv_cache.kv_window(k, 8, jnp.int32(1)), jnp.float32))
    scales = np.asarray(k["s"])[1, 0, 3]
    assert np.all(np.abs(back[0, 3] - np.asarray(new)[0, 0]) <= scales / 2 + 1e-6)
    back[0, 3] = 0
    assert not back.any() and not np.asarray(k["q8"])[0].any() and np.asarray(v["q8"])[1, 0, 3].any()


@pytest.mark.parametrize("width,by_blocks", [(128, True), (64, False)], ids=["whole-lanes", "time-minor"])
def test_a_window_that_is_not_whole_blocks_goes_by_blocks_where_the_leaf_can_take_it(monkeypatch, width,
                                                                                    by_blocks):
    """A 320-slot allocation (two ``BLOCK``s and 64 slots): a heads-first
    leaf whose width is whole lane tiles writes its rows' tokens by blocks of
    its own (forty whole 8-slot blocks of float32: none reaches past the
    leaf) and equals the window path slot for slot; a time-minor leaf keeps
    the window path; the rows' read by length never takes such a window."""
    rs = np.random.RandomState(5)
    pool = jnp.asarray(rs.normal(size=(2, 3, 2, 320, width)), jnp.float32)
    new = jnp.asarray(rs.normal(size=(3, 2, width)), jnp.float32)
    cols = jnp.asarray([300, 5, 320], jnp.int32)                  # the last row is parked
    window = kv_cache.write(pool, jnp.int32(1), new, cols, 320, heads_first=True)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 1)
    assert kv_cache.takes_block_write(320, 1, ragged=kv_cache._takes_ragged(pool)) is by_blocks
    assert not kv_cache.takes_block_write(320, 1) and kv_cache.takes_block_write(256, 1)
    calls, kernel = [], kv_cache._write_blocks
    monkeypatch.setattr(kv_cache, "_write_blocks", lambda *a: calls.append(a[4]) or kernel(*a))
    block = kv_cache.write(pool, jnp.int32(1), new, cols, 320, heads_first=True)
    assert calls == ([320] if by_blocks else [])
    np.testing.assert_array_equal(block, window)
    assert int((np.asarray(block) != np.asarray(pool)).sum()) == 2 * 2 * width
    flat = jnp.zeros((1, 3, 320, 2, 64), jnp.float32)             # time before heads, 2 heads of 64
    assert not kv_cache.takes_length_read(flat, 320, tokens=1, heads=2, masked_only=True)
    assert kv_cache.takes_length_read(flat, 256, tokens=1, heads=2, masked_only=True)
