"""The KV pool rides the layer scan's CARRY and a step rewrites, in place,
only the window it reads.

``forward_with_cache`` used to hand the stacked pool to ``lax.scan`` as a
scanned input and take it back as a stacked output: every call sliced each
layer's whole (B, T, H, hd) K and V out of the pool and wrote them back.
These tests pin the new shape of the program (one layer scan, pool in the
carry, nothing pool- or layer-sized moved under the loop) and its values
against a reference that does what the old code did: take a layer's slice,
scatter the new tokens into it with ``.at[rows, cols].set(mode="drop")``,
attend with the public per-layer ``softmax_context``, put the slice back.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.ops.transformer import inference_ops as ops
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.telemetry.hlo_scopes import scope_table

B, T, L = 4, 32, 3
PARKED = T  # a row (or a pad column) at the cache length writes nothing


def _cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=L, num_heads=4, num_kv_heads=2,
                max_seq_len=64, pos_embedding="rope", norm_type="rmsnorm",
                activation="silu_glu", use_bias=False, tie_embeddings=False, dtype="float32")
    base.update(kw)
    return tf.TransformerConfig(**base)


def _random_cache(cfg, length=T, seed=3):
    """A pool with something in every slot, so an untouched slot can be told
    from a rewritten one."""
    rng = np.random.RandomState(seed)

    def fill(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.randint(-127, 128, a.shape), jnp.int8)
        if a.shape[-1] == 1:  # int8 scales
            return jnp.asarray(rng.uniform(0.01, 0.1, a.shape), a.dtype)
        return jnp.asarray(rng.normal(size=a.shape), a.dtype)

    return jax.tree.map(fill, tf.init_cache(cfg, B, length))


def _fused_positions(width=8):
    """The fused-prefill tick's segment: decode rows ride column 0, row 3
    carries a 6-token prompt chunk at 4..9, every other column is a pad."""
    positions = np.full((B, width), PARKED, np.int32)
    positions[:, 0] = [3, 9, PARKED, 4]
    positions[3, :6] = np.arange(4, 10)
    return jnp.asarray(positions)


# name -> (config overrides, cache length, S, pos, positions, read_len, rows left untouched)
CASES = {
    "scalar_decode": ({}, T, 1, jnp.int32(5), None, None, ()),
    "scalar_segment": ({}, T, 3, jnp.int32(5), None, None, ()),
    "prefill_static": ({}, T, 8, 0, None, None, ()),
    "tick": ({}, T, 1, jnp.asarray([3, 9, PARKED, 0], jnp.int32), None, None, (2,)),
    "fused_segment": ({}, T, 8, jnp.asarray([3, 9, PARKED, 4], jnp.int32), _fused_positions(),
                      None, (2,)),
    "int8": (dict(kv_cache_dtype="int8"), T, 1, jnp.asarray([3, 9, PARKED, 0], jnp.int32),
             None, None, (2,)),
    "ring": (dict(local_attn_windows=(8,) * L, rolling_kv_cache=True), 8, 1, jnp.int32(13),
             None, None, ()),
    "tight_read": ({}, T, 1, jnp.asarray([3, 9, PARKED, 0], jnp.int32), None, 16, (2,)),
    "tight_read_fused": ({}, T, 8, jnp.asarray([3, 9, PARKED, 4], jnp.int32), _fused_positions(),
                         16, (2,)),
    # read buckets above one 128-slot block, with the rule's constant at zero (``block_path``):
    # the rows' write goes into each row's own block, rows in different blocks in one call
    "tick_blocks": (dict(max_seq_len=512), 512, 1, jnp.asarray([3, 130, 512, 255], jnp.int32), None,
                    256, (2,)),
    "int8_blocks": (dict(max_seq_len=512, kv_cache_dtype="int8"), 512, 1,
                    jnp.asarray([127, 128, 512, 511], jnp.int32), None, None, (2,)),
    "scalar_decode_blocks": (dict(max_seq_len=256), 256, 1, jnp.int32(200), None, None, ()),
}


@pytest.fixture(autouse=True)
def block_path(request, monkeypatch):
    """The ``*_blocks`` cases run the rows' write by blocks (a toy row holds
    far fewer bytes than the rule asks for)."""
    params = getattr(getattr(request.node, "callspec", None), "params", {})
    if str(params.get("name", "")).endswith("_blocks"):
        monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)


def _case(name):
    overrides, length, S, pos, positions, read_len, untouched = CASES[name]
    cfg = _cfg(**overrides)
    params = tf.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, cfg.vocab_size, (B, S)), jnp.int32)
    return cfg, params, tokens, _random_cache(cfg, length), pos, positions, read_len, untouched


def _forward(cfg, params, tokens, cache, pos, positions, read_len):
    if isinstance(pos, int):  # the prefill program traces with a literal
        fn = jax.jit(lambda p, t, c: tf.forward_with_cache(p, cfg, t, c, pos, positions, read_len))
        return fn(params, tokens, cache)
    fn = jax.jit(lambda p, t, c, at: tf.forward_with_cache(p, cfg, t, c, at, positions, read_len))
    return fn(params, tokens, cache, pos)


# ---------------------------------------------------------------------------
# the reference: a layer's slice out, the old scatter, the slice back
# ---------------------------------------------------------------------------

def _ref_write_layer(cache, new, pos, positions, ring):
    """The per-layer write as it was before the pool moved into the carry."""
    new = new.astype(cache.dtype)
    rows = jnp.arange(new.shape[0], dtype=jnp.int32)[:, None]
    if ring:
        length = cache.shape[1]
        cols = jnp.where(positions >= pos + new.shape[1] - length, positions % length, length)
        return cache.at[rows, cols].set(new, mode="drop")
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice(cache, new, (0, pos, 0, 0))
    return cache.at[rows, positions].set(new, mode="drop")


def _ref_update(pool_k, pool_v, k, v, pos, positions=None, ring=False, layer=None,
                write_len=None):
    def write(pool, new):
        if isinstance(pool, dict):
            q8, s = kv_cache.quantize_kv(new)
            return {"q8": write(pool["q8"], q8), "s": write(pool["s"], s)}
        return pool.at[layer].set(_ref_write_layer(pool[layer], new, pos, positions, ring))

    return write(pool_k, k), write(pool_v, v)


def _ref_context(q, pool_k, pool_v, pos, layer=None, **kw):
    one = lambda pool: jax.tree.map(lambda a: a[layer], pool)
    return ops.softmax_context(q, one(pool_k), one(pool_v), pos, **kw)


@pytest.fixture
def reference(monkeypatch):
    """``forward_with_cache`` with the model's two cache ops swapped for the
    per-layer reference ones."""
    def run(cfg, params, tokens, cache, pos, positions, read_len):
        with monkeypatch.context() as patched:
            patched.setattr(tf, "update_kv_cache", _ref_update)
            patched.setattr(tf, "softmax_context", _ref_context)
            return tf.forward_with_cache(params, cfg, tokens, cache, pos, positions, read_len)

    return run


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CASES))
def test_pool_is_carried_not_scanned(name):
    """One layer scan; the pool's leaves are in its carry and nothing of the
    pool's shape is among its stacked outputs."""
    cfg, params, tokens, cache, pos, positions, read_len, _ = _case(name)
    jaxpr = jax.make_jaxpr(
        lambda p, t, c: tf.forward_with_cache(p, cfg, t, c, pos, positions, read_len)
    )(params, tokens, cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1, [e.primitive.name for e in jaxpr.jaxpr.eqns]
    scan = scans[0]
    assert scan.params["length"] == cfg.num_layers
    n_carry = scan.params["num_carry"]
    pool_shapes = sorted(a.shape for a in jax.tree.leaves(cache))
    carried = sorted(v.aval.shape for v in scan.outvars[:n_carry] if v.aval.shape in pool_shapes)
    assert carried == pool_shapes
    stacked = [v.aval.shape for v in scan.outvars[n_carry:]]
    assert not [s for s in stacked if s in pool_shapes], stacked


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\][^ ]*\s+([\w\-]+)\((.*)$")


def _moved_under_loops(text):
    """[(opcode, name, elements moved)] for every copy, dynamic-slice and
    dynamic-update-slice whose ``op_name`` lies under ``while/body``: a
    copy or slice moves its result, an update moves its update operand
    (its result is the buffer it updates in place)."""
    paths = scope_table(text)
    elements, out = {}, []
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        name, _, dims, opcode, rest = m.groups()
        elements[name] = int(np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64))
        if "while/body" not in paths.get(name, "") or \
                opcode not in ("copy", "dynamic-slice", "dynamic-update-slice"):
            continue
        moved = elements[name]
        if opcode == "dynamic-update-slice":
            update = re.findall(r"%([\w.\-]+)", rest)[1]
            moved = elements[update]
        out.append((opcode, name, moved))
    return out


TICK_T = 64  # with ffn 48 a layer's K (B x 64 x 2 x 8) outweighs every weight slice of the scan


@pytest.fixture(scope="module")
def toy_tick():
    """The plain serving tick of a toy pool, tight-read at a quarter of the
    pool's length, compiled for the CPU with its cache donated."""
    from deepspeed_tpu import comm
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn

    comm.destroy()
    comm.init_distributed(mesh_shape={"data": 1}, devices=jax.devices()[:1], verbose=False)
    cfg = _cfg(ffn_hidden_size=48)
    params = tf.init(jax.random.PRNGKey(0), cfg)
    sds = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    fn, _, _ = compile_pool_tick_fn(comm.get_mesh(), cfg, None, B, TICK_T, 1, 0.0, 0, 1.0,
                                    read_len=TICK_T // 4)
    row = jax.ShapeDtypeStruct((B,), jnp.int32)
    compiled = fn.lower(sds(params), sds(tf.init_cache(cfg, B, TICK_T)), row, row, row, row, row,
                        row, sds(jax.random.PRNGKey(0))).compile()
    comm.destroy()
    return cfg, len(jax.tree.leaves(params)), compiled.as_text()


def test_tick_moves_nothing_layer_sized_under_the_loop(toy_tick):
    cfg, _, text = toy_tick
    layer = B * TICK_T * cfg.kv_heads * cfg.head_dim
    moved = _moved_under_loops(text)
    assert moved, "no copy / slice / update found under while/body: the reader is blind"
    assert not [m for m in moved if m[2] >= layer], moved


def test_tick_cache_is_aliased_to_its_output(toy_tick):
    """Donation: the returned pool IS the donated one (``input_output_alias``
    names both cache operands, which follow the parameter leaves)."""
    _, n_params, text = toy_tick
    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\{\d+\}: \((\d+),", header)}
    assert {n_params, n_params + 1} <= aliased, header[:400]


# ---------------------------------------------------------------------------
# values
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CASES))
def test_matches_the_per_layer_reference(name, reference):
    cfg, params, tokens, cache, pos, positions, read_len, untouched = _case(name)
    want_logits, want_cache = reference(cfg, params, tokens, cache, pos, positions, read_len)
    logits, new_cache = _forward(cfg, params, tokens, cache, pos, positions, read_len)
    for got, want in zip(jax.tree.leaves(new_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # rows whose output anyone reads (a parked row attends nothing real)
    live = [b for b in range(B) if b not in untouched]
    np.testing.assert_allclose(np.asarray(logits)[live], np.asarray(want_logits)[live],
                               rtol=2e-5, atol=2e-5)
    # parked rows and pad columns leave the pool bit-identical
    for got, before in zip(jax.tree.leaves(new_cache), jax.tree.leaves(cache)):
        for row in untouched:
            np.testing.assert_array_equal(np.asarray(got)[:, row], np.asarray(before)[:, row])
    if name in ("fused_segment", "tight_read_fused"):
        written = np.zeros((B, T), bool)
        cols = np.asarray(positions)
        for b in range(B):
            written[b, cols[b][cols[b] < T]] = True
        got, before = np.asarray(new_cache["k"]), np.asarray(cache["k"])
        np.testing.assert_array_equal(got[:, ~written], before[:, ~written])
        assert not np.array_equal(got[:, written], before[:, written])


@pytest.mark.parametrize("name", ["tick_blocks", "tick"])
def test_the_blocks_cases_write_one_block_a_row(name):
    """What ``*_blocks`` puts under test: the block-write kernel, once for K
    and once for V in the layer scan's body, and no window-sized select."""
    cfg, params, tokens, cache, pos, positions, read_len, _ = _case(name)
    jaxpr = jax.make_jaxpr(
        lambda p, t, c, at: tf.forward_with_cache(p, cfg, t, c, at, positions, read_len)
    )(params, tokens, cache, pos)

    def calls(jaxpr):       # walked, not printed: a time-minor leaf's call is a ``jit`` of its own
        for eqn in jaxpr.eqns:    # (PR 54), and K's and V's share one body in the printed text
            yield eqn.primitive.name == "pallas_call" and eqn.params["name"] == "kv_block_write"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    assert sum(calls(jaxpr.jaxpr)) == (2 if name == "tick_blocks" else 0)


def test_write_past_the_read_window_drops():
    """``write_len`` bounds the slots a pool write touches: a column at or
    beyond it drops like one beyond the cache (the caller's ``read_len``
    covers every live position, so nothing live is lost)."""
    pool = jnp.asarray(np.random.RandomState(0).normal(size=(L, B, T, 2, 8)), jnp.float32)
    new = jnp.ones((B, 1, 2, 8), jnp.float32)
    pos = jnp.asarray([3, 15, 16, PARKED], jnp.int32)
    got, _ = kv_cache.update_kv_cache(pool, pool, new, new, pos, pos[:, None], layer=1, write_len=16)
    want = np.asarray(pool).copy()
    want[1, 0, 3] = 1.0
    want[1, 1, 15] = 1.0
    np.testing.assert_array_equal(np.asarray(got), want)
