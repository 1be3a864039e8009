"""A layer plan whose every layer is latent attention (MLA): the cache is one
pool of ONE leaf, a token's latent and its one rotated key, which is the keys
and the values of all heads. The pool's format behind ``kv_cache.py``, the
plan's checks (a plan of latent layers alone passes: the latent pool bears a
row's length), the rows' kernel against the einsum form at ragged lengths,
what a parked row, an empty slot and a reused slot may and may not touch, the
block path of the rows' write, and every place that refuses a plan saying why
truthfully. (The mathematics against the reference: ``tests/benchmark/
test_bench_glm4_moe_lite.py``.)"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from deepspeed_tpu.inference import ContinuousBatchingEngine
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.pallas.mla_attention import (expanded_entries, mla_decode,
                                                    mla_decode_reference, mla_expand)
from deepspeed_tpu.ops.transformer import kv_cache

KINDS = (LayerKind("dense", mixer="mla", rope_theta=1e6, ffn="dense", ffn_size=96),
         LayerKind("moe", mixer="mla", rope_theta=1e6, ffn="moe", ffn_size=32))


def config(**over):
    base = dict(vocab_size=97, hidden_size=64, num_layers=3, num_heads=4, head_size=24,
                v_head_size=32, pos_embedding="rope", norm_type="rmsnorm", activation="silu_glu",
                tie_embeddings=False, use_bias=False, dtype="float32", attn_impl="pallas",
                max_seq_len=256, layer_kinds=KINDS, layer_plan=(0, 1, 1), rope_interleaved=False,
                mla_q_rank=48, mla_kv_rank=40, mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=32,
                moe_num_experts=8, moe_top_k=2, moe_shared_size=32, moe_shared_gated=False,
                moe_routed_scale=1.8)
    return TransformerConfig(**dict(base, **over))


@pytest.fixture(scope="module")
def model():
    return TransformerModel(config())


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def test_parameters_of_a_latent_layer_and_an_ungated_shared_expert(model, params):
    dense, moe = params["layers"]["dense"], params["layers"]["moe"]
    assert {k: v.shape for k, v in moe["mla"].items()} == {
        "wdq": (2, 64, 48), "q_norm": (2, 48), "wuq": (2, 48, 4 * 24), "wdkv": (2, 64, 48),
        "kv_norm": (2, 40), "wukv": (2, 40, 4 * 48), "wo": (2, 4 * 32, 64)}
    assert "attn" not in dense and set(dense["mla"]) == set(moe["mla"])
    assert set(moe["mlp"]) == {"gate", "gate_bias", "wg", "wi", "wo", "shared_wg", "shared_wi",
                               "shared_wo"}              # no shared_gate
    assert model.num_params() == sum(x.size for x in jax.tree.leaves(params))
    names = jax.tree.leaves(model.logical_specs(params), is_leaf=lambda x: isinstance(x, tuple))
    assert [len(n) for n in names] == [leaf.ndim for leaf in jax.tree.leaves(params)]
    gated = TransformerModel(config(moe_shared_gated=True))
    assert gated.num_params() == model.num_params() + 2 * 64


@pytest.mark.parametrize("bad,why", [
    (dict(mla_kv_rank=0), "five mla sizes"),
    (dict(mla_rope_dim=7, head_size=23), "even rotary"),
    (dict(head_size=32), "head_dim = unrotated"),
    (dict(v_head_size=16), "v_head_dim = the value width"),
    (dict(layer_kinds=(dataclasses.replace(KINDS[0], mixer="mamba"), KINDS[1])), "mixer"),
    (dict(layer_kinds=(LayerKind("w", window=8, ffn="dense", ffn_size=96),), layer_plan=(0, 0, 0)),
     "full-attention layer or a latent-attention layer"),
])
def test_a_plan_the_program_cannot_run_is_refused(bad, why):
    with pytest.raises(ValueError, match=why):
        config(**bad)


def test_the_latent_pool_is_one_leaf_of_whole_lane_tiles_and_bears_the_rows_length():
    cfg = config()          # every layer latent: no full pool, and the plan passes
    assert [(s.name, s.layers, s.kv_heads, s.k_width, s.v_width) for s in kv_cache.specs(cfg)] == [
        ("latent", 3, 1, 128, 0)]
    assert kv_cache.latent_width(cfg) == 128 and kv_cache.state_spec(cfg) is None
    assert kv_cache.latent_width(config(mla_kv_rank=512, mla_rope_dim=64, head_size=80)) == 640
    cache = tf.init_cache(cfg, 3, 128)
    assert jax.tree.map(lambda a: a.shape, cache) == {"latent": {"c": (3, 3, 1, 128, 128)}}
    assert kv_cache.alloc_len(cfg, cache) == 128
    assert kv_cache.pool_bytes(cfg, cache) == {"latent": 3 * 3 * 128 * 128 * 4}
    assert kv_cache.read_bytes_by_pool(cfg, 40) == {"latent": 3 * 40 * 128 * 4}
    assert kv_cache.read_bytes_per_row(cfg, 40) == 3 * 40 * 128 * 4
    grown = jax.jit(lambda c: kv_cache.grow(cfg, c, 256))(cache)
    assert grown["latent"]["c"].shape == (3, 3, 1, 256, 128)
    specs = kv_cache.partition_spec(cfg, None, ("data", "fsdp"))
    assert specs == {"latent": {"c": PartitionSpec(None, ("data", "fsdp"), None, None, None)}}
    assert kv_cache.shard_width(None, cfg) == 1           # pools of a plan are never split over `tensor`
    assert not kv_cache.rows_write_by_blocks(cfg, cache, None)    # a toy row is far under the rule


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("size", [1024, 384, 96])
def test_the_rows_kernel_reads_each_row_to_its_own_length(size, dtype, tol):
    """``mla_decode`` in interpret mode against the einsum form: ragged
    lengths over several time blocks, a row of length 0 (parked, empty), a
    row that fills the bucket, 20 heads (padded to whole tiles inside)."""
    rs = np.random.RandomState(size)
    B, H, W, L = 6, 20, 256, 2
    q = jnp.asarray(rs.randn(B, H, W) * 0.5, dtype)
    pool = jnp.asarray(rs.randn(L, B, 1, 1024, W), dtype)
    lengths = jnp.minimum(jnp.asarray([0, 1, 130, 513, 700, 1024], jnp.int32), size)
    got = mla_decode(q, pool, jnp.int32(1), lengths, size=size, sm_scale=0.1)
    want = mla_decode_reference(q, pool, jnp.int32(1), lengths, size=size, sm_scale=0.1)
    assert got.shape == (B, H, W) and got.dtype == dtype
    assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()) < tol
    assert float(jnp.abs(got[0].astype(jnp.float32)).max()) == 0.0
    # what lies past a row's length is never read: poison there changes nothing
    past = jnp.arange(1024)[None, :] >= lengths[:, None]
    poisoned = jnp.where(past[None, :, None, :, None], jnp.asarray(1e4, dtype), pool)
    again = mla_decode(q, poisoned, jnp.int32(1), lengths, size=size, sm_scale=0.1)
    assert jnp.array_equal(again, got)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("size,end", [(2048, 1), (2048, 512), (2048, 700), (2048, 2048),
                                      (2048, 5000), (384, 100), (96, 96)])
def test_a_chunks_expansion_stops_at_the_block_that_holds_its_last_key(size, end, dtype, tol):
    """``mla_expand`` in interpret mode against ``layer_plan._mla_expand``
    (the einsum form ``forward_plan`` takes) on the blocks it has to expand:
    whole key tiles of the flash chunk kernel up to the chunk's end, 20 heads
    in groups, and nothing read past that (poison there changes nothing)."""
    rs = np.random.RandomState(end)
    cfg = config(num_heads=20, dtype="float32" if dtype == jnp.float32 else "bfloat16")
    kr, dn, dr, dv = cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
    row = layer_plan._stored(jnp.asarray(rs.randn(size, kr + dr), dtype), cfg)
    p = {"wukv": jnp.asarray(rs.randn(kr, 20 * (dn + dv)) * 0.2, dtype)}
    n = expanded_entries(end, size)
    assert n == min(-(-end // min(size, 512)) * min(size, 512), size) and n % min(size, 512) == 0
    wuk, wuv = (jnp.transpose(w, (1, 0, 2)) for w in layer_plan._mla_up(p, cfg))
    with jax.default_matmul_precision("highest"):
        k, v = mla_expand(row, wuk, wuv, jnp.int32(end), rank=kr, rope=dr)
        want_k, want_v = layer_plan._mla_expand(row, p, cfg)
    assert k.shape == (20, size, dn + dr) and v.shape == (20, size, dv) and k.dtype == v.dtype == dtype
    for got, want in ((k, want_k), (v, want_v)):
        gap = jnp.abs(got[:, :n].astype(jnp.float32) - want[:, :n].astype(jnp.float32))
        assert float(gap.max()) <= tol
    poisoned = jnp.where(jnp.arange(size)[:, None] >= n, jnp.asarray(1e4, dtype), row)
    again = mla_expand(poisoned, wuk, wuv, jnp.int32(end), rank=kr, rope=dr)
    with jax.default_matmul_precision("highest"):
        assert jnp.array_equal(again[0][:, :n], k[:, :n]) and jnp.array_equal(again[1][:, :n], v[:, :n])


def _ticker(cfg, read_len):
    return jax.jit(lambda params, c, t, p, ch: layer_plan.forward_plan_cached(
        params, cfg, t, p, c, read_len=read_len, chunk=ch))


_shared_ticker = functools.cache(_ticker)


def tick(cfg, params, toks, pos, cache, chunk=None, read_len=None, traced_anew=False):
    """One tick. Calls of equal shapes share ONE traced program (a tick of this plan is seconds of
    trace, lowering and compile, and the cases below make twenty), but for a case that patches
    what the trace reads: it asks for its own."""
    run = _ticker(cfg, read_len) if traced_anew else _shared_ticker(cfg, read_len)
    return run(params, cache, jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32), chunk)


def chunk_of(tokens, start, width, slot, length):
    n = len(tokens)
    toks, at = np.zeros(width, np.int32), np.full(width, length, np.int32)
    toks[:n], at[:n] = tokens, np.arange(start, start + n)
    return layer_plan.Chunk(jnp.asarray(toks), jnp.asarray(at), jnp.int32(slot), jnp.int32(n - 1))


def test_a_parked_row_and_an_empty_slot_leave_the_pools_bytes_untouched(model, params):
    cfg, T = model.cfg, 128
    cache = jax.tree.map(lambda a: a + 3.0, tf.init_cache(cfg, 4, T))    # every byte known
    pos = [5, T, 9, T]                                       # rows 1 and 3: parked, empty
    _, after, stats = tick(cfg, params, [1, 2, 3, 4], pos, cache)
    leaf, before = np.asarray(after["latent"]["c"]), np.asarray(cache["latent"]["c"])
    assert stats.shape == (5,)                               # the routing counters, nothing more
    assert np.array_equal(leaf[:, 1], before[:, 1]) and np.array_equal(leaf[:, 3], before[:, 3])
    for row, at in ((0, 5), (2, 9)):
        changed = np.flatnonzero((leaf[:, row, 0] != before[:, row, 0]).any(axis=(0, 2)))
        assert changed.tolist() == [at]                      # the one entry, in every layer
        assert np.all(leaf[:, row, 0, at, 48:] == 0.0)       # latent 40 + key 8, then zeros
    # a chunk beside them touches its own row's entries and no other row
    _, after2, _ = tick(cfg, params, [1, 2, 3, 4], [T, T, 10, T], after,
                        chunk=chunk_of(np.arange(6), 0, 8, 1, T))
    leaf2 = np.asarray(after2["latent"]["c"])
    assert np.array_equal(leaf2[:, 0], leaf[:, 0]) and np.array_equal(leaf2[:, 3], leaf[:, 3])
    changed = np.flatnonzero((leaf2[:, 1, 0] != leaf[:, 1, 0]).any(axis=(0, 2)))
    assert changed.tolist() == list(range(6))                # the chunk's real tokens, not its pads


def test_a_slot_reused_by_a_shorter_request_never_reads_the_longer_ones_latents(model, params):
    cfg, T = model.cfg, 128
    rs = np.random.RandomState(1)
    short = rs.randint(0, 97, 7)

    def serve_short(cache):
        out, cache, _ = tick(cfg, params, [0, 0], [T, T], cache, chunk=chunk_of(short, 0, 8, 0, T))
        logits = [np.asarray(out[0])]
        for i in range(3):
            out, cache, _ = tick(cfg, params, [int(np.argmax(logits[-1])), 0], [7 + i, T], cache)
            logits.append(np.asarray(out[0]))
        return np.stack(logits)

    clean = serve_short(tf.init_cache(cfg, 2, T))
    # the slot held a long request before: its entries are still there, past the short one's
    used = tf.init_cache(cfg, 2, T)
    long = rs.randint(0, 97, 96)
    for start in range(0, 96, 32):
        _, used, _ = tick(cfg, params, [0, 0], [T, T], used,
                          chunk=chunk_of(long[start:start + 32], start, 32, 0, T))
    assert float(jnp.abs(used["latent"]["c"][:, 0, 0, 64:96]).max()) > 0.1
    assert np.allclose(serve_short(used), clean, atol=1e-5)


def test_the_rows_write_takes_the_block_path_where_the_rule_says_and_writes_the_same_bytes(
        model, params, monkeypatch):
    cfg, T = model.cfg, 256
    cache = tf.init_cache(cfg, 3, T)
    pos = [130, T, 7]
    want = tick(cfg, params, [1, 2, 3], pos, cache, traced_anew=True)
    assert not kv_cache.rows_write_by_blocks(cfg, cache, None)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 1 << 10)
    assert kv_cache.rows_write_by_blocks(cfg, cache, None)            # 256 slots: two blocks
    assert not kv_cache.rows_write_by_blocks(cfg, cache, 128)         # one block IS the window
    calls, sound = [], kv_cache._write_blocks
    monkeypatch.setattr(kv_cache, "_write_blocks",
                        lambda pool, *a: calls.append(pool.shape) or sound(pool, *a))
    got = tick(cfg, params, [1, 2, 3], pos, cache, traced_anew=True)
    assert calls and set(calls) == {(3, 3, 1, 256, 128)}    # the kernel (interpreted here), a run of layers
    assert np.array_equal(np.asarray(got[1]["latent"]["c"]), np.asarray(want[1]["latent"]["c"]))
    assert np.allclose(got[0], want[0], atol=1e-6)


def test_the_engine_reads_lengths_off_the_latent_pool_and_counts_its_reads(model, params):
    kw = dict(config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
              params=params, max_slots=2, cache_len=128, prefill_chunk=32)
    eng = ContinuousBatchingEngine(model, **kw)
    assert eng._pools[0].length == 128 and set(eng.kv_pool_bytes()) == {"latent"}
    rs = np.random.RandomState(2)
    rids = [eng.submit(rs.randint(0, 97, n).astype(np.int32), max_new_tokens=4) for n in (40, 9)]
    while eng.has_work():
        eng.step()
    assert [len(eng.result(r)) for r in rids] == [44, 13]
    stats = eng.tick_stats()
    assert stats["latent_pool_bytes"] == stats["kv_pool_bytes_latent"] == 3 * 2 * 128 * 128 * 4
    # two chunks of the first request and one of the second, each expanded to the read bucket
    # (128 entries: one key tile of the chunk kernel)
    assert stats["prefill_chunk_tokens"] == 49 and stats["mla_expand_tokens"] == 3 * 128
    # the first token of a request is its last chunk's; each of the three others reads its row
    # to the entry it has just written
    assert stats["mla_row_keys"] == sum(40 + i + 1 for i in range(3)) + sum(9 + i + 1 for i in range(3))
    from deepspeed_tpu.serving.loadgen import format_summary, host_overhead

    said = host_overhead(stats)
    assert said["latent_pool_bytes"] == stats["latent_pool_bytes"]
    assert said["mla_row_keys_per_tick"] > 0 and said["mla_expand_tokens_per_chunk"] > 0
    printed = format_summary(dict(
        requests=2, outcomes={"finished": 2}, wall_s=1.0, throughput_tok_s=8.0, goodput_tok_s=8.0,
        shed_rate=0.0, host=said))
    assert "latent pool    0.000 GB   rows read" in printed and "a chunk expands" in printed


def test_what_a_plan_cannot_do_yet_says_why_and_names_no_pool_it_does_not_have(model, params):
    cache = tf.init_cache(model.cfg, 2, 64)
    with pytest.raises(NotImplementedError, match="chunk by chunk"):
        tf.forward_with_cache(params, model.cfg, jnp.zeros((2, 8), jnp.int32), cache, 0)
    kw = dict(config={"dtype": "float32", "mesh": {"shape": {"data": 1, "tensor": 1}}},
              params=params, max_slots=2, cache_len=64)
    with pytest.raises(NotImplementedError, match="one token a row and one chunk") as said:
        ContinuousBatchingEngine(model, tokens_per_tick=2, **kw)
    assert "state pool" not in str(said.value)
    with pytest.raises(NotImplementedError, match="one token a row and one chunk"):
        ContinuousBatchingEngine(model, **dict(kw, config=dict(
            kw["config"], speculative={"enabled": True, "pool": True, "mode": "ngram"})))
    with pytest.raises(NotImplementedError, match="have no splice yet") as said:
        ContinuousBatchingEngine(model, **kw).register_prefix(np.arange(5))
    assert "recurrent state" not in str(said.value)
