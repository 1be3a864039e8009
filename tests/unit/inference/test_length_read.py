"""The rows' read by length (PR 39): which calls of ``softmax_context`` go
through ``decode_rows`` and which keep ``_masked_attention``
(``kv_cache.takes_length_read``, the ONE rule), that a
``ContinuousBatchingEngine`` hands out the same greedy tokens either way,
and what ``tick_stats()`` counts of it. Toy sizes: the rule's byte threshold
is lowered where the kernel is wanted (interpreted on the CPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.models.transformer import TransformerConfig
from deepspeed_tpu.ops.transformer import inference_ops, kv_cache
from deepspeed_tpu.serving import loadgen
from serving_toys import built, drain, prompt

FLOOR = 128   # the default: the rule turns at a 256-slot read, and no case here is about the ladder under it
LENGTH = 384
BASE = TransformerConfig(vocab_size=160, hidden_size=64, num_layers=2, num_heads=4,
                         max_seq_len=LENGTH, dtype="float32")
# every variant on a mesh of ONE device, as a one-chip deployment has it, but the last
VARIANTS = {
    "plain": {},
    "int8_kv": {"config": {"kv_cache_dtype": "int8"}},
    "alibi": {"cfg": {"pos_embedding": "alibi"}},
    "layer_windows": {"cfg": {"local_attn_windows": (24, 0)}},
    "one_window": {"cfg": {"local_attn_windows": (200, 200)}},
    "gqa_rope": {"cfg": {"pos_embedding": "rope", "num_kv_heads": 2}},
    "tensor2": {"tensor": 2},
}


@pytest.fixture(scope="module")
def models():
    comm.destroy()
    return lambda variant: built(dataclasses.replace(BASE, **VARIANTS[variant].get("cfg", {})))


def _engine(models, variant, **kw):
    model, params = models(variant)
    spec = VARIANTS[variant]
    config = {"dtype": "float32", "kv_read_floor": FLOOR, **spec.get("config", {}),
              "mesh": {"shape": {"data": 1, "tensor": spec.get("tensor", 1)}}}
    kw = {"max_slots": 4, "cache_len": LENGTH, "prefill_chunk": 128, "donate_cache": False, **kw}
    return ContinuousBatchingEngine(model, params=params, config=config, **kw)


def _prompt(n, seed=0):
    return prompt(n, BASE.vocab_size, seed)


def _serve(cb, sizes, new):
    """Prompts of ``sizes`` admitted two steps apart, so that rows of
    different lengths decode side by side, chunks ride their ticks and the
    fourth slot stays empty (a parked row); greedy streams by request."""
    rids = []
    for n in sizes:
        rids.append(cb.submit(_prompt(n, seed=n), max_new_tokens=new))
        cb.step()
        cb.step()
    return drain(cb, rids)


# -- the rule, at softmax_context ---------------------------------------------

def _call(monkeypatch, *, tokens=1, heads=4, kv_heads=4, width=16, read_len=256, alloc=384,
          scalar_pos=False, int8=False, split=False, slot=None, threshold=0, **kw):
    """``softmax_context`` over a stacked pool with ``decode_rows`` spied on:
    (times the kernel was called, the output)."""
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", threshold)
    calls = []
    kernel = inference_ops.decode_rows
    monkeypatch.setattr(inference_ops, "decode_rows",
                        lambda *a, **k: (calls.append(k["size"]), kernel(*a, **k))[1])
    B = 3
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, tokens, heads, width), jnp.float32)
    k, v = (jax.random.normal(key, (2, B, alloc, kv_heads, width), jnp.float32) for key in ks[1:])
    if int8:
        k, v = ({"q8": (a * 20).astype(jnp.int8), "s": jnp.ones(a.shape[:-1] + (1,), jnp.float32)}
                for a in (k, v))
    depth = jnp.asarray([5, 200, alloc], jnp.int32)          # the last row is parked
    pos = jnp.int32(7) if scalar_pos else depth
    positions = (pos + jnp.arange(tokens))[None].repeat(B, 0) if scalar_pos else (
        depth[:, None] + jnp.arange(tokens)[None, :])
    if slot is not None:                                     # a chunk's own read: ONE row's segment
        q, pos, positions = q[:1], depth[:1], positions[:1]
    run = lambda: inference_ops.softmax_context(q, k, v, pos, positions=positions, read_len=read_len,
                                                layer=1, slot=slot, **kw)
    out = kv_cache.split_over_chips(run)() if split else run()
    return len(calls), out


def test_one_token_a_row_of_a_long_window_takes_the_kernel(monkeypatch):
    """... and hands out what the contraction of the whole window hands out
    for the rows that are not parked."""
    n, got = _call(monkeypatch)
    assert n == 1
    monkeypatch.undo()
    n, want = _call(monkeypatch, threshold=kv_cache.BLOCK_WRITE_MIN_ROW_BYTES)
    assert n == 0
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2], atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[2].any()                       # the parked row read nothing


@pytest.mark.parametrize("why,kw", [
    ("several_tokens_a_row", {"tokens": 2}),
    ("rows_at_one_depth", {"scalar_pos": True}),
    ("int8_pool", {"int8": True}),
    ("alibi", {"alibi_slopes": jnp.asarray([0.5, 0.25, 0.125, 0.0625])}),
    ("local_window", {"local_window": 24}),
    ("traced_local_window", {"local_window": jnp.int32(0)}),
    ("ring", {"scalar_pos": True, "ring": True, "local_window": 24, "read_len": None}),
    ("split_over_chips", {"split": True}),
    ("a_128_slot_read", {"read_len": 128}),
    ("a_read_that_is_not_whole_blocks", {"read_len": 200}),
    ("a_chunks_own_row", {"slot": jnp.int32(1)}),
    ("grouped_key_heads", {"kv_heads": 2}),
    ("a_width_of_whole_lanes", {"width": 128}),
    ("a_row_under_the_byte_threshold", {"threshold": kv_cache.BLOCK_WRITE_MIN_ROW_BYTES}),
])
def test_everything_else_keeps_the_masked_contraction(monkeypatch, why, kw):
    """One case an exclusion of ``kv_cache.takes_length_read``: the kernel is
    not called, so the call runs the program it ran before."""
    n, out = _call(monkeypatch, **kw)
    assert n == 0 and not np.isnan(np.asarray(out)).any()


@pytest.mark.parametrize("variant,taken", [
    ("plain", True), ("int8_kv", False), ("alibi", False), ("layer_windows", False),
    ("one_window", False), ("gqa_rope", False), ("tensor2", False)])
def test_the_host_reads_the_same_rule_off_the_configuration(models, monkeypatch, variant, taken):
    """``rows_read_to_length`` (what ``tick_stats()`` counts by) per variant
    of the model and the mesh, at a read of 256 slots with the threshold at
    zero; with the threshold as committed no toy takes the kernel."""
    cb = _engine(models, variant)
    pool = cb._pools[0]
    assert not kv_cache.rows_read_to_length(cb.cfg, pool.cache, 256, cb.mesh)
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    assert kv_cache.rows_read_to_length(cb.cfg, pool.cache, 256, cb.mesh) == taken
    assert kv_cache.rows_read_to_length(cb.cfg, pool.cache, None, cb.mesh) == taken
    assert not kv_cache.rows_read_to_length(cb.cfg, pool.cache, 128, cb.mesh)


# -- the engine ----------------------------------------------------------------

SIZES, NEW = (20, 300, 140), 12


@pytest.fixture(scope="module")
def xla_streams(models):
    """The streams with every read on the masked contraction (a toy row is
    far under the committed threshold)."""
    cb = _engine(models, "plain")
    out = _serve(cb, SIZES, NEW)
    assert cb.tick_stats()["length_read_ticks"] == 0
    return out


def test_engine_hands_out_the_same_greedy_tokens_with_the_kernel(models, monkeypatch, xla_streams):
    """Rows at different lengths, an empty slot, chunks riding the ticks, read
    buckets 256 and the whole 384-slot pool: token for token the streams of
    the XLA path, and ``generate``'s."""
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    cb = _engine(models, "plain")
    got = _serve(cb, SIZES, NEW)
    for a, b in zip(got, xla_streams):
        np.testing.assert_array_equal(a, b)
    model, params = models("plain")
    plain = deepspeed_tpu.init_inference(model, params=params, config={"dtype": "float32"})
    want = np.asarray(plain.generate(_prompt(300, seed=300)[None, :], max_new_tokens=NEW))[0]
    np.testing.assert_array_equal(got[1], want)
    st = cb.tick_stats()
    assert 0 < st["length_read_ticks"] < st["ticks"]        # the first ticks read 32-128 slots
    fused = [key for key in cb._pools[0].tick_fns if key[0] is not None and key[1] in (256, None)]
    assert fused                                             # a chunk rode a tick that read by length


def test_tick_stats_count_length_reads_as_dispatched(models, monkeypatch):
    """``length_read_ticks`` is the host's reading of the rule at each tick's
    read bucket; ``row_keys_live`` the slots the live rows of those ticks
    attend (each its cached tokens and the one it writes), ``row_keys_read``
    the whole blocks that covers; ``ds_loadgen`` prints both."""
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    cb = _engine(models, "plain")
    seen = []
    dispatch = cb._dispatch_tick

    def spy(pool):
        before = dict(cb.tick_stats())
        live = [int(pool.disp_pos[s]) for s, r in pool.active.items()
                if not r.chunks and pool.disp_gen[s] < r.quota]
        rec = dispatch(pool)
        after = cb.tick_stats()
        if after["ticks"] > before["ticks"] or rec is not None:
            seen.append((live, after["length_read_ticks"] - before["length_read_ticks"],
                         after["row_keys_live"] - before["row_keys_live"],
                         after["row_keys_read"] - before["row_keys_read"]))
        return rec

    monkeypatch.setattr(cb, "_dispatch_tick", spy)
    _serve(cb, SIZES, NEW)
    st = cb.tick_stats()
    counted = [s for s in seen if s[1]]
    assert st["length_read_ticks"] == len(counted) > 0
    for live, _, held, read in counted:
        assert held == sum(p + 1 for p in live)
        assert read == sum(-(-(p + 1) // 128) * 128 for p in live)
    for live, taken, held, read in seen:
        assert taken or (held == 0 and read == 0)
    assert st["row_keys_read"] % 128 == 0 and st["row_keys_read"] >= st["row_keys_live"] > 0
    host = loadgen.host_overhead(st)
    assert host["length_read_share"] == pytest.approx(st["length_read_ticks"] / st["ticks"], abs=1e-4)
    assert host["row_keys_read_per_live"] == pytest.approx(
        st["row_keys_read"] / st["row_keys_live"], abs=1e-4)
    text = loadgen.format_summary(loadgen.summarize([], 1.0, tick_stats=st))
    assert "length reads" in text and "keys read ÷ live" in text
