"""The one-kind fused-prefill tick runs B + W tokens — the rows' next
tokens, then ONE row's prompt chunk beside them — and one chunk width a read
bucket (inference/decoding.py, models/transformer.forward_tick_cached,
inference/continuous.py). Token streams against ``generate`` and against
separate prefill at toy size, and what the lowered program may not hold."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.inference.continuous import ContinuousBatchingEngine
from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache
from serving_toys import built, drain as _drain, prompt

COARSE = 128  # the default tight-read floor: 128, 256 and the whole pool. What a case gets whose subject
#               is the stream: one or two crossings, a plain and a fused program each
FLOOR = 16    # ... and a small floor where the ladder of read buckets is the subject
LENGTH = 384
BASE = TransformerConfig(vocab_size=160, hidden_size=64, num_layers=2, num_heads=4,
                         max_seq_len=LENGTH, dtype="float32")

VARIANTS = {
    "plain": {},
    "int8_kv": {"config": {"kv_cache_dtype": "int8"}},
    "alibi": {"cfg": {"pos_embedding": "alibi"}},
    "layer_windows": {"cfg": {"local_attn_windows": (24, 0)}},
    "gqa_rope": {"cfg": {"pos_embedding": "rope", "num_kv_heads": 2}},
    "tensor2": {"tensor": 2},
    # a mesh of ONE device, as a one-chip deployment has it (the default mesh here spans the
    # eight virtual devices, and a pool on several chips keeps the window write)
    "one_chip": {"tensor": 1},
    "int8_one_chip": {"tensor": 1, "config": {"kv_cache_dtype": "int8"}},
}


@pytest.fixture(scope="module")
def models():
    comm.destroy()
    return lambda variant="plain": built(dataclasses.replace(BASE, **VARIANTS[variant].get("cfg", {})))


def _engine(models, variant="plain", floor=COARSE, **kw):
    """Continuous engine of a variant. Donation off: the CPU backend blocks
    a donated dispatch, and these tests compare schedules."""
    model, params = models(variant)
    spec = VARIANTS[variant]
    config = {"dtype": "float32", "kv_read_floor": floor, **spec.get("config", {})}
    if "tensor" in spec:
        config["mesh"] = {"shape": {"data": 1, "tensor": spec["tensor"]}}
    kw.setdefault("max_slots", 3)
    kw.setdefault("cache_len", LENGTH)
    kw.setdefault("prefill_chunk", 128)
    kw.setdefault("donate_cache", False)
    return ContinuousBatchingEngine(model, params=params, config=config, **kw)


_PLAIN = {}


def _generate(models, variant, prompt, new):
    if variant not in _PLAIN:  # one plain engine a variant: its programs compile once
        model, params = models(variant)
        config = {"dtype": "float32", **VARIANTS[variant].get("config", {})}
        _PLAIN[variant] = deepspeed_tpu.init_inference(model, params=params, config=config)
    return np.asarray(_PLAIN[variant].generate(prompt[None, :], max_new_tokens=new))[0]


def _prompt(n, seed=0):
    return prompt(n, BASE.vocab_size, seed)


def _serve_one(cb, prompt, new, live_rows):
    """``prompt`` admitted while ``live_rows`` other requests decode (their
    rows ride the same ticks as its chunks) or into an idle pool."""
    others = [cb.submit(_prompt(5 + 3 * i, seed=10 + i), max_new_tokens=40)
              for i in range(live_rows)]
    for _ in range(3 if live_rows else 0):
        cb.step()
    rid = cb.submit(prompt, max_new_tokens=new)
    out = _drain(cb, others + [rid])
    return out[-1], out[:-1]


# -- token streams ---------------------------------------------------------

@pytest.mark.parametrize("live_rows", [0, 2], ids=["none_live", "rows_live"])
@pytest.mark.parametrize("n", [1, 15, 16, 17, 127, 128, 300])
def test_greedy_stream_equals_generate_for_every_chunk_size(models, n, live_rows):
    """Prompts of 1 … 300 tokens (300 = three chunks of the 128 cap, the
    last one padded): the fused tick's greedy stream is ``generate``'s."""
    prompt = _prompt(n, seed=n)
    cb = _engine(models, floor=FLOOR)
    got, _ = _serve_one(cb, prompt, 10, live_rows)
    np.testing.assert_array_equal(got, _generate(models, "plain", prompt, 10))
    st = cb.tick_stats()
    chunks = -(-n // 128)
    assert st["prefill_chunk_tokens"] >= n
    assert (st["prefill_chunk_tokens"] + st["prefill_pad_tokens"]) % 128 == 0
    assert st["fused_prefill_ticks"] >= chunks


@pytest.mark.parametrize("n", [17, 300])
def test_sampled_stream_equals_separate_prefill(models, n):
    """Sampling draws from fold_in(fold_in(key, rid), token index) on the
    device: fused and separate admission give the same sampled stream."""
    prompt = _prompt(n, seed=n)
    outs = []
    for fused in (True, False):
        cb = _engine(models, temperature=0.8, top_k=20, seed=7, fused_prefill=fused)
        outs.append(_serve_one(cb, prompt, 12, 2))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    for a, b in zip(outs[0][1], outs[1][1]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("slot", [0, 1, 2], ids=["first", "middle", "last"])
def test_admitting_slot_first_middle_last(models, slot):
    """The chunk's row can be any slot; the rows on either side keep
    decoding their own streams."""
    cb = _engine(models)
    holders = [_prompt(6 + i, seed=20 + i) for i in range(3)]
    rids = [cb.submit(p, max_new_tokens=60) for p in holders]
    for _ in range(4):
        cb.step()
    assert cb.cancel(rids[slot])
    prompt = _prompt(150, seed=33)
    rid = cb.submit(prompt, max_new_tokens=8)
    cb.step()
    assert cb._pools[0].active[slot].rid == rid
    keep = [r for i, r in enumerate(rids) if i != slot]
    out = _drain(cb, keep + [rid])
    np.testing.assert_array_equal(out[-1], _generate(models, "plain", prompt, 8))
    for got, p in zip(out, [h for i, h in enumerate(holders) if i != slot]):
        np.testing.assert_array_equal(got, _generate(models, "plain", p, 60))


@pytest.mark.parametrize("variant", ["int8_kv", "alibi", "layer_windows", "gqa_rope", "tensor2"])
def test_variants_equal_separate_prefill_and_generate(models, variant):
    """Every cache and attention variant the (B, W) layout served: an int8
    pool, ALiBi, a per-layer window, grouped heads with rotary positions, a
    pool whose heads are split over ``tensor``."""
    prompt = _prompt(200, seed=5)
    fused, fused_others = _serve_one(_engine(models, variant), prompt, 10, 2)
    sep, sep_others = _serve_one(_engine(models, variant, fused_prefill=False), prompt, 10, 2)
    np.testing.assert_array_equal(fused, sep)
    for a, b in zip(fused_others, sep_others):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(fused, _generate(models, variant, prompt, 10))


def test_chunk_crossing_a_read_bucket(models):
    """A chunk cap that is no power of two: chunks sit astride the read
    buckets (48 … 95 crosses 64), and the tick reads the bucket that covers
    the chunk's END."""
    prompt = _prompt(110, seed=9)
    cb = _engine(models, floor=FLOOR, prefill_chunk=48)
    got, _ = _serve_one(cb, prompt, 10, 1)
    np.testing.assert_array_equal(got, _generate(models, "plain", prompt, 10))
    widths = {k[0] for k in cb._pools[0].tick_fns if k[0] is not None}
    assert widths == {48}


def test_eos_on_the_first_token(models):
    """The chunk's sampled column is the request's first AND last token."""
    prompt = _prompt(140, seed=11)
    want = _generate(models, "plain", prompt, 4)
    cb = _engine(models, eos_token_id=int(want[prompt.size]))
    got, others = _serve_one(cb, prompt, 10, 2)
    np.testing.assert_array_equal(got, want[:prompt.size + 1])
    assert all(o.size for o in others)


@pytest.mark.parametrize("variant", ["one_chip", "int8_one_chip", "tensor2"])
def test_streams_through_the_block_write_above_one_block(models, monkeypatch, variant):
    """Read buckets 128, 256 and the whole 384-slot pool with the rule's
    constant at zero: the live rows' tokens go into each row's 128-slot block
    (plain and fused ticks; since PR 54 a time-minor pool's 128-slot window
    too), the streams are ``generate``'s, and ``tick_stats()`` counts the
    ticks dispatched on such programs. A pool split over two chips keeps the
    window write (the kernel cannot be partitioned)."""
    prompt = _prompt(300, seed=300)
    want = _generate(models, variant, prompt, 10)        # window path: before the constant moves
    before = _engine(models, variant)
    _serve_one(before, _prompt(20, seed=2), 4, 1)
    assert before.tick_stats()["block_write_ticks"] == 0   # a toy row is far under the constant
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    cb = _engine(models, variant)
    got, others = _serve_one(cb, prompt, 10, 2)
    np.testing.assert_array_equal(got, want)
    assert all(o.size for o in others)
    st = cb.tick_stats()
    by_blocks = sum(1 for (_, read_len) in cb._pools[0].tick_fns if read_len in (128, 256, None))
    assert by_blocks >= 3 and (st["block_write_ticks"] == st["ticks"]) == (variant != "tensor2")
    assert (st["block_write_ticks"] > 0) == (variant != "tensor2")
    # the other requests' first steps read one block: the kernel's too, and their rows alone are live
    assert any(read_len == 128 for (_, read_len) in cb._pools[0].tick_fns)
    assert 0 < st["block_write_rows"] < 3 * st["block_write_ticks"] or variant == "tensor2"


# -- the program -----------------------------------------------------------

def _lowered_tick(slots, width, read_len=None):
    comm.destroy()
    cfg = dataclasses.replace(BASE, max_seq_len=256)
    model = TransformerModel(cfg)
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=jax.devices()[:1])
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    from jax.sharding import NamedSharding, PartitionSpec

    p_sh = jax.tree.map(lambda a: NamedSharding(mesh, PartitionSpec()), params)
    fn, _, _ = compile_pool_tick_fn(mesh, cfg, p_sh, slots, 256, 1, 0.0, 0, 1.0,
                                    read_len=read_len, chunk=width, donate=False)
    from deepspeed_tpu.models import transformer as tf

    cache = jax.eval_shape(lambda: tf.init_cache(cfg, slots, 256))
    row = jax.ShapeDtypeStruct((slots,), jnp.int32)
    wide = jax.ShapeDtypeStruct((width,), jnp.int32)
    text = fn.lower(params, cache, row, row, row, row, row, row,
                    jax.ShapeDtypeStruct((2,), jnp.uint32), wide, wide,
                    jax.ShapeDtypeStruct((), jnp.int32), row, row).as_text()
    comm.destroy()
    return text, cfg


@pytest.mark.parametrize("read_len", [None, 128], ids=["read_all", "read128"])
def test_lowered_tick_is_one_loop_over_b_plus_w_tokens(read_len):
    """ONE ``stablehlo.while`` (the layer scan with the pool in its carry);
    nothing of shape (B, W, hidden) or (B, W, vocab); the logits are
    (B + 1, vocab)."""
    B, W = 3, 32
    text, cfg = _lowered_tick(B, W, read_len)
    assert text.count("stablehlo.while") == 1
    shapes = set(re.findall(r"tensor<([0-9x]+)x[a-z]+[0-9]+>", text))
    assert f"{B}x{W}x{cfg.vocab_size}" not in shapes
    assert f"{B}x{W}x{cfg.hidden_size}" not in shapes
    assert not any(s.startswith(f"{B}x{W}x") for s in shapes), sorted(shapes)
    assert f"{B + 1}x{cfg.vocab_size}" in shapes
    assert f"1x{B + W}x{cfg.hidden_size}" in shapes
    rows_of_logits = [np.prod([int(d) for d in s.split("x")[:-1]]) for s in shapes
                      if s.endswith(f"x{cfg.vocab_size}")]
    assert max(rows_of_logits) == B + 1, sorted(shapes)


def test_precompile_counts_two_programs_a_read_bucket(models):
    """A one-kind pool: a plain and ONE fused program a read bucket."""
    cb = _engine(models, floor=FLOOR, max_slots=2, cache_len=128)
    buckets = {cb._read_len(cb._pools[0], e) for e in range(1, 129)}
    assert len(buckets) == 4                                  # 16, 32, 64, all
    assert cb.precompile_tick_programs() == 2 * len(buckets)
    assert len(cb._pools[0].tick_fns) == 2 * len(buckets)
    assert {k[0] for k in cb._pools[0].tick_fns} == {None, 128}


def test_tick_stats_count_real_and_pad_tokens(models):
    """``prefill_chunk_tokens`` + ``prefill_pad_tokens`` = width x fused
    ticks; the pad share is read, not reckoned."""
    cb = _engine(models, floor=FLOOR, prefill_chunk=64)
    rids = [cb.submit(_prompt(n, seed=n), max_new_tokens=3) for n in (64, 100, 7)]
    _drain(cb, rids)
    st = cb.tick_stats()
    assert st["prefill_chunk_tokens"] == 64 + 100 + 7
    assert st["fused_prefill_ticks"] == 1 + 2 + 1
    assert st["prefill_pad_tokens"] == 4 * 64 - (64 + 100 + 7)


def test_tick_stats_count_the_ticks_whose_rows_wrote_by_blocks(models, monkeypatch):
    """``block_write_ticks`` is the host's reading of ``kv_cache``'s own rule
    at each tick's read bucket: a time-minor pool's window goes by blocks from
    HALF the constant's bytes a row (PR 54), so with the constant at the bytes
    of a 512-slot row of this pool the ticks that read 256 slots or the whole
    pool count and the 128-slot bucket does not; at a 256-slot row's bytes the
    128-slot ticks count too; ``ds_loadgen`` prints the share."""
    from deepspeed_tpu.serving import loadgen

    row_256 = 256 * BASE.num_heads * (BASE.hidden_size // BASE.num_heads) * 4
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", row_256)
    assert kv_cache.takes_block_write(128, row_256 // 2, time_minor=True)
    assert not kv_cache.takes_block_write(128, row_256 // 2 - 1, time_minor=True)
    assert not kv_cache.takes_block_write(128, 1 << 40)              # a leaf kept as written
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 2 * row_256)
    cb = _engine(models, "one_chip")
    seen = []
    dispatch = cb._tick_fn
    monkeypatch.setattr(cb, "_tick_fn", lambda pool, read_len, chunk=None: (
        seen.append(read_len), dispatch(pool, read_len, chunk=chunk))[1])
    rids = [cb.submit(_prompt(n, seed=n), max_new_tokens=6) for n in (20, 140, 300)]
    _drain(cb, rids)
    st = cb.tick_stats()
    assert st["ticks"] == len(seen)
    assert st["block_write_ticks"] == sum(1 for r in seen if r is None or r >= 256) > 0
    assert st["block_write_ticks"] < st["ticks"]
    host = loadgen.host_overhead(st)
    assert host["block_write_share"] == pytest.approx(st["block_write_ticks"] / st["ticks"], abs=1e-4)


def test_a_tick_counts_its_live_rows_blocks_and_a_128_slot_tick_is_a_block_write_tick(models, monkeypatch):
    """Two requests in a pool of eight rows, every tick at the 128-slot read
    bucket: each is a block-write tick (PR 54: a time-minor leaf's 128-slot
    window goes to the kernel), a tick whose rows are 2 of 8 live counts 2
    rows' blocks and their bytes (in and out, K and V, two layers, a block of
    128 slots x 4 heads x 16 float32), and a tick that carries only a chunk
    counts no row."""
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    cb = _engine(models, "one_chip", max_slots=8)
    keys = ("ticks", "block_write_ticks", "block_write_rows", "block_write_bytes")
    a_row = 2 * 2 * 2 * 128 * 4 * 16 * 4
    for n in (5, 8):
        cb.submit(_prompt(n, seed=n), max_new_tokens=6)
    deltas, before = [], cb.tick_stats()
    while cb.has_work():
        cb.step()
        after = cb.tick_stats()
        deltas.append(tuple(after[k] - before[k] for k in keys))
        before = after
    assert {read_len for (_, read_len) in cb._pools[0].tick_fns} == {128}
    assert set(deltas) <= {(0, 0, 0, 0)} | {(1, 1, live, live * a_row) for live in (0, 1, 2)}
    assert (1, 1, 2, 2 * a_row) in deltas and (1, 1, 0, 0) in deltas      # 2 of 8 live; the first chunk alone
    # a request's first token comes from its chunk's tick; its row then writes a token a tick, five times
    assert after["block_write_rows"] == 2 * 5 and after["block_write_ticks"] == after["ticks"]


def _looped_lane_plan():
    """Two layers of one kind walked twice, 2 key-value heads of 128: a pool of 4 layer-steps whose
    two leaves are whole lane tiles wide (8-slot blocks in float32)."""
    cfg = TransformerConfig(
        vocab_size=160, hidden_size=64, num_layers=2, num_heads=2, head_size=128, ffn_hidden_size=96,
        pos_embedding="rope", norm_type="rmsnorm", norm_position="sandwich", activation="silu_glu",
        tie_embeddings=False, use_bias=False, layer_kinds=(LayerKind(name="f", kv_heads=2),),
        layer_plan=(0, 0), loop_steps=2, max_seq_len=LENGTH, dtype="float32")
    return built(cfg)


@pytest.mark.parametrize("name", ["time_minor", "lane_aligned_plan"])
def test_tick_stats_count_the_bytes_the_rows_block_writes_moved(models, monkeypatch, name):
    """``block_write_bytes``: each LIVE row's block (``block_write_rows``, a
    row a token step: a parked row or an empty slot moves nothing), in and
    out, of every leaf and layer-step, in the ticks ``block_write_ticks``
    counts, read off static shapes: a time-minor pool's block is 128 slots, a
    lane-aligned leaf's one sublane tile (8 of float32); 0 where no tick wrote
    by blocks; and ``ds_loadgen`` prints it as MB a tick."""
    from deepspeed_tpu.serving import loadgen

    if name == "time_minor":
        model, params = models("one_chip")
        rows, leaves, layer_steps, block = 3, 2, 2, 128 * 4 * 16 * 4
    else:
        model, params = _looped_lane_plan()
        rows, leaves, layer_steps, block = 3, 2, 4, 8 * 2 * 128 * 4

    def serve():
        cb = ContinuousBatchingEngine(
            model, params=params, max_slots=rows, cache_len=LENGTH, prefill_chunk=128, donate_cache=False,
            config={"dtype": "float32", "kv_read_floor": COARSE, "mesh": {"shape": {"data": 1, "tensor": 1}}})
        _drain(cb, [cb.submit(_prompt(n, seed=n), max_new_tokens=5) for n in (20, 140, 300)])
        return cb.tick_stats()

    st = serve()                                                  # a toy row is far under the constant
    assert st["block_write_ticks"] == 0 and st["block_write_bytes"] == 0 and st["block_write_rows"] == 0
    assert loadgen.host_overhead(st)["block_write_mb_per_tick"] is None
    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    st = serve()
    a_row = block * 2 * leaves * layer_steps
    assert 0 < st["block_write_ticks"] <= st["ticks"]
    # a request decodes 4 tokens after its prefill's first, and not every row is live in every tick
    assert st["block_write_ticks"] < st["block_write_rows"] < rows * st["block_write_ticks"]
    assert st["block_write_bytes"] == st["block_write_rows"] * a_row
    a_tick = st["block_write_bytes"] / st["block_write_ticks"]
    host = loadgen.host_overhead(st)
    assert host["block_write_mb_per_tick"] == pytest.approx(a_tick / 1e6, abs=1e-3)
    text = loadgen.format_summary({"outcomes": {}, "requests": 3, "wall_s": 1.0, "throughput_tok_s": 1.0,
                                   "goodput_tok_s": 1.0, "shed_rate": 0.0, "host": host})
    assert f"block writes {host['block_write_share']:.1%} ({a_tick / 1e6:.1f} MB a tick)" in text
