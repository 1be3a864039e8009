"""Ahead-of-time compiles for a *described* TPU (``v5e:2x2``): what the
chip's compiler refuses, it refuses here, at no chip time.

Interpret-mode tests cannot see a block shape the Mosaic lowering rejects,
a kernel that overflows VMEM, or a step that does not fit HBM; every Pallas
kernel of ``deepspeed_tpu/ops/pallas/`` and one whole model step at a real
size therefore compile here for the TPU with ``interpret`` forced off
(``force_interpret(False)`` — the process's backend is the CPU, the target
is not). Nothing runs: a compile that passes says nothing about results or
times. Skipped where libtpu cannot describe the topology.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from deepspeed_tpu import comm
from deepspeed_tpu.ops.pallas.block_sparse_attention import block_sparse_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.interpret import force_interpret
from deepspeed_tpu.telemetry.hlo_scopes import scope_table

HBM_BYTES = 15.75e9  # what one v5e chip reports usable


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to compile against
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # an executable for an unattached chip can be written to the persistent
    # cache but not read back (it warns and recompiles): keep these out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _lower(fn, *args):
    with force_interpret(False):
        return jax.jit(fn).lower(*args)


def _sum_grad(attn):
    return lambda q, k, v: jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


def _flash(mode, B, S, H, D, kv_heads=None):
    def build(topo):
        one = SingleDeviceSharding(topo.devices[0])
        q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=one)
        kv = jax.ShapeDtypeStruct((B, S, kv_heads or H, D), jnp.bfloat16, sharding=one)
        attn = {
            "fwd": lambda q, k, v: flash_attention(q, k, v, causal=True),
            "bwd": _sum_grad(lambda q, k, v: flash_attention(q, k, v, causal=True)),
            "window": lambda q, k, v: flash_attention(q, k, v, causal=True, window=256),
        }[mode]
        return _lower(attn, q, kv, kv), 3 if mode == "bwd" else 1
    return build


def _decode_rows(B, H, x, layers, length):
    """The rows' one-token attention over the stacked pools as the serving
    tick keeps them, (L, B, T, H, x), read in place through their time-minor view."""
    def build(topo):
        from deepspeed_tpu.ops.pallas.decode_attention import decode_rows
        from deepspeed_tpu.ops.transformer import kv_cache

        one = SingleDeviceSharding(topo.devices[0])
        q = jax.ShapeDtypeStruct((B, H, x), jnp.bfloat16, sharding=one)
        pool = jax.ShapeDtypeStruct((layers, B, length, H, x), jnp.bfloat16, sharding=one)
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        lengths = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one)
        attn = lambda q, k, v, layer, lengths: decode_rows(  # noqa: E731
            q, kv_cache.time_minor(k), kv_cache.time_minor(v), layer, lengths, size=length,
            block=kv_cache.BLOCK, sm_scale=x ** -0.5)
        return _lower(attn, q, pool, pool, scalar, lengths), 1
    return build


def _block_sparse(topo):
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import FixedSparsityConfig

    B, S, H, D, block = 4, 1024, 12, 64, 128
    layout = FixedSparsityConfig(num_heads=H, block=block).make_layout(S)
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    attn = _sum_grad(lambda q, k, v: block_sparse_attention(
        q, k, v, layout, causal=True, block=block))
    return _lower(attn, q, q, q), 3


def _flash_on_mesh(mesh_shape, spec):
    """The model's attention entry point on all four described chips.
    GSPMD cannot partition a Mosaic kernel (the TPU lowering refuses:
    "wrap the call in a shard_map"), so the model must run it per-shard
    on ANY multi-device mesh: heads over 'tensor' for tensor-parallel
    serving, batch over 'fsdp' for ZeRO/data-parallel training."""
    def build(topo):
        from deepspeed_tpu.models.transformer import TransformerConfig, _flash_sharded

        mesh = comm.build_mesh(mesh_shape, devices=topo.devices)
        comm.set_mesh(mesh)
        cfg = TransformerConfig(hidden_size=1024, num_heads=16, attn_impl="pallas")
        q = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, spec))
        attn = _sum_grad(lambda q, k, v: _flash_sharded(q, k, v, cfg, causal=True))
        return _lower(attn, q, q, q), 3
    return build


def _gpt2_350m_step(n_kernels, **remat_policy):
    """TransformerModel.loss + grad at gpt2-350m, micro-batch 8, seq 1024,
    bf16, remat, attn_impl="pallas" — chip_smoke.py's training model."""
    def build(topo):
        from deepspeed_tpu.models.transformer import TransformerModel

        one = SingleDeviceSharding(topo.devices[0])
        model = TransformerModel.from_preset("gpt2-350m", dtype="bfloat16", remat=True,
                                             attn_impl="pallas", **remat_policy)
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one),
            jax.eval_shape(model.init, jax.random.PRNGKey(0)))
        batch = {"input_ids": jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one)}
        step = lambda p, b: jax.value_and_grad(  # noqa: E731
            lambda p: model.loss(p, b).astype(jnp.float32))(p)
        return _lower(step, params, batch), n_kernels
    return build


CASES = {
    "flash-fwd-8x1024x16x64": _flash("fwd", 8, 1024, 16, 64),
    "flash-bwd-8x1024x16x64": _flash("bwd", 8, 1024, 16, 64),
    "flash-window-8x1024x16x64": _flash("window", 8, 1024, 16, 64),
    # a chip's share of the four-chip training cell (gpt2-xl: 25 heads)
    "flash-fwd-8x1024x25x64": _flash("fwd", 8, 1024, 25, 64),
    "flash-bwd-8x1024x25x64": _flash("bwd", 8, 1024, 25, 64),
    # the longest sequence a head walks in ONE grid step (`flash_attention._WHOLE`)
    "flash-bwd-4x2048x16x128": _flash("bwd", 4, 2048, 16, 128),
    # a head too long for one step (since PR 50): blocks of 1,024 on both axes (`flash_attention._MAJOR`; at head
    # width 128 as at 64, their bytes within `_WHOLE`'s), each pair walked in tiles of 256 rows with the state in
    # VMEM scratch once a (row tile, grid step) and the statistics as rows; 4,096 is four such blocks an axis
    "flash-fwd-2x4096x32x128": _flash("fwd", 2, 4096, 32, 128),
    "flash-bwd-2x4096x32x128": _flash("bwd", 2, 4096, 32, 128),
    "flash-window-2x4096x32x128": _flash("window", 2, 4096, 32, 128),
    "flash-gqa-bwd-2x4096x32x128-kv8": _flash("bwd", 2, 4096, 32, 128, kv_heads=8),
    # the LFM2 training cell's attention layer: 2 rows x 32 query / 8 key-value heads of 64 at S 8,192
    "flash-fwd-2x8192x32x64-kv8": _flash("fwd", 2, 8192, 32, 64, kv_heads=8),
    "flash-bwd-2x8192x32x64-kv8": _flash("bwd", 2, 8192, 32, 64, kv_heads=8),
    "flash-window-2x8192x32x64-kv8": _flash("window", 2, 8192, 32, 64, kv_heads=8),
    "block-sparse-bwd-4x1024x12x64": _block_sparse,
    "flash-bwd-mesh-tensor4": _flash_on_mesh(
        {"tensor": 4}, PartitionSpec(None, None, "tensor", None)),
    "flash-bwd-mesh-fsdp4": _flash_on_mesh(
        {"fsdp": 4}, PartitionSpec("fsdp", None, None, None)),
    # the serving rows' read at the benchmark's two GPT-2 cells: 16 slots of gpt2-xl, 40 of gpt2-medium
    "decode-rows-xl-16x1024x25x64": _decode_rows(16, 25, 64, 48, 1024),
    "decode-rows-medium-40x1024x16x64": _decode_rows(40, 16, 64, 24, 1024),
    # fwd + dq + dkv inside the layer scan: the default policy keeps the
    # forward kernel's output and log-sum-exp, so remat does not rerun it
    "gpt2-350m-loss-grad-mb8": _gpt2_350m_step(3),
    # fwd + remat'd fwd + dq + dkv
    "gpt2-350m-loss-grad-mb8-nothing-saveable": _gpt2_350m_step(
        4, remat_policy="nothing_saveable"),
}


@pytest.fixture(scope="module")
def compiled(topo):
    """case -> (lowered, compiled, kernels expected), each compiled once."""
    @functools.cache
    def of(case):
        lowered, n_kernels = CASES[case](topo)
        return lowered, lowered.compile(), n_kernels  # raises what the chip's compiler would
    return of


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(compiled, case):
    lowered, executable, n_kernels = compiled(case)
    # the Mosaic kernel itself was lowered, not the interpreter's loops
    assert lowered.as_text().count("tpu_custom_call") >= n_kernels
    mem = executable.memory_analysis()
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < HBM_BYTES, (case, resident)


def test_layer_checkpoint_keeps_the_flash_residuals_dense(compiled):
    """What the default remat policy saves of the flash forward, stacked by
    the layer scan: the output as the model's (B, S, D) and the log-sum-exp
    as (B, H, S). The kernel's own (B, H, S, 64) and (B, H, S, 1) would be
    padded to 128 lanes (twice the bytes) and to whole (8, 128) tiles (128
    times). One Mosaic call fewer than ``nothing_saveable``, for one
    (24, 8, 1024, 1024) bfloat16 array more — which the compile holds twice."""
    _, kept, _ = compiled("gpt2-350m-loss-grad-mb8")
    _, recomputed, _ = compiled("gpt2-350m-loss-grad-mb8-nothing-saveable")
    text = kept.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert recomputed.as_text().count('custom_call_target="tpu_custom_call"') == 4
    assert "bf16[24,8,1024,1024]" in text and "f32[24,8,16,1024]" in text
    assert "[24,8,16,1024,64]" not in text and "[24,8,16,1024,1]" not in text
    grown = (kept.memory_analysis().temp_size_in_bytes
             - recomputed.memory_analysis().temp_size_in_bytes)
    assert 0 < grown <= 0.9e9, grown


@pytest.mark.parametrize("preset,slots,read_len,chunk,by_length", [
    ("gpt2-1.5b", 16, 256, None, True), ("gpt2-1.5b", 16, None, None, True),
    ("gpt2-1.5b", 16, 512, 128, True), ("gpt2-1.5b", 16, None, 128, True),
    ("gpt2-350m", 40, None, None, True), ("gpt2-1.5b", 16, 128, None, False),
    ("gpt2-350m", 40, None, 128, True), ("gpt2-350m", 40, 256, None, True),
    ("gpt2-1.5b", 16, 128, 128, False), ("gpt2-350m", 40, 128, None, False),
    ("gpt2-350m", 40, 128, 128, False),
], ids=["plain-read256", "plain-read1024", "fused128-read512", "fused128-read1024",
        "chat-plain-read1024", "plain-read128", "chat-fused128-read1024", "chat-plain-read256",
        "fused128-read128", "chat-plain-read128", "chat-fused128-read128"])
def test_serving_tick_updates_the_kv_pool_in_place(topo, preset, slots, read_len, chunk, by_length):
    """The gpt2-xl serving tick (16 slots x 1024, the benchmark's batch
    cell) and gpt2-medium's (40 x 1024, the chat cell) for the chip: the
    chip lays the pool bf16[48,16,1024,25,64] out TIME-minor (heads-minor
    would pad (25, 64) 2.6x), and a token-sized scatter or
    ``dynamic_update_slice`` on the carried pool makes the compiler turn the
    whole pool heads-minor and back — pool-sized copies and 12 GB of
    temporaries. The tick must compile to an in-place update: no ``copy`` of
    the pool's or a layer's shape, the pool aliased to the output,
    temporaries far under the pool's 5 GB. At every read bucket of these
    shapes, the 128-slot one too since PR 54 (``kv_cache.takes_block_write``
    with ``time_minor``), the rows' tokens go in through ``kv_block_write``
    (one call for K, one for V, in the layer loop; the pool enters it as its
    (L, B, H, x, T) transpose, which must be a bitcast here, not a copy; the
    tokens as ONE (1, H, x, 128) tile with the rows on the lanes, and PR 32's
    (rows, H, x, 128) broadcast, 10 MB a call at gpt2-medium, is nowhere) and
    no op of ``attn.kv_write`` yields a value of the window's shape.
    ``by_length`` is what ``kv_cache.takes_length_read`` decides, on the
    rule it had: above one 128-slot block the rows' READ goes through
    ``decode_rows`` (PR 39): once in the layer loop, K and V in one call, the
    pools entering as their (L, B, H * x, T) view, which again
    must be a bitcast (the copy check above); the rows' float32 logits
    (B, heads, 1, T), which the compiler keeps as (B, heads, T), are then
    nowhere in the program, and at a 128-slot read they are. The q / k / v
    products take the stacked weights by the layer's index as the engine
    holds them (PR 46): no layer's (D, D) slice is transposed or read into
    VMEM by an op of its own."""
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    length = 1024
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
    model = tf.TransformerModel.from_preset(preset, dtype="bfloat16", max_seq_len=length,
                                            attn_impl="pallas")
    cfg = model.cfg
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda a: one, abstract)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one),
                          abstract)
    with force_interpret(False):
        fn, cache_sh, _ = compile_pool_tick_fn(mesh, cfg, p_sh, slots, length, 1, 0.0, 0, 1.0,
                                               read_len=read_len, chunk=chunk)
        cache = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(lambda: tf.init_cache(cfg, slots, length)), cache_sh)
        row = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        args = [params, cache, row, row, row, row, row, row,
                jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = 2 * cfg.num_layers * slots * length * cfg.kv_heads * cfg.head_dim * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 8, mem.temp_size_in_bytes
    text = compiled.as_text()
    kv = rf"{slots},{length},{cfg.kv_heads},{cfg.head_dim}\]"
    copies = re.findall(rf"= bf16\[(?:\d+,)?{kv}\S* copy\(", text)
    assert not copies, copies
    # nor is a q / k / v weight (PR 46, ``tf._heads_product``): before, a layer's slice of each was
    # read into VMEM by an op of its own and two or three of them transposed there, every layer
    D = cfg.hidden_size
    moved = re.findall(rf"= bf16\[(?:\d+,)?{D},{D}\](?:\S* copy|\{{[^}}]*S\(1\)\}} dynamic-slice)\(", text)
    assert not moved, moved
    # values of the window's shape among the ops of the rows' / the chunk's write
    scopes = scope_table(text)
    window = re.compile(rf"\s*(?:ROOT\s+)?%?([\w.\-]+) = bf16\[(?:1,)?{slots},{read_len or length},"
                        rf"{cfg.kv_heads},{cfg.head_dim}\]")
    rewritten = [m.group(1) for m in map(window.match, text.splitlines())
                 if m and "attn.kv_write" in scopes.get(m.group(1), "")]
    assert not rewritten, rewritten
    assert len(re.findall(r" custom-call\(.*kv_block_write", text)) == 2
    # the tokens the kernel is handed: one tile with the rows on the lanes, no block a row
    tile = re.compile(rf"\s*(?:ROOT\s+)?%?([\w.\-]+) = bf16\[(\d+),{cfg.kv_heads},{cfg.head_dim},128\]")
    tokens = {m.group(2) for m in map(tile.match, text.splitlines())
              if m and "attn.kv_write" in scopes.get(m.group(1), "")}
    assert tokens == {"1"}, tokens
    assert len(re.findall(r" custom-call\(.*decode_rows", text)) == (1 if by_length else 0)
    logits = re.findall(rf"= f32\[{slots},{cfg.num_heads},(?:1,)?{read_len or length}\]", text)
    assert bool(logits) != by_length, logits[:3]


def test_plan_tick_writes_its_full_pool_by_blocks_in_place(topo, monkeypatch):
    """A toy layer plan (heads BEFORE time: the same kernel with time on the
    second-minor axis, or on the minor one where a leaf's width is not whole
    lanes) with the rule's constant at zero: the full pool's rows go by
    blocks in both of its layers' scans, the 8-slot window ring keeps the
    window path, both pools are aliased and nothing of the full window's
    shape comes out of ``attn.kv_write``."""
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf
    from deepspeed_tpu.ops.transformer import kv_cache

    monkeypatch.setattr(kv_cache, "BLOCK_WRITE_MIN_ROW_BYTES", 0)
    kinds = (tf.LayerKind("dense_full", kv_heads=1, rope_theta=1e7, ffn="dense", ffn_size=256),
             tf.LayerKind("moe_window", kv_heads=2, window=8, rope_theta=1e4, sink=True, ffn="moe",
                          ffn_size=128),
             tf.LayerKind("moe_full", kv_heads=1, rope_theta=1e7, ffn="moe", ffn_size=128))
    cfg = tf.TransformerConfig(
        vocab_size=512, hidden_size=256, num_layers=5, num_heads=4, head_size=192, v_head_size=128,
        rope_dim=64, pos_embedding="rope", norm_type="rmsnorm", activation="silu_glu",
        tie_embeddings=False, use_bias=False, dtype="bfloat16", attn_impl="pallas", max_seq_len=512,
        layer_kinds=kinds, layer_plan=(0, 1, 1, 1, 2), moe_num_experts=16, moe_top_k=4,
        moe_experts_held=(4, 8))
    slots, length = 8, 512
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
    abstract = jax.eval_shape(tf.TransformerModel(cfg).init, jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda a: one, abstract)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one),
                          abstract)
    with force_interpret(False):
        fn, cache_sh, _ = compile_pool_tick_fn(mesh, cfg, p_sh, slots, length, 1, 0.0, 0, 1.0,
                                               read_len=256)
        cache = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(lambda: tf.init_cache(cfg, slots, length)), cache_sh)
        row = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        compiled = fn.lower(params, cache, row, row, row, row, row, row,
                            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    comm.destroy()
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * 2 for a in jax.tree.leaves(cache))
    text, scopes = compiled.as_text(), scope_table(compiled)
    shaped = lambda dims: [m.group(1) for m in map(re.compile(
        rf"\s*(?:ROOT\s+)?%?([\w.\-]+) = bf16\[(?:1,)?{dims}\]").match, text.splitlines())
        if m and "attn.kv_write" in scopes.get(m.group(1), "")]
    assert not shaped(f"{slots},1,256,192") and not shaped(f"{slots},1,256,128")   # the full windows
    assert len(re.findall(r" custom-call\(.*kv_block_write", text)) == 4      # K, V x two full runs
    assert shaped(f"{slots},2,8,192")                # the ring, through its window
    assert not re.findall(r"= bf16\[\d+,8,1,512,\d+\]\S* copy\(", text)   # no copy of the full pool


def test_this_module_compiles_at_the_default_level():
    """``tests/conftest.py`` compiles the suite's CPU programs cheaply and
    lists this module among those that keep the backend's default level."""
    assert jax.config.read("jax_disable_most_optimizations") is False
