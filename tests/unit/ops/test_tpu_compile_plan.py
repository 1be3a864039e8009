"""Ahead-of-time compiles, for a *described* ``v5e:2x2``, of what a layer
plan adds: the flash chunk kernel at the published head widths (192 for
queries and keys, not a multiple of the 128 lanes; 128 for values), the
grouped matmul over the held experts, and the MiMo-V2.5 serving tick at the
benchmark's cut (``benchmark/configs/mimo-v2.5.json``, 32 slots of 16,896
positions): both pools updated in place, no copy of a pool, temporaries
far under the pools; and what a state pool adds (PR 34): the chunked
delta-rule scan and the rows' in-place state step at the published widths,
flash at head width 256, and the Qwen3-Next serving tick at the benchmark's
cut (``benchmark/configs/qwen3-next-80b-a3b.json``) with the key-value pool
and the state pool both in place; and what a latent pool adds (PR 38): the
rows' absorbed attention over the pool in place (``mla_decode``) at 20 heads
over 640 stored columns, and the GLM-4.7-Flash serving tick at the
benchmark's cut (``benchmark/configs/glm-4.7-flash.json``) with the latent
pool in place and the block write at width 640. Nothing runs. Skipped where
libtpu cannot describe the topology."""

import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from deepspeed_tpu import comm
from deepspeed_tpu.ops.pallas.interpret import force_interpret

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to compile against
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(topo, fn, *shapes):
    sh = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
    with force_interpret(False):
        lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


def _qkv_weights_moved(text, abstract):
    """The ops of a compiled tick that re-lay out a ``wq`` / ``wk`` / ``wv``
    leaf of ``abstract`` or a layer's slice of one, by the value's shape: a
    ``copy`` (the transposed weight a heads-first product wanted before PR
    46), or a ``dynamic-slice`` into VMEM (``S(1)``: the layer's weight read
    by an op of its own, ahead of the product, where the product can take
    the stack by the layer's index)."""
    shapes = set()

    def note(path, leaf):
        if getattr(path[-1], "key", None) in ("wq", "wk", "wv"):
            shapes.add(",".join(map(str, leaf.shape[-2:])))

    jax.tree_util.tree_map_with_path(note, abstract)
    assert shapes
    value = rf"= bf16\[(?:\d+,)*(?:{'|'.join(sorted(shapes))})\]"
    return re.findall(value + r"\S* copy\(.*", text) + re.findall(
        value + r"\{[^}]*S\(1\)\} dynamic-slice\(.*", text)


def _block_writes(text):
    """How many ``kv_block_write`` calls the compiled program holds, each with
    its pool operand aliased to its result (updated where it lies)."""
    calls = [call.split("backend_config=")[0]
             for call in re.findall(r"%kv_block_write\S* = \S+ custom-call\(.*", text)]
    assert all("output_to_operand_aliasing={{}: (3, {})}" in call for call in calls), calls
    return len(calls)


@pytest.mark.parametrize("shape,dtype,size", [
    ((192, 16, 16, 320, 128), jnp.bfloat16, 320),       # Ouro: twenty 16-slot blocks, none past the leaf
    ((192, 16, 16, 320, 128), jnp.bfloat16, 256),
    ((2, 32, 8, 16896, 128), jnp.bfloat16, 2048),       # MiMo's values
    ((3, 8, 2, 16896, 256), jnp.bfloat16, 2048),        # Qwen3-Next
    ((6, 32, 1, 16896, 640), jnp.bfloat16, 2048),       # the latent pool
    ((2, 64, 25, 2048, 128), jnp.bfloat16, 2048),       # 100 KiB a row's block: 32 of 64 rows a grid step
    ((4, 16, 16, 2048, 128), jnp.float32, 512),         # 8-slot blocks
    ((4, 16, 16, 2048, 128), jnp.int8, 512),            # 32-slot blocks
], ids=["ouro-320", "ouro-256", "mimo-values", "qwen3-next", "latent-640", "two-steps", "float32", "int8"])
def test_block_write_of_a_lane_aligned_leaf_compiles_in_place(topo, shape, dtype, size):
    """The rows' one-token write where the leaf's width is whole lanes
    (PR 45): one ``kv_block_write`` call whose DMAs move one sublane tile of
    slots a row, the pool aliased, no temporary at all (the token array of a
    128-slot block a row is gone) and no copy of the leaf."""
    from deepspeed_tpu.ops.transformer import kv_cache

    L, B, H, T, x = shape
    sh = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh)
            for s, d in ((shape, dtype), ((B, H, x), dtype), ((B,), jnp.int32), ((), jnp.int32))]
    assert kv_cache.takes_block_write(size, size * H * x * jnp.dtype(dtype).itemsize, ragged=True)
    with force_interpret(False):
        compiled = jax.jit(lambda pool, new, cols, layer: kv_cache.write(
            pool, layer, new, cols, size, heads_first=True), donate_argnums=0).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _block_writes(text) == 1
    assert mem.temp_size_in_bytes == 0 and mem.alias_size_in_bytes == math.prod(shape) * jnp.dtype(dtype).itemsize
    assert not re.findall(rf"\[{L},{B},{H},{T},{x}\]\S* copy\(", text)


def _compile_flash_chunk(topo, W, T, H, kv, dk, dv, window=None, q_off=None, sink=True):
    """``flash_attention_chunk`` at these shapes for the described chip: the Mosaic call keeps the
    name every cell's ``flash_roofline.*`` reads, and the step's blocks and state fit VMEM (the
    compile raises where they do not). ``q_off`` an int: static, as a window layer passes it."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_chunk

    bf, i32 = jnp.bfloat16, jnp.int32
    sh = SingleDeviceSharding(topo.devices[0])
    shapes = [((W, H, dk), bf), ((kv, T, dk), bf), ((kv, T, dv), bf), ((), i32), ((), i32),
              ((H,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]

    def fn(q, k, v, a, b, s):
        return flash_attention_chunk(q, k, v, a if q_off is None else q_off, b, s if sink else None,
                                     window)
    with force_interpret(False):
        lowered = jax.jit(fn).lower(*args)
    assert "flash_chunk_fwd" in lowered.as_text() and "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


@pytest.mark.parametrize("W,T,kv,window", [(1024, 16896, 4, None), (1024, 1152, 8, 128),
                                           (256, 384, 8, 128), (512, 2048, 4, None)])
def test_flash_chunk_kernel_compiles_at_the_published_widths(topo, W, T, kv, window):
    _compile_flash_chunk(topo, W, T, 64, kv, 192, 128, window)


@pytest.mark.parametrize("W", [1024, 512, 256])
def test_flash_chunk_kernel_compiles_with_the_window_layers_static_offset(topo, W):
    # as ``layer_plan._attend_cached`` calls it: the ring's tail joined to the chunk, the offset
    # and the window the same Python int, only the row's first key traced
    _compile_flash_chunk(topo, W, 128 + W, 64, 8, 192, 128, window=128, q_off=128)


@pytest.mark.parametrize("H,kv,width", [(32, 8, 128), (20, 20, 256)],
                         ids=["granite-32-over-8-at-128", "glm-20-over-20-at-256"])
def test_flash_chunk_kernel_compiles_at_the_other_plans_shapes(topo, H, kv, width):
    _compile_flash_chunk(topo, 1024, 16896, H, kv, width, width, sink=False)


@pytest.mark.parametrize("tokens", [32, 1056], ids=["decode-rows", "rows-and-a-chunk"])
def test_held_experts_layer_compiles_at_the_published_widths(topo, tokens):
    from deepspeed_tpu.moe import held_experts as he

    def layer(h, gate, bias, wg, wi, wo):
        chosen, weights = he.route(h, gate, bias, 8)
        return he.held_experts_ffn(h, chosen, weights, {"wg": wg, "wi": wi, "wo": wo}, 48, 16,
                                   layer=jnp.int32(2))

    bf = jnp.bfloat16
    compiled = _compile(topo, layer, ((tokens, 4096), bf), ((4096, 256), bf), ((256,), bf),
                        ((5, 16, 4096, 2048), bf), ((5, 16, 4096, 2048), bf),
                        ((5, 16, 2048, 4096), bf))
    # the kernel reads the layer out of the stack: no copy of a layer's experts (268 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 200e6


@pytest.mark.parametrize("N,k,D,F,E,count,gated", [
    (1056, 10, 4096, 768, 72, 36, True), (640, 22, 1024, 2688, 512, 128, False),
    (1056, 4, 2048, 1536, 64, 64, True)], ids=["granite-top10", "nemotron-top22-latent", "glm-top4"])
def test_the_serving_expert_layers_way_back_never_lays_top_k_on_the_sublanes(topo, N, k, D, F, E, count, gated):
    """A fused tick's expert layer at the cells' shapes: no ``reshape`` or ``copy`` of the gathered
    rows as a rank-3 array (with k second-minor the chip pads top-10 to 16 in a copy of it all:
    0.68 ms a Granite layer and as much again around it, PERF.md section 6, PR 52), and the way back alone
    (``held_experts._combine``) needs less scratch than the gather's own output."""
    from deepspeed_tpu.moe import held_experts as he

    def layer(h, gate, bias, valid, *stacks):
        chosen, weights = he.route(h, gate, bias, k)
        experts = dict(zip(("wg", "wi", "wo") if gated else ("wi", "wo"), stacks))
        return he.held_experts_ffn(h, chosen, weights, experts, 0, count, valid=valid, layer=jnp.int32(1))

    bf = jnp.bfloat16
    stacks = [((2, count, D, F), bf)] * (2 if gated else 1) + [((2, count, F, D), bf)]
    text = _compile(topo, layer, ((N, D), bf), ((D, E), bf), ((E,), bf), ((N,), jnp.bool_), *stacks).as_text()
    gathered = [m.group(0) for m in re.finditer(
        r"= \w+\[(\d+),(\d+),(\d+)\]\{[^}]*\} (?:reshape|copy)\(", text)
        if sorted(map(int, m.groups())) == sorted((N, k, D))]
    assert not gathered, gathered
    M = he.buffer_rows(N, k, count, he.row_tile(N, k))
    sh = SingleDeviceSharding(topo.devices[0])
    alone = jax.jit(he._combine).lower(*[jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in (
        ((M, D), bf), ((N, k), jnp.int32), ((N, k), jnp.float32))]).compile()
    assert alone.memory_analysis().temp_size_in_bytes < N * k * D * 2, alone.memory_analysis()


def test_the_training_expert_layer_compiles_a_bucket_and_the_whole_buffer(topo):
    """The LFM2 training cell's expert layer (16,384 tokens, top-4 of 64 with 8 held, 2,048 x
    1,536), forward and gradient under a checkpoint: one conditional with two branches each way,
    and in the bucket's branch nothing sized by the worst-case buffer (66,560 rows) or by the
    65,536 assignments."""
    from deepspeed_tpu.moe import held_experts as he

    N, D, F, E, k, count = 16384, 2048, 1536, 64, 4, 8
    assert (he.bucket_rows(N, k, count, E, 128), he.buffer_rows(N, k, count, 128)) == (17408, 66560)

    def layer(x, gate, bias, wg, wi, wo):
        def body(x, gate, wg, wi, wo):
            chosen, weights = he.route(x, gate, bias, k)
            return x + he.held_experts_ffn(x, chosen, weights, {"wg": wg, "wi": wi, "wo": wo}, 0, count,
                                           grad=True, n_experts=E)[0]

        loss = lambda *a: jax.checkpoint(body)(*a).astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(x, gate, wg, wi, wo)

    bf = jnp.bfloat16
    sh = SingleDeviceSharding(topo.devices[0])
    shapes = [(N, D), (D, E), (E,), (count, D, F), (count, D, F), (count, F, D)]
    compiled = jax.jit(layer).lower(*[jax.ShapeDtypeStruct(s, bf, sharding=sh) for s in shapes]).compile()
    text = compiled.as_text()
    conds = re.findall(r" conditional\(.*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", text)
    assert len(conds) == 2, conds     # the forward's and the backward's; the checkpoint's replay is dead
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^%?([\w.\-]+) \([^\n]*\) -> [^\n]*\{\n(.*?)^\}", text, re.S | re.M)}

    def reach(name, seen):
        if name in seen or name not in bodies:
            return seen
        seen.add(name)
        for callee in re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", bodies[name]):
            reach(callee, seen)
        return seen

    products = 0
    for whole, bucket in conds:       # branch 0 is the predicate false: the whole buffer
        in_bucket = "".join(bodies[c] for c in reach(bucket, set()))
        in_whole = "".join(bodies[c] for c in reach(whole, set()))
        assert "[17408," in in_bucket and "[66560," in in_whole
        assert not re.search(r"\[(?:66560|65536),", in_bucket), re.findall(r".*\[(?:66560|65536),.*", in_bucket)[:3]
        products += len(re.findall(r"custom-call\(.*ragged-dot", in_bucket))
    assert products == 15             # the parent's whole-buffer layer makes 15 too (4 forward, 11 back)
    mem = compiled.memory_analysis()
    print("temp_size_in_bytes", mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes < 2.3e9, mem.temp_size_in_bytes    # 2.02 GB (the parent's layer: 2.51)


@pytest.mark.parametrize("read_len,chunk", [(None, None), (1024, None), (None, 1024), (2048, 256)],
                         ids=["plain", "plain-read1024", "fused1024", "fused256-read2048"])
def test_mimo_tick_updates_both_pools_in_place(topo, read_len, chunk):
    from benchmark import models_mimo_v2
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    with open(os.path.join(ROOT, "benchmark", "configs", "mimo-v2.5.json")) as fh:
        config = json.load(fh)
    slots, length = 32, 16896
    model = models_mimo_v2.build_model(config, max_seq_len=length, remat=False, attn_impl="pallas")
    cfg = model.cfg
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
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
        args = [params, cache, row, row, row, row, row, row, jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = sum(a.size * 2 for a in jax.tree.leaves(cache))
    assert pool_bytes == 32 * (2 * 4 * 16896 + 5 * 8 * 128) * 320 * 2   # 2.87 GB, not 19.4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # 0.143 / 0.004 / 0.376 / 0.091 GB since PR 46 (0.142 / 0.112 / 0.343 / 0.274 before): with a
    # 1,024-token chunk the q product's result (1,056 x 12,288, 26 MB) is re-laid out for the heads
    # where the parent transposed a layer's wq (100 MB) for it
    assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 15.75e9
    # the full-length pool is never copied (the ring, 0.1 GB, may be re-laid out for a chunk)
    text = compiled.as_text()
    copies = re.findall(r"= bf16\[(?:\d+,)?32,4,16896,\d+\]\S* copy\(", text)
    assert not copies, copies
    # nor is a q / k / v weight: the products take a layer's slice as it is held (PR 46: seven
    # transposed slices before; one prefetched into VMEM asynchronously, ``copy-start``, is no such op)
    assert not _qkv_weights_moved(text, abstract)
    # the full layers' rows go by blocks from a 512-slot read on (both leaves, K time-minor in 128-slot
    # blocks and V in sublane tiles, one layer body a run of full layers), every call in place; since
    # PR 54 so do the KEYS of the window layers' 128-slot ring (time-minor, 8 heads of 192: 393 KB a
    # row, over half the constant; the kernel moves the live rows' blocks alone), one call in their
    # run's body, while the ring's values (whole lanes, 262 KB) keep the window path
    assert _block_writes(text) in (3, 5)


def test_flash_chunk_kernel_compiles_at_head_width_256(topo):
    _compile_flash_chunk(topo, 1024, 16896, 16, 2, 256, 256, sink=False)   # Qwen3-Next: 16 over 2


@pytest.mark.parametrize("W", [1024, 256])
def test_delta_rule_chunk_scan_compiles_at_the_published_widths(topo, W):
    from deepspeed_tpu.ops.pallas.gated_delta import gdn_chunk

    f32 = jnp.float32
    _compile(topo, gdn_chunk, ((W, 32, 128), f32), ((W, 32, 128), f32), ((W, 32, 128), f32),
             ((W, 32), f32), ((W, 32), f32), ((32, 128, 128), f32))


def test_delta_rule_rows_step_updates_the_state_pool_in_place(topo):
    from deepspeed_tpu.ops.pallas.gated_delta import gdn_step_pool

    f32 = jnp.float32
    compiled = _compile(topo, lambda pool, layer, *a: gdn_step_pool(pool, layer, *a),
                        ((9, 32, 32, 128, 128), f32), ((), jnp.int32), ((32, 32, 128), f32),
                        ((32, 32, 128), f32), ((32, 32, 128), f32), ((32, 32), f32), ((32, 32), f32))
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6      # no copy of the pool (604 MB)


@pytest.mark.parametrize("read_len,chunk", [(None, None), (None, 1024), (2048, 256)],
                         ids=["plain", "fused1024", "fused256-read2048"])
def test_qwen3_next_tick_updates_both_kinds_of_pool_in_place(topo, read_len, chunk):
    from benchmark import models_qwen3_next
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    with open(os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as fh:
        config = json.load(fh)
    slots, length = 32, 16896
    model = models_qwen3_next.build_model(config, max_seq_len=length, remat=False,
                                          attn_impl="pallas")
    cfg = model.cfg
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
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
        args = [params, cache, row, row, row, row, row, row, jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert pool_bytes == 3 * 32 * 2 * 16896 * 512 * 2 + 9 * 32 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes                     # 3.32 + 0.62 GB, in place
    assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes    # 0.07 plain, 0.23 with a chunk
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 15.75e9
    text = compiled.as_text()
    # neither pool is copied, and no run's weights are sliced out of their kind's stack
    assert not re.findall(r"= bf16\[(?:\d+,)?32,2,16896,\d+\]\S* copy\(", text)
    assert not re.findall(r"= f32\[(?:\d+,)?32,32,128,128\]\S* (?:copy|dynamic-slice)\(", text)
    assert not re.findall(r"= bf16\[3,2048,12288\]\S* slice\(", text)
    assert not _qkv_weights_moved(text, abstract)
    assert "gdn_step" in text and ("gdn_chunk_fwd" in text) == (chunk is not None)


@pytest.mark.parametrize("size", [16896, 2048])
def test_the_rows_latent_attention_reads_the_pool_in_place(topo, size):
    from deepspeed_tpu.ops.pallas.mla_attention import mla_decode

    bf, i32 = jnp.bfloat16, jnp.int32
    compiled = _compile(topo, lambda q, pool, layer, lengths: mla_decode(
        q, pool, layer, lengths, size=size, sm_scale=1 / 16.0),
        ((32, 20, 640), bf), ((6, 32, 1, 16896, 640), bf), ((), i32), ((32,), i32))
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6      # no copy of the pool (4.15 GB)


@pytest.mark.parametrize("size", [16896, 2048])
def test_a_chunks_expansion_compiles_at_the_published_widths(topo, size):
    from deepspeed_tpu.ops.pallas.mla_attention import mla_expand

    bf = jnp.bfloat16
    compiled = _compile(topo, lambda row, wuk, wuv, end: mla_expand(
        row, wuk, wuv, end, rank=512, rope=64),
        ((size, 640), bf), ((20, 512, 192), bf), ((20, 512, 256), bf), ((), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 1e6       # the kernel alone, no relayout


@pytest.mark.parametrize("read_len,chunk", [(None, None), (None, 1024), (2048, 256)],
                         ids=["plain", "fused1024", "fused256-read2048"])
def test_glm_tick_updates_the_latent_pool_in_place(topo, read_len, chunk):
    from benchmark import models_glm4_moe_lite
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    with open(os.path.join(ROOT, "benchmark", "configs", "glm-4.7-flash.json")) as fh:
        config = json.load(fh)
    slots, length = 32, 16896
    model = models_glm4_moe_lite.build_model(config, max_seq_len=length, remat=False,
                                             attn_impl="pallas")
    cfg = model.cfg
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
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
        args = [params, cache, row, row, row, row, row, row, jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = sum(a.size * 2 for a in jax.tree.leaves(cache))
    assert pool_bytes == 6 * 32 * 16896 * 640 * 2                    # 4.15 GB, not 66.4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.6e9, mem.temp_size_in_bytes    # 0.002 plain, 0.45 with a chunk
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 15.75e9
    text = compiled.as_text()
    assert not re.findall(r"= bf16\[(?:\d+,)?32,1,16896,\d+\]\S* copy\(", text)
    assert "mla_decode" in text and ("flash_chunk_fwd" in text) == (chunk is not None)
    assert ("mla_expand" in text) == (chunk is not None)
    assert _block_writes(text) >= 1                                  # 2,048 slots x 1,280 B a row: over the rule
    assert not re.findall(r"bf16\[32,1,128,640\]", text)             # no 128-slot block a row anywhere


SSD_POOL = ((9, 32, 64, 128, 128), jnp.float32)           # Granite 4.0-H Small's state pool: 1.21 GB


def _in_place(compiled):
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9         # no copy of the pool (1.21 GB)


def test_state_space_rows_step_updates_the_state_pool_in_place(topo):
    from deepspeed_tpu.ops.pallas.ssd import ssd_step_pool

    f32 = jnp.float32
    _in_place(_compile(topo, ssd_step_pool, SSD_POOL, ((), jnp.int32), ((32, 8192), f32),
                       ((32, 128), f32), ((32, 128), f32), ((32, 128), f32)))


@pytest.mark.parametrize("W", [1024, 128])
def test_state_space_chunk_scan_compiles_with_the_state_pool_in_place(topo, W):
    from deepspeed_tpu.ops.pallas.ssd import ssd_chunk_pool

    f32, i32 = jnp.float32, jnp.int32
    _in_place(_compile(topo, ssd_chunk_pool, SSD_POOL, ((), i32), ((), i32), ((W, 8192), f32),
                       ((W, 128), f32), ((W, 128), f32), ((W, 128), f32)))


@pytest.mark.parametrize("read_len,chunk", [(None, None), (None, 1024), (2048, 256)],
                         ids=["plain", "fused1024", "fused256-read2048"])
def test_granite_tick_updates_both_kinds_of_pool_in_place(topo, read_len, chunk):
    """What a state-space mixer adds (PR 42): the Granite-4.0-H-Small tick at
    the benchmark's cut with the key-value pool of its one attention layer
    and the float32 state pool of its nine Mamba-2 layers both in place
    (the two kernels alone: the tests above)."""
    from benchmark import models_granitemoehybrid
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    with open(os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-small.json")) as fh:
        config = json.load(fh)
    slots, length = 32, 16896
    model = models_granitemoehybrid.build_model(config, max_seq_len=length, remat=False,
                                                attn_impl="pallas")
    cfg = model.cfg
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
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
        args = [params, cache, row, row, row, row, row, row, jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert pool_bytes == 32 * 8 * 16896 * 256 * 2 + 9 * 32 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes                     # 2.21 + 1.22 GB, in place
    assert mem.temp_size_in_bytes < 0.7e9, mem.temp_size_in_bytes
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 0.85 * 16.91e9, resident
    print("granite tick", read_len, chunk, "temp", mem.temp_size_in_bytes / 1e9, "arguments",
          mem.argument_size_in_bytes / 1e9, "resident", resident / 1e9)
    text = compiled.as_text()
    # neither pool is copied, no slab of the state pool is sliced out, no run's weights either
    assert not re.findall(r"= bf16\[(?:\d+,)?32,8,16896,\d+\]\S* copy\(", text)
    assert not re.findall(r"= f32\[(?:\d+,)?32,64,128,128\]\S* (?:copy|dynamic-slice)\(", text)
    assert not re.findall(r"= bf16\[[45],4096,16768\]\S* slice\(", text)
    assert not _qkv_weights_moved(text, abstract)
    assert "ssd_step" in text and ("ssd_chunk_fwd" in text) == (chunk is not None)
    assert ("flash_chunk_fwd" in text) == (chunk is not None)


@pytest.mark.parametrize("read_len,chunk", [(None, None), (256, 256)], ids=["plain", "fused256-read256"])
def test_ouro_tick_walks_one_layer_body_four_times_with_both_pool_leaves_in_place(topo, read_len,
                                                                                 chunk):
    """What a loop adds (PR 44): the Ouro-2.6B tick at the benchmark's sizes,
    the model whole (48 layers x 4 passes, 5.34 GB) and its pool of 192
    layer-steps (8.05 GB at 16 slots of 320) carried and donated through BOTH
    scans, the one over the passes and the one over the layers: no temporary
    of the pool's size, no copy of a leaf, and ONE layer body in the program
    (flash at 16 heads over 16 key-value heads of 128, a 320-long row). The
    q / k / v products take their weights as the engine holds them (PR 46,
    ``tf._heads_product``): no transposed copy of a stacked ``wq`` / ``wk`` /
    ``wv`` hoisted out of both loops (0.4 GB of temporaries each, read and
    written every tick, before), and no layer's slice of one read into VMEM
    ahead of its product: the product takes the stack by the layer's index."""
    from benchmark import models_ouro
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    with open(os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "cells", "serve-ouro-2.6b-chat-batch.json")) as fh:
        cell = json.load(fh)["serve_looped"]
    slots, length = cell["slots"], cell["cache_len"]
    model = models_ouro.build_model(config, max_seq_len=length, remat=False, attn_impl="pallas")
    cfg = model.cfg
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
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
        args = [params, cache, row, row, row, row, row, row, jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert pool_bytes == 192 * slots * length * 2 * 16 * 128 * 2     # 1.5 MiB a position
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes                     # 8.05 GB, in place
    assert mem.temp_size_in_bytes < 0.15e9, mem.temp_size_in_bytes   # 0.0007 plain, 0.0023 fused
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 13.6e9, resident
    print("ouro tick", read_len, chunk, "temp", mem.temp_size_in_bytes / 1e9, "arguments",
          mem.argument_size_in_bytes / 1e9, "resident", resident / 1e9)
    text = compiled.as_text()
    assert not re.findall(rf"= bf16\[(?:\d+,)?{slots},16,{length},128\]\S* copy\(", text)
    assert not re.findall(r"= bf16\[(?:\d+,)?2048,5632\]\S* copy\(", text)   # nor of a layer's weights
    assert not _qkv_weights_moved(text, abstract)
    assert ("flash_chunk_fwd" in text) == (chunk is not None)
    # the rows' write (PR 45): K and V once in the one layer body, each pool leaf aliased through the
    # call, and no temporary of a 128-slot block a row (the token array the parent's call read)
    assert _block_writes(text) == 2
    assert not re.findall(rf"bf16\[{slots},16,128,128\]", text)
    # one layer body: the scan over the passes around the scan over the layers, and no other loop
    assert len(re.findall(r" while\(", text)) == 2


def test_this_module_compiles_at_the_default_level():
    """``tests/conftest.py`` compiles the suite's CPU programs cheaply and
    lists this module among those that keep the backend's default level."""
    assert jax.config.read("jax_disable_most_optimizations") is False


@pytest.mark.parametrize("chunk", [None, 512], ids=["plain", "fused512"])
def test_nemotron_tick_of_one_sublayer_a_layer_keeps_pools_and_weights_in_place(topo, chunk):
    """What layers of ONE sublayer, eight groups of ``B`` and ``C`` and
    experts in a latent add (PR 51): the Nemotron-3-Super tick at the
    benchmark's cut (128 slots of 4,096; eleven runs of one layer, each
    reading its layer out of its kind's stack) with the key-value pool of its
    one attention layer and the float32 state pool of its five Mamba-2 layers
    in place, no layer's weights copied out of a stack, both ``ssd`` kernels,
    the grouped matmul and the flash chunk kernel inside."""
    from benchmark import models_nemotron_h
    from deepspeed_tpu.inference.decoding import compile_pool_tick_fn
    from deepspeed_tpu.models import transformer as tf

    with open(os.path.join(ROOT, "benchmark", "configs", "nemotron-3-super-120b-a12b.json")) as fh:
        config = json.load(fh)
    slots, length = 128, 4096
    model = models_nemotron_h.build_model(config, max_seq_len=length, remat=False, attn_impl="pallas")
    cfg = model.cfg
    mesh = comm.build_mesh({"data": 1, "tensor": 1}, devices=topo.devices[:1])
    one = NamedSharding(mesh, PartitionSpec())
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = jax.tree.map(lambda a: one, abstract)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16, sharding=one),
                          abstract)
    with force_interpret(False):
        fn, cache_sh, _ = compile_pool_tick_fn(mesh, cfg, p_sh, slots, length, 1, 0.0, 0, 1.0,
                                               read_len=None, chunk=chunk)
        cache = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            jax.eval_shape(lambda: tf.init_cache(cfg, slots, length)), cache_sh)
        row = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
        args = [params, cache, row, row, row, row, row, row, jax.ShapeDtypeStruct((2,), jnp.uint32)]
        if chunk is not None:
            wide = jax.ShapeDtypeStruct((chunk,), jnp.int32)
            args += [wide, wide, jax.ShapeDtypeStruct((), jnp.int32), row, row]
        compiled = fn.lower(*args).compile()
    comm.destroy()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert pool_bytes == 128 * 2 * 4096 * 256 * 2 + 5 * 128 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes                     # 0.54 + 2.72 GB, in place
    assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes    # 0.05 plain, 0.27 with a chunk
    resident = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert resident < 15.0e9, resident                               # ISSUE 51's line for 128 slots
    print("nemotron tick", chunk, "temp", mem.temp_size_in_bytes / 1e9, "arguments",
          mem.argument_size_in_bytes / 1e9, "resident", resident / 1e9)
    text = compiled.as_text()
    # neither pool is copied, no slab of the state pool is sliced out, and a run of one layer reads
    # its layer where the stack lies: no copy of a mixer's, a latent's or a shared expert's matrix
    assert not re.findall(r"= bf16\[(?:\d+,)?128,2,4096,\d+\]\S* copy\(", text)
    assert not re.findall(r"= f32\[(?:\d+,)?128,64,128,128\]\S* (?:copy|dynamic-slice)\(", text)
    for shape in ("4096,18560", "8192,4096", "4096,5376", "5376,4096", "4096,1024", "1024,4096",
                  "128,1024,2688", "128,2688,1024"):
        assert not re.findall(r"= bf16\[(?:\d+,)?%s\]\S* copy\(" % shape, text), shape
    assert not _qkv_weights_moved(text, abstract)
    assert "ssd_step" in text and "moe_grouped_matmul" in text
    assert ("ssd_chunk_fwd" in text) == ("flash_chunk_fwd" in text) == (chunk is not None)
