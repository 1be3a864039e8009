"""Kernel numerical-parity tests (reference: tests/unit/ops/ — custom kernels
vs torch reference; here Pallas/jnp kernels vs jnp reference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, mha_reference, tile_walk
from deepspeed_tpu.ops.quantizer import (
    dequantize,
    fake_quantize,
    quantize,
    quantize_per_channel,
    dequantize_per_channel,
)


def _qkv(B=2, S=128, H=4, hd=64, nkv=None, seed=0, dtype=np.float32, Sk=None):
    rs = np.random.RandomState(seed)
    nkv, Sk = nkv or H, Sk or S
    return (
        jnp.asarray(rs.randn(B, S, H, hd).astype(dtype)),
        jnp.asarray(rs.randn(B, Sk, nkv, hd).astype(dtype)),
        jnp.asarray(rs.randn(B, Sk, nkv, hd).astype(dtype)),
    )


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_parity(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_gqa(self):
        q, k, v = _qkv(H=8, nkv=2)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = mha_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_gradients(self):
        q, k, v = _qkv(S=64)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v) ** 2)

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)

    def test_gqa_gradients(self):
        q, k, v = _qkv(S=64, H=4, nkv=2)
        gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, block_q=32, block_k=32) ** 2), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)

    def test_transformer_pallas_attn_matches_xla(self):
        from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

        base = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, max_seq_len=32)
        pal = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, max_seq_len=32,
                                attn_impl="pallas")
        m0, m1 = TransformerModel(base), TransformerModel(pal)
        params = m0.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)).astype(np.int32))
        l0, l1 = m0.loss(params, {"input_ids": tokens}), m1.loss(params, {"input_ids": tokens})
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-4)


# (Sq, Sk, kwargs of flash_attention, grouped kv heads, gradients too): by the
# classes of tile the walk meets at the shape (``flash_attention._Walk``)
TILE_CLASS_CASES = {
    # skipped + full + crossed tiles of 128, every loop bound static
    "causal-384": (384, 384, {}, None, True),
    "causal-512": (512, 512, {}, None, True),
    "causal-640": (640, 640, {}, None, True),
    # every tile full: no mask is built
    "non-causal-256": (256, 256, dict(causal=False), None, True),
    # a band narrower than a tile (two crossed tiles a row of tiles, none full)
    "window-48-of-384": (384, 384, dict(window=48), None, True),
    # ... wider than one, misaligned and aligned (crossed, full, crossed)
    "window-200-of-512": (512, 512, dict(window=200), None, True),
    "window-256-of-512": (512, 512, dict(window=256), None, True),
    "grouped-heads-256": (256, 256, {}, 2, True),
    # the grid cut on both axes: traced bounds, clamped index maps, steps past the diagonal
    "causal-512-blocks-256": (512, 512, dict(block_q=256, block_k=256), None, True),
    "causal-512-blocks-256x128": (512, 512, dict(block_q=256, block_k=128), None, True),
    "window-200-of-512-blocks-256": (512, 512, dict(window=200, block_q=256, block_k=256), None, True),
    "non-causal-512-blocks-256": (512, 512, dict(causal=False, block_q=256, block_k=256), None, True),
    # Sq != Sk, forward only
    "causal-128-keys-384": (128, 384, {}, None, False),
    "causal-384-keys-128": (384, 128, {}, None, False),
    "non-causal-256-keys-128": (256, 128, dict(causal=False), None, False),
    # one tile: S <= 128, and lengths no tile of 128 divides
    "causal-64": (64, 64, {}, None, True),
    "causal-128": (128, 128, {}, None, True),
    "causal-192": (192, 192, {}, None, True),
    "causal-320": (320, 320, {}, None, True),
    # a head too long for one grid step: a pair of (outer, major) blocks walked in register tiles, the state
    # through scratch once a (row tile, grid step). 2,560 = five blocks of 512 on both axes (no longer block
    # divides it); 3,072 takes blocks of 1,024 on both (whole pieces and the diagonal's)
    "causal-2560-auto": (2560, 2560, {}, None, True),
    "grouped-heads-4x1-of-3072": (3072, 3072, {}, 1, True),
    "window-300-of-3072": (3072, 3072, dict(window=300), None, True),  # shorter than a major block
    "non-causal-2560": (2560, 2560, dict(causal=False), None, True),   # whole pieces only
    "causal-2560-keys-4096": (2560, 4096, {}, None, False),            # 512 rows against 1,024 keys a step
    # a caller's blocks stay the grid's, and are walked in tiles where they are whole tiles
    "causal-1024-blocks-512": (1024, 1024, dict(block_q=512, block_k=512), None, True),
}


@pytest.mark.parametrize("case", list(TILE_CLASS_CASES))
def test_flash_parity_by_tile_class(case):
    Sq, Sk, kw, nkv, grads = TILE_CLASS_CASES[case]
    q, k, v = _qkv(B=1, S=Sq, Sk=Sk, H=4 if nkv else 2, nkv=nkv)
    ref_kw = {a: b for a, b in kw.items() if a in ("causal", "window")}
    out = flash_attention(q, k, v, **kw)
    ref = mha_reference(q, k, v, **ref_kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)
    if not grads:
        return
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(*a, **kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(mha_reference(*a, **ref_kw) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def _unmasked(Sq, Sk, causal, window):
    d = np.arange(Sq)[:, None] - np.arange(Sk)[None, :]
    ok = np.ones((Sq, Sk), bool)
    if causal:
        ok &= d >= 0
    if window is not None:
        ok &= d < window
    return ok


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("Sq,Sk,bq,bk,causal,window", [
    (1024, 1024, None, None, True, None),   # the training cells' shape: one grid step a head
    (2048, 2048, None, None, True, None),   # still one step a head
    (1024, 1024, 512, 512, True, None), (4096, 4096, None, None, True, None),
    (4096, 4096, None, None, True, 256), (1024, 1024, None, None, True, 200),
    (1024, 1024, 256, 512, True, 700), (512, 512, None, None, False, None),
    (384, 128, None, None, True, None), (128, 384, None, None, True, None),
    (192, 192, None, None, True, None), (256, 256, 64, 64, True, 17), (576, 576, None, None, True, None),
    (8192, 8192, None, None, True, None), (8192, 8192, None, None, True, 1000),  # the LFM2 training cell's shape
    (2560, 2560, None, None, True, None), (2560, 4096, None, None, True, None), (3072, 3072, None, None, False, None),
])
def test_tile_walk_covers_every_unmasked_pair_once(Sq, Sk, bq, bk, causal, window, kernel):
    """The walk, asked on the host with the kernels' own object: every
    unmasked (q, k) lies in exactly one visited tile, no visited tile is
    wholly masked, and a tile that builds no mask holds no masked pair."""
    ok = _unmasked(Sq, Sk, causal, window)
    seen = np.zeros((Sq, Sk), np.int32)
    for q0, k0, tq, tk, crossed in tile_walk(Sq, Sk, bq, bk, causal, window, kernel=kernel):
        part = ok[q0:q0 + tq, k0:k0 + tk]
        assert part.shape == (tq, tk) and part.any(), (q0, k0)
        assert crossed == (not part.all()), (q0, k0, crossed)
        seen[q0:q0 + tq, k0:k0 + tk] += 1
    assert seen.max() == 1 and (seen[ok] == 1).all()


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_tile_walk_at_the_training_cells_shape(kernel):
    """S 1,024 causal in one grid step a head: 36 of 64 tiles' worth of 128
    computed (0.5625 S^2; blocks of 512 computed whole were 0.75), the 8 on
    the diagonal masked and no other."""
    tiles = tile_walk(1024, 1024, causal=True, kernel=kernel)
    assert sum(t[4] for t in tiles) == 8 and all(t[2:4] == (128, 128) for t in tiles if t[4])
    assert sum(t[2] * t[3] for t in tiles) == 36 * 128 * 128 <= 0.57 * 1024 * 1024


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_tile_walk_at_the_lfm2_cells_shape(kernel):
    """S 8,192 causal, a head too long for one grid step: the pairs of 1,024-blocks are walked in register tiles —
    the regime's row tile (``_MAJOR``: 256 rows, the price of a start's tracing and lowering: PERF.md section 6,
    PR 50) by 128 columns where the diagonal crosses, else by the kernel's wide tile — 0.516 S^2 computed (136
    blocks of 512 computed whole were 0.531; 128-row tiles would be 0.508), a mask built on the 64 tiles of
    256 x 128 the diagonal touches and on no other."""
    from deepspeed_tpu.ops.pallas.flash_attention import _MAJOR

    _, _, rows, wide = _MAJOR[kernel]
    tiles = tile_walk(8192, 8192, causal=True, kernel=kernel)
    outer, inner = (3, 2) if kernel == "dkv" else (2, 3)  # a tile is (q0, k0, queries, keys, crossed); dkv's rows are keys
    assert {t[outer] for t in tiles} == {rows} and {t[inner] for t in tiles} <= {128, wide}
    assert sum(t[4] for t in tiles) == 2 * 8192 // rows and all(t[inner] == 128 for t in tiles if t[4])
    assert sum(t[2] * t[3] for t in tiles) <= 0.52 * 8192 * 8192
    assert len(tiles) <= 136 * 5  # what a start traces and lowers grows with it


class TestFlashResidualsUnderRemat:
    """A checkpointed block that keeps the kernel's named output and
    log-sum-exp (policy ``flash_saveable``) hands the backward kernels the
    bits a recomputation (``nothing_saveable``) would: gradients are EQUAL."""

    @pytest.mark.parametrize("kw", [
        dict(causal=True), dict(causal=False), dict(causal=True, window=48),
        dict(causal=True, nkv=2),
    ], ids=["causal", "non-causal", "windowed", "gqa"])
    def test_gradients_equal_recomputation(self, kw):
        from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import checkpoint_wrapper

        kw = dict(kw)
        H, hd, nkv = 4, 64, kw.pop("nkv", 4)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(2, 128, H * hd).astype(np.float32))
        w = jnp.asarray(rs.randn(H * hd, (H + 2 * nkv) * hd).astype(np.float32) * 0.05)

        def block(x, w):  # q, k, v rebuilt from the block's input, as a layer does
            q, k, v = jnp.split(x @ w, [H * hd, (H + nkv) * hd], axis=-1)
            heads = lambda a, n: a.reshape(2, 128, n, hd)
            o = flash_attention(heads(q, H), heads(k, nkv), heads(v, nkv),
                                block_q=64, block_k=64, **kw)
            return jnp.sum(o.reshape(x.shape) * x)

        grads = [jax.jit(jax.grad(checkpoint_wrapper(block, policy=policy), argnums=(0, 1)))(x, w)
                 for policy in ("flash_saveable", "nothing_saveable")]
        for a, b in zip(*grads):
            assert np.abs(np.asarray(a)).max() > 0
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestFlashTensorParallel:
    def test_no_allgather_under_tp(self):
        """GSPMD cannot partition a pallas_call: without the shard_map
        wrapper (_flash_sharded) a TP mesh ALL-GATHERS q/k/v and computes
        every head on every chip. Pin the fixed behavior: zero all-gathers
        and per-shard operand shapes in the compiled HLO, plus numerical
        parity with the unsharded path."""
        import dataclasses
        import re

        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu import comm
        from deepspeed_tpu.models import transformer as tf

        comm.destroy()
        mesh = comm.init_distributed(mesh_shape={"data": 2, "tensor": 4},
                                     verbose=False)
        # GQA: nkv=4 < nh=8, both dividing tp=4 — the subtle property is
        # that per-shard query-head-to-KV-head grouping stays aligned
        cfg = tf.TransformerConfig(vocab_size=64, hidden_size=256, num_layers=1,
                                   num_heads=8, num_kv_heads=4, max_seq_len=64,
                                   attn_impl="pallas")
        B, S, H, hd = 4, 64, 8, 32
        sh = NamedSharding(mesh, P("data", None, "tensor", None))
        rs = np.random.RandomState(0)
        q = jax.device_put(jnp.asarray(rs.randn(B, S, H, hd), jnp.float32), sh)
        k, v = (jax.device_put(jnp.asarray(rs.randn(B, S, 4, hd), jnp.float32), sh)
                for _ in range(2))
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        f = jax.jit(lambda a, b, c: tf._attention(a, b, c, cfg, positions),
                    in_shardings=(sh, sh, sh), out_shardings=sh)
        txt = f.lower(q, k, v).compile().as_text()
        assert not re.search(r"all-gather", txt), "flash attention re-gathered under TP"
        ref = tf._attention(q, k, v,
                            dataclasses.replace(cfg, attn_impl="xla"), positions)
        np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        comm.destroy()

    def test_block_sparse_no_allgather_under_tp(self):
        """Same GSPMD-unpartitionable story for the block-sparse kernel:
        heads AND their per-head layout rows must shard over 'tensor'."""
        import re

        from jax.sharding import NamedSharding, PartitionSpec as P

        from deepspeed_tpu import comm
        from deepspeed_tpu.models import transformer as tf

        comm.destroy()
        mesh = comm.init_distributed(mesh_shape={"data": 2, "tensor": 4},
                                     verbose=False)
        cfg = tf.TransformerConfig(
            vocab_size=64, hidden_size=256, num_layers=1, num_heads=8,
            max_seq_len=128, attn_impl="block_sparse",
            sparse_attention={"mode": "fixed", "block": 32})
        B, S, H, hd = 4, 128, 8, 32
        sh = NamedSharding(mesh, P("data", None, "tensor", None))
        rs = np.random.RandomState(0)
        q, k, v = (jax.device_put(jnp.asarray(rs.randn(B, S, H, hd), jnp.float32), sh)
                   for _ in range(3))
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        f = jax.jit(lambda a, b, c: tf._attention(a, b, c, cfg, positions),
                    in_shardings=(sh, sh, sh), out_shardings=sh)
        txt = f.lower(q, k, v).compile().as_text()
        assert not re.search(r"all-gather", txt), "block-sparse re-gathered under TP"
        # parity vs the unsharded kernel BEFORE destroy (after destroy both
        # sides would take the plain path and the check would be vacuous);
        # the eager ref call sees the live mesh too but runs outside jit
        # shardings, exercising the reshard-any-caller property
        got = np.asarray(f(q, k, v))
        comm.destroy()
        ref = tf._attention(q, k, v, cfg, positions)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-4, atol=1e-5)


class TestSlidingWindowFlash:
    """Tile-pruned sliding-window flash path (Mistral-style; the reference's
    SparseSelfAttention local modes, deepspeed/ops/sparse_attention): the
    kernel grid only visits k-blocks inside the window band, so compute and
    HBM are O(S*window), and a static uniform ``local_attn_windows`` routes
    the model through it."""

    # (S, window, blocks): band narrower than / wider than / equal to a
    # block, misaligned windows, window >= S (degenerates to full causal)
    @pytest.mark.parametrize("S,window,blk", [
        (128, 32, 64), (128, 100, 64), (256, 17, 64), (128, 1, 64), (256, 300, 128),
    ])
    def test_forward_parity(self, S, window, blk):
        q, k, v = _qkv(S=S)
        out = flash_attention(q, k, v, block_q=blk, block_k=blk, window=window)
        ref = mha_reference(q, k, v, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_gradients_parity(self):
        q, k, v = _qkv(S=128)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=64, block_k=64, window=48) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, window=48) ** 2)

        gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)

    def test_gqa_window(self):
        q, k, v = _qkv(S=128, H=8, nkv=2)
        out = flash_attention(q, k, v, block_q=64, block_k=64, window=48)
        ref = mha_reference(q, k, v, window=48)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    def test_uniform_window_model_matches_xla(self):
        """A uniform local_attn_windows config must produce the same loss on
        the pallas path (static window -> tile-pruned flash) as on the xla
        path (masked einsum) — both under the layer scan and remat."""
        from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

        kw = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=64, local_attn_windows=(24, 24), remat=True)
        m_xla = TransformerModel(TransformerConfig(**kw))
        m_pal = TransformerModel(TransformerConfig(**kw, attn_impl="pallas"))
        params = m_xla.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 64)).astype(np.int32))
        batch = {"input_ids": tokens}
        np.testing.assert_allclose(float(m_pal.loss(params, batch)),
                                   float(m_xla.loss(params, batch)), rtol=1e-4)
        # gradients agree too (the custom VJP band kernels)
        gp = jax.grad(lambda p: m_pal.loss(p, batch))(params)
        gx = jax.grad(lambda p: m_xla.loss(p, batch))(params)
        for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gx)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)

    def test_alternating_windows_still_correct(self):
        """GPT-Neo-style alternation (varying windows) keeps the traced
        einsum path under scan — parity with the unrolled static path."""
        from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

        kw = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                  max_seq_len=64, local_attn_windows=(16, 0), remat=True)
        m_scan = TransformerModel(TransformerConfig(**kw, scan_layers=True))
        # unrolled + remat: windows stay static through jax.checkpoint
        # (static_argnums), so the local layer takes the flash band path
        m_unroll = TransformerModel(TransformerConfig(**kw, scan_layers=False,
                                                      attn_impl="pallas"))
        params = m_scan.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 64)).astype(np.int32))
        batch = {"input_ids": tokens}
        np.testing.assert_allclose(float(m_scan.loss(params, batch)),
                                   float(m_unroll.loss(params, batch)), rtol=1e-4)


class TestQuantizer:
    def test_symmetric_roundtrip(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(4, 256).astype(np.float32))
        q, scale, zp = quantize(x, num_bits=8, num_groups=4, symmetric=True)
        assert q.dtype == jnp.int8 and zp is None
        back = dequantize(q, scale, num_groups=4, out_shape=x.shape)
        err = np.abs(np.asarray(back - x))
        assert err.max() < np.abs(np.asarray(x)).max() / 127 * 1.01

    def test_asymmetric_roundtrip(self):
        rs = np.random.RandomState(1)
        x = jnp.asarray((rs.rand(8, 128) * 5 + 3).astype(np.float32))  # shifted range
        q, scale, zp = quantize(x, num_bits=8, num_groups=8, symmetric=False)
        back = dequantize(q, scale, zp, num_groups=8, out_shape=x.shape)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=float(scale.max()) * 1.01)

    def test_stochastic_rounding_unbiased(self):
        x = jnp.full((1, 1024), 0.5004, jnp.float32) * 127 / 127  # between grid points
        keys = jax.random.split(jax.random.PRNGKey(0), 64)
        vals = []
        for k in keys:
            q, scale, _ = quantize(x, num_bits=8, num_groups=1, stochastic=True, rng=k)
            vals.append(float(dequantize(q, scale, num_groups=1).mean()))
        assert abs(np.mean(vals) - 0.5004) < 2e-3

    def test_fake_quantize_straight_through(self):
        x = jnp.asarray(np.random.RandomState(0).randn(4, 64).astype(np.float32))
        g = jax.grad(lambda x: jnp.sum(fake_quantize(x, num_bits=4, num_groups=4) * 2.0))(x)
        np.testing.assert_allclose(np.asarray(g), 2.0 * np.ones_like(g), rtol=1e-6)

    def test_per_channel(self):
        rs = np.random.RandomState(2)
        w = jnp.asarray(rs.randn(64, 32).astype(np.float32))
        q, scale = quantize_per_channel(w, axis=0)
        back = dequantize_per_channel(q, scale, dtype=jnp.float32)
        rel = np.abs(np.asarray(back - w)).max() / np.abs(np.asarray(w)).max()
        assert rel < 0.02
