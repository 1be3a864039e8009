"""The q / k / v products of a program that holds a KV pool pin their result
row-major (``tf._heads_product``, PR 46) so that the chip's compiler takes
the stacked weights as the engine holds them. On the CPU the pin is a custom
call that changes no value: the heads a cached caller gets are bit for bit
those of the plain product; a quantized leaf and a program whose pools span
several chips are left as they were; and a TRAINING step, which holds no
pool, lowers to the text it lowered to before. (What the pin does to the
compiled tick: ``tests/unit/ops/test_tpu_compile*.py``.)"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache

PIN = "LayoutConstraint"
# hidden, heads, head width, key-value heads: grouped, one a query head, and gpt2-xl's 1,600
# columns (12.5 lane tiles: the width where the chip's tiles and the compile most often part)
SHAPES = {"gqa": (256, 8, 32, 2), "mha": (256, 4, 64, 4), "width1600": (1600, 25, 64, 25)}
TOKENS = 24


def _plan_cfg(shape, **over):
    D, nh, hd, kv = SHAPES[shape]
    kinds = (LayerKind("full", kv_heads=kv, rope_theta=1e6, ffn="dense", ffn_size=64),)
    base = dict(vocab_size=64, hidden_size=D, num_layers=1, num_heads=nh, head_size=hd, rope_dim=16,
                pos_embedding="rope", norm_type="rmsnorm", activation="silu_glu", use_bias=False,
                tie_embeddings=False, dtype="bfloat16", attn_impl="xla", max_seq_len=64,
                layer_kinds=kinds, layer_plan=(0,))
    return TransformerConfig(**dict(base, **over))


def _one_kind_cfg(shape, **over):
    D, nh, hd, kv = SHAPES[shape]
    base = dict(vocab_size=64, hidden_size=D, num_layers=1, num_heads=nh, num_kv_heads=kv,
                dtype="bfloat16", attn_impl="xla", max_seq_len=64)
    return TransformerConfig(**dict(base, **over))


def _layer_attn(cfg, seed):
    """Layer 0's attention leaves in bfloat16, at ten times the init scale so
    that the heads are of order one and a bfloat16 ulp of them is no zero."""
    layers = tf.init(jax.random.PRNGKey(seed), cfg)["layers"]
    attn = (layers["full"] if cfg.plan is not None else layers)["attn"]
    return jax.tree.map(lambda a: (a[0] * 10.0).astype(jnp.bfloat16), attn)


def _same_bits(pinned, plain):
    for a, b in zip(pinned, plain):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert float(jnp.abs(a.astype(jnp.float32)).max()) > 0.1
        np.testing.assert_array_equal(np.asarray(a.view(jnp.uint16)), np.asarray(b.view(jnp.uint16)))


def _pinned_heads_equal_plain(heads, h, attn):
    """``heads(h, attn, **product)`` jitted with and without the pinned
    product: three constraints in the one's text and none in the other's,
    and the same bits out. Returns the pinned q, k, v."""
    plain = jax.jit(lambda h, p: heads(h, p))
    pinned = jax.jit(lambda h, p: heads(h, p, product=tf._heads_product))
    assert pinned.lower(h, attn).as_text().count(PIN) == 3 and PIN not in plain.lower(h, attn).as_text()
    out = pinned(h, attn)
    _same_bits(out, plain(h, attn))
    return out


@pytest.mark.parametrize("shape,qk_norm", [("gqa", False), ("gqa", True), ("mha", False), ("mha", True),
                                           ("width1600", False), ("width1600", True)])
def test_a_plans_heads_through_the_pinned_product_are_the_plain_products_bits(shape, qk_norm):
    cfg = _plan_cfg(shape, qk_norm=qk_norm)
    attn = _layer_attn(cfg, 1)
    assert ("q_norm" in attn) == qk_norm
    h = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, cfg.hidden_size), jnp.bfloat16)
    positions = jnp.arange(TOKENS, dtype=jnp.int32)
    kind = cfg.layer_kinds[0]
    q, k, v = _pinned_heads_equal_plain(
        lambda h, p, **product: layer_plan._project(h, p, kind, cfg, positions, **product), h, attn)
    D, nh, hd, kv = SHAPES[shape]
    assert (q.shape, k.shape, v.shape) == ((TOKENS, nh, hd), (TOKENS, kv, hd), (TOKENS, kv, hd))


@pytest.mark.parametrize("shape,bias,pos", [("gqa", False, "rope"), ("gqa", True, "learned"),
                                            ("mha", True, "learned"), ("mha", False, "rope"),
                                            ("width1600", True, "learned"), ("width1600", False, "rope")])
def test_a_one_kind_models_heads_through_the_pinned_product_are_the_plain_products_bits(shape, bias,
                                                                                      pos):
    cfg = _one_kind_cfg(shape, use_bias=bias, pos_embedding=pos)
    attn = _layer_attn(cfg, 3)
    assert ("bq" in attn) == bias
    if bias:  # drawn at zero: give the sum something to round
        attn = dict(attn, **{b: jax.random.normal(jax.random.PRNGKey(i), attn[b].shape, jnp.bfloat16)
                             for i, b in enumerate(("bq", "bk", "bv"))})
    h = jax.random.normal(jax.random.PRNGKey(4), (2, TOKENS, cfg.hidden_size), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(TOKENS, dtype=jnp.int32), (2, TOKENS))
    q, k, v = _pinned_heads_equal_plain(
        lambda h, p, **product: tf._qkv(h, p, cfg, positions, **product), h, attn)
    D, nh, hd, kv = SHAPES[shape]
    assert (q.shape, k.shape) == ((2, TOKENS, nh, hd), (2, TOKENS, kv, hd)) and v.shape == k.shape


def test_a_quantized_leaf_keeps_its_int8_product_and_a_split_program_its_plain_one(monkeypatch):
    from deepspeed_tpu.ops import quantizer

    x = jax.random.normal(jax.random.PRNGKey(5), (TOKENS, 256), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 128), jnp.bfloat16)
    leaf = {"q8": jnp.round(w.astype(jnp.float32) * 40).astype(jnp.int8),
            "s": jnp.full((128,), 1 / 40, jnp.float32)}
    calls = []
    real = quantizer.int8_linear
    monkeypatch.setattr(quantizer, "int8_linear", lambda *a: calls.append(1) or real(*a))
    lowered = jax.jit(tf._heads_product).lower(x, leaf)
    assert calls == [1] and PIN not in lowered.as_text()
    _same_bits((jax.jit(tf._heads_product)(x, leaf),), (tf._linear(x, leaf),))
    # a dense leaf: pinned in a one-chip program, the plain product where the pools span chips
    assert PIN in jax.jit(tf._heads_product).lower(x, w).as_text()
    split = lambda product: jax.jit(kv_cache.split_over_chips(lambda x, w: product(x, w))).lower(x, w).as_text()
    assert PIN not in split(tf._heads_product) and split(tf._heads_product) == split(tf._linear)
    assert not kv_cache.traced_over_chips()


# sha256 of the toy training steps' StableHLO as the parent of PR 46 (51acfc2) lowered them. The
# training cells (``train_tokens_per_s``, bound 0.01) must compile the programs they compiled: a
# change that moves these on purpose records its own, and says so where its numbers are.
TRAIN_TEXT = {
    "one-kind": "e645cd1c47e836610d38338cbacb184657f854015e7ea81f339733ee680d3890",
    "plan": "45489149f5ce9ea4a7550f927d050454a46cac1f5432a73ad1230d4a9974de01",
}


@pytest.mark.parametrize("which", sorted(TRAIN_TEXT))
def test_a_training_step_holds_no_pool_and_lowers_to_the_text_it_had(which):
    cfg = (_one_kind_cfg("gqa", use_bias=True, num_layers=2, dtype="float32") if which == "one-kind"
           else _plan_cfg("gqa", qk_norm=True, dtype="float32"))
    model = TransformerModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"input_ids": jax.ShapeDtypeStruct((2, TOKENS), jnp.int32)}
    text = jax.jit(jax.value_and_grad(model.loss)).lower(params, batch).as_text()
    assert PIN not in text
    assert hashlib.sha256(text.encode()).hexdigest() == TRAIN_TEXT[which]
