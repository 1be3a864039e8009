"""A layer plan with a gated short-convolution kind (``mixer="conv"``) on the
training path: the plan's checks, that it trains and is refused where a cache
or a tick is asked of it, which attention kinds take the flash kernels under
``attn_impl="pallas"`` (by what the call can see), the counters a plan's
training forward hands out and that a model without them compiles the
micro-step it always did, and the two scopes. (The model against its plain
reference: ``tests/benchmark/test_bench_lfm2_moe.py``.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import comm
from deepspeed_tpu.models import layer_plan
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.telemetry import hlo_scopes
from deepspeed_tpu.telemetry.hlo_scopes import Scope

SEQ = 64


def plan_config(kinds, plan, **over):
    base = dict(vocab_size=97, hidden_size=64, num_layers=len(plan), num_heads=4, ffn_hidden_size=96,
                pos_embedding="rope", norm_type="rmsnorm", activation="silu_glu", use_bias=False,
                layer_kinds=kinds, layer_plan=plan, max_seq_len=SEQ)
    return TransformerConfig(**dict(base, **over))


CONV = LayerKind(name="conv", mixer="conv", ffn="dense")
ATTN = LayerKind(name="attn", kv_heads=2, ffn="dense")
SINK = LayerKind(name="sink", kv_heads=2, sink=True, ffn="dense")
MOE = LayerKind(name="moe", mixer="conv", ffn="moe", ffn_size=32)
TOKENS = np.random.RandomState(0).randint(0, 97, (2, SEQ)).astype(np.int32)


def lowered_loss(cfg):
    model = TransformerModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.jit(jax.grad(lambda p: model.loss(p, {"input_ids": TOKENS}))).lower(params).as_text()


def test_a_convolution_kind_has_its_three_leaves_and_needs_two_taps():
    cfg = plan_config((CONV, ATTN), (0, 1, 0))
    tree = TransformerModel(cfg).init(jax.random.PRNGKey(0))["layers"]["conv"]["conv"]
    assert {n: a.shape for n, a in tree.items()} == {
        "win": (2, 64, 192), "conv": (2, 64, 3), "wo": (2, 64, 64)}
    assert CONV.pool is None        # no pool until a state-pool row can be a convolution's tail
    gdn = LayerKind(name="gdn", mixer="gdn", ffn="dense")   # so it trains beside a state-pool kind
    assert plan_config((CONV, ATTN, gdn), (0, 1, 2), gdn_key_heads=2, gdn_value_heads=2,
                       gdn_key_dim=16, gdn_value_dim=16).plan[2].pool == "state"
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(
        TransformerModel(cfg).init(jax.random.PRNGKey(0))))
    with pytest.raises(ValueError, match="at least two taps"):
        plan_config((CONV, ATTN), (0, 1), conv_taps=1)
    with pytest.raises(ValueError, match="mixer 'fir'"):
        plan_config((dataclasses.replace(ATTN, name="fir", mixer="fir"), ATTN), (0, 1))


def test_serving_a_convolution_kind_is_refused_in_one_sentence():
    cfg = plan_config((CONV, ATTN), (0, 1))
    why = "trains .* and is not served yet: the state pool has no row that is a convolution's tail"
    for ask in (lambda: kv_cache.specs(cfg), lambda: kv_cache.state_spec(cfg),
                lambda: tf.init_cache(cfg, 2, SEQ),
                lambda: layer_plan.forward_plan_cached(None, cfg, jnp.zeros(2, jnp.int32),
                                                       jnp.zeros(2, jnp.int32), {})):
        with pytest.raises(NotImplementedError, match=why):
            ask()
    assert kv_cache.specs(plan_config((ATTN,), (0,)))    # a plan without one is served as it was


def test_flash_takes_the_attention_kinds_it_can_by_what_the_call_sees():
    """Under ``attn_impl="pallas"`` a plain attention kind's training forward
    holds the kernel; a kind with a sink logit, or with keys and values of
    two widths, lowers to the text it has under ``"xla"``."""
    plain = lambda impl, **over: lowered_loss(plan_config((CONV, ATTN), (0, 1), attn_impl=impl, **over))
    assert plain("pallas") != plain("xla")
    sink = lambda impl: lowered_loss(plan_config((CONV, SINK), (0, 1), attn_impl=impl))
    assert sink("pallas") == sink("xla")
    wide = dict(head_size=16, v_head_size=32)
    assert plain("pallas", **wide) == plain("xla", **wide)
    cfg = plan_config((CONV, ATTN), (0, 1), attn_impl="pallas")
    assert layer_plan._takes_flash(cfg, ATTN) and not layer_plan._takes_flash(cfg, SINK)


def test_flash_in_a_plans_forward_is_the_einsums_attention_window_and_all():
    window = LayerKind(name="window", kv_heads=2, window=16, ffn="dense")
    grads = {}
    for impl in ("xla", "pallas"):
        model = TransformerModel(plan_config((CONV, ATTN, window), (0, 1, 2, 0), attn_impl=impl,
                                             remat=True))
        params = model.init(jax.random.PRNGKey(1))
        grads[impl] = jax.jit(jax.value_and_grad(lambda p: model.loss(p, {"input_ids": TOKENS})))(params)
    assert float(grads["xla"][0]) == pytest.approx(float(grads["pallas"][0]), abs=1e-5)
    for a, b in zip(jax.tree.leaves(grads["xla"][1]), jax.tree.leaves(grads["pallas"][1])):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_scope_table_of_a_convolution_plans_forward_shows_its_two_scopes():
    model = TransformerModel(plan_config((CONV, ATTN), (0, 1)))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    compiled = jax.jit(lambda p: model.loss(p, {"input_ids": TOKENS})).lower(params).compile()
    seen = {hlo_scopes.model_scope(path) for path in hlo_scopes.scope_table(compiled).values()}
    assert {Scope.MIX_CONV, Scope.CONV_SHORT, Scope.ATTN_FULL} <= seen
    assert {Scope.MIX_CONV, Scope.CONV_SHORT} <= hlo_scopes.MODEL_SCOPES


def test_counters_are_a_plans_with_expert_layers_and_nobody_elses():
    routed = TransformerModel(plan_config((ATTN, MOE), (0, 1, 1), moe_num_experts=4, moe_top_k=2))
    assert routed.loss_with_counters is not None
    loss, counters = routed.loss_with_counters(routed.init(jax.random.PRNGKey(0)), {"input_ids": TOKENS})
    assert counters.shape == (len(routed.counter_names),) and counters.dtype == jnp.int32
    assert int(counters[0]) == int(counters[1]) == TOKENS.size * 2 * 2 and int(counters[3]) == 2
    assert TransformerModel(plan_config((CONV, ATTN), (0, 1))).loss_with_counters is None
    gpt2 = TransformerModel(TransformerConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                                              max_seq_len=SEQ))
    assert gpt2.loss_with_counters is None
    with pytest.raises(ValueError, match="a layer plan's"):
        tf.forward(gpt2.init(jax.random.PRNGKey(0)), gpt2.cfg, TOKENS, return_stats=True)


def test_a_model_without_counters_compiles_the_micro_step_it_always_did():
    """GPT-2's ``train_micro`` against the micro-step written out as the
    engine had it before any model counted (loss, gradient, accumulate: six
    arguments, two results, the accumulator donated): the same text."""
    comm.destroy()
    model = TransformerModel(TransformerConfig(vocab_size=97, hidden_size=32, num_layers=2,
                                               num_heads=2, max_seq_len=SEQ))
    config = {"train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
              "zero_optimization": {"stage": 0}, "mesh": {"fsdp": 1}, "steps_per_print": 10 ** 9}
    mesh = comm.build_mesh(config["mesh"], devices=jax.devices()[:1])
    engine = deepspeed_tpu.initialize(model=model, config=config, mesh=mesh)[0]
    assert engine.counter_acc is None and engine.moe_stats() == {} and engine._counter_args() == ()

    def micro_fn(params, grad_acc, batch, rng, scale, pld_theta):
        def scaled_loss(p):
            return model.loss(p, batch, rng).astype(jnp.float32) * scale

        loss, grads = jax.value_and_grad(scaled_loss)(params)
        with jax.named_scope(Scope.GRAD_ACCUMULATE):
            new_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / 1.0, grad_acc, grads)
        return loss / scale, new_acc

    before = jax.jit(micro_fn, donate_argnums=(1,),
                     in_shardings=(engine.param_shardings, engine.grad_shardings,
                                   engine.batch_sharding, None, None, None),
                     out_shardings=(engine.replicated, engine.grad_shardings))
    args = (engine.params, engine.grad_acc, engine._shard_batch({"input_ids": TOKENS}),
            jax.random.PRNGKey(0), engine.scale_state.scale, jnp.float32(1.0))
    assert engine._micro_fn.lower(*args).as_text() == before.lower(*args).as_text()
    comm.destroy()
