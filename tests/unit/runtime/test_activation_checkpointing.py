"""Activation checkpointing tests (reference:
tests/unit/runtime/activation_checkpointing/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from jax.ad_checkpoint import saved_residuals
except ImportError:  # jax 0.9: public alias removed
    from jax._src.ad_checkpoint import saved_residuals

from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac


@pytest.fixture(autouse=True)
def _reset_config():
    yield
    ac.reset()


def _mlp(w1, w2, x):
    h = jnp.tanh(x @ w1)
    return jnp.sum((h @ w2) ** 2)


class TestCheckpoint:
    def test_gradients_match_unchckpointed(self):
        key = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        w1 = jax.random.normal(k1, (16, 32))
        w2 = jax.random.normal(k2, (32, 8))
        x = jax.random.normal(k3, (4, 16))

        g_plain = jax.grad(_mlp, argnums=(0, 1))(w1, w2, x)
        wrapped = ac.checkpoint_wrapper(_mlp, policy="nothing_saveable")
        g_remat = jax.grad(wrapped, argnums=(0, 1))(w1, w2, x)
        for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
            # f32 tolerance, not bitwise: XLA:CPU fuses the rematerialized
            # tanh differently from the saved-residual path, reassociating
            # the reduction (measured max 1.8e-5 abs / 2.4e-3 rel on this
            # jaxlib — docs/known_failures.md)
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-5)

    def test_checkpoint_api(self):
        """checkpoint(fn, *args) executes fn (reference checkpointing.py:708)."""
        x = jnp.arange(8.0)
        out = ac.checkpoint(lambda t: jnp.sum(t * 2), x)
        assert float(out) == float(jnp.sum(x * 2))

    def test_remat_reduces_saved_residuals(self):
        key = jax.random.PRNGKey(1)
        w1 = jax.random.normal(key, (64, 64))
        w2 = jax.random.normal(key, (64, 64))
        x = jax.random.normal(key, (8, 64))

        def deep(w1, w2, x):
            for _ in range(4):
                x = jnp.tanh(x @ w1) @ w2
            return jnp.sum(x)

        plain = saved_residuals(deep, w1, w2, x)
        remat = saved_residuals(ac.checkpoint_wrapper(deep, policy="nothing_saveable"), w1, w2, x)
        assert len(remat) < len(plain), (len(remat), len(plain))


class TestConfigure:
    def test_configure_from_dict(self):
        ac.configure(deepspeed_config={
            "activation_checkpointing": {
                "partition_activations": True,
                "cpu_checkpointing": False,
                "policy": "dots_saveable",
            }
        })
        assert ac.is_configured()
        assert ac._CONFIG.partition_activations
        assert ac._CONFIG.policy == "dots_saveable"

    def test_kwargs_override_block(self):
        ac.configure(
            deepspeed_config={"activation_checkpointing": {"partition_activations": False}},
            partition_activations=True,
        )
        assert ac._CONFIG.partition_activations

    def test_policy_resolution(self):
        for name in ("nothing_saveable", "flash_saveable", "dots_saveable",
                     "dots_with_no_batch_dims", "full"):
            assert ac.resolve_policy(name) is not None

    def test_the_model_default_is_a_known_policy(self):
        from deepspeed_tpu.models.transformer import TransformerConfig

        assert TransformerConfig().remat_policy == "flash_saveable"
        assert TransformerConfig().remat_policy in ac.POLICIES
        # the default of checkpoint_wrapper for a user's own function stays
        assert ac.CheckpointConfig().policy == "nothing_saveable"

    def test_offload_policy(self):
        pol = ac.resolve_policy("offload")
        assert pol is not None
        # cpu_checkpointing flag routes any name to the offload policy
        ac.configure(deepspeed_config={"activation_checkpointing": {"cpu_checkpointing": True}})
        assert ac.resolve_policy("nothing_saveable") is not None

    def test_tpu_config_object(self):
        from deepspeed_tpu.runtime.config import TpuConfig

        cfg = TpuConfig({
            "train_batch_size": 8,
            "activation_checkpointing": {"policy": "dots_saveable", "cpu_checkpointing": False},
        })
        ac.configure(deepspeed_config=cfg)
        assert ac._CONFIG.policy == "dots_saveable"


class TestRNGTracker:
    def test_named_streams(self):
        tracker = ac.RNGStatesTracker()
        tracker.add("default", 0)
        tracker.add("model-parallel-rng", 1)
        a = tracker.fork("model-parallel-rng")
        b = tracker.fork("model-parallel-rng")
        assert not jnp.array_equal(a, b)
        with pytest.raises(Exception):
            tracker.add("default", 2)
        with pytest.raises(Exception):
            tracker.fork("missing")

    def test_model_parallel_seed_distinct_ranks(self):
        ac.model_parallel_seed(1234, tp_rank=0)
        k0 = ac.get_rng_tracker().fork()
        ac.model_parallel_seed(1234, tp_rank=1)
        k1 = ac.get_rng_tracker().fork()
        assert not jnp.array_equal(k0, k1)

    def test_state_save_restore(self):
        ac.model_parallel_seed(7)
        tracker = ac.get_rng_tracker()
        saved = tracker.get_states()
        a = tracker.fork("default")
        tracker.set_states(saved)
        b = tracker.fork("default")
        assert jnp.array_equal(a, b)


class TestModelIntegration:
    def test_remat_model_grads_match(self):
        """Flagship model: remat on/off must produce identical gradients."""
        from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

        base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2, max_seq_len=16)
        rng = jax.random.PRNGKey(0)
        batch = {
            "input_ids": jax.random.randint(rng, (2, 16), 0, 64),
            "labels": jax.random.randint(rng, (2, 16), 0, 64),
        }
        m_plain = TransformerModel(TransformerConfig(**base, remat=False))
        m_remat = TransformerModel(TransformerConfig(**base, remat=True, remat_policy="nothing_saveable"))
        params = m_plain.init(rng)
        g_plain = jax.grad(lambda p: m_plain.loss(p, batch, None))(params)
        g_remat = jax.grad(lambda p: m_remat.loss(p, batch, None))(params)
        for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_remat)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


def _pallas_calls(jaxpr, name):
    """How many ``pallas_call`` equations of that kernel name a jaxpr holds,
    bodies of scans, checkpoints and shard_maps included."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub, name)
    return n


class TestFlashResidualsSaved:
    """The default policy keeps the flash forward kernel's output and
    log-sum-exp (``flash_attention.RESIDUAL_NAMES``), so the backward pass of
    a checkpointed layer does not run the kernel a second time; the bits are
    the ones the recomputation would produce."""

    BASE = dict(vocab_size=64, hidden_size=32, num_layers=3, num_heads=2, max_seq_len=64)

    def _grad_fn(self, batch, **kw):
        from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

        model = TransformerModel(TransformerConfig(**{**self.BASE, "remat": True, **kw}))
        return model, jax.grad(lambda p: model.loss(p, batch, None))

    def _batch(self, rows=2):
        toks = np.random.RandomState(0).randint(0, 64, (rows, 64)).astype(np.int32)
        return {"input_ids": jnp.asarray(toks)}

    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_one_flash_forward_a_layer_body(self, scan_layers):
        """ONE ``flash_fwd`` a layer body in the gradient's jaxpr (the scan
        has one body, the unrolled stack one a layer) where
        ``nothing_saveable`` holds two, and the same gradients to the bit."""
        batch = self._batch()
        bodies = 1 if scan_layers else self.BASE["num_layers"]
        grads = {}
        for policy, fwd_calls in (("flash_saveable", 1), ("nothing_saveable", 2)):
            kw = {} if policy == "flash_saveable" else {"remat_policy": policy}  # the default
            model, grad = self._grad_fn(batch, attn_impl="pallas", scan_layers=scan_layers, **kw)
            params = model.init(jax.random.PRNGKey(0))
            jaxpr = jax.make_jaxpr(grad)(params).jaxpr
            assert _pallas_calls(jaxpr, "flash_fwd") == fwd_calls * bodies, policy
            assert _pallas_calls(jaxpr, "flash_bwd_dq") == bodies
            assert _pallas_calls(jaxpr, "flash_bwd_dkv") == bodies
            grads[policy] = jax.jit(grad)(params)
        for a, b in zip(*map(jax.tree.leaves, grads.values())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_one_flash_forward_under_shard_map(self):
        """On a four-device ``fsdp`` mesh the kernel runs under ``shard_map``
        (``transformer._flash_sharded``): the policy reaches its body."""
        from deepspeed_tpu import comm

        comm.destroy()
        comm.set_mesh(comm.build_mesh({"fsdp": 4}, devices=jax.devices()[:4]))
        batch = self._batch(rows=4)
        grads = []
        for policy, fwd_calls in (("flash_saveable", 1), ("nothing_saveable", 2)):
            model, grad = self._grad_fn(batch, attn_impl="pallas", remat_policy=policy)
            params = model.init(jax.random.PRNGKey(0))
            jaxpr = jax.make_jaxpr(grad)(params)
            assert "shard_map" in str(jaxpr)
            assert _pallas_calls(jaxpr.jaxpr, "flash_fwd") == fwd_calls, policy
            grads.append(jax.jit(grad)(params))
        for a, b in zip(*map(jax.tree.leaves, grads)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_without_the_kernel_it_is_nothing_saveable(self):
        """``attn_impl="xla"`` carries no name: the default lowers to the
        text ``nothing_saveable`` lowers to."""
        batch = self._batch()
        texts = []
        for policy in ("flash_saveable", "nothing_saveable"):
            model, grad = self._grad_fn(batch, attn_impl="xla", remat_policy=policy)
            params = model.init(jax.random.PRNGKey(0))
            texts.append(jax.jit(grad).lower(params).as_text())
        assert texts[0] == texts[1]


class TestPartitionActivations:
    """partition_activations for real (VERDICT r3 #4; reference
    activation_checkpointing/checkpointing.py:366): the layer-boundary
    residual is sharded over the ``tensor`` axis, so the remat stash is
    stored 1/TP instead of replicated."""

    # the partition constraint in sdy text: UNCONSTRAINED batch, seq dim on
    # the tensor (or sequence+tensor) axis; the always-on embedding/batch
    # constraints (models/transformer.py _constrain_tp/
    # _constrain_batch_sharding) never produce these shapes. One copy so a
    # jax sdy pretty-printer change breaks every assert loudly, not just one.
    PARTITION_SPEC = '[{?}, {"tensor"}, {?}]'
    PARTITION_SPEC_SP = '[{?}, {"sequence", "tensor"}, {?}]'

    def _seq_partition_in(self, txt):
        """Whether the layer-boundary seq-dim constraint appears in the
        lowered text, in EITHER spelling: the sdy pretty-print above
        (jax with shardy), or GSPMD's ``@Sharding`` custom call whose
        devices vector splits ONLY dim 1 of a 3D (B, S, H) activation
        (``devices=[1,<tp>,1,...]``) — this jaxlib lowers through GSPMD.
        The always-on embedding/batch constraints never produce that
        shape: vocab constraints split dim 0 of 2D tables, batch
        constraints split dim 0 (docs/known_failures.md)."""
        import re

        if self.PARTITION_SPEC in txt or self.PARTITION_SPEC_SP in txt:
            return True
        return bool(re.search(
            r"@Sharding[^\n]*devices=\[1,[2-9]\d*,1[,\]]", txt))

    def _setup(self, tensor=4, hidden=128, layers=4, seq=256):
        from deepspeed_tpu import comm
        from deepspeed_tpu.models.transformer import TransformerConfig, TransformerModel

        comm.destroy()
        comm.init_distributed(mesh_shape={"data": -1, "tensor": tensor}, verbose=False)
        cfg = TransformerConfig(
            vocab_size=256, hidden_size=hidden, num_layers=layers,
            num_heads=4, max_seq_len=seq, dtype="float32", remat=True,
            remat_policy="nothing_saveable",
        )
        model = TransformerModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = np.random.RandomState(0).randint(0, 256, (4, seq)).astype(np.int32)
        batch = {"input_ids": toks}

        def loss(p, b):
            out = model.loss(p, b)
            return out[0] if isinstance(out, tuple) else out

        return loss, params, batch

    def test_grad_parity(self):
        loss, params, batch = self._setup(hidden=32, layers=2, seq=64)
        l_off, g_off = jax.jit(jax.value_and_grad(loss))(params, batch)
        ac.configure(deepspeed_config={"activation_checkpointing": {"partition_activations": True}})
        l_on, g_on = jax.jit(jax.value_and_grad(loss))(params, batch)
        np.testing.assert_allclose(float(l_off), float(l_on), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g_off), jax.tree.leaves(g_on)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)

    def test_stash_sharded_and_memory_drops(self):
        """The flag must (a) inject a sharding constraint at the layer
        boundary and (b) cut compiled temp memory toward 1/TP (measured
        0.29x at TP=4 — stash + peak activations)."""
        loss, params, batch = self._setup()

        def lower(p, b):
            return jax.jit(jax.value_and_grad(loss)).lower(p, b)

        low_off = lower(params, batch)
        assert not self._seq_partition_in(low_off.as_text())
        off_bytes = low_off.compile().memory_analysis().temp_size_in_bytes
        ac.configure(deepspeed_config={"activation_checkpointing": {"partition_activations": True}})
        jax.clear_caches()
        low_on = lower(params, batch)
        assert self._seq_partition_in(low_on.as_text())
        on_bytes = low_on.compile().memory_analysis().temp_size_in_bytes
        assert on_bytes < 0.6 * off_bytes, (on_bytes, off_bytes)

    def test_noop_without_tensor_axis(self):
        """tensor=1 mesh: the flag must inject no partition constraint
        (the always-on embedding/batch constraints are allowed)."""
        from deepspeed_tpu import comm

        loss, params, batch = self._setup(tensor=1, hidden=32, layers=2, seq=64)
        ac.configure(deepspeed_config={"activation_checkpointing": {"partition_activations": True}})
        txt = jax.jit(jax.value_and_grad(loss)).lower(params, batch).as_text()
        assert not self._seq_partition_in(txt)
