"""A layer plan whose layers are ONE sublayer each (a mixer with no FFN, an
FFN with no mixer and no row in any pool), Mamba-2 with groups of ``B`` and
``C``, expert layers that work in a latent with un-gated squared-ReLU experts:
what ``check_plan`` takes and refuses, parameter shapes and counts, the pools,
the serving tick through chunks and rows against the uncached forward, the
counters a tick and ``tick_stats()`` carry, and the row tile's rule. (The
program against the float32 reference: ``tests/benchmark/test_bench_nemotron_h.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import layer_plan, transformer as tf
from deepspeed_tpu.models.transformer import LayerKind, TransformerConfig, TransformerModel
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.transformer import kv_cache

MAMBA = LayerKind(name="mamba", mixer="ssm", ffn="none")
ATTN = LayerKind(name="attention", kv_heads=1, ffn="none")
EXPERTS = LayerKind(name="experts", mixer="none", ffn="moe", ffn_size=48, ffn_latent=16)
DENSE = LayerKind(name="dense", mixer="none", ffn="dense", ffn_size=40)
KINDS = {"M": MAMBA, "*": ATTN, "E": EXPERTS, "D": DENSE}


def config(pattern="MEM*E", dtype="float32", groups=2, **over):
    kinds = tuple(dict.fromkeys(KINDS[c] for c in pattern))
    base = dict(
        vocab_size=96, hidden_size=32, num_layers=len(pattern), num_heads=4, head_size=8,
        pos_embedding="none", norm_type="rmsnorm", activation="relu2", tie_embeddings=False,
        use_bias=False, layer_kinds=kinds, layer_plan=tuple(kinds.index(KINDS[c]) for c in pattern),
        ssm_heads=8, ssm_head_dim=64, ssm_state=16, ssm_groups=groups, ssm_conv=4,
        moe_num_experts=16, moe_top_k=4, moe_experts_held=(4, 8), moe_score="sigmoid",
        moe_routed_scale=5.0, moe_shared_size=24, moe_shared_gated=False, dtype=dtype, max_seq_len=64)
    return TransformerConfig(**dict(base, **over))


def test_a_layer_is_one_sublayer_with_one_norm():
    cfg = config("MEM*ED")
    shapes = {k.name: layer_plan._layer_shapes(cfg, k) for k in cfg.layer_kinds}
    assert {g for g, _ in shapes["mamba"]} == {"ln1", "ssm"}
    assert {g for g, _ in shapes["attention"]} == {"ln1", "attn"}
    assert {g for g, _ in shapes["experts"]} == {"ln2", "mlp"} == {g for g, _ in shapes["dense"]}
    # squared-ReLU experts: two matrices, no gate's; the latent's two projections; the
    # router and the shared expert at the model's width
    mlp = {n: s for (g, n), (s, _) in shapes["experts"].items() if g == "mlp"}
    assert mlp == {"gate": (32, 16), "gate_bias": (16,), "wi": (8, 16, 48), "wo": (8, 48, 16),
                   "latent_down": (32, 16), "latent_up": (16, 32), "shared_wi": (32, 24),
                   "shared_wo": (24, 32)}
    assert {n: s for (g, n), (s, _) in shapes["dense"].items() if g == "mlp"} == {
        "wi": (32, 40), "wo": (40, 32)}
    assert shapes["mamba"][("ssm", "conv")][0] == (8 * 64 + 2 * 2 * 16, 4)   # inner + 2 x groups x state
    params = tf.init(jax.random.PRNGKey(0), cfg)
    assert cfg.num_params() == sum(p.size for p in jax.tree.leaves(params))
    assert [(r.kind.name, r.kind_start, r.n, r.pool_start) for r in layer_plan.runs(cfg)] == [
        ("mamba", 0, 1, 0), ("experts", 0, 1, 0), ("mamba", 1, 1, 1), ("attention", 0, 1, 0),
        ("experts", 1, 1, 1), ("dense", 0, 1, 2)]
    assert EXPERTS.pool is None and DENSE.pool is None and MAMBA.pool == "state"
    specs = tf.logical_specs(params, cfg)
    assert specs["layers"]["experts"]["mlp"]["latent_down"] == ("layers", "embed", None)
    assert specs["layers"]["experts"]["mlp"]["wi"] == ("layers", "expert", "embed", "mlp")


def test_runs_still_merge_equal_neighbours():
    cfg = config("MMEE*")
    assert [(r.kind.name, r.n) for r in layer_plan.runs(cfg)] == [
        ("mamba", 2), ("experts", 2), ("attention", 1)]


def test_a_layer_without_a_mixer_counts_in_no_pool():
    cfg = config("MEM*E")
    assert [(s.name, s.layers) for s in kv_cache.specs(cfg)] == [("full", 1)]
    assert kv_cache.state_spec(cfg).layers == 2
    cache = tf.init_cache(cfg, 3, 32)
    assert set(cache) == {"full", "state"} and cache["state"]["s"].shape == (2, 3, 4, 16, 128)
    assert cache["state"]["conv"].shape == (2, 3, 3, 8 * 64 + 2 * 2 * 16)
    kv_cache.refuse_unserved(cfg)   # nothing to refuse


@pytest.mark.parametrize("bad,match", [
    (dict(layer_kinds=(LayerKind(name="nothing", mixer="none", ffn="none"), ATTN), layer_plan=(0, 1),
          num_layers=2), "neither a mixer nor an FFN"),
    (dict(layer_kinds=(LayerKind(name="latent", ffn="dense", ffn_latent=8),), layer_plan=(0,),
          num_layers=1), "latent width"),
    (dict(ssm_groups=3), "groups of whole stored tiles"),
    (dict(ssm_groups=8), "groups of whole stored tiles"),     # 8 heads of 64: a tile is two heads
    (dict(ssm_groups=0), "groups of whole stored tiles"),
    (dict(activation="gelu"), "squared ReLU"),
    (dict(layer_kinds=(MAMBA, EXPERTS), layer_plan=(0, 1, 0, 1, 1), ), "full-attention layer"),
], ids=["no-sublayer", "latent-of-a-dense-ffn", "heads-not-whole-groups", "a-tile-in-two-groups",
        "no-group", "activation", "no-attention"])
def test_check_plan_refuses(bad, match):
    with pytest.raises(ValueError, match=match):
        config(**bad)


def test_four_groups_of_two_heads_are_taken():
    assert config(groups=4).ssm_groups == 4 and config(groups=1).ssm_groups == 1


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 0.25)])
@pytest.mark.parametrize("groups", [1, 2, 4])
def test_chunks_then_rows_through_the_pools_are_the_uncached_forward(dtype, tol, groups):
    """Prefill in chunks of 16, then decoding through both pools, every logit
    against the whole-sequence forward's (bfloat16: within a quarter of a
    logit of logits whose spread is ~1; float32: 2e-4)."""
    cfg = config("MEM*E", dtype, groups)
    params = tf.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 96)
    want = np.asarray(tf.forward(params, cfg, toks)[0], np.float32)
    B, L, W = 2, 64, 16
    cache = tf.init_cache(cfg, B, L)
    step = jax.jit(lambda p, t, ps, ca, ch: layer_plan.forward_plan_cached(p, cfg, t, ps, ca, chunk=ch))
    parked = jnp.full((B,), L, jnp.int32)
    for r in range(B):
        for first in (0, W):
            ch = layer_plan.Chunk(toks[r, first:first + W], first + jnp.arange(W, dtype=jnp.int32),
                                  jnp.int32(r), jnp.int32(W - 1))
            lg, cache, st = step(params, jnp.zeros((B,), jnp.int32), parked, cache, ch)
            assert np.abs(np.asarray(lg[r], np.float32) - want[r, first + W - 1]).max() < tol
            assert st.shape == (layer_plan.stats_len(cfg),) == (8,)
            # two expert layers of five; the chunk's 16 real tokens; no row stepped
            assert int(st[3]) == 2 and int(st[5]) == W and int(st[6]) == 0
            assert int(st[7]) >= int(st[1]) and int(st[7]) % 16 == 0      # rows walked: whole 16-row tiles
    pos = jnp.full((B,), 2 * W, jnp.int32)
    for t in range(2 * W, 40):
        lg, cache, st = step(params, toks[:, t], pos, cache, None)
        assert np.abs(np.asarray(lg, np.float32) - want[:, t]).max() < tol
        assert int(st[0]) == B * 4 * 2 and int(st[6]) == B and int(st[7]) == 16 * int(st[4])
        pos = pos + 1


def test_plans_of_whole_layers_keep_the_five_counters():
    whole = TransformerConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4, pos_embedding="rope",
        norm_type="rmsnorm", activation="silu_glu", use_bias=False,
        layer_kinds=(LayerKind(name="a", kv_heads=1, ffn="moe", ffn_size=16),), layer_plan=(0, 0),
        moe_num_experts=8, moe_top_k=2, moe_experts_held=(0, 4))
    assert not layer_plan.counts_rows(whole) and layer_plan.stats_len(whole) == 5
    assert layer_plan.counts_rows(config()) and layer_plan.stats_len(config()) == 8
    assert layer_plan.stats_len(config("E*")) == 6     # no state pool: five and the rows walked


@pytest.mark.parametrize("n,k,tile", [
    (32, 10, 16), (160, 10, 16), (288, 8, 128), (288, 10, 128), (544, 4, 128), (288, 4, 16),
    (1056, 8, 128),            # every tick of the plans served before the rule: as N k >= 2048 gave it
    (128, 22, 16),             # top-22: 128 decode rows make 2,816 assignments, and are decode rows
    (128 + 256, 22, 128), (128 + 512, 22, 128)])
def test_row_tile(n, k, tile):
    assert held_experts.row_tile(n, k) == tile
    if n >= 256 or n * k < 2048:
        assert tile == (128 if n * k >= 2048 else 16)


def test_experts_take_their_form_from_the_parameter_tree():
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(12, 16), jnp.float32)
    wi, wg, wo = (jnp.asarray(rng.randn(*s) * 0.3, jnp.float32) for s in ((4, 16, 24), (4, 16, 24), (4, 24, 16)))
    chosen = jnp.asarray(rng.randint(0, 8, (12, 2)), jnp.int32).at[:, 1].set((jnp.arange(12) % 4) + 8)
    chosen = chosen.at[:, 0].set(jnp.arange(12) % 8)
    weights = jnp.asarray(rng.rand(12, 2), jnp.float32)

    def plain(form):
        out = np.zeros((12, 16), np.float32)
        for t in range(12):
            for j in range(2):
                e = int(chosen[t, j]) - 2
                if 0 <= e < 4:
                    up = h[t] @ wi[e]
                    act = jax.nn.silu(h[t] @ wg[e]) * up if form == "swiglu" else jnp.square(jax.nn.relu(up))
                    out[t] += float(weights[t, j]) * np.asarray(act @ wo[e])
        return out

    for form, experts in (("swiglu", {"wg": wg, "wi": wi, "wo": wo}), ("relu2", {"wi": wi, "wo": wo})):
        for grad in (False, True):
            got, counts = held_experts.held_experts_ffn(h, chosen, weights, experts, 2, 4, grad=grad,
                                                        n_experts=12)
            assert np.allclose(got, plain(form), atol=1e-4), (form, grad)
            assert int(counts.sum()) == int(((chosen >= 2) & (chosen < 6)).sum())


def test_tick_stats_and_the_tick_event_carry_the_counters():
    from deepspeed_tpu.analysis import event_schemas
    from deepspeed_tpu.inference import ContinuousBatchingEngine
    from deepspeed_tpu.inference.continuous import _PLAN_TICK_FIELDS
    from deepspeed_tpu.telemetry.hlo_scopes import Scope

    cfg = config("MEM*E")
    model = TransformerModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousBatchingEngine(model, params=params, max_slots=2, cache_len=64, prefill_chunk=16,
                                   seed=0)
    rng = np.random.RandomState(0)
    for n in (20, 9):
        eng.submit(rng.randint(0, 96, n).astype(np.int32), max_new_tokens=6)
    while eng.has_work():
        eng.step()
    s = eng.tick_stats()
    assert s["moe_expert_layers"] == 2 * s["moe_ticks"] > 0
    assert 0 < s["moe_filled_rows"] == s["moe_held_assignments"] <= s["moe_buffer_rows"]
    assert s["moe_buffer_rows"] % 16 == 0 and s["ssm_step_rows"] > 0 and s["ssm_chunk_tokens"] == 29
    assert s["state_pool_bytes"] == 2 * 2 * (8 * 64 * 16 * 4 + 3 * 576 * 2)   # float32 states, bfloat16 tails
    optional = event_schemas.schema_for("serving_tick")["optional"]
    assert set(_PLAN_TICK_FIELDS) <= set(optional) and set(_PLAN_TICK_FIELDS) - {"ssm_chunk_tokens",
                                                                               "ssm_step_rows"} <= set(s)
    assert Scope.MOE_LATENT == "moe.latent"
    text = jax.jit(lambda p, t: tf.forward(p, cfg, t)[0]).lower(
        params, jnp.zeros((1, 8), jnp.int32)).as_text(debug_info=True)
    assert "moe.latent" in text and "moe.shared" in text and "ssm.scan" in text


def test_flops_per_token_counts_attention_where_a_layer_attends():
    cfg = config("MEM*E")
    one_kind = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=3, num_heads=4)
    seq = 128
    head = cfg.vocab_size * cfg.hidden_size
    assert cfg.flops_per_token(seq) == 6.0 * (cfg.num_params() - 2 * head) + 6 * head + 12 * 1 * 32 * seq
    assert one_kind.flops_per_token(seq) == (6.0 * (one_kind.num_params() - 64 * 32) + 6 * 64 * 32
                                             + 12 * 3 * 32 * seq)
