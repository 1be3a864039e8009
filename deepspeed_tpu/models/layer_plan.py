"""Layers of several kinds in one stack: the layer plan.

``TransformerConfig.layer_kinds`` lists the kinds of layer a model has
(:class:`~deepspeed_tpu.models.transformer.LayerKind`: its mixer, softmax
attention with its reach, its key-value heads, its rotary base and whether a
sink joins its softmax, the gated delta rule or a state-space scan; a dense or
an expert FFN; or ONE of the two alone, a layer of one norm and one residual add)
and ``layer_plan`` says which kind each layer is. Kinds differ in parameter SHAPES, so the parameters are stacked
per kind (``params["layers"][kind.name]``, leading axis = that kind's
layers in model order) and the stack is walked as **one scan per run of
equal layers**: a plan ``D W W W W W F`` is a call, a scan of five and a
call. A scan over periods with the period unrolled inside would compile
one body for the whole depth of a periodic model, but needs the plan to BE
periodic (a leading dense layer, a cut in depth and a last period of
another length all break it); the run walk takes any plan, and a model cut
to one period costs the same either way. A run that is not the whole of
its kind's stack reads each of its layers out of that stack as it goes.

The KV cache is one pool per attention reach, ``cache["full"]`` and
``cache["window"]``, in one tree the slot manager carries and donates
whole; its format (axis order, shapes, bytes, the in-place write and the
window read) is ``ops/transformer/kv_cache.py``'s, and the attention here
contracts the windows it hands out heads before time. ``full`` rows are
written and read like the one-kind pool (in place, tight reads).
``window`` is a ring: position p of a row lives in slot p mod window,
which is all a window layer can ever attend, so a window layer costs
``window`` positions of memory and of read however long the row is. A
prefill chunk never reads its own keys through the ring: it attends the
ring's tail (the ``window`` positions before the chunk, put in order) joined
to the chunk's own keys, then writes its last ``window`` tokens. A
delta-rule layer keeps no keys: its rows live in ``cache["state"]``, a
recurrent state and the tail of its convolution a row, which the rows' one
token steps in place and a chunk scans from (``ops/pallas/gated_delta.py``).
A state-space layer (Mamba-2) lives in the same pool with a state of its own
shape and another rule (``ops/pallas/ssd.py``): a scalar decay a head and no
overwrite, so a plan has delta-rule layers or state-space layers, not both.
A latent-attention layer (MLA) keeps ONE vector a token in ``cache["latent"]``,
its normed latent and the rotated key all heads share, which is the keys and
the values of every head: the rows' one token attends it in the ABSORBED form
(``W_UK`` folded into the query, ``W_UV`` taken out of the output, the pool
read in place by ``ops/pallas/mla_attention.py``), a chunk in the EXPANDED
form (its row's latents up to the chunk's end through ``W_UKV`` into heads,
then the flash chunk kernel) - two forms of one function.

A plan may be walked several times over the SAME weights (``loop_steps`` T > 1,
a looped model): an outer ``lax.scan`` over the passes around :func:`_walk`,
so a program holds each run's layer body once however many passes there are;
the final norm closes every pass and its output is what the next pass starts
from, and every pass keeps keys and values of its own: a keyed pool holds T x
its kinds' layers, layer i of pass t at ``t x (the pool's layers a pass) + i``
(:func:`_pass_slot`). Under ``norm_position`` ``"sandwich"`` a layer has four
norms: ``ln1_post`` / ``ln2_post`` norm what the mixer and the FFN return,
before the residual add.

A gated short convolution (``mixer="conv"``) is a mixer of the training
forward only: [b | c | u] = h W_in, a causal depthwise convolution of
``conv_taps`` taps over b * u, gated by c, through W_out. Its serving row
would be the convolution's tail and nothing else, which the state pool does
not hold yet: ``kv_cache.specs`` and :func:`forward_plan_cached` refuse it.

Two entry points: :func:`forward_plan` (no cache: training, the reference
comparison) and :func:`forward_plan_cached` (the serving tick: every slot's
one decode token at its own depth and, with ``chunk``, ONE admitting
row's prefill chunk beside them, as one flat list of tokens through the
projections and FFNs).
"""

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.transformer import kv_cache
from deepspeed_tpu.telemetry.hlo_scopes import Scope


class Chunk(NamedTuple):
    """One admitting row's prefill chunk riding a tick."""
    toks: jnp.ndarray   # (W,) int32
    pos: jnp.ndarray    # (W,) int32 absolute positions; pads carry the pool's length
    slot: jnp.ndarray   # () int32 the row it belongs to
    emit: jnp.ndarray   # () int32 the column whose logits the tick samples


class Run(NamedTuple):
    kind: object
    kind_start: int   # first layer of the run within its kind's stack
    n: int
    pool_start: int   # ... and within its pool


def check_plan(cfg):
    kinds, plan = cfg.layer_kinds, cfg.layer_plan
    if plan is None or len(plan) != cfg.num_layers:
        raise ValueError("layer_plan must name a kind for each of num_layers layers")
    if any(not 0 <= i < len(kinds) for i in plan):
        raise ValueError(f"layer_plan {plan} names a kind outside layer_kinds")
    if len({k.name for k in kinds}) != len(kinds):
        raise ValueError("layer kinds need distinct names")
    for pool in ("full", "window"):
        shapes = {(k.kv_heads, k.window) for k in kinds if k.pool == pool}
        if len(shapes) > 1:
            raise ValueError(f"kinds of the {pool} pool differ in key-value heads or window: "
                             f"{sorted(shapes)}")
    if not any(k.pool in ("full", kv_cache.LATENT) for k in cfg.plan):
        raise ValueError("a layer plan needs a full-attention layer or a latent-attention layer: "
                         "the slot manager reads a row's length off the full or the latent pool")
    if any(k.mixer == "mla" for k in kinds):
        sizes = (cfg.mla_q_rank, cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim)
        if (min(sizes) < 1 or cfg.mla_rope_dim % 2 or cfg.head_dim != cfg.mla_nope_dim + cfg.mla_rope_dim
                or cfg.v_head_dim != cfg.mla_v_dim):
            raise ValueError(f"latent-attention kinds need the five mla sizes {sizes}, an even rotary "
                             f"width, head_dim = unrotated + rotated width ({cfg.head_dim}) and "
                             f"v_head_dim = the value width ({cfg.v_head_dim})")
    if len({k.mixer for k in kinds if k.pool == "state"}) > 1:
        raise ValueError("kinds of the state pool share one mixer (the pool's shape is the "
                         "configuration's): delta-rule layers or state-space layers, not both")
    if any(k.mixer == "gdn" for k in kinds):
        # the state pool's shape is the configuration's, so its kinds agree in it
        sizes = (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim)
        if min(sizes) < 1 or cfg.gdn_value_heads % cfg.gdn_key_heads or cfg.gdn_conv < 2:
            raise ValueError(f"kinds of the state pool need gdn key/value heads and widths, value "
                             f"heads a multiple of key heads, and a convolution: {sizes}, "
                             f"{cfg.gdn_conv} taps")
    if any(k.mixer == "ssm" for k in kinds):
        from deepspeed_tpu.ops.pallas.ssd import heads_per_tile

        sizes = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        if (min(sizes) < 1 or cfg.ssm_groups < 1 or cfg.ssm_conv < 2 or cfg.ssm_heads % cfg.ssm_groups
                or (cfg.ssm_heads // cfg.ssm_groups) % heads_per_tile(cfg.ssm_head_dim, cfg.ssm_heads)):
            raise ValueError(f"state-space kinds of the state pool need ssm heads, head width and "
                             f"state width, groups of whole stored tiles of heads and a "
                             f"convolution: {sizes}, {cfg.ssm_groups} groups, {cfg.ssm_conv} taps")
    if any(k.mixer == "conv" for k in kinds) and cfg.conv_taps < 2:
        raise ValueError(f"a short-convolution kind needs at least two taps: conv_taps {cfg.conv_taps}")
    if cfg.moe_score not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_score {cfg.moe_score!r}")
    for k in kinds:
        if k.mixer not in ("attention", "gdn", "ssm", "mla", "conv", "none"):
            raise ValueError(f"kind {k.name}: mixer {k.mixer!r}")
        if k.mixer == "attention" and cfg.num_heads % k.kv_heads:
            raise ValueError(f"kind {k.name}: {cfg.num_heads} heads over {k.kv_heads} kv heads")
        if k.ffn not in ("dense", "moe", "none"):
            raise ValueError(f"kind {k.name}: ffn {k.ffn!r}")
        if k.mixer == "none" and k.ffn == "none":
            raise ValueError(f"kind {k.name} has neither a mixer nor an FFN")
        if k.ffn == "moe" and cfg.moe_num_experts < 1:
            raise ValueError(f"kind {k.name} routes but moe_num_experts is 0")
        if k.ffn_latent < 0 or (k.ffn_latent and k.ffn != "moe"):
            raise ValueError(f"kind {k.name}: a latent width ({k.ffn_latent}) is an expert FFN's")
    if (cfg.pos_embedding not in ("rope", "none") or cfg.norm_position not in ("pre", "sandwich")
            or cfg.use_bias or cfg.activation not in ("silu_glu", "relu2") or not cfg.causal
            or cfg.kv_cache_dtype != "model"):
        raise ValueError("a layer plan takes rotary positions or none at all (pos_embedding "
                         "'rope' | 'none'), pre-norm or sandwich-norm blocks, no biases, SwiGLU "
                         "or the un-gated squared ReLU (activation 'silu_glu' | 'relu2'), "
                         "causal attention and a KV cache in the model's dtype")
    if cfg.loop_steps < 1:
        raise ValueError(f"loop_steps {cfg.loop_steps}: a plan is walked at least once")
    if cfg.loop_steps > 1 and any(k.pool == "state" for k in kinds):
        raise ValueError("a looped plan (loop_steps > 1) takes keyed pools only: whether a "
                         "recurrent state is kept a pass or carried from pass to pass is a "
                         "decision no published model here needs, and none is made")
    if cfg.pos_embedding == "none" and any(k.mixer == "mla" for k in kinds):
        raise ValueError("latent attention keeps a rotated key: it needs rotary positions")


def runs(cfg):
    """The plan as runs of equal layers."""
    out, seen_kind, seen_pool = [], {}, {}
    for kind in cfg.plan:
        ks, ps = seen_kind.get(kind.name, 0), seen_pool.get(kind.pool, 0)
        if out and out[-1].kind is kind:
            out[-1] = out[-1]._replace(n=out[-1].n + 1)
        else:
            out.append(Run(kind, ks, 1, ps))
        seen_kind[kind.name], seen_pool[kind.pool] = ks + 1, ps + 1
    return out


def layers_of(cfg, kind) -> int:
    return sum(k is kind for k in cfg.plan)


def pool_shapes(cfg):  # {pool: (layers, kv_heads, window or 0)}; kept for test_bench_mimo_v2.py
    return {s.name: (s.layers, s.kv_heads, s.ring or 0) for s in kv_cache.specs(cfg)}


kv_read_bytes_by_pool = kv_cache.read_bytes_by_pool  # kept for tests/benchmark/test_bench_mimo_v2.py


def _ffn_size(cfg, kind):
    return kind.ffn_size or cfg.ffn_size


def _layer_shapes(cfg, kind):
    """{(group, leaf): (shape, scale of its normal init; None = ones)} of one layer."""
    D, nh, dk, dv, F = cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.v_head_dim, _ffn_size(cfg, kind)
    out_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    norm = 0.1 if cfg.norm_one_plus else None   # (1 + w): w zero-centred
    # a sublayer's norm(s) come with it: ln1 the mixer's, ln2 the FFN's
    names = [n for n, part in (("ln1", kind.mixer), ("ln2", kind.ffn)) if part != "none"]
    if cfg.norm_position == "sandwich":
        names += [n + "_post" for n in names]
    shapes = {(n, "scale"): ((D,), norm) for n in names}
    if kind.mixer == "none":
        pass
    elif kind.mixer == "gdn":
        Hk, Hv, gk, gv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
        C = 2 * Hk * gk + Hv * gv
        shapes.update({
            ("gdn", "wqkvz"): ((D, C + Hv * gv), 1 / math.sqrt(D)),    # q, k, v (convolved), z
            ("gdn", "wba"): ((D, 2 * Hv), 1 / math.sqrt(D)),           # beta's and the decay's inputs
            ("gdn", "conv"): ((C, cfg.gdn_conv), 1 / math.sqrt(cfg.gdn_conv)),
            ("gdn", "a_log"): ((Hv,), 1.0),
            ("gdn", "dt_bias"): ((Hv,), 1.0),
            ("gdn", "norm"): ((gv,), None),
            ("gdn", "wo"): ((Hv * gv, D), out_scale / math.sqrt(Hv * gv)),
        })
    elif kind.mixer == "ssm":
        Hs, N = cfg.ssm_heads, cfg.ssm_state
        inner = Hs * cfg.ssm_head_dim
        C = inner + 2 * cfg.ssm_groups * N
        shapes.update({
            ("ssm", "win"): ((D, inner + C + Hs), 1 / math.sqrt(D)),      # z, [x | B | C] (convolved), dt
            ("ssm", "conv"): ((C, cfg.ssm_conv), 1 / math.sqrt(cfg.ssm_conv)),
            ("ssm", "conv_bias"): ((C,), 0.1),
            ("ssm", "a_log"): ((Hs,), 1.0),
            ("ssm", "dt_bias"): ((Hs,), 1.0),
            ("ssm", "d"): ((Hs,), 1.0),
            ("ssm", "norm"): ((inner,), None),
            ("ssm", "wo"): ((inner, D), out_scale / math.sqrt(inner)),
        })
    elif kind.mixer == "conv":
        shapes.update({
            ("conv", "win"): ((D, 3 * D), 1 / math.sqrt(D)),                # b, c (the two gates), u
            ("conv", "conv"): ((D, cfg.conv_taps), 1 / math.sqrt(cfg.conv_taps)),
            ("conv", "wo"): ((D, D), out_scale / math.sqrt(D)),
        })
    elif kind.mixer == "mla":
        qr, kr, dn, dr = cfg.mla_q_rank, cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim
        shapes.update({
            ("mla", "wdq"): ((D, qr), 1 / math.sqrt(D)),
            ("mla", "q_norm"): ((qr,), norm),
            ("mla", "wuq"): ((qr, nh * (dn + dr)), 1 / math.sqrt(qr)),      # a head: [unrotated | rotated]
            ("mla", "wdkv"): ((D, kr + dr), 1 / math.sqrt(D)),              # [latent | the shared key]
            ("mla", "kv_norm"): ((kr,), norm),
            ("mla", "wukv"): ((kr, nh * (dn + dv)), 1 / math.sqrt(kr)),     # a head: [key | value]
            ("mla", "wo"): ((nh * dv, D), out_scale / math.sqrt(nh * dv)),
        })
    else:
        shapes.update({
            ("attn", "wq"): ((D, nh * dk), 1 / math.sqrt(D)),
            ("attn", "wk"): ((D, kind.kv_heads * dk), 1 / math.sqrt(D)),
            ("attn", "wv"): ((D, kind.kv_heads * dv), 1 / math.sqrt(D)),
            ("attn", "wo"): ((nh * dv, D), out_scale / math.sqrt(nh * dv)),
        })
        if kind.sink:
            shapes[("attn", "sink")] = ((nh,), 1.0)
        if cfg.attn_out_gate:
            shapes[("attn", "wq_gate")] = ((D, nh * dv), 1 / math.sqrt(D))
        if cfg.qk_norm:
            shapes[("attn", "q_norm")] = shapes[("attn", "k_norm")] = ((dk,), norm)
    if kind.ffn == "moe":
        E, held = cfg.moe_num_experts, cfg.held_experts[1]
        Dx = kind.ffn_latent or D    # the width the routed experts work at
        shapes.update({
            ("mlp", "gate"): ((D, E), 0.02),
            ("mlp", "wg"): ((held, Dx, F), 1 / math.sqrt(Dx)),
            ("mlp", "wi"): ((held, Dx, F), 1 / math.sqrt(Dx)),
            ("mlp", "wo"): ((held, F, Dx), out_scale / math.sqrt(F)),
        })
        if kind.ffn_latent:
            shapes.update({("mlp", "latent_down"): ((D, Dx), 1 / math.sqrt(D)),
                           ("mlp", "latent_up"): ((Dx, D), 1 / math.sqrt(Dx))})
        if cfg.moe_score == "sigmoid":
            # the selection bias balancing leaves behind: small beside the scores' spread,
            # large enough to decide some choices
            shapes[("mlp", "gate_bias")] = ((E,), 0.01)
        if cfg.moe_shared_size:
            Fs = cfg.moe_shared_size
            shapes.update({
                ("mlp", "shared_wg"): ((D, Fs), 1 / math.sqrt(D)),
                ("mlp", "shared_wi"): ((D, Fs), 1 / math.sqrt(D)),
                ("mlp", "shared_wo"): ((Fs, D), out_scale / math.sqrt(Fs)),
            })
            if cfg.moe_shared_gated:
                shapes[("mlp", "shared_gate")] = ((D, 1), 1 / math.sqrt(D))
    elif kind.ffn == "dense":
        shapes.update({
            ("mlp", "wg"): ((D, F), 1 / math.sqrt(D)),
            ("mlp", "wi"): ((D, F), 1 / math.sqrt(D)),
            ("mlp", "wo"): ((F, D), out_scale / math.sqrt(F)),
        })
    if cfg.activation == "relu2":    # W_down relu(W_up x)^2: two matrices, no gate
        shapes = {at: v for at, v in shapes.items() if at not in (("mlp", "wg"), ("mlp", "shared_wg"))}
    return shapes


def num_params(cfg) -> int:
    D, V = cfg.hidden_size, cfg.vocab_size
    total = V * D + D + (0 if cfg.tie_embeddings else V * D)
    if cfg.loop_steps > 1:
        total += D + 1   # the exit gate
    for kind in cfg.plan:
        total += sum(math.prod(shape) for shape, _ in _layer_shapes(cfg, kind).values())
    return total


def init_layers(rng, cfg):
    """{kind.name: that kind's layers stacked}; float32 leaves, or the
    model's dtype straight away under ``cfg.init_in_model_dtype`` (each leaf
    is drawn and cast in one fusion, so the float32 tree never exists)."""
    dtype = cfg.jnp_dtype if cfg.init_in_model_dtype else jnp.float32
    out = {}
    for ki, kind in enumerate(cfg.layer_kinds):
        n = layers_of(cfg, kind)
        if not n:
            continue
        tree = {}
        for li, ((group, name), (shape, scale)) in enumerate(sorted(_layer_shapes(cfg, kind).items())):
            key = jax.random.fold_in(jax.random.fold_in(rng, ki), li)
            if scale is None:
                leaf = jnp.ones((n,) + shape, dtype)
            else:
                leaf = (jax.random.normal(key, (n,) + shape, jnp.float32) * scale).astype(dtype)
            tree.setdefault(group, {})[name] = leaf
        out[kind.name] = tree
    return out


def init_exit_gate(rng, cfg):
    """A looped model's exit gate, one linear map with a bias on the normed
    state a pass ends in: ``{"w": (D,), "b": ()}``, float32 (the bias 0)."""
    D = cfg.hidden_size
    return {"w": jax.random.normal(rng, (D,), jnp.float32) / math.sqrt(D),
            "b": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# pieces of a layer
# ---------------------------------------------------------------------------

def _tf():
    from deepspeed_tpu.models import transformer as tf

    return tf


def _project(h, attn_p, kind, cfg, positions, product=None):
    """h (N, D), positions (N,) -> q (N, nh, dk), k (N, kv, dk), v (N, kv, dv); ``product`` as ``tf._qkv``'s."""
    tf = _tf()
    with jax.named_scope(Scope.ATTN_QKV):
        N, product = h.shape[0], product or tf._linear
        q = product(h, attn_p["wq"]).reshape(1, N, cfg.num_heads, cfg.head_dim)
        k = product(h, attn_p["wk"]).reshape(1, N, kind.kv_heads, cfg.head_dim)
        v = product(h, attn_p["wv"]).reshape(N, kind.kv_heads, cfg.v_head_dim)
        if cfg.qk_norm:  # over each head's width, before it turns
            q = tf._norm(q, attn_p["q_norm"], None, cfg)
            k = tf._norm(k, attn_p["k_norm"], None, cfg)
        turn = lambda a: a    # pos_embedding "none": no position signal anywhere
        if cfg.pos_embedding == "rope":
            turn = lambda a: tf._rope(a, positions[None], kind.rope_theta, cfg.rope_dim,
                                      cfg.rope_interleaved)
        q, k = turn(q)[0], turn(k)[0]
        if cfg.attn_value_scale is not None:
            v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
    return q, k, v


def _attn_out(att, h, attn_p, cfg):
    """att (..., nh * dv) -> (..., D): through the output gate where the
    model has one (sigmoid of a projection of the layer's input), then Wo."""
    tf = _tf()
    if cfg.attn_out_gate:
        with jax.named_scope(Scope.ATTN_GATE):
            gate = tf._linear(h, attn_p["wq_gate"]).reshape(att.shape)
            att = (att.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(att.dtype)
    return tf._attn_out_proj(att, attn_p, cfg)


def _scale(cfg):
    return cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(cfg.head_dim)


def _grouped_attention(q, k, v, ok, sink, scale):
    """q (B, S, nh, dk) against k (B, kv, T, dk), v (B, kv, T, dv) where
    ``ok`` (B|1, S, T) says which keys a query attends; query head h reads
    key-value head h // (nh / kv) WITHOUT the keys being repeated. ``sink``
    (nh,): a per-head logit in the softmax's denominator, with no value."""
    B, S, nh, dk = q.shape
    kv = k.shape[1]
    g = nh // kv
    logits = jnp.einsum("bsngd,bntd->bngst", q.reshape(B, S, kv, g, dk), k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(ok[:, None, None], logits, jnp.float32(-1e30))
    m = logits.max(axis=-1, keepdims=True)
    if sink is not None:
        s = sink.astype(jnp.float32).reshape(1, kv, g, 1, 1)
        m = jnp.maximum(m, s)
    p = jnp.where(ok[:, None, None], jnp.exp(logits - m), 0.0)
    denom = p.sum(axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(s - m)
    p = (p / jnp.maximum(denom, 1e-20)).astype(v.dtype)
    out = jnp.einsum("bngst,bntd->bsngd", p, v)
    return out.reshape(B, S, nh, v.shape[-1])


def _hidden_act(h, p, up, gate):
    """An FFN's hidden activation by the form its parameters have: SwiGLU
    where ``p`` holds the gate's matrix, else the un-gated squared ReLU."""
    tf = _tf()
    if gate in p:
        return jax.nn.silu(tf._linear(h, p[gate])) * tf._linear(h, p[up])
    return jnp.square(jax.nn.relu(tf._linear(h, p[up])))


def _ffn(h, mlp_p, kind, cfg, valid, grad):
    """h (N, D) -> (out (N, D), stats (:func:`ffn_stats_len`,) int32:
    assignments made, to held experts, the most one held expert got, expert
    layers, held experts hit; where the plan counts rows, also the buffer
    rows the grouped matmul walked: the rows it filled are the assignments
    to held experts).
    ``grad``: the caller differentiates this (the training forward)."""
    tf = _tf()
    if kind.ffn == "dense":
        with jax.named_scope(Scope.MLP):
            act = _hidden_act(h, mlp_p, "wi", "wg")
            return tf._linear(act, mlp_p["wo"]), jnp.zeros((ffn_stats_len(cfg),), jnp.int32)
    from deepspeed_tpu.moe import held_experts as he

    first, count = cfg.held_experts
    chosen, weights = he.route(h, mlp_p["gate"], mlp_p.get("gate_bias"), cfg.moe_top_k,
                               cfg.moe_score, scale=cfg.moe_routed_scale, norm_eps=cfg.moe_norm_eps)
    u = h
    if kind.ffn_latent:   # the routed experts work in a latent; the router and the shared expert do not
        with jax.named_scope(Scope.MOE_LATENT):
            u = tf._linear(h, mlp_p["latent_down"])
    out, counts = he.held_experts_ffn(
        u, chosen, weights, {n: mlp_p[n] for n in _EXPERT_LEAVES if n in mlp_p}, first, count,
        grad=grad, valid=valid, layer=mlp_p.get("layer"), n_experts=cfg.moe_num_experts)
    if kind.ffn_latent:   # linear: once over the experts' weighted sum
        with jax.named_scope(Scope.MOE_LATENT):
            out = tf._linear(out, mlp_p["latent_up"])
    if cfg.moe_shared_size:  # every chip computes it alike, whatever was routed where
        with jax.named_scope(Scope.MOE_SHARED):
            act = _hidden_act(h, mlp_p, "shared_wi", "shared_wg")
            if cfg.moe_shared_gated:
                gate = jax.nn.sigmoid(tf._linear(h, mlp_p["shared_gate"]).astype(jnp.float32))
                out = out + (tf._linear(act, mlp_p["shared_wo"]) * gate).astype(out.dtype)
            else:
                out = out + tf._linear(act, mlp_p["shared_wo"]).astype(out.dtype)
    made = (h.shape[0] if valid is None else valid.sum(dtype=jnp.int32)) * cfg.moe_top_k
    stats = [jnp.asarray(made, jnp.int32), counts.sum(dtype=jnp.int32),
             counts.max().astype(jnp.int32), jnp.int32(1), (counts > 0).sum(dtype=jnp.int32)]
    if counts_rows(cfg):   # each expert's rows padded to whole row tiles
        tm = he.row_tile(h.shape[0], cfg.moe_top_k)
        stats.append(((counts + tm - 1) // tm * tm).sum(dtype=jnp.int32))
    return out, jnp.stack(stats)


# -- the gated delta rule mixer (ops/pallas/gated_delta.py has the rule itself) --

def _gdn_project(h, p, cfg):
    """h (N, D) -> (u (N, C) the convolution's inputs [q | k | v], z (N, Hv,
    dv) the output gate's, g (N, Hv) the log decay, beta (N, Hv)), the last
    two in float32."""
    tf = _tf()
    Hv = cfg.gdn_value_heads
    C = p["conv"].shape[0]
    qkvz, ba = tf._linear(h, p["wqkvz"]), tf._linear(h, p["wba"]).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, Hv:] + p["dt_bias"].astype(jnp.float32))
    return qkvz[:, :C], qkvz[:, C:].reshape(-1, Hv, cfg.gdn_value_dim), g, beta


def _causal_conv(seq, w, bias=None):
    """Causal depthwise convolution (plus ``bias`` (C,)) as a sum of shifted
    products: seq (..., T + K - 1, C), its first K - 1 steps the inputs
    before the first output; w (C, K). Returns (..., T, C), float32."""
    K = w.shape[1]
    T = seq.shape[-2] - (K - 1)
    acc = sum(seq[..., j:j + T, :].astype(jnp.float32) * w[:, j].astype(jnp.float32)
              for j in range(K))
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return acc


def _causal_conv_silu(seq, w, bias=None):
    """:func:`_causal_conv`, then SiLU, in ``seq``'s dtype."""
    return jax.nn.silu(_causal_conv(seq, w, bias)).astype(seq.dtype)


def _gdn_conv(seq, w):
    with jax.named_scope(Scope.GDN_CONV):
        return _causal_conv_silu(seq, w)


def _gdn_heads(u, cfg):
    """Convolved u (N, C) -> q, k (N, Hv, dk) L2-normalised (q scaled), v
    (N, Hv, dv), float32; value head j reads key head j // (Hv / Hk)."""
    Hk, Hv, dk, dv = cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    u = u.astype(jnp.float32)
    unit = lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)
    q = unit(u[:, :Hk * dk].reshape(-1, Hk, dk)) * dk ** -0.5
    k = unit(u[:, Hk * dk:2 * Hk * dk].reshape(-1, Hk, dk))
    rep = lambda a: jnp.repeat(a, Hv // Hk, axis=1)
    return rep(q), rep(k), u[:, 2 * Hk * dk:].reshape(-1, Hv, dv)


def _gdn_out(o, z, p, cfg):
    """o, z (N, Hv, dv) -> (N, D): RMSNorm of each head's output (a plain
    weight), gated by silu(z), through Wo."""
    tf = _tf()
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.norm_eps) * p["norm"].astype(jnp.float32)
    y = (o * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return tf._linear(y.reshape(y.shape[0], -1), p["wo"])


def _hold(g, beta, valid):
    """A token that is not ``valid`` (a chunk's pad, a parked row, an empty
    slot) takes ``g = 0`` and ``beta = 0``: the step that leaves the state
    exactly as it was. Looked up when a tick is traced (a test plants a
    fault here)."""
    return jnp.where(valid[:, None], g, 0.0), jnp.where(valid[:, None], beta, 0.0)


def _gdn_plain(h, p, cfg, B, S):
    """The mixer over whole sequences from a zero state, token by token:
    h (B * S, D) -> (B * S, D)."""
    from deepspeed_tpu.ops.pallas.gated_delta import gdn_recurrence

    with jax.named_scope(Scope.MIX_GDN):
        u, z, g, beta = _gdn_project(h, p, cfg)
        u = u.reshape(B, S, -1)
        u = _gdn_conv(jnp.pad(u, ((0, 0), (cfg.gdn_conv - 1, 0), (0, 0))), p["conv"])
        q, k, v = (a.reshape((B, S) + a.shape[1:]) for a in _gdn_heads(u.reshape(B * S, -1), cfg))
        zero = jnp.zeros((cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim), jnp.float32)
        with jax.named_scope(Scope.GDN_SCAN):
            o = jax.vmap(lambda *a: gdn_recurrence(*a, zero)[0])(
                q, k, v, g.reshape(B, S, -1), beta.reshape(B, S, -1))
        return _gdn_out(o.reshape((B * S,) + o.shape[2:]), z, p, cfg)


def _gdn_cached(h, p, cfg, pool, layer, B, chunk, valid):
    """The mixer of one layer of the tick: h (N, D) holds the B rows' single
    tokens, then the chunk's W. Each valid row's state takes one step and
    its convolution tail shifts by one; the chunk scans from its own row's
    state and tail (that row is parked among the rows) and leaves the state
    after its last real token and that token's last inputs. Returns ((N, D),
    the state pool)."""
    from deepspeed_tpu.ops.pallas.gated_delta import gdn_chunk, gdn_step_pool

    with jax.named_scope(Scope.MIX_GDN):
        u, z, g, beta = _gdn_project(h, p, cfg)
        g, beta = _hold(g, beta, valid)
        tails = jax.lax.dynamic_index_in_dim(pool["conv"], layer, 0, keepdims=False)   # (B, K-1, C)
        seq = jnp.concatenate([tails, u[:B, None]], axis=1)
        q, k, v = _gdn_heads(_gdn_conv(seq, p["conv"])[:, 0], cfg)
        with jax.named_scope(Scope.GDN_STEP):   # in place (as XLA ops on the layer's slab: PERF.md section 6, PR 34)
            o, states = gdn_step_pool(pool["s"], layer, q, k, v, g[:B], beta[:B])
        tails = jnp.where(valid[:B, None, None], seq[:, 1:], tails)
        if chunk is not None:
            at = (layer, chunk.slot, 0, 0, 0)
            seq = jnp.concatenate([jax.lax.dynamic_index_in_dim(tails, chunk.slot, 0, keepdims=False),
                                   u[B:]])
            q, k, v = _gdn_heads(_gdn_conv(seq, p["conv"]), cfg)
            with jax.named_scope(Scope.GDN_SCAN):
                oc, state = gdn_chunk(q, k, v, g[B:], beta[B:], jax.lax.dynamic_slice(
                    states, at, (1, 1) + states.shape[2:])[0, 0])
            states = jax.lax.dynamic_update_slice(states, state[None, None], at)
            real = valid[B:].sum(dtype=jnp.int32)
            tail = jax.lax.dynamic_slice_in_dim(seq, real, cfg.gdn_conv - 1, axis=0)   # the last real token's inputs
            tails = jax.lax.dynamic_update_index_in_dim(tails, tail, chunk.slot, 0)
            o = jnp.concatenate([o, oc])
        pool = {"s": states, "conv": jax.lax.dynamic_update_index_in_dim(pool["conv"], tails, layer, 0)}
        return _gdn_out(o, z, p, cfg), pool


# -- the state-space mixer (Mamba-2; ops/pallas/ssd.py has the scan itself) --

def _ssm_project(h, p, cfg):
    """h (N, D) -> (z (N, inner) the gate's inputs, u (N, C) the convolution's
    inputs [x | B | C], dt (N, H) = softplus(. + dt_bias), float32)."""
    tf = _tf()
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    zxbcdt = tf._linear(h, p["win"])
    dt = jax.nn.softplus(zxbcdt[:, -cfg.ssm_heads:].astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return zxbcdt[:, :inner], zxbcdt[:, inner:-cfg.ssm_heads], dt


def _ssm_conv(seq, p):
    with jax.named_scope(Scope.SSM_CONV):
        return _causal_conv_silu(seq, p["conv"], p["conv_bias"])


def _ssm_lanes(per_head, cfg):
    """(N, H) -> (N, inner): a head's scalar on each of its channels."""
    return jnp.repeat(per_head, cfg.ssm_head_dim, axis=-1)


def _ssm_parts(u, dt, p, cfg):
    """Convolved u (N, C), dt (N, H) -> (x (N, inner), dt x, a = dt A (N, H),
    B, C (N, groups x state width), group by group), float32. The heads stay
    side by side along the channels (a (N, H, 64) array would leave half of
    every lane tile empty and cost a re-layout each way: PERF.md section 6,
    PR 42)."""
    N = cfg.ssm_groups * cfg.ssm_state
    u = u.astype(jnp.float32)
    x = u[:, :-2 * N]
    a = -jnp.exp(p["a_log"].astype(jnp.float32)) * dt
    return x, _ssm_lanes(dt, cfg) * x, a, u[:, -2 * N:-N], u[:, -N:]


def _group_mean_square(y, cfg):
    """Mean square of y (N, inner) over each group's channels, on each of
    them: (N, inner), or (N, 1) where the one group is the whole width.
    Looked up when a program is traced (a test plants a fault here)."""
    if cfg.ssm_groups == 1:
        return (y * y).mean(-1, keepdims=True)
    by_group = y.reshape(y.shape[0], cfg.ssm_groups, -1)
    return jnp.repeat((by_group * by_group).mean(-1), by_group.shape[-1], axis=-1)


def _ssm_out(y, x, z, p, cfg):
    """y, x, z (N, inner) -> (N, D): the skip ``D x``, the gate silu(z), THEN
    an RMSNorm over each group's share of the inner width (one group: over
    all of it), through Wo."""
    tf = _tf()
    y = y + _ssm_lanes(p["d"].astype(jnp.float32)[None], cfg) * x
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(_group_mean_square(y, cfg) + cfg.norm_eps) * p["norm"].astype(jnp.float32)
    return tf._linear(y.astype(z.dtype), p["wo"])


def _hold_dt(dt, valid):
    """A token that is not ``valid`` (a chunk's pad, a parked row, an empty
    slot) takes ``dt = 0``: decay exp(0) = 1, nothing added, the step that
    leaves the state exactly as it was (:func:`_hold`'s idea). Looked up when
    a tick is traced (a test plants a fault here)."""
    return jnp.where(valid[:, None], dt, 0.0)


def _ssm_plain(h, p, cfg, B, S):
    """The mixer over whole sequences from a zero state, token by token:
    h (B * S, D) -> (B * S, D)."""
    from deepspeed_tpu.ops.pallas.ssd import ssd_recurrence

    with jax.named_scope(Scope.MIX_SSM):
        z, u, dt = _ssm_project(h, p, cfg)
        u = u.reshape(B, S, -1)
        u = _ssm_conv(jnp.pad(u, ((0, 0), (cfg.ssm_conv - 1, 0), (0, 0))), p)
        x, _, a, Bm, Cm = _ssm_parts(u.reshape(B * S, -1), dt, p, cfg)
        rows = lambda v: v.reshape((B, S) + v.shape[1:])
        heads = (cfg.ssm_heads, cfg.ssm_head_dim)
        by_group = lambda v: v.reshape(B, S, cfg.ssm_groups, cfg.ssm_state)
        zero = jnp.zeros(heads + (cfg.ssm_state,), jnp.float32)
        with jax.named_scope(Scope.SSM_SCAN):
            y = jax.vmap(lambda *v: ssd_recurrence(*v, zero)[0])(
                x.reshape((B, S) + heads), rows(dt), rows(a), by_group(Bm), by_group(Cm))
        return _ssm_out(y.reshape(x.shape), x, z, p, cfg)


def _ssm_cached(h, p, cfg, pool, layer, B, chunk, valid):
    """The mixer of one layer of the tick, as :func:`_gdn_cached`: each valid
    row's state takes one step in place and its convolution tail shifts by
    one; the chunk scans from its own row's state and tail (that row is
    parked among the rows) and leaves the state after its last real token and
    that token's last inputs. Returns ((N, D), the state pool)."""
    from deepspeed_tpu.ops.pallas.ssd import ssd_chunk_pool, ssd_step_pool

    with jax.named_scope(Scope.MIX_SSM):
        z, u, dt = _ssm_project(h, p, cfg)
        dt = _hold_dt(dt, valid)
        tails = jax.lax.dynamic_index_in_dim(pool["conv"], layer, 0, keepdims=False)   # (B, K-1, C)
        seq = jnp.concatenate([tails, u[:B, None]], axis=1)
        x, xd, a, Bm, Cm = _ssm_parts(_ssm_conv(seq, p)[:, 0], dt[:B], p, cfg)
        with jax.named_scope(Scope.SSM_STEP):
            y, states = ssd_step_pool(pool["s"], layer, xd, a, Bm, Cm)
        tails = jnp.where(valid[:B, None, None], seq[:, 1:], tails)
        if chunk is not None:
            seq = jnp.concatenate([jax.lax.dynamic_index_in_dim(tails, chunk.slot, 0, keepdims=False),
                                   u[B:]])
            xc, xd, a, Bm, Cm = _ssm_parts(_ssm_conv(seq, p), dt[B:], p, cfg)
            with jax.named_scope(Scope.SSM_SCAN):
                yc, states = ssd_chunk_pool(states, layer, chunk.slot, xd, a, Bm, Cm)
            real = valid[B:].sum(dtype=jnp.int32)
            tail = jax.lax.dynamic_slice_in_dim(seq, real, cfg.ssm_conv - 1, axis=0)   # the last real token's inputs
            tails = jax.lax.dynamic_update_index_in_dim(tails, tail, chunk.slot, 0)
            y, x = jnp.concatenate([y, yc]), jnp.concatenate([x, xc])
        pool = {"s": states, "conv": jax.lax.dynamic_update_index_in_dim(pool["conv"], tails, layer, 0)}
        return _ssm_out(y, x, z, p, cfg), pool


# -- the gated short convolution mixer --

def _conv_plain(h, p, cfg, B, S):
    """The mixer over whole sequences, nothing before a row's first token:
    h (B * S, D) -> (B * S, D). [b | c | u] = h W_in; the convolution runs
    over b * u, with no activation; c gates what it gives; W_out."""
    tf = _tf()
    D, K = cfg.hidden_size, cfg.conv_taps
    with jax.named_scope(Scope.MIX_CONV):
        bcu = tf._linear(h, p["win"])
        b, c, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
        v = jnp.pad((b * u).reshape(B, S, D), ((0, 0), (K - 1, 0), (0, 0)))
        with jax.named_scope(Scope.CONV_SHORT):
            z = _causal_conv(v, p["conv"]).astype(h.dtype)
        return tf._linear(c * z.reshape(B * S, D), p["wo"])


# -- the latent-attention mixer (MLA; ops/pallas/mla_attention.py reads the pool for the rows) --

def _stored(x, cfg):
    """x (..., latent + rotated width) zero-padded along its last axis to
    the columns the pool stores a token in (``kv_cache.latent_width``)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, kv_cache.latent_width(cfg) - x.shape[-1])])


def _mla_project(h, p, kind, cfg, positions):
    """h (N, D), positions (N,) -> (q (N, nh, dn + dr) with its rotary part
    turned, tok (N, stored width): each token's cache entry [normed latent |
    rotated shared key | zeros], as ``kv_cache.latent_width`` lays it out)."""
    tf = _tf()
    N = h.shape[0]
    kr, dn, dr = cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim
    turn = lambda a, at=positions: tf._rope(a, at[None], kind.rope_theta, None,
                                            cfg.rope_interleaved)[0]
    with jax.named_scope(Scope.MLA_Q):
        cq = tf._norm(tf._linear(h, p["wdq"]), p["q_norm"], None, cfg)
        q = tf._linear(cq, p["wuq"]).reshape(N, cfg.num_heads, dn + dr)
        q = jnp.concatenate([q[..., :dn], turn(q[None, ..., dn:])], axis=-1)
    with jax.named_scope(Scope.MLA_LATENT):
        ckv = tf._linear(h, p["wdkv"])
        c = tf._norm(ckv[:, :kr], p["kv_norm"], None, cfg)
        r = turn(ckv[None, :, None, kr:])[:, 0]
        tok = _stored(jnp.concatenate([c, r], axis=-1), cfg)
    return q, tok


def _mla_up(p, cfg):
    """W_UKV by head: (W_UK (kr, nh, dn), W_UV (kr, nh, dv))."""
    w = p["wukv"].reshape(cfg.mla_kv_rank, cfg.num_heads, cfg.mla_nope_dim + cfg.mla_v_dim)
    return w[..., :cfg.mla_nope_dim], w[..., cfg.mla_nope_dim:]


def _mla_expand(toks, p, cfg):
    """Cached entries toks (T, stored width) -> every head's keys (nh, T, dn
    + dr) and values (nh, T, dv): the latent through W_UKV, the one rotated
    key repeated to each head."""
    kr, dr = cfg.mla_kv_rank, cfg.mla_rope_dim
    wuk, wuv = _mla_up(p, cfg)
    with jax.named_scope(Scope.MLA_EXPAND):
        c, r = toks[:, :kr], toks[:, kr:kr + dr]
        k = jnp.einsum("tk,khd->htd", c, wuk)
        k = jnp.concatenate([k, jnp.broadcast_to(r[None], (cfg.num_heads,) + r.shape)], axis=-1)
        return k, jnp.einsum("tk,khd->htd", c, wuv)


def _mla_absorb(q, p, cfg):
    """q (N, nh, dn + dr) -> (N, nh, stored width): W_UK folded into the
    unrotated part, the rotated part beside it, zeros where the pool's
    entries have zeros: a head's score against a cached entry is their dot
    product."""
    dn = cfg.mla_nope_dim
    qa = jnp.einsum("nhd,khd->nhk", q[..., :dn], _mla_up(p, cfg)[0])
    return _stored(jnp.concatenate([qa.astype(q.dtype), q[..., dn:]], axis=-1), cfg)


def _mla_unabsorb(u, p, cfg):
    """u (N, nh, >= kr): a head's average of cached entries -> its output (N, nh, dv)."""
    return jnp.einsum("nhk,khd->nhd", u[..., :cfg.mla_kv_rank], _mla_up(p, cfg)[1])


def _mla_plain(h, p, kind, cfg, B, S, positions):
    """The mixer over whole sequences, expanded form: h (B * S, D) -> (B * S, D)."""
    tf = _tf()
    with jax.named_scope(Scope.MIX_MLA):
        q, tok = _mla_project(h, p, kind, cfg, positions)
        k, v = jax.vmap(lambda t: _mla_expand(t, p, cfg))(tok.reshape(B, S, -1))
        pos = jnp.arange(S, dtype=jnp.int32)
        with jax.named_scope(Scope.ATTN_LATENT):
            att = _grouped_attention(q.reshape(B, S, *q.shape[1:]), k, v,
                                     (pos[None, :] <= pos[:, None])[None], None, _scale(cfg))
        return tf._attn_out_proj(att.reshape(B * S, -1), p, cfg)


def _mla_cached(h, p, kind, cfg, pool, layer, pos, all_pos, chunk, read_len, length):
    """The mixer of one layer of the tick: h (N, D) holds the B rows' single
    tokens, then the chunk's W. Every token's entry is written; the rows
    attend their pool rows in the absorbed form, each to its own length (a
    parked row and an empty slot read nothing); the chunk attends its row in
    the expanded form: the row's entries up to the chunk's end through
    ``W_UKV`` into heads, then the flash chunk kernel. Returns ((N, D), the
    latent pool's leaf)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_chunk
    from deepspeed_tpu.ops.pallas import mla_attention

    tf = _tf()
    B = pos.shape[0]
    size, scale = read_len or length, _scale(cfg)
    with jax.named_scope(Scope.MIX_MLA):
        q, tok = _mla_project(h, p, kind, cfg, all_pos)
        with jax.named_scope(Scope.ATTN_KV_WRITE):
            pool = _write_rows(pool, layer, tok[:B, None], pos, size)
        with jax.named_scope(Scope.MLA_ABSORB):
            qa = _mla_absorb(q[:B], p, cfg)
        with jax.named_scope(Scope.ATTN_LATENT):
            u = mla_attention.mla_decode(qa, pool, layer, jnp.where(pos < length, pos + 1, 0),
                                         size=size, sm_scale=scale)
        with jax.named_scope(Scope.MLA_ABSORB):
            att = _mla_unabsorb(u, p, cfg).reshape(B, -1)
        if chunk is not None:
            W = q.shape[0] - B
            real, first = chunk.pos < length, chunk.pos[0]
            width = min(W, size)
            with jax.named_scope(Scope.ATTN_KV_WRITE):
                start = jnp.clip(first, 0, size - width)
                cols = jnp.where(real, chunk.pos - start, width)
                pool = _write(pool, layer, tok[B:, None], cols, width, slot=chunk.slot, start=start)
            with jax.named_scope(Scope.ATTN_KV_READ):
                row = _window(pool, layer, size, slot=chunk.slot)[0]               # (size, stored width)
            with jax.named_scope(Scope.MLA_EXPAND):
                wuk, wuv = (jnp.transpose(w, (1, 0, 2)) for w in _mla_up(p, cfg))
                k, v = mla_attention.mla_expand(row, wuk, wuv, first + W, rank=cfg.mla_kv_rank,
                                                rope=cfg.mla_rope_dim)
            with jax.named_scope(Scope.ATTN_LATENT):
                out = flash_attention_chunk(q[B:], k, v, q_off=first, sm_scale=scale)
            att = jnp.concatenate([att, out.reshape(W, -1)])
        return tf._attn_out_proj(att, p, cfg), pool


def chunk_attention_tiles(cfg, W: int, size: int, first: int):
    """What the flash chunk kernel does for ONE prefill chunk of ``W`` columns
    whose first token sits at position ``first``, the full pool read to
    ``size``, summed over the plan's attention layers: (score tiles computed,
    of them masked, K/V tiles fetched) — the calls :func:`_attend_cached` and
    :func:`_mla_cached` make, asked of the kernel's own walk on the host."""
    from deepspeed_tpu.ops.pallas.flash_attention import chunk_tiles

    nh, dk, dv, itemsize = cfg.num_heads, cfg.head_dim, cfg.v_head_dim, jnp.dtype(cfg.jnp_dtype).itemsize
    total = [0, 0, 0]
    for kind in cfg.layer_kinds:
        if kind.pool == "state":
            continue
        if kind.window:  # the ring's tail joined to the chunk's own keys, the offset a Python int
            R = kind.window
            call = chunk_tiles(W, nh, kind.kv_heads, R + W, dk, dv, R, max(R - first, 0), R, True, itemsize)
        else:            # the row as cached; a latent layer expands a key-value head a query head
            kv = nh if kind.mixer == "mla" else kind.kv_heads
            call = chunk_tiles(W, nh, kv, size, dk, dv, first, itemsize=itemsize)
        total = [t + cfg.loop_steps * layers_of(cfg, kind) * c for t, c in zip(total, call)]
    return tuple(total)


GDN_STATS = 2   # beside the routing counters: real tokens the chunk's scan took, rows stepped
ROW_STATS = 1   # ... and LAST: buffer rows the grouped matmuls walked (those filled: the held assignments)


def counts_rows(cfg) -> bool:
    """Do ``cfg``'s expert layers count :data:`ROW_STATS`? Where an expert
    layer is a layer of its own (a kind with no mixer): the plans whose every
    layer has both sublayers keep the ticks they had, to the text."""
    return any(k.mixer == "none" and k.ffn == "moe" for k in cfg.layer_kinds)


def ffn_stats_len(cfg) -> int:
    return 5 + (ROW_STATS if counts_rows(cfg) else 0)


def stats_len(cfg) -> int:
    """Counters a tick of ``cfg`` returns: the five routing counters, where
    it has a state pool :data:`GDN_STATS` more, and where its expert layers
    count rows (:func:`counts_rows`) :data:`ROW_STATS` more."""
    return ffn_stats_len(cfg) + (GDN_STATS if kv_cache.state_spec(cfg) is not None else 0)


def state_counters(cfg) -> tuple:
    """``tick_stats()``'s names for the :data:`GDN_STATS` counters, after the
    state pool's mixer."""
    mixer = next(k.mixer for k in cfg.layer_kinds if k.pool == "state")
    return (mixer + "_chunk_tokens", mixer + "_step_rows")


def _merge_stats(a, b):
    return jnp.stack([a[0] + b[0], a[1] + b[1], jnp.maximum(a[2], b[2]), a[3] + b[3],
                      a[4] + b[4]] + [a[i] + b[i] for i in range(5, a.shape[0])])


_EXPERT_LEAVES = ("wg", "wi", "wo")


def _walk(cfg, layers, carry, layer_fn):
    """Walk the plan: ``layer_fn(carry, layer_params, kind, pool_index) ->
    carry`` over every layer, one ``lax.scan`` per run of equal layers (a
    run of one is a plain call). An expert kind's expert weights do not ride
    the scan as sliced inputs (a slice handed to the grouped-matmul kernel
    is a copy of the layer's experts, every tick): ``layer_params["mlp"]``
    carries the kind's whole stacks and ``"layer"``, the index into them."""
    for run in runs(cfg):
        stack = layers[run.kind.name]
        experts = {}
        if run.kind.ffn == "moe":
            experts = {n: stack["mlp"][n] for n in _EXPERT_LEAVES if n in stack["mlp"]}
            stack = dict(stack, mlp={n: p for n, p in stack["mlp"].items()
                                     if n not in _EXPERT_LEAVES})

        def one(c, layer_p, kind_index, pool_index, kind=run.kind, experts=experts):
            if experts:
                layer_p = dict(layer_p, mlp=dict(layer_p["mlp"], layer=kind_index, **experts))
            return layer_fn(c, layer_p, kind, pool_index)

        if run.n == 1:
            carry = one(carry, jax.tree.map(lambda p: p[run.kind_start], stack),
                        jnp.int32(run.kind_start), jnp.int32(run.pool_start))
            continue
        steps = jnp.arange(run.n, dtype=jnp.int32)
        if run.n != layers_of(cfg, run.kind):
            # one run of several of its kind (a period that repeats): each step reads its
            # layer out of the kind's whole stack, where a slice of the run would be a copy of
            # the run's weights, every tick
            def step(c, inp, stack=stack):
                at = lambda p: jax.lax.dynamic_index_in_dim(p, inp[0], 0, keepdims=False)
                return one(c, jax.tree.map(at, stack), *inp), None

            carry, _ = jax.lax.scan(step, carry, (run.kind_start + steps, run.pool_start + steps))
            continue
        carry, _ = jax.lax.scan(lambda c, inp: (one(c, *inp), None), carry,
                                (stack, run.kind_start + steps, run.pool_start + steps))
    return carry


def _embed(params, cfg, tokens, dtype):
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(dtype)
    return x if cfg.embed_scale == 1.0 else x * jnp.asarray(cfg.embed_scale, dtype)


def _add(x, out, cfg):
    """The residual stream plus what a mixer or an FFN gives, times the model's multiplier."""
    return x + (out if cfg.residual_scale == 1.0 else out * jnp.asarray(cfg.residual_scale, out.dtype))


def _norm_as(scope, x, scale, cfg):
    """``tf._norm`` under ``scope`` in its own scope's place (the innermost
    scope of an op's path is the one a trace is read by)."""
    with jax.named_scope(scope):
        return _tf()._norm.__wrapped__(x, scale, None, cfg)


def _post(out, layer_p, name, cfg):
    """What a sublayer returns, through the sandwich's second norm where the
    model has one (``ln1_post`` / ``ln2_post``)."""
    if cfg.norm_position != "sandwich":
        return out
    return _norm_as(Scope.NORM_POST, out, layer_p[name]["scale"], cfg)


def _pass_slot(per_pass, step, pool_index):
    """Where layer ``pool_index`` (within its pool, within a pass) of pass
    ``step`` keeps its keys and values: every pass has ``per_pass`` layers of
    the pool to itself. Looked up when a program is traced (a test and
    ``tools/ouro_cell_variant.py`` plant faults here)."""
    return step * per_pass + pool_index


def _passes(cfg, params, carry, one_pass, keep_states=False):
    """``one_pass(carry, step) -> carry`` (a tuple, ``carry[0]`` the residual
    stream) ``cfg.loop_steps`` times over the same weights as ONE
    ``lax.scan``, the final norm after every pass. Returns (carry, the
    normed state every pass ended in, stacked, if ``keep_states``). A plan
    walked once is a plain call with ``step`` None and no norm here: its
    caller's head norms."""
    if cfg.loop_steps == 1:
        return one_pass(carry, None), None

    def body(c, step):
        c = one_pass(c, step)
        x = _norm_as(Scope.LOOP_NORM, c[0], params["final_norm"]["scale"], cfg)
        return (x,) + tuple(c[1:]), (x if keep_states else None)

    return jax.lax.scan(body, carry, jnp.arange(cfg.loop_steps, dtype=jnp.int32))


def exit_pdf(params, states):
    """The exit gate's distribution over the passes: ``states`` (T, ..., D),
    the normed state each pass ended in -> (..., T) float32. ``lambda_t =
    sigmoid(w . x_t + b)``; pass t < T exits with ``lambda_t prod_{j<t} (1 -
    lambda_j)``, the last takes what is left."""
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(jnp.einsum("t...d,d->t...", states.astype(jnp.float32),
                                    gate["w"].astype(jnp.float32)) + gate["b"].astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    pdf = jnp.concatenate([(lam * before)[:-1], stay[-2:-1]])
    return jnp.moveaxis(pdf, 0, -1)


def _logits(x, params, cfg):
    logits = _tf()._vocab_head(x, params, cfg, cfg.jnp_dtype)
    return logits if cfg.logit_scale == 1.0 else logits * jnp.asarray(cfg.logit_scale, logits.dtype)


def _head(x, params, cfg):
    return _logits(_tf()._norm(x, params["final_norm"]["scale"], None, cfg), params, cfg)


# ---------------------------------------------------------------------------
# no cache: training and the reference comparison
# ---------------------------------------------------------------------------

def _takes_flash(cfg, kind) -> bool:
    """Does an attention kind's training forward go through the flash
    kernels (``cfg.attn_impl == "pallas"``)? By what the call can see: the
    training kernels have no sink logit and one head width for keys and
    values, so a kind with either keeps the masked einsum."""
    return cfg.attn_impl == "pallas" and not kind.sink and cfg.head_dim == cfg.v_head_dim


def forward_plan(params, cfg, tokens, return_hidden=False, return_exit=False, return_stats=False):
    """tokens (B, S) -> (logits (B, S, V), 0.0): whole sequences, attention
    by the flash kernels under ``attn_impl="pallas"`` (:func:`_takes_flash`)
    and by masked einsum otherwise, the expert layers' grouped matmul by
    ``ragged_dot``, which has a gradient. ``return_exit`` (a looped model): a
    further result, the exit gate's distribution over the passes (B, S, T).
    ``return_stats``: a further result, the five routing counters of
    :func:`_ffn` merged over the expert layers as a tick's are ((5,) int32)."""
    tf = _tf()
    dtype = cfg.jnp_dtype
    B, S = tokens.shape
    with jax.named_scope(Scope.EMBED):
        x = _embed(params, cfg, tokens, dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S)).reshape(-1)
    qpos = jnp.arange(S, dtype=jnp.int32)[:, None]
    kpos = jnp.arange(S, dtype=jnp.int32)[None, :]

    def mix(h, layer_p, kind):
        if kind.mixer == "gdn":
            return _gdn_plain(h, layer_p["gdn"], cfg, B, S)
        if kind.mixer == "ssm":
            return _ssm_plain(h, layer_p["ssm"], cfg, B, S)
        if kind.mixer == "mla":
            return _mla_plain(h, layer_p["mla"], kind, cfg, B, S, positions)
        if kind.mixer == "conv":
            return _conv_plain(h, layer_p["conv"], cfg, B, S)
        q, k, v = _project(h, layer_p["attn"], kind, cfg, positions)
        if _takes_flash(cfg, kind):
            with jax.named_scope(Scope.ATTN_WINDOW if kind.window else Scope.ATTN_FULL):
                att = tf._flash_sharded(*(a.reshape(B, S, *a.shape[1:]) for a in (q, k, v)), cfg,
                                        causal=True, window=kind.window or None)
            return _attn_out(att.reshape(B * S, -1), h, layer_p["attn"], cfg)
        ok = kpos <= qpos
        if kind.window:
            ok = ok & (qpos - kpos < kind.window)
        with jax.named_scope(Scope.ATTN_WINDOW if kind.window else Scope.ATTN_FULL):
            att = _grouped_attention(
                q.reshape(B, S, *q.shape[1:]),
                k.reshape(B, S, *k.shape[1:]).transpose(0, 2, 1, 3),
                v.reshape(B, S, *v.shape[1:]).transpose(0, 2, 1, 3),
                ok[None], layer_p["attn"].get("sink"), _scale(cfg))
        return _attn_out(att.reshape(B * S, -1), h, layer_p["attn"], cfg)

    def layer(carry, layer_p, kind, _):   # carry: (x,), or (x, the counters so far) where they are asked for
        x = carry[0]
        if kind.mixer != "none":
            h = tf._norm(x, layer_p["ln1"]["scale"], None, cfg).reshape(B * S, -1)
            x = _add(x, _post(mix(h, layer_p, kind), layer_p, "ln1_post", cfg).reshape(B, S, -1), cfg)
        if kind.ffn == "none":   # the mixer alone: one norm, one residual add
            return (x,) + tuple(carry[1:])
        h = tf._norm(x, layer_p["ln2"]["scale"], None, cfg).reshape(B * S, -1)
        out, st = _ffn(h, layer_p["mlp"], kind, cfg, None, grad=True)
        x = _add(x, _post(out, layer_p, "ln2_post", cfg).reshape(B, S, -1), cfg)
        return (x, _merge_stats(carry[1], st)) if return_stats else (x,)

    if cfg.remat:
        layer = jax.checkpoint(layer, policy=tf._resolve_remat_policy(cfg.remat_policy),
                               static_argnums=(2,))
    layers = tf._cast_layers(params["layers"], dtype)
    carry = (x, jnp.zeros((ffn_stats_len(cfg),), jnp.int32)) if return_stats else (x,)
    (x, *stats), states = _passes(cfg, params, carry, lambda c, step: _walk(cfg, layers, c, layer),
                                  keep_states=return_exit)
    if cfg.loop_steps == 1:
        x = tf._norm(x, params["final_norm"]["scale"], None, cfg)
    extra = (exit_pdf(params, states),) if return_exit else ()
    extra += tuple(st[:5] for st in stats)   # the five routing counters (counter_names)
    if return_hidden:
        return (x, jnp.float32(0.0)) + extra
    return (_logits(x, params, cfg), jnp.float32(0.0)) + extra


# ---------------------------------------------------------------------------
# the serving tick
# ---------------------------------------------------------------------------

# the pools' window and write, in the order this attention contracts them
_window = partial(kv_cache.window, heads_first=True)
_write = partial(kv_cache.write, heads_first=True)
# kept, and looked up when a tick is traced: tools/mimo_cell_variant.py and
# tests/benchmark/test_mimo_cell_variant.py replace it by assignment
_write_rows = _write


def _attend_cached(q, k, v, attn_p, kind, cfg, pk, pv, layer, pos, chunk, read_len, length):
    """Attention of one layer of the tick: the B rows' single tokens against
    their pool rows and, with ``chunk``, the chunk's W tokens against its
    row. q/k/v hold the rows first, then the chunk. Returns ((N, nh * dv),
    pool_k, pool_v)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_chunk

    B = pos.shape[0]
    sink, scale, R = attn_p.get("sink"), _scale(cfg), kind.window
    size = R or read_len or length
    with jax.named_scope(Scope.ATTN_KV_WRITE):
        # a parked row (pos = the pool's length) writes nothing in either pool
        cols = jnp.where(pos >= length, size, pos % R) if R else pos
        pk = _write_rows(pk, layer, k[:B], cols, size)
        pv = _write_rows(pv, layer, v[:B], cols, size)
    with jax.named_scope(Scope.ATTN_KV_READ):
        kw, vw = _window(pk, layer, size), _window(pv, layer, size)
        slot = jnp.arange(size, dtype=jnp.int32)[None, :]
        # the position a ring slot holds once this token is written: the largest one not
        # past the row's depth that falls in the slot; below zero, nothing was written
        kpos = pos[:, None] - ((pos[:, None] - slot) % R) if R else slot
    with jax.named_scope(Scope.ATTN_WINDOW if R else Scope.ATTN_FULL):
        ok = (kpos <= pos[:, None]) & (kpos >= 0)
        rows = _grouped_attention(q[:B, None], kw, vw, ok[:, None, :], sink, scale)[:, 0]
    if chunk is None:
        return rows.reshape(B, -1), pk, pv

    qc, kc, vc = q[B:], k[B:], v[B:]
    W = qc.shape[0]
    real = chunk.pos < length
    first = chunk.pos[0]
    if R:
        with jax.named_scope(Scope.ATTN_KV_READ):
            # the window before the chunk, in order of position: position first - R + j
            # lives in slot (first + j) mod R
            tail_k = jnp.roll(_window(pk, layer, R, slot=chunk.slot), -(first % R), axis=1)
            tail_v = jnp.roll(_window(pv, layer, R, slot=chunk.slot), -(first % R), axis=1)
        with jax.named_scope(Scope.ATTN_WINDOW):
            out = flash_attention_chunk(
                qc, jnp.concatenate([tail_k, kc.transpose(1, 0, 2)], axis=1),
                jnp.concatenate([tail_v, vc.transpose(1, 0, 2)], axis=1), q_off=R,
                k_min=jnp.maximum(R - first, 0), sink=sink, window=R, sm_scale=scale)
        with jax.named_scope(Scope.ATTN_KV_WRITE):
            last = first + real.sum(dtype=jnp.int32)
            cols = jnp.where(real & (chunk.pos >= last - R), chunk.pos % R, R)
            pk = _write(pk, layer, kc, cols, R, slot=chunk.slot)
            pv = _write(pv, layer, vc, cols, R, slot=chunk.slot)
    else:
        width = min(W, size)
        with jax.named_scope(Scope.ATTN_KV_WRITE):
            start = jnp.clip(first, 0, size - width)
            cols = jnp.where(real, chunk.pos - start, width)
            pk = _write(pk, layer, kc, cols, width, slot=chunk.slot, start=start)
            pv = _write(pv, layer, vc, cols, width, slot=chunk.slot, start=start)
        with jax.named_scope(Scope.ATTN_KV_READ):
            row_k = _window(pk, layer, size, slot=chunk.slot)
            row_v = _window(pv, layer, size, slot=chunk.slot)
        with jax.named_scope(Scope.ATTN_FULL):
            out = flash_attention_chunk(qc, row_k, row_v, q_off=first, sink=sink, sm_scale=scale)
    return jnp.concatenate([rows.reshape(B, -1), out.reshape(W, -1)]), pk, pv


def forward_plan_cached(params, cfg, tokens, pos, cache, read_len: Optional[int] = None,
                        chunk: Optional[Chunk] = None):
    """One tick of a layer-plan model. tokens (B,), pos (B,): every slot's
    next token and the count of tokens its row has cached (a parked row
    carries the pool's length: it writes nothing and its output means
    nothing). ``chunk``: ONE row's next prefill chunk, run beside the rows as
    part of the same flat list of tokens; the row it belongs to is parked
    among the rows, and its logits (at column ``chunk.emit``) take that
    row's place in the output. ``read_len`` (static) tight-reads the full
    pool. Returns (logits (B, V), cache, stats (:func:`stats_len`,) int32:
    expert assignments made / to held experts / the most one held expert got
    in a layer / expert layers / held experts that got a token, summed over
    the layers; with a state pool also the real tokens the chunk's scan
    took and the rows whose state this tick stepped; where the expert layers
    count rows, :data:`ROW_STATS` last)."""
    tf = _tf()
    kv_cache.refuse_unserved(cfg)
    dtype = cfg.jnp_dtype
    B = tokens.shape[0]
    length = kv_cache.alloc_len(cfg, cache)
    if read_len is not None and read_len >= length:
        read_len = None
    all_toks, all_pos = tokens, pos
    if chunk is not None:
        all_toks = jnp.concatenate([tokens, chunk.toks])
        all_pos = jnp.concatenate([pos, chunk.pos])
    with jax.named_scope(Scope.EMBED):
        x = _embed(params, cfg, all_toks, dtype)
    valid = all_pos < length

    per_pass = {s.name: s.layers // cfg.loop_steps for s in kv_cache.specs(cfg)}

    def layer(carry, layer_p, kind, pool_index, step):
        x, pools, stats = carry
        if kind.mixer == "none":   # the FFN alone: one norm, one residual add, no row in any pool
            h = tf._norm(x, layer_p["ln2"]["scale"], None, cfg)
            out, st = _ffn(h, layer_p["mlp"], kind, cfg, valid, grad=False)
            return _add(x, _post(out, layer_p, "ln2_post", cfg), cfg), pools, _merge_stats(stats, st)
        pool = pools[kind.pool]
        if step is not None:   # a looped plan: this pass's own layer-step of the pool
            pool_index = _pass_slot(per_pass[kind.pool], step, pool_index)
        h = tf._norm(x, layer_p["ln1"]["scale"], None, cfg)
        if kind.mixer == "gdn":
            out, pool = _gdn_cached(h, layer_p["gdn"], cfg, pool, pool_index, B, chunk, valid)
        elif kind.mixer == "ssm":
            out, pool = _ssm_cached(h, layer_p["ssm"], cfg, pool, pool_index, B, chunk, valid)
        elif kind.mixer == "mla":
            out, leaf = _mla_cached(h, layer_p["mla"], kind, cfg, pool["c"], pool_index, pos,
                                    all_pos, chunk, read_len, length)
            pool = {"c": leaf}
        else:
            q, k, v = _project(h, layer_p["attn"], kind, cfg, all_pos, product=tf._heads_product)
            att, pk, pv = _attend_cached(q, k, v, layer_p["attn"], kind, cfg, pool["k"], pool["v"],
                                         pool_index, pos, chunk, read_len, length)
            out, pool = _attn_out(att, h, layer_p["attn"], cfg), {"k": pk, "v": pv}
        x = _add(x, _post(out, layer_p, "ln1_post", cfg), cfg)
        if kind.ffn == "none":   # the mixer alone
            return x, dict(pools, **{kind.pool: pool}), stats
        h = tf._norm(x, layer_p["ln2"]["scale"], None, cfg)
        out, st = _ffn(h, layer_p["mlp"], kind, cfg, valid, grad=False)
        return (_add(x, _post(out, layer_p, "ln2_post", cfg), cfg), dict(pools, **{kind.pool: pool}),
                _merge_stats(stats, st))

    layers = tf._cast_layers(params["layers"], dtype)
    (x, cache, stats), _ = _passes(
        cfg, params, (x, cache, jnp.zeros((ffn_stats_len(cfg),), jnp.int32)),
        lambda c, step: _walk(cfg, layers, c, partial(layer, step=step)))
    if kv_cache.state_spec(cfg) is not None:
        state = jnp.stack([valid[B:].sum(dtype=jnp.int32), valid[:B].sum(dtype=jnp.int32)])
        stats = jnp.concatenate([stats[:5], state, stats[5:]] if counts_rows(cfg) else [stats, state])
    rows = x[:B]
    if chunk is not None:  # the admitting row's place is taken by the chunk's sampled column
        rows = jax.lax.dynamic_update_slice(rows, x[B + chunk.emit][None], (chunk.slot, 0))
    # a looped plan's last pass ended in the final norm already
    return (_head if cfg.loop_steps == 1 else _logits)(rows, params, cfg), cache, stats
