"""GLM-4.7-Flash's language model in plain float32 ``jax.numpy``: forward,
loss, gradients. Written from ``config.json`` (``model_type``
``glm4_moe_lite``:
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json) and the
papers its keys name (DeepSeek-V2 for the latent attention, DeepSeek-V3 for
the routing); nothing here is shared with the program (no kernel, no cache,
no absorbed projections, no sorting of tokens by expert, no layer plan).
Callers run it under ``jax.default_matmul_precision("highest")``.

**Equations.** ``norm`` is RMSNorm (eps ``rms_norm_eps``, a learned scale, no
bias anywhere). Every layer is ``x += MLA(norm(x))``; ``x += FFN_l(norm(x))``;
then a final norm and an untied output head.

- MLA (``num_attention_heads`` heads, ``q_lora_rank``, ``kv_lora_rank``,
  ``qk_nope_head_dim`` dn, ``qk_rope_head_dim`` dr, ``v_head_dim`` dv):
  ``c_q = norm(h W_DQ)``; a head's ``[q_nope | q_rope] = c_q W_UQ`` (dn | dr);
  ``[c_raw | r_raw] = h W_DKV`` (``kv_lora_rank`` | dr); ``c = norm(c_raw)``;
  ``r = RoPE(r_raw)``, ONE rotated key shared by all heads; ``q_rope <-
  RoPE(q_rope)``; theta ``rope_theta`` over all dr dims, half-split pairing,
  no scaling; a head's ``[k_nope | v] = c W_UKV`` (dn | dv);
  ``s(t, j) = (q_nope(t) . k_nope(j) + q_rope(t) . r(j)) / sqrt(dn + dr)``,
  causal softmax ``p``; ``o = concat_heads(sum_j p(t, j) v(j)) W_O``.
  This is the EXPANDED form: every head's keys and values are made from the
  latent, and attention is ordinary. (The program's decoding rows use the
  absorbed form, the same function, and are checked against this one.)
- FFN of the first ``first_k_dense_replace`` layers: ``W_o (silu(W_g h) *
  W_i h)``, width ``intermediate_size``.
- FFN of the later layers: ``s = sigmoid(h W_r)`` in float32, one score an
  expert; ``T`` = the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the
  selection bias of ``topk_method`` noaux_tc: it chooses and enters nothing
  else; ``n_group`` = ``topk_group`` = 1, so no group limit);
  ``w_e = routed_scaling_factor x s_e / sum_{e in T} s_e``
  (``norm_topk_prob``); ``y = sum_{e in T and HELD} w_e SwiGLU_e(h) +
  SwiGLU_shared(h)``, the shared expert added with no gate and no factor.
  HELD is the contiguous share ``deployment.held_experts`` names (in the
  committed configuration: every expert).

``Arch.without`` names pieces to leave out or change (``routed_scale`` (the
factor 1), ``shared``, ``rope_key`` (the shared key's score left out),
``kv_norm``, ``q_norm``, ``scale_576`` (scores / sqrt(kv_lora_rank + dr)),
``bias`` (the selection bias left out of the choice)): what a program that
dropped the piece would compute; the tests use it, the benchmark never.

**Departures from the published implementation**, none changing the
mathematics above; the configuration file's ``assumed`` lists what
``config.json`` does not say: the rotary pairing is half-split (the published
interleaved form is a permutation of W_UQ's and W_DKV's rotary columns);
``W_UQ``'s and ``W_UKV``'s columns are grouped by head; the
multi-token-prediction module (``num_nextn_predict_layers``) is not here.

**For memory, not mathematics:** rows of a batch one at a time, a row's
queries in blocks of 128, each layer's weights cast to float32 when the layer
runs and its experts one at a time; every held expert is applied to every
token and weighed by w_e or zero.

The parameter tree is the model's own: ``embed.tok``, ``final_norm.scale``,
``lm_head.w`` and ``layers.dense`` / ``layers.moe``, each kind's layers
stacked in model order: ``mla.{wdq, q_norm, wuq, wdkv, kv_norm, wukv, wo}``,
``ln1/ln2.scale``, ``mlp.{wg, wi, wo}`` (an expert kind: a leading axis over
the held experts, and ``mlp.{gate, gate_bias, shared_wg, shared_wi,
shared_wo}``).
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2
from benchmark.reference.gpt2 import FAULTS, adamw, global_norm  # noqa: F401  (the interface)

F32 = jnp.float32
QUERY_BLOCK = 128


class Arch(NamedTuple):
    n_heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    eps: float
    dense_layers: int
    n_layers: int
    top_k: int
    routed_scale: float
    held_first: int
    held_count: int
    without: tuple = ()


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    held = config["deployment"]["held_experts"]
    return Arch(
        int(m["num_attention_heads"]), int(m["kv_lora_rank"]), int(m["qk_nope_head_dim"]),
        int(m["qk_rope_head_dim"]), int(m["v_head_dim"]), float(m["rope_theta"]),
        float(m["rms_norm_eps"]), int(m["first_k_dense_replace"]), int(m["num_hidden_layers"]),
        int(m["num_experts_per_tok"]), float(m["routed_scaling_factor"]),
        int(held["first"]), int(held["count"]))


def _as_is(x):
    return x


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _rotate(x, theta):
    """x (S, heads, d): each pair (i, i + d/2) turned by position x theta^(-2i/d)."""
    S, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mla(h, w, ar: Arch, r):
    """h (S, D) -> (S, D), the expanded form."""
    S = h.shape[0]
    nh, kr, dn, dr, dv = ar.n_heads, ar.kv_rank, ar.nope, ar.rope, ar.v_dim
    cq = r(h) @ r(w["wdq"])
    if "q_norm" not in ar.without:
        cq = _norm(cq, w["q_norm"], ar.eps)
    q = (r(cq) @ r(w["wuq"])).reshape(S, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rotate(q[..., dn:], ar.theta)
    ckv = r(h) @ r(w["wdkv"])
    c = ckv[:, :kr]
    if "kv_norm" not in ar.without:
        c = _norm(c, w["kv_norm"], ar.eps)
    key = _rotate(ckv[:, None, kr:], ar.theta)[:, 0]                      # (S, dr): all heads'
    kv = (r(c) @ r(w["wukv"])).reshape(S, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = math.sqrt(kr + dr) if "scale_576" in ar.without else math.sqrt(dn + dr)
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, start, qb)
        s = jnp.einsum("qhd,thd->hqt", r(take(q_nope)), r(k_nope))
        if "rope_key" not in ar.without:
            s = s + jnp.einsum("qhd,td->hqt", r(take(q_rope)), r(key))
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s / scale, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thd->qhd", r(p), r(v)).reshape(qb, nh * dv)

    o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, nh * dv)
    return r(o) @ r(w["wo"])


def _swiglu(h, wg, wi, wo, r):
    return r(_silu(r(h) @ r(wg)) * (r(h) @ r(wi))) @ r(wo)


def _experts(h, m, ar: Arch, r):
    """h (S, D) -> the held experts' part of the layer's output plus the shared expert's."""
    scores = 1.0 / (1.0 + jnp.exp(-(r(h) @ r(m["gate"].astype(F32)))))          # (S, E)
    choose = scores if "bias" in ar.without else scores + m["gate_bias"].astype(F32)
    _, chosen = jax.lax.top_k(choose, ar.top_k)
    picked = (chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(1)
    weights = jnp.where(picked, scores, 0.0)
    weights = weights / weights.sum(-1, keepdims=True)
    if "routed_scale" not in ar.without:
        weights = ar.routed_scale * weights

    def one(y, expert):
        wg, wi, wo, e = expert
        return y + weights[:, e, None] * _swiglu(h, wg.astype(F32), wi.astype(F32),
                                                 wo.astype(F32), r), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        m["wg"], m["wi"], m["wo"], ar.held_first + jnp.arange(ar.held_count)))
    if "shared" not in ar.without:
        y = y + _swiglu(h, m["shared_wg"], m["shared_wi"], m["shared_wo"], r)
    return y


def _row_hidden(params, tokens, ar: Arch, remat, r):
    """tokens (S,) -> final-normed hidden states (S, D)."""
    x = params["embed"]["tok"][tokens].astype(F32)
    for l in range(ar.n_layers):
        moe = l >= ar.dense_layers
        name, i = ("moe", l - ar.dense_layers) if moe else ("dense", l)

        def layer(x, w, moe=moe):
            # the experts stay as stored until their turn; the rest of the layer in float32
            experts = {n: w["mlp"][n] for n in ("wg", "wi", "wo")} if moe else {}
            w = jax.tree.map(lambda a: a.astype(F32), dict(w, mlp={
                n: a for n, a in w["mlp"].items() if n not in experts}))
            x = x + _mla(_norm(x, w["ln1"]["scale"], ar.eps), w["mla"], ar, r)
            h = _norm(x, w["ln2"]["scale"], ar.eps)
            if moe:
                return x + _experts(h, dict(w["mlp"], **experts), ar, r)
            return x + _swiglu(h, w["mlp"]["wg"], w["mlp"]["wi"], w["mlp"]["wo"], r)

        w = jax.tree.map(lambda a: a[i], params["layers"][name])
        x = (jax.checkpoint(layer) if remat else layer)(x, w)
    return _norm(x, params["final_norm"]["scale"], ar.eps)


def hidden(params, tokens, arch, remat=False, operand=_as_is):
    """tokens (B, S) -> final-normed hidden states (B, S, D), float32; the
    rows one at a time."""
    return jax.lax.map(lambda row: _row_hidden(params, row, arch, remat, operand), tokens)


def logits_at(params, tokens, at, arch, operand=_as_is):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S)."""
    x = jnp.take_along_axis(hidden(params, tokens, arch, operand=operand), at[:, :, None], axis=1)
    return operand(x) @ operand(params["lm_head"]["w"].astype(F32))


def loss_sum(params, tokens, arch, weights=None, operand=_as_is):
    """Summed next-token cross-entropy over the B * (S - 1) predicted
    positions; with ``weights`` (B,), (weighted, plain) as ``gpt2.loss_sum``."""
    x = hidden(params, tokens, arch, remat=True, operand=operand)[:, :-1]
    logits = operand(x) @ operand(params["lm_head"]["w"].astype(F32))
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=2)[..., 0]
    nll = (jax.nn.logsumexp(logits, axis=-1) - picked).sum(-1)
    if weights is None:
        return nll.sum()
    return (nll * weights).sum(), nll.sum()


def loss_and_grads(params, tokens, arch, rows_per_pass, skip_rows=0, row_sharding=None,
                   loss_sum=loss_sum):
    return gpt2.loss_and_grads(params, tokens, arch, rows_per_pass, skip_rows, row_sharding,
                               loss_sum=loss_sum)


def train(params, tokens, arch, steps, optimizer, rows_per_pass, fault=None,
          out_shardings=None, row_sharding=None, norm=global_norm,
          loss_and_grads=loss_and_grads):
    return gpt2.train(params, tokens, arch, steps, optimizer, rows_per_pass, fault,
                      out_shardings, row_sharding, norm=norm, loss_and_grads=loss_and_grads)
