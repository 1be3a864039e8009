"""LFM2-24B-A2B's language model in plain float32 ``jax.numpy``: forward,
loss, gradients. Written from ``config.json`` (``model_type`` ``lfm2_moe``:
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json) and what
its keys name (LFM2's gated short convolution; DeepSeek-V3's sigmoid routing
with a selection bias); nothing here is shared with the program (no kernel,
no sorting of tokens by expert, no ragged product, no layer plan, none of its
remat policies). Callers run it under
``jax.default_matmul_precision("highest")``.

**Equations.** ``norm`` is RMSNorm (eps ``norm_eps``, a learned scale, no
bias anywhere). Layer i is ``x += Mixer_i(norm(x))`` (``operator_norm``);
``x += FFN_i(norm(x))`` (``ffn_norm``); after the last layer a norm
(``embedding_norm``) and the output head, TIED to the token embedding. The
loss is the mean next-token cross-entropy over the vocabulary the
configuration keeps.

- Mixer, ``layer_types[i] == "conv"`` (``conv_L_cache`` K taps, no bias):
  ``[b | c | u] = h W_in`` (D -> 3D, split in that order); ``v = b * u``;
  ``z_t = sum_{j<K} w_j * v_{t-(K-1)+j}``, ``v`` zero before a row's first
  token (causal, depthwise: a weight (D, K), NO activation);
  ``y = (c * z) W_out``.
- Mixer, ``"full_attention"``: ``num_attention_heads`` query and
  ``num_key_value_heads`` key-value heads (query head h reads key-value head
  h // (heads / key-value heads)); RMSNorm over each head's width of q and of
  k (a weight a head width long each) BEFORE the rotation; rotary over the
  whole head, half-split pairing, theta ``rope_theta``; causal softmax at
  1 / sqrt(head width); ``W_o``.
- FFN of the first ``num_dense_layers`` layers: ``W_o (silu(W_g h) * W_i h)``,
  width ``intermediate_size``.
- FFN of the later layers: ``s = sigmoid(h W_r)`` in float32, one score for
  each of the PUBLISHED experts; ``T`` = the ``num_experts_per_tok`` largest
  of ``s + b`` (``use_expert_bias``: ``b`` chooses and enters nothing else);
  ``w_e = routed_scaling_factor x s_e / (sum_{e in T} s_e + 1e-6)``
  (``norm_topk_prob``); ``y = sum_{e in T and HELD} w_e SwiGLU_e(h)``, each
  expert of width ``moe_intermediate_size``; no shared expert, no auxiliary
  loss. HELD is the contiguous share ``deployment.held_experts`` names; what
  the absent experts would add is left out.

``Arch.without`` names pieces to leave out or change (``swap_bc`` (the two
gates exchanged), ``taps_reversed``, ``qk_norm``, ``bias`` (the selection
bias left out of the choice), ``bias_in_weights`` (the weights taken from
``s + b``), ``norm_eps`` (the 1e-6 left out)): what a program that got the
piece wrong would compute; the tests use it, the benchmark never.
``chosen=`` (``routing``, ``experts``, ``hidden``, ``loss_sum``) takes each
token's choice of experts from outside: what the reference computes once it
agrees on the choice with another computation, whose rounding chose
otherwise for a few tokens in a hundred (PERF.md section 6, PR 48: how the
expert leaves' gradient gap was traced to the choice); the benchmark never.

**For memory, not mathematics:** rows of a batch one at a time, every layer
under ``jax.checkpoint``, a row's queries in blocks of 256 each under
``jax.checkpoint`` of its own (whole, the float32 scores of one row of 8,192
are 8.6 GB), a row's tokens through an FFN in blocks of 1,024 likewise (whole,
the dense FFN's float32 temporaries of one row are 3 GB and the reference's
training step needed 16.2 of the chip's 16.9 GB), each layer's weights cast to float32 when the layer runs, its
experts one at a time; every held expert is applied to every token and
weighed by w_e or zero.

The parameter tree is the model's own: ``embed.tok``, ``final_norm.scale``
and ``layers.<mixer>_<ffn>`` (``conv_dense``, ``attn_dense``, ``conv_moe``,
``attn_moe``; only those the plan has), each kind's layers stacked in model
order: ``ln1/ln2.scale``, ``conv.{win, conv, wo}`` or ``attn.{wq, wk, wv, wo,
q_norm, k_norm}``, ``mlp.{wg, wi, wo}`` (an expert kind: a leading axis over
the held experts, and ``mlp.{gate, gate_bias}``).
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2
from benchmark.reference.gpt2 import FAULTS, adamw, global_norm  # noqa: F401  (the interface)

F32 = jnp.float32
QUERY_BLOCK = 256
TOKEN_BLOCK = 1024
MIXERS = {"conv": "conv", "full_attention": "attn"}


class Arch(NamedTuple):
    n_heads: int
    n_kv_heads: int
    theta: float
    eps: float
    layer_types: tuple     # "conv" | "full_attention", layer by layer
    dense_layers: int
    top_k: int
    routed_scale: float
    weight_eps: float      # the published 1e-6 in the weights' denominator
    held_first: int
    held_count: int
    without: tuple = ()


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    held = config["deployment"]["held_experts"]
    return Arch(
        int(m["num_attention_heads"]), int(m["num_key_value_heads"]),
        float(m["rope_parameters"]["rope_theta"]),
        float(m["norm_eps"]), tuple(m["layer_types"]), int(m["num_dense_layers"]),
        int(m["num_experts_per_tok"]), float(m["routed_scaling_factor"]), 1e-6,
        int(held["first"]), int(held["count"]))


def kind_of(ar: Arch, i):
    """(the name of layer i's stack in the parameter tree, its index there)."""
    name = lambda j: MIXERS[ar.layer_types[j]] + ("_dense" if j < ar.dense_layers else "_moe")
    return name(i), sum(name(j) == name(i) for j in range(i))


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


def conv_mixer(h, w, ar: Arch, r=_as_is):
    """h (S, D) -> (S, D): the gated short convolution."""
    S, D = h.shape
    K = w["conv"].shape[1]
    b, c, u = jnp.split(r(h) @ r(w["win"]), 3, axis=1)
    if "swap_bc" in ar.without:
        b, c = c, b
    v = jnp.concatenate([jnp.zeros((K - 1, D), F32), b * u])
    taps = w["conv"][:, ::-1] if "taps_reversed" in ar.without else w["conv"]
    z = sum(taps[:, j] * v[j:j + S] for j in range(K))     # z_t = sum_j w_j v_{t-(K-1)+j}
    return r(c * z) @ r(w["wo"])


def attention(h, w, ar: Arch, r=_as_is):
    """h (S, D) -> (S, D): grouped-query attention, a block of queries at a time."""
    S = h.shape[0]
    nh, nkv = ar.n_heads, ar.n_kv_heads
    q = (r(h) @ r(w["wq"])).reshape(S, nh, -1)
    k = (r(h) @ r(w["wk"])).reshape(S, nkv, -1)
    v = (r(h) @ r(w["wv"])).reshape(S, nkv, -1)
    hd = q.shape[-1]
    if "qk_norm" not in ar.without:
        q, k = _norm(q, w["q_norm"], ar.eps), _norm(k, w["k_norm"], ar.eps)
    q, k = _rotate(q, ar.theta), _rotate(k, ar.theta)
    q = q.reshape(S, nkv, nh // nkv, hd)     # query head h = (h // group, h % group)
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    @jax.checkpoint
    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        s = jnp.einsum("qngd,tnd->ngqt", r(jax.lax.dynamic_slice_in_dim(q, start, qb)), r(k))
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s / math.sqrt(hd), -jnp.inf), axis=-1)
        return jnp.einsum("ngqt,tnd->qngd", r(p), r(v)).reshape(qb, nh * hd)

    o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, nh * hd)
    return r(o) @ r(w["wo"])


def _swiglu(h, wg, wi, wo, r):
    return r(_silu(r(h) @ r(wg)) * (r(h) @ r(wi))) @ r(wo)


def _by_token_blocks(fn, *per_token):
    """``fn(*per_token)`` for a ``fn`` that treats every token alike and arrays
    (S, ...) of one row each token, a block of tokens at a time."""
    S = per_token[0].shape[0]
    if S % TOKEN_BLOCK or S == TOKEN_BLOCK:
        return fn(*per_token)
    blocks = tuple(a.reshape(S // TOKEN_BLOCK, TOKEN_BLOCK, -1) for a in per_token)
    return jax.lax.map(lambda b: jax.checkpoint(fn)(*b), blocks).reshape(S, -1)


def routing(h, m, ar: Arch, r=_as_is, chosen=None):
    """h (S, D) -> (S, E) float32: each token's weight on every published
    expert, zero on those it did not choose. ``chosen`` (S, top_k), where
    given, is the choice (another computation's, fed in: the scores, weights
    and gradients are then what both would have if they agreed on it)."""
    scores = 1.0 / (1.0 + jnp.exp(-(r(h) @ r(m["gate"].astype(F32)))))
    biased = scores + m["gate_bias"].astype(F32)
    if chosen is None:
        _, chosen = jax.lax.top_k(scores if "bias" in ar.without else biased, ar.top_k)
    picked = (chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(1)
    weights = jnp.where(picked, biased if "bias_in_weights" in ar.without else scores, 0.0)
    total = weights.sum(-1, keepdims=True)
    return ar.routed_scale * weights / (total if "norm_eps" in ar.without else total + ar.weight_eps)


def experts(h, m, ar: Arch, r=_as_is, chosen=None):
    """h (S, D) -> the held experts' part of the layer's output."""
    weights = routing(h, m, ar, r, chosen)

    def one(y, expert):
        wg, wi, wo, e = expert
        return y + weights[:, e, None] * _swiglu(h, wg.astype(F32), wi.astype(F32),
                                                 wo.astype(F32), r), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        m["wg"], m["wi"], m["wo"], ar.held_first + jnp.arange(ar.held_count)))
    return y


def _row_hidden(params, tokens, ar: Arch, remat, r, chosen=None):
    """tokens (S,) -> final-normed hidden states (S, D); ``chosen`` (expert
    layers, S, top_k) or None: see ``routing``."""
    x = params["embed"]["tok"][tokens].astype(F32)
    for i, mixer in enumerate(ar.layer_types):
        moe = i >= ar.dense_layers
        name, at = kind_of(ar, i)

        def layer(x, w, fed, mixer=mixer, moe=moe):
            # the experts stay as stored until their turn; the rest of the layer in float32
            held = {n: w["mlp"][n] for n in ("wg", "wi", "wo")} if moe else {}
            w = jax.tree.map(lambda a: a.astype(F32), dict(w, mlp={
                n: a for n, a in w["mlp"].items() if n not in held}))
            h = _norm(x, w["ln1"]["scale"], ar.eps)
            x = x + (conv_mixer(h, w["conv"], ar, r) if mixer == "conv"
                     else attention(h, w["attn"], ar, r))
            h = _norm(x, w["ln2"]["scale"], ar.eps)
            if moe:
                return x + _by_token_blocks(lambda t, c=None: experts(t, dict(w["mlp"], **held), ar, r, c),
                                            *((h,) if fed is None else (h, fed)))
            return x + _by_token_blocks(
                lambda t: _swiglu(t, w["mlp"]["wg"], w["mlp"]["wi"], w["mlp"]["wo"], r), h)

        w = jax.tree.map(lambda a: a[at], params["layers"][name])
        fed = chosen[i - ar.dense_layers] if moe and chosen is not None else None
        x = (jax.checkpoint(layer) if remat else layer)(x, w, fed)
    return _norm(x, params["final_norm"]["scale"], ar.eps)


def hidden(params, tokens, arch, remat=False, operand=_as_is, chosen=None):
    """tokens (B, S) -> final-normed hidden states (B, S, D), float32; the
    rows one at a time. ``chosen`` (B, expert layers, S, top_k) or None."""
    return jax.lax.map(lambda row: _row_hidden(params, row[0], arch, remat, operand, row[1]),
                       (tokens, chosen))


def logits_at(params, tokens, at, arch, operand=_as_is):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S)."""
    x = jnp.take_along_axis(hidden(params, tokens, arch, operand=operand), at[:, :, None], axis=1)
    return operand(x) @ operand(params["embed"]["tok"].astype(F32).T)


def loss_sum(params, tokens, arch, weights=None, operand=_as_is, chosen=None):
    """Summed next-token cross-entropy over the B * (S - 1) predicted
    positions; with ``weights`` (B,), (weighted, plain) as ``gpt2.loss_sum``."""
    x = hidden(params, tokens, arch, remat=True, operand=operand, chosen=chosen)[:, :-1]
    logits = operand(x) @ operand(params["embed"]["tok"].astype(F32).T)
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
