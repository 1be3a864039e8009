"""MiMo-V2's language model in plain float32 ``jax.numpy``: forward, loss,
gradients. Written from ``config.json`` (``model_type`` ``mimo_v2``:
https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json) and the
papers its keys name; nothing here is shared with the program (no kernel, no
cache, no sorting of tokens by expert, no layer plan). Callers run it under
``jax.default_matmul_precision("highest")``.

**Equations.** With ``h = RMSNorm(x)`` (Zhang & Sennrich 2019, eps
``layernorm_epsilon``, a learned scale, no mean, no bias):

- Block: ``x += Attn_kind(RMSNorm(x))``; ``x += FFN_l(RMSNorm(x))``; after the
  last block a final RMSNorm and an output head of its own (untied).
- Attention, kind full (``hybrid_layer_pattern`` 0) or window (1):
  ``q = h Wq -> (heads, head_dim)``, ``k = h Wk -> (kv, head_dim)``,
  ``v = attention_value_scale * (h Wv) -> (kv, v_head_dim)``; ``kv`` is
  ``num_key_value_heads`` (full) / ``swa_num_key_value_heads`` (window) and
  query head i reads key-value head ``i // (heads / kv)`` (Ainslie et al.
  2023). Rotary embedding (Su et al. 2021) in the half-split form on the
  first ``int(partial_rotary_factor * head_dim)`` dims of q and k (dimension
  i turns with dimension i + rot/2 by position x theta^(-2i/rot)), theta
  ``rope_theta`` (full) / ``swa_rope_theta`` (window); the other dims pass.
  Logits ``q . k / sqrt(head_dim)``; full: causal; window: causal and
  ``i - j < sliding_window``. Where ``add_*_attention_sink_bias``, a learned
  scalar ``s_h`` a query head joins the softmax's denominator and brings no
  value: ``p_ij = e^{l_ij} / (e^{s_h} + sum_j e^{l_ij})``.
  ``o = (P v) Wo``, ``Wo: heads * v_head_dim -> hidden``. No biases.
- FFN where ``moe_layer_freq`` is 0: ``Wo (silu(Wg h) * Wi h)``, width
  ``intermediate_size`` (SwiGLU, Shazeer 2020).
- FFN where it is 1: ``s = sigmoid(h Wr)``, one score for each of the
  PUBLISHED experts; ``T`` = the ``num_experts_per_tok`` experts with the
  largest ``s + b`` (``b`` the selection bias of ``topk_method`` noaux_tc:
  it decides the choice and enters nothing else);
  ``w_e = s_e / sum_{e in T} s_e`` (``norm_topk_prob``);
  ``y = sum_{e in T and HELD} w_e Expert_e(h)``, each expert a SwiGLU of width
  ``moe_intermediate_size``. HELD is every expert in the whole model and,
  under a configuration file's ``deployment.held_experts``, the contiguous
  share one chip of an expert-parallel deployment holds: what the absent
  experts would add is left out, here as in the program, and the partial
  result goes on.

**Departures from the published implementation**, none changing the
mathematics above; the assumptions ``config.json`` forces are listed in the
configuration file's ``assumed``:

- ``attention_value_scale`` is applied to v (it is linear: on v, on P v or on
  the output it gives the same o);
- no QK norm, ``attention_chunk_size`` / ``hybrid_block_size`` /
  ``attention_projection_layout`` / ``rope_scaling`` (type default) read as
  changing no equation;
- the vision and audio towers and the MTP layers are not here (no key).

**For memory, not mathematics:** rows of a batch are taken one at a time,
a row's queries in blocks of 128 (the float32 scores of 64 heads over 16,896
keys are 550 MB a block), each layer's weights are cast to float32 when the
layer runs and an expert layer's experts one at a time (the float32 tree,
13.7 GB at the benchmark's cut, never exists); every held expert is applied
to every token and weighed by w_e or zero.

The parameter tree is the model's own: ``embed.tok``, ``final_norm.scale``,
``lm_head.w`` and ``layers.<kind>`` with ``<kind>`` one of ``dense_full``,
``dense_window``, ``moe_full``, ``moe_window``, each holding that kind's
layers stacked in model order: ``attn.w[qkvo]`` (``attn.sink`` where the kind
has one), ``ln1/ln2.scale``, ``mlp.wg/wi/wo`` (an expert kind: a leading
axis over the held experts, and ``mlp.gate`` (hidden, published experts),
``mlp.gate_bias``).
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
    head_dim: int
    v_head_dim: int
    rotary: int
    eps: float
    value_scale: float
    kv_heads: tuple      # (full, window)
    theta: tuple
    sink: tuple
    window: int
    window_layers: tuple  # per layer: 1 window, 0 full
    moe_layers: tuple     # per layer: 1 experts, 0 dense
    top_k: int
    held_first: int
    held_count: int


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    held = config["deployment"]["held_experts"]
    return Arch(
        int(m["num_attention_heads"]), int(m["head_dim"]), int(m["v_head_dim"]),
        int(m["partial_rotary_factor"] * m["head_dim"]) // 2 * 2, float(m["layernorm_epsilon"]),
        float(m["attention_value_scale"]),
        (int(m["num_key_value_heads"]), int(m["swa_num_key_value_heads"])),
        (float(m["rope_theta"]), float(m["swa_rope_theta"])),
        (bool(m["add_full_attention_sink_bias"]), bool(m["add_swa_attention_sink_bias"])),
        int(m["sliding_window"]), tuple(int(v) for v in m["hybrid_layer_pattern"]),
        tuple(int(v) for v in m["moe_layer_freq"]), int(m["num_experts_per_tok"]),
        int(held["first"]), int(held["count"]))


def _as_is(x):
    return x


def _norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w["scale"].astype(F32)


def _rotate(x, theta, rot):
    """x (S, heads, hd): of the first ``rot`` dims, each pair (i, i + rot/2)
    turned by position x theta^(-2i/rot); the rest unturned."""
    S = x.shape[0]
    angle = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _attention(h, a, ar: Arch, window: bool, r):
    """h (S, D) -> (S, D)."""
    S = h.shape[0]
    nh, dk, dv, kv = ar.n_heads, ar.head_dim, ar.v_head_dim, ar.kv_heads[window]
    g = nh // kv
    q = _rotate((r(h) @ r(a["wq"])).reshape(S, nh, dk), ar.theta[window], ar.rotary)
    k = _rotate((r(h) @ r(a["wk"])).reshape(S, kv, dk), ar.theta[window], ar.rotary)
    v = ar.value_scale * (r(h) @ r(a["wv"])).reshape(S, kv, dv)
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb).reshape(qb, kv, g, dk)
        s = jnp.einsum("qngd,tnd->ngqt", r(qs), r(k)) / math.sqrt(dk)  # head n*g+i reads kv head n
        ok = kpos <= qpos
        if window:
            ok = ok & (qpos - kpos < ar.window)
        s = jnp.where(ok, s, -jnp.inf)
        if ar.sink[window]:  # one more column in the denominator, none in the value sum
            sink = jnp.broadcast_to(a["sink"].reshape(kv, g, 1, 1), (kv, g, qb, 1))
            p = jax.nn.softmax(jnp.concatenate([s, sink], axis=-1), axis=-1)[..., :-1]
        else:
            p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ngqt,tnd->qngd", r(p), r(v)).reshape(qb, nh * dv)

    o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, nh * dv)
    return r(o) @ r(a["wo"])


def _swiglu(h, wg, wi, wo, r):
    gate = r(h) @ r(wg)
    return r(gate / (1.0 + jnp.exp(-gate)) * (r(h) @ r(wi))) @ r(wo)


def _experts(h, m, ar: Arch, r):
    """h (S, D) -> the held experts' part of the layer's output."""
    scores = 1.0 / (1.0 + jnp.exp(-(r(h) @ r(m["gate"].astype(F32)))))          # (S, E)
    _, chosen = jax.lax.top_k(scores + m["gate_bias"].astype(F32), ar.top_k)
    picked = (chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(1)
    weights = jnp.where(picked, scores, 0.0)
    weights = weights / weights.sum(-1, keepdims=True)

    def one(y, expert):
        wg, wi, wo, e = expert
        return y + weights[:, e, None] * _swiglu(h, wg.astype(F32), wi.astype(F32),
                                                 wo.astype(F32), r), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        m["wg"], m["wi"], m["wo"], ar.held_first + jnp.arange(ar.held_count)))
    return y


def _layers(params, ar: Arch):
    """(kind name, index within the kind's stack) for each layer."""
    seen, out = {}, []
    for window, moe in zip(ar.window_layers, ar.moe_layers):
        name = ("moe" if moe else "dense") + ("_window" if window else "_full")
        out.append((name, seen.get(name, 0), bool(window), bool(moe)))
        seen[name] = seen.get(name, 0) + 1
    return out


def _row_hidden(params, tokens, ar: Arch, remat, r):
    """tokens (S,) -> final-normed hidden states (S, D)."""
    x = params["embed"]["tok"][tokens].astype(F32)
    for name, i, window, moe in _layers(params, ar):
        def layer(x, w, window=window, moe=moe):
            # the experts stay as stored until their turn; the rest of the layer in float32
            experts = {n: w["mlp"][n] for n in ("wg", "wi", "wo")} if moe else {}
            w = jax.tree.map(lambda a: a.astype(F32), dict(w, mlp={
                n: a for n, a in w["mlp"].items() if n not in experts}))
            x = x + _attention(_norm(x, w["ln1"], ar.eps), w["attn"], ar, window, r)
            h = _norm(x, w["ln2"], ar.eps)
            if moe:
                return x + _experts(h, dict(w["mlp"], **experts), ar, r)
            return x + _swiglu(h, w["mlp"]["wg"], w["mlp"]["wi"], w["mlp"]["wo"], r)

        w = jax.tree.map(lambda a: a[i], params["layers"][name])
        x = (jax.checkpoint(layer) if remat else layer)(x, w)
    return _norm(x, params["final_norm"], ar.eps)


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
