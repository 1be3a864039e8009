"""Qwen3-Next's language model in plain float32 ``jax.numpy``: forward, loss,
gradients. Written from ``config.json`` (``model_type`` ``qwen3_next``:
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
and, for what its keys do not say, the published ``modeling_qwen3_next.py``
as the configuration file's ``assumed`` lists it; nothing here is shared with
the program (no kernel, no cache, no chunked scan, no sorting of tokens by
expert, no layer plan). Callers run it under
``jax.default_matmul_precision("highest")``.

**Equations.** ``norm`` is RMSNorm (eps ``rms_norm_eps``) with scale
``(1 + w)``. Layer i (0-based) is a full-attention layer where ``(i + 1) %
full_attention_interval == 0``, else a Gated DeltaNet layer; every layer is
``x += mixer(norm(x))``; ``x += moe(norm(x))``; then a final norm and an
untied output head.

- Gated DeltaNet mixer (``linear_num_key_heads`` Hk, ``linear_num_value_heads``
  Hv, ``linear_key_head_dim`` = ``linear_value_head_dim`` = d,
  ``linear_conv_kernel_dim`` K):
  1. ``[q~, k~, v~, z] = h W_qkvz`` (Hk d + Hk d + Hv d + Hv d columns, flat
     in that order), ``[b, a] = h W_ba`` (Hv + Hv);
  2. ``u = silu(conv(concat(q~, k~, v~)))``: causal depthwise convolution over
     time, ``u_t[c] = silu(sum_j w[c, j] u~_{t-(K-1)+j}[c])``, zeros before the
     sequence's start; split back into q, k (Hk heads) and v (Hv heads);
  3. q, k L2-normalised over d (``x / sqrt(sum x^2 + 1e-6)``), q scaled by
     ``1/sqrt(d)``; value head j uses key head ``j // (Hv / Hk)``;
  4. ``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) softplus(a_t + dt_bias)``,
     ``alpha_t = exp(g_t)``, one scalar a value head and token;
  5. the gated delta rule on a state S (d x d a head, zero at the start),
     TOKEN BY TOKEN: ``S' = alpha_t S``; ``delta = beta_t (v_t - S'^T k_t)``;
     ``S = S' + k_t delta^T``; ``o_t = S^T q_t``;
  6. ``y_t = rmsnorm_d(o_t) * w_n * silu(z_t)`` a head (a plain weight),
     heads concatenated, ``out = y W_o``.
- Gated full attention: ``q = h W_q``, ``gate = h W_qgate`` (both heads x
  ``head_dim``), ``k = h W_k``, ``v = h W_v`` (``num_key_value_heads``), no
  biases; q and k through ``norm`` over each head's width; rotary, half-split,
  on the first ``partial_rotary_factor x head_dim`` dims, base ``rope_theta``;
  causal softmax attention at ``1/sqrt(head_dim)``, query head n reading
  key-value head ``n // (heads / kv)``; ``out = (attn * sigmoid(gate)) W_o``.
- Expert layer: ``p = softmax(h W_r)`` over ALL the published experts; the
  ``num_experts_per_tok`` largest; their weights divided by their sum
  (``norm_topk_prob``); ``y = sum_{e chosen and HELD} w_e SwiGLU_e(h) +
  sigmoid(h . w_s) SwiGLU_shared(h)``. HELD is the contiguous share a
  configuration file's ``deployment.held_experts`` names: what the absent
  experts would add is left out, here as in the program.

``Arch.without`` names pieces to leave out (``decay``, ``beta``, ``conv``,
``conv_tail`` (the convolution restarting every ``tail_every`` positions),
``l2norm``, ``z_gate``, ``attn_gate``, ``qk_norm``, ``partial_rotary``,
``shared``, ``shared_gate``, ``softmax_first`` (top-k first, softmax over the
chosen)): what a program that dropped the piece would compute; the tests and
the planted faults use it, the benchmark never.

**For memory, not mathematics:** rows of a batch one at a time, a row's
queries in blocks of 128, each layer's weights cast to float32 when the layer
runs and its experts one at a time.

The parameter tree is the model's own: ``embed.tok``, ``final_norm.scale``,
``lm_head.w`` and ``layers.gdn`` / ``layers.full``, each kind's layers
stacked in model order: ``gdn.{wqkvz, wba, conv, a_log, dt_bias, norm, wo}``
or ``attn.{wq, wq_gate, wk, wv, wo, q_norm, k_norm}``, ``ln1/ln2.scale``,
``mlp.{gate, wg, wi, wo (a leading axis over the held experts), shared_wg,
shared_wi, shared_wo, shared_gate}``.
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
    kv_heads: int
    head_dim: int
    rotary: int
    theta: float
    eps: float
    key_heads: int        # Gated DeltaNet
    value_heads: int
    key_dim: int
    value_dim: int
    taps: int
    full_layers: tuple    # per layer: 1 full attention, 0 Gated DeltaNet
    top_k: int
    held_first: int
    held_count: int
    without: tuple = ()
    tail_every: int = 0


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    held = config["deployment"]["held_experts"]
    every = int(m["full_attention_interval"])
    return Arch(
        int(m["num_attention_heads"]), int(m["num_key_value_heads"]), int(m["head_dim"]),
        int(m["partial_rotary_factor"] * m["head_dim"]) // 2 * 2, float(m["rope_theta"]),
        float(m["rms_norm_eps"]), int(m["linear_num_key_heads"]), int(m["linear_num_value_heads"]),
        int(m["linear_key_head_dim"]), int(m["linear_value_head_dim"]),
        int(m["linear_conv_kernel_dim"]),
        tuple(int((i + 1) % every == 0) for i in range(int(m["num_hidden_layers"]))),
        int(m["num_experts_per_tok"]), int(held["first"]), int(held["count"]))


def _as_is(x):
    return x


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + scale.astype(F32))


def _rotate(x, theta, rot):
    """x (S, heads, hd): of the first ``rot`` dims, each pair (i, i + rot/2)
    turned by position x theta^(-2i/rot); the rest unturned."""
    S = x.shape[0]
    angle = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def _attention(h, a, ar: Arch, r):
    """h (S, D) -> (S, D)."""
    S = h.shape[0]
    nh, kv, hd = ar.n_heads, ar.kv_heads, ar.head_dim
    g = nh // kv
    q = (r(h) @ r(a["wq"])).reshape(S, nh, hd)
    k = (r(h) @ r(a["wk"])).reshape(S, kv, hd)
    v = (r(h) @ r(a["wv"])).reshape(S, kv, hd)
    if "qk_norm" not in ar.without:
        q, k = _norm(q, a["q_norm"], ar.eps), _norm(k, a["k_norm"], ar.eps)
    rot = hd if "partial_rotary" in ar.without else ar.rotary
    q, k = _rotate(q, ar.theta, rot), _rotate(k, ar.theta, rot)
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb).reshape(qb, kv, g, hd)
        s = jnp.einsum("qngd,tnd->ngqt", r(qs), r(k)) / math.sqrt(hd)  # head n*g+i reads kv head n
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s, -jnp.inf), axis=-1)
        return jnp.einsum("ngqt,tnd->qngd", r(p), r(v)).reshape(qb, nh * hd)

    o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, nh * hd)
    if "attn_gate" not in ar.without:
        o = o * _sigmoid(r(h) @ r(a["wq_gate"]))
    return r(o) @ r(a["wo"])


def _delta_net(h, w, ar: Arch, r):
    """h (S, D) -> (S, D): steps 1-6 of the module's docstring."""
    S = h.shape[0]
    Hk, Hv, dk, dv, K = ar.key_heads, ar.value_heads, ar.key_dim, ar.value_dim, ar.taps
    C = 2 * Hk * dk + Hv * dv
    qkvz = r(h) @ r(w["wqkvz"])
    ba = r(h) @ r(w["wba"])
    u, z = qkvz[:, :C], qkvz[:, C:].reshape(S, Hv, dv)
    if "conv" not in ar.without:
        t = jnp.arange(S)
        acc = jnp.zeros_like(u)
        for j in range(K):
            back = K - 1 - j
            tap = jnp.pad(u, ((back, 0), (0, 0)))[:S] * w["conv"][:, j]
            if "conv_tail" in ar.without:   # the taps do not reach across a boundary
                tap = jnp.where(((t - back) // ar.tail_every == t // ar.tail_every)[:, None], tap, 0.0)
            acc = acc + tap
        u = _silu(acc)
    q = u[:, :Hk * dk].reshape(S, Hk, dk)
    k = u[:, Hk * dk:2 * Hk * dk].reshape(S, Hk, dk)
    v = u[:, 2 * Hk * dk:].reshape(S, Hv, dv)
    if "l2norm" not in ar.without:
        q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q = jnp.repeat(q / math.sqrt(dk), Hv // Hk, axis=1)
    k = jnp.repeat(k, Hv // Hk, axis=1)
    beta = jnp.ones((S, Hv)) if "beta" in ar.without else _sigmoid(ba[:, :Hv])
    alpha = jnp.exp(-jnp.exp(w["a_log"]) * jax.nn.softplus(ba[:, Hv:] + w["dt_bias"]))
    if "decay" in ar.without:
        alpha = jnp.ones_like(alpha)

    def step(state, tok):
        q_t, k_t, v_t, a_t, b_t = tok
        state = a_t[:, None, None] * state
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), F32), (r(q), r(k), r(v), alpha, beta))
    o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + ar.eps) * w["norm"]
    if "z_gate" not in ar.without:
        o = o * _silu(z)
    return r(o.reshape(S, Hv * dv)) @ r(w["wo"])


def _swiglu(h, wg, wi, wo, r):
    return r(_silu(r(h) @ r(wg)) * (r(h) @ r(wi))) @ r(wo)


def _experts(h, m, ar: Arch, r):
    """h (S, D) -> the held experts' part of the layer's output plus the shared expert's."""
    logits = r(h) @ r(m["gate"].astype(F32))                                    # (S, E)
    if "softmax_first" in ar.without:
        top, chosen = jax.lax.top_k(logits, ar.top_k)
        picked = (chosen[:, :, None] == jnp.arange(logits.shape[1])[None, None, :])
        weights = (picked * jax.nn.softmax(top, axis=-1)[:, :, None]).sum(1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, ar.top_k)
        picked = (chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(1)
        weights = jnp.where(picked, scores, 0.0)
        weights = weights / weights.sum(-1, keepdims=True)

    def one(y, expert):
        wg, wi, wo, e = expert
        return y + weights[:, e, None] * _swiglu(h, wg.astype(F32), wi.astype(F32),
                                                 wo.astype(F32), r), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        m["wg"], m["wi"], m["wo"], ar.held_first + jnp.arange(ar.held_count)))
    if "shared" not in ar.without:
        shared = _swiglu(h, m["shared_wg"], m["shared_wi"], m["shared_wo"], r)
        if "shared_gate" not in ar.without:
            shared = shared * _sigmoid(r(h) @ r(m["shared_gate"]))
        y = y + shared
    return y


def _layers(ar: Arch):
    """(kind name, index within the kind's stack) for each layer."""
    seen, out = {}, []
    for full in ar.full_layers:
        name = "full" if full else "gdn"
        out.append((name, seen.get(name, 0)))
        seen[name] = seen.get(name, 0) + 1
    return out


def _row_hidden(params, tokens, ar: Arch, remat, r):
    """tokens (S,) -> final-normed hidden states (S, D)."""
    x = params["embed"]["tok"][tokens].astype(F32)
    for name, i in _layers(ar):
        def layer(x, w, name=name):
            # the experts stay as stored until their turn; the rest of the layer in float32
            experts = {n: w["mlp"][n] for n in ("wg", "wi", "wo")}
            w = jax.tree.map(lambda a: a.astype(F32), dict(w, mlp={
                n: a for n, a in w["mlp"].items() if n not in experts}))
            h = _norm(x, w["ln1"]["scale"], ar.eps)
            x = x + (_attention(h, w["attn"], ar, r) if name == "full"
                     else _delta_net(h, w["gdn"], ar, r))
            return x + _experts(_norm(x, w["ln2"]["scale"], ar.eps), dict(w["mlp"], **experts), ar, r)

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
