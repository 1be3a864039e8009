"""Granite 4.0-H's language model (``model_type`` ``granitemoehybrid``) in plain
float32 ``jax.numpy``: forward, loss, gradients. Written from ``config.json``
(https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json)
and, for what its keys do not say, the Mamba-2 paper's recurrence (Dao & Gu
2024, "Transformers are SSMs", the state-space-duality form with a scalar decay
a head) as the configuration file's ``assumed`` lists it; nothing here is
shared with the program (no kernel, no cache, no chunked scan, no sorting of
tokens by expert, no layer plan). Callers run it under
``jax.default_matmul_precision("highest")``.

**Equations.** ``norm`` is RMSNorm (eps ``rms_norm_eps``) with a plain
weight. ``x0 = embedding_multiplier E[token]``. Layer i is a Mamba-2 layer
where ``layer_types[i] == "mamba"``, else an attention layer; every layer is
``x += residual_multiplier mixer(norm(x))``; ``x += residual_multiplier
moe(norm(x))``; then a final norm and the TIED head, ``logits = norm(x) E^T /
logits_scaling``.

- Mamba-2 mixer (``mamba_n_heads`` H, ``mamba_d_head`` P, inner width I = H P
  = ``mamba_expand`` x hidden, ``mamba_d_state`` N, ONE group, ``mamba_d_conv``
  K taps):
  1. ``[z | xBC | dt] = h W_in`` (I + (I + 2N) + H columns, in that order);
  2. ``xBC = silu(conv(xBC) + b_conv)``: causal depthwise convolution over
     time, ``u_t[c] = sum_j w[c, j] u~_{t-(K-1)+j}[c]``, zeros before the
     sequence's start; ``[x | B | C] = xBC`` (I | N | N), x as (H, P);
  3. ``dt_t = softplus(dt_t + dt_bias)`` (H,), ``a_t = dt_t A`` with ``A =
     -exp(A_log)`` (H,): one scalar a head and token;
  4. the recurrence on a state S (P x N a head, zero at the start), TOKEN BY
     TOKEN: ``S = exp(a_t) S + (dt_t x_t) B_t^T``; ``y_t = S C_t + D x_t``
     (D one scalar a head; B and C shared by every head);
  5. ``y = rmsnorm_I(y * silu(z)) * w_n``: the gate BEFORE the norm, the norm
     over all I at once; ``out = y W_out``.
- Attention mixer: ``q, k, v = h W_q, h W_k, h W_v`` (no biases, no norm),
  NO rotary turn and no other position signal (``position_embedding_type``
  ``nope``), causal softmax of ``attention_multiplier q k^T`` (NOT
  1/sqrt(width)), query head n reading key-value head ``n // (heads / kv)``,
  ``out = attn W_o``.
- Expert layer: router logits ``h W_r`` over ALL the published experts, the
  ``num_experts_per_tok`` largest, softmax over those; ``y = sum_{e chosen
  and HELD} w_e SwiGLU_e(h) + SwiGLU_shared(h)`` (no gate on the shared
  expert). HELD is the contiguous share a configuration file's
  ``deployment.held_experts`` names: what the absent experts would add is
  left out, here as in the program.

``Arch.without`` names pieces to leave out or to change (``decay``,
``softplus``, ``dt_bias``, ``skip`` (the ``D x``), ``conv``, ``conv_bias``,
``conv_tail`` (the convolution restarting every ``tail_every`` positions),
``z_gate``, ``gate_before_norm`` (the norm first, then the gate),
``embedding_multiplier``, ``residual_multiplier``, ``attention_multiplier``
(1/sqrt(width) in its place), ``logits_scaling``, ``nope`` (rotary positions
turned on), ``shared``, ``topk_first`` (softmax over all the experts first,
then the top-k renormalised)): what a program that dropped the piece would
compute; the tests and the planted faults use it, the benchmark never.

**For memory, not mathematics:** rows of a batch one at a time (and of a
row only the positions asked for kept), a row's queries in blocks of 128, a
run of equal layers as a ``lax.scan`` over their index so that one layer's
weights are read out of the stack at a time, each layer's weights cast to
float32 when the layer runs and its experts one at a time.

The parameter tree is the model's own: ``embed.tok``, ``final_norm.scale``
and ``layers.mamba`` / ``layers.attention``, each kind's layers stacked in
model order: ``ssm.{win, conv, conv_bias, a_log, dt_bias, d, norm, wo}`` or
``attn.{wq, wk, wv, wo}``, ``ln1/ln2.scale``, ``mlp.{gate, wg, wi, wo (a
leading axis over the held experts), shared_wg, shared_wi, shared_wo}``.
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
    theta: float          # unused by the model (nope); the planted fault "nope" turns it on
    eps: float
    ssm_heads: int        # Mamba-2
    ssm_head_dim: int
    ssm_state: int
    taps: int
    layer_types: tuple    # per layer: "mamba" | "attention"
    top_k: int
    held_first: int
    held_count: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    without: tuple = ()
    tail_every: int = 0


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    held = config["deployment"]["held_experts"]
    return Arch(
        int(m["num_attention_heads"]), int(m["num_key_value_heads"]),
        int(m["hidden_size"]) // int(m["num_attention_heads"]), float(m["rope_theta"]),
        float(m["rms_norm_eps"]), int(m["mamba_n_heads"]), int(m["mamba_d_head"]),
        int(m["mamba_d_state"]), int(m["mamba_d_conv"]), tuple(m["layer_types"]),
        int(m["num_experts_per_tok"]), int(held["first"]), int(held["count"]),
        float(m["embedding_multiplier"]), float(m["residual_multiplier"]),
        float(m["attention_multiplier"]), float(m["logits_scaling"]))


def _as_is(x):
    return x


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale.astype(F32)


def _rotate(x, theta):
    """x (S, heads, hd): each pair (i, i + hd/2) turned by position x
    theta^(-2i/hd). Not part of the model: the fault ``nope`` plants it."""
    S, hd = x.shape[0], x.shape[-1]
    angle = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, a, ar: Arch, r):
    """h (S, D) -> (S, D)."""
    S = h.shape[0]
    nh, kv, hd = ar.n_heads, ar.kv_heads, ar.head_dim
    g = nh // kv
    q = (r(h) @ r(a["wq"])).reshape(S, nh, hd)
    k = (r(h) @ r(a["wk"])).reshape(S, kv, hd)
    v = (r(h) @ r(a["wv"])).reshape(S, kv, hd)
    if "nope" in ar.without:
        q, k = _rotate(q, ar.theta), _rotate(k, ar.theta)
    scale = 1.0 / math.sqrt(hd) if "attention_multiplier" in ar.without else ar.attention_multiplier
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb).reshape(qb, kv, g, hd)
        s = jnp.einsum("qngd,tnd->ngqt", r(qs), r(k)) * scale        # head n*g+i reads kv head n
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s, -jnp.inf), axis=-1)
        return jnp.einsum("ngqt,tnd->qngd", r(p), r(v)).reshape(qb, nh * hd)

    o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, nh * hd)
    return r(o) @ r(a["wo"])


def _mamba(h, w, ar: Arch, r):
    """h (S, D) -> (S, D): steps 1-5 of the module's docstring."""
    S = h.shape[0]
    H, P, N, K = ar.ssm_heads, ar.ssm_head_dim, ar.ssm_state, ar.taps
    inner = H * P
    win = w["win"]      # [z | xBC | dt]: three products, so that no (S, 16,768) array is kept
    z, u, dt = (r(h) @ r(win[:, :inner]), r(h) @ r(win[:, inner:-H]), r(h) @ r(win[:, -H:]))
    if "conv" not in ar.without:
        t = jnp.arange(S)
        acc = jnp.zeros_like(u)
        for j in range(K):
            back = K - 1 - j
            tap = jnp.pad(u, ((back, 0), (0, 0)))[:S] * w["conv"][:, j]
            if "conv_tail" in ar.without:   # the taps do not reach across a boundary
                tap = jnp.where(((t - back) // ar.tail_every == t // ar.tail_every)[:, None], tap, 0.0)
            acc = acc + tap
        if "conv_bias" not in ar.without:
            acc = acc + w["conv_bias"]
        u = _silu(acc)
    x, Bm, Cm = u[:, :inner].reshape(S, H, P), u[:, inner:inner + N], u[:, inner + N:]
    if "dt_bias" not in ar.without:
        dt = dt + w["dt_bias"]
    if "softplus" not in ar.without:
        dt = jax.nn.softplus(dt)
    decay = jnp.exp(-jnp.exp(w["a_log"]) * dt)
    if "decay" in ar.without:
        decay = jnp.ones_like(decay)

    def step(state, tok):
        x_t, dt_t, d_t, b_t, c_t = tok
        state = d_t[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (r(x), dt, decay, r(Bm), r(Cm)))
    if "skip" not in ar.without:
        y = y + w["d"][:, None] * x
    y = y.reshape(S, inner)
    rms = lambda v: v / jnp.sqrt((v * v).mean(-1, keepdims=True) + ar.eps) * w["norm"]
    gate = 1.0 if "z_gate" in ar.without else _silu(z)
    y = rms(y) * gate if "gate_before_norm" in ar.without else rms(y * gate)
    return r(y) @ r(w["wo"])


def _swiglu(h, wg, wi, wo, r):
    return r(_silu(r(h) @ r(wg)) * (r(h) @ r(wi))) @ r(wo)


def _experts(h, m, ar: Arch, r):
    """h (S, D) -> the held experts' part of the layer's output plus the shared expert's."""
    logits = r(h) @ r(m["gate"].astype(F32))                                    # (S, E)
    if "topk_first" in ar.without:   # softmax over all, the top-k, divided by their sum
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, ar.top_k)
        picked = (chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(1)
        weights = jnp.where(picked, scores, 0.0)
        weights = weights / weights.sum(-1, keepdims=True)
    else:
        top, chosen = jax.lax.top_k(logits, ar.top_k)
        picked = (chosen[:, :, None] == jnp.arange(logits.shape[1])[None, None, :])
        weights = (picked * jax.nn.softmax(top, axis=-1)[:, :, None]).sum(1)

    def one(y, expert):
        wg, wi, wo, e = expert
        return y + weights[:, e, None] * _swiglu(h, wg.astype(F32), wi.astype(F32),
                                                 wo.astype(F32), r), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        m["wg"], m["wi"], m["wo"], ar.held_first + jnp.arange(ar.held_count)))
    if "shared" not in ar.without:
        y = y + _swiglu(h, m["shared_wg"], m["shared_wi"], m["shared_wo"], r)
    return y


def _runs(ar: Arch):
    """(kind name, first index within the kind's stack, layers) for each run of equal layers."""
    seen, out = {}, []
    for name in ar.layer_types:
        if out and out[-1][0] == name:
            out[-1][2] += 1
        else:
            out.append([name, seen.get(name, 0), 1])
        seen[name] = seen.get(name, 0) + 1
    return [tuple(run) for run in out]


def _row_hidden(params, tokens, ar: Arch, remat, r):
    """tokens (S,) -> final-normed hidden states (S, D). A run of equal layers
    is a ``lax.scan`` over their index (for memory: one layer's weights are
    read out of the stack at a time)."""
    x = params["embed"]["tok"][tokens].astype(F32)
    if "embedding_multiplier" not in ar.without:
        x = x * ar.embedding_multiplier
    res = 1.0 if "residual_multiplier" in ar.without else ar.residual_multiplier
    for name, start, count in _runs(ar):
        def layer(x, j, name=name, start=start):
            w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, start + j, 0, keepdims=False),
                             params["layers"][name])
            # the experts stay as stored until their turn; the rest of the layer in float32
            experts = {n: w["mlp"][n] for n in ("wg", "wi", "wo")}
            w = jax.tree.map(lambda a: a.astype(F32), dict(w, mlp={
                n: a for n, a in w["mlp"].items() if n not in experts}))
            h = _norm(x, w["ln1"]["scale"], ar.eps)
            x = x + res * (_attention(h, w["attn"], ar, r) if name == "attention"
                           else _mamba(h, w["ssm"], ar, r))
            return x + res * _experts(_norm(x, w["ln2"]["scale"], ar.eps),
                                      dict(w["mlp"], **experts), ar, r), None

        x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x, jnp.arange(count))
    return _norm(x, params["final_norm"]["scale"], ar.eps)


def hidden(params, tokens, arch, remat=False, operand=_as_is):
    """tokens (B, S) -> final-normed hidden states (B, S, D), float32; the
    rows one at a time."""
    return jax.lax.map(lambda row: _row_hidden(params, row, arch, remat, operand), tokens)


def _head(x, params, ar: Arch, operand):
    logits = operand(x) @ operand(params["embed"]["tok"].astype(F32).T)          # tied
    return logits if "logits_scaling" in ar.without else logits / ar.logits_scaling


def logits_at(params, tokens, at, arch, operand=_as_is):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S);
    the rows one at a time, each keeping only its K positions."""
    x = jax.lax.map(lambda row: _row_hidden(params, row[0], arch, False, operand)[row[1]], (tokens, at))
    return _head(x, params, arch, operand)


def loss_sum(params, tokens, arch, weights=None, operand=_as_is):
    """Summed next-token cross-entropy over the B * (S - 1) predicted
    positions; with ``weights`` (B,), (weighted, plain) as ``gpt2.loss_sum``."""
    x = hidden(params, tokens, arch, remat=True, operand=operand)[:, :-1]
    logits = _head(x, params, arch, operand)
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
