"""Nemotron 3 Super's language model (``model_type`` ``nemotron_h``) in plain
float32 ``jax.numpy``: forward, loss, gradients. Written from ``config.json``
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json)
and, for what its keys do not say, the Mamba-2 paper's recurrence (Dao & Gu
2024, the state-space-duality form with a scalar decay a head) and
DeepSeek-V3's bias-selected sigmoid routing, as the configuration file's
``assumed`` lists them; nothing here is shared with the program (no kernel, no
cache, no chunked scan, no sorting of tokens by expert, no layer plan).
Callers run it under ``jax.default_matmul_precision("highest")``.

**Equations.** ``norm`` is RMSNorm (eps ``layer_norm_epsilon``) with a plain
weight. ``x0 = E[token]`` (no multiplier). ``hybrid_override_pattern`` names
each layer's ONE sublayer, ``M`` | ``*`` | ``E``, and a layer is ``x +=
sublayer(norm(x))``: one norm, one residual add. Then ``logits = norm(x)
W_head^T``, the head untied.

- ``M``, Mamba-2 (``mamba_num_heads`` H of ``mamba_head_dim`` P, inner width I
  = H P, ``ssm_state_size`` N, ``n_groups`` G groups of H / G consecutive
  heads, ``conv_kernel`` K taps):
  1. ``[z | xBC | dt] = h W_in`` (I + (I + 2 G N) + H columns, in that order);
  2. ``xBC = silu(conv(xBC) + b_conv)``: causal depthwise convolution over
     time, zeros before the sequence's start; ``[x | B | C] = xBC`` (I | G N |
     G N), x as (H, P), B and C as (G, N);
  3. ``dt_t = softplus(dt_t + dt_bias)`` (H,), unclamped; ``A = -exp(A_log)``;
  4. head i, of group i // (H / G), on a state S (P x N, zero at the start),
     TOKEN BY TOKEN: ``S = exp(dt_t A) S + (dt_t x_t) B_t^T``; ``y_t = S C_t +
     D x_t``;
  5. ``y = rmsnorm_by_group(y * silu(z)) * w_n``: the gate BEFORE the norm,
     the norm over each group's I / G channels; ``out = y W_out``.
- ``*``, attention: ``q, k, v = h W_q, h W_k, h W_v`` (no biases, no norm), NO
  rotary turn and no other position signal, causal softmax of ``q k^T /
  sqrt(head_dim)``, query head n reading key-value head ``n // (heads / kv)``,
  ``out = attn W_o``.
- ``E``, LatentMoE: ``s = sigmoid(h W_r)`` in float32 over ALL the published
  experts; the ``num_experts_per_tok`` largest of ``s + bias``; weights
  ``routed_scaling_factor s_e / (sum over the chosen of s + eps)``; ``u = h
  W_ld`` (hidden -> ``moe_latent_size``); ``r = sum_{e chosen and HELD} w_e
  W2_e relu(W1_e u)^2``; ``out = r W_lu + W_s2 relu(W_s1 h)^2``. HELD is the
  contiguous share the configuration file's ``deployment.held_experts``
  names: what the absent experts would add to ``r`` is left out, here as in
  the program.

``Arch.without`` names pieces to leave out or to change (``decay``,
``skip`` (the ``D x``), ``conv``, ``conv_bias``, ``z_gate``,
``gate_before_norm`` (the norm first, then the gate), ``group_norm`` (ONE norm
over all I channels), ``groups`` (group 0's B and C given to every head),
``nope`` (rotary positions turned on), ``shared``, ``latent_up`` (the
up-projection left out: the routed experts' sum, 1,024 wide, never reaches the
4,096-wide residual stream), ``routed_scale`` (1 in the factor's place), ``select_bias``, ``relu2`` (a plain
ReLU)): what a program that dropped the piece would compute; the tests and the
planted faults use it, the benchmark never.

**For memory, not mathematics:** rows of a batch one at a time (and of a row
only the positions asked for kept), a row's queries in blocks of 128, each
layer's weights cast to float32 when the layer runs and its experts read out
of their stack one at a time.

The parameter tree is the model's own: ``embed.tok``, ``lm_head.w``,
``final_norm.scale`` and ``layers.mamba`` / ``layers.attention`` /
``layers.experts``, each kind's layers stacked in model order: ``ln1.scale`` +
``ssm.{win, conv, conv_bias, a_log, dt_bias, d, norm, wo}`` or ``attn.{wq, wk,
wv, wo}``; ``ln2.scale`` + ``mlp.{gate, gate_bias, latent_down, latent_up, wi,
wo (a leading axis over the held experts), shared_wi, shared_wo}``.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2
from benchmark.reference.gpt2 import FAULTS, adamw, global_norm  # noqa: F401  (the interface)

F32 = jnp.float32
QUERY_BLOCK = 128
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


class Arch(NamedTuple):
    n_heads: int
    kv_heads: int
    head_dim: int
    theta: float          # read by nothing in the model; the planted fault "nope" turns it on
    eps: float
    ssm_heads: int        # Mamba-2
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    taps: int
    pattern: str          # a character a layer: M | * | E
    top_k: int
    held_first: int
    held_count: int
    routed_scale: float
    norm_eps: float       # added to the chosen scores' sum
    without: tuple = ()


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    held = config["deployment"]["held_experts"]
    return Arch(
        int(m["num_attention_heads"]), int(m["num_key_value_heads"]), int(m["head_dim"]),
        float(m["rope_theta"]), float(m["layer_norm_epsilon"]), int(m["mamba_num_heads"]),
        int(m["mamba_head_dim"]), int(m["ssm_state_size"]), int(m["n_groups"]),
        int(m["conv_kernel"]), str(m["hybrid_override_pattern"]), int(m["num_experts_per_tok"]),
        int(held["first"]), int(held["count"]), float(m["routed_scaling_factor"]),
        float(config["assumed_values"]["moe_norm_eps"]))


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
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb).reshape(qb, kv, g, hd)
        s = jnp.einsum("qngd,tnd->ngqt", r(qs), r(k)) / math.sqrt(hd)   # head n*g+i reads kv head n
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s, -jnp.inf), axis=-1)
        return jnp.einsum("ngqt,tnd->qngd", r(p), r(v)).reshape(qb, nh * hd)

    o = jax.lax.map(block, jnp.arange(0, S, qb)).reshape(S, nh * hd)
    return r(o) @ r(a["wo"])


def _mamba(h, w, ar: Arch, r):
    """h (S, D) -> (S, D): steps 1-5 of the module's docstring."""
    S = h.shape[0]
    H, P, N, G, K = ar.ssm_heads, ar.ssm_head_dim, ar.ssm_state, ar.ssm_groups, ar.taps
    inner = H * P
    win = w["win"]      # [z | xBC | dt]: three products, so that no (S, 18,560) array is kept
    z, u, dt = (r(h) @ r(win[:, :inner]), r(h) @ r(win[:, inner:-H]), r(h) @ r(win[:, -H:]))
    if "conv" not in ar.without:
        acc = jnp.zeros_like(u)
        for j in range(K):
            acc = acc + jnp.pad(u, ((K - 1 - j, 0), (0, 0)))[:S] * w["conv"][:, j]
        if "conv_bias" not in ar.without:
            acc = acc + w["conv_bias"]
        u = _silu(acc)
    x = u[:, :inner].reshape(S, H, P)
    Bm = u[:, inner:inner + G * N].reshape(S, G, N)
    Cm = u[:, inner + G * N:].reshape(S, G, N)
    if "groups" in ar.without:       # every head reads group 0's
        Bm, Cm = (jnp.broadcast_to(v[:, :1], v.shape) for v in (Bm, Cm))
    dt = jax.nn.softplus(dt + w["dt_bias"])
    decay = jnp.exp(-jnp.exp(w["a_log"]) * dt)
    if "decay" in ar.without:
        decay = jnp.ones_like(decay)

    def step(state, tok):
        x_t, dt_t, d_t, b_t, c_t = tok
        b_t, c_t = (jnp.repeat(v, H // G, axis=0) for v in (b_t, c_t))     # (H, N): head i, group i // (H / G)
        state = d_t[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (r(x), dt, decay, r(Bm), r(Cm)))
    if "skip" not in ar.without:
        y = y + w["d"][:, None] * x
    y = y.reshape(S, inner)
    groups = 1 if "group_norm" in ar.without else G

    def rms(v):      # over each group's channels
        v = v.reshape(S, groups, inner // groups)
        return (v / jnp.sqrt((v * v).mean(-1, keepdims=True) + ar.eps)).reshape(S, inner) * w["norm"]

    gate = 1.0 if "z_gate" in ar.without else _silu(z)
    y = rms(y) * gate if "gate_before_norm" in ar.without else rms(y * gate)
    return r(y) @ r(w["wo"])


def _relu2(h, wi, wo, ar: Arch, r):
    up = jnp.maximum(r(h) @ r(wi), 0.0)
    return r(up if "relu2" in ar.without else up * up) @ r(wo)


def _experts(h, m, layer, ar: Arch, r):
    """h (S, D) -> the held experts' part of the layer's output, through the
    latent, plus the shared expert's. ``m["wi"]`` / ``m["wo"]`` are the stacks
    of ALL the expert layers, of which this is layer ``layer`` (for memory: an
    expert is read out of the stack at its turn; a layer's slice would be a
    copy of 0.7 GB a matrix)."""
    scores = jax.nn.sigmoid(r(h) @ r(m["gate"].astype(F32)))                     # (S, E)
    bias = 0.0 if "select_bias" in ar.without else m["gate_bias"].astype(F32)
    _, chosen = jax.lax.top_k(scores + bias, ar.top_k)
    picked = (chosen[:, :, None] == jnp.arange(scores.shape[1])[None, None, :]).any(1)
    weights = jnp.where(picked, scores, 0.0)
    weights = weights / (weights.sum(-1, keepdims=True) + ar.norm_eps)
    if "routed_scale" not in ar.without:
        weights = weights * ar.routed_scale
    u = r(h) @ r(m["latent_down"])

    stacked = {n: m[n].reshape((-1,) + m[n].shape[2:]) for n in ("wi", "wo")}   # (layers x held, ...)

    def one(y, i):
        wi, wo = (jax.lax.dynamic_index_in_dim(stacked[n], layer * ar.held_count + i, 0, keepdims=False)
                  for n in ("wi", "wo"))
        return y + weights[:, ar.held_first + i, None] * _relu2(u, wi.astype(F32), wo.astype(F32), ar, r), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(ar.held_count))
    y = jnp.zeros_like(h) if "latent_up" in ar.without else r(y) @ r(m["latent_up"])
    if "shared" not in ar.without:
        y = y + _relu2(h, m["shared_wi"], m["shared_wo"], ar, r)
    return y


def _row_hidden(params, tokens, ar: Arch, remat, r):
    """tokens (S,) -> final-normed hidden states (S, D), layer by layer in the
    pattern's order, each kind's next layer read out of its stack."""
    x = params["embed"]["tok"][tokens].astype(F32)
    seen = {}
    for c in ar.pattern:
        name = KINDS[c]
        j = seen.get(name, 0)
        seen[name] = j + 1

        def layer(x, name=name, j=j):
            w = params["layers"][name]
            if name == "experts":     # the experts stay in their stack, as stored, until their turn
                experts = {n: w["mlp"][n] for n in ("wi", "wo")}
                w = jax.tree.map(lambda a: a[j].astype(F32), dict(w, mlp={
                    n: a for n, a in w["mlp"].items() if n not in experts}))
                return x + _experts(_norm(x, w["ln2"]["scale"], ar.eps), dict(w["mlp"], **experts), j, ar, r)
            w = jax.tree.map(lambda a: a[j].astype(F32), w)
            h = _norm(x, w["ln1"]["scale"], ar.eps)
            return x + (_attention(h, w["attn"], ar, r) if name == "attention"
                        else _mamba(h, w["ssm"], ar, r))

        x = (jax.checkpoint(layer) if remat else layer)(x)
    return _norm(x, params["final_norm"]["scale"], ar.eps)


def hidden(params, tokens, arch, remat=False, operand=_as_is):
    """tokens (B, S) -> final-normed hidden states (B, S, D), float32; the
    rows one at a time."""
    return jax.lax.map(lambda row: _row_hidden(params, row, arch, remat, operand), tokens)


def _head(x, params, operand):
    return operand(x) @ operand(params["lm_head"]["w"].astype(F32))              # untied


def logits_at(params, tokens, at, arch, operand=_as_is):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S);
    the rows one at a time, each keeping only its K positions."""
    x = jax.lax.map(lambda row: _row_hidden(params, row[0], arch, False, operand)[row[1]], (tokens, at))
    return _head(x, params, operand)


def loss_sum(params, tokens, arch, weights=None, operand=_as_is):
    """Summed next-token cross-entropy over the B * (S - 1) predicted
    positions; with ``weights`` (B,), (weighted, plain) as ``gpt2.loss_sum``."""
    x = hidden(params, tokens, arch, remat=True, operand=operand)[:, :-1]
    logits = _head(x, params, operand)
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
