"""Ouro's looped language model (``model_type`` ``ouro``, "Scaling Latent
Reasoning via Looped Language Models") in plain float32 ``jax.numpy``:
forward, loss, gradients, the exit gate's distribution. Written from
``config.json`` (https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json)
and, for what its keys do not say, as the configuration file's ``assumed``
lists it; nothing here is shared with the program (no kernel, no cache, no
layer plan, no scan over passes). Callers run it under
``jax.default_matmul_precision("highest")``.

**Equations.** ``norm(v) = v / sqrt(mean(v^2) + eps) * w`` (RMSNorm, a plain
weight, ``rms_norm_eps``). ``x_0 = E[token]``. For pass ``t = 1 .. T``
(``total_ut_steps``): ``u = x_{t-1}``; through the SAME ``L`` layers, ``u =
layer_l(u)``; ``x_t = norm_f(u)``: the one final norm closes every pass and
its output is what the next pass starts from. ``logits = x_T W_head`` (a head
of its own, untied).

- Layer (sandwich norm, four norms): ``u += norm_1'(attn(norm_1(u)))``;
  ``u += norm_2'(W_d (silu(W_g h) * W_u h))`` with ``h = norm_2(u)``.
- Attention: ``q, k, v = h W_q, h W_k, h W_v`` (no biases, no norm on q or k),
  q and k turned by the token's position (half-split pairs ``(i, i + hd/2)``,
  angle ``position x theta^(-2i/hd)``, the same position in every pass),
  causal softmax of ``q k^T / sqrt(hd)``, query head n reading key-value head
  ``n // (heads / kv heads)``, ``W_o``. A full forward pass has no cache: pass
  t of layer l attends the keys pass t of layer l computed, which is what a
  cache of ``T x L`` independent layer-caches holds.
- Exit gate: ``lambda_t = sigmoid(w_g . x_t + b_g)``; ``p_t = lambda_t
  prod_{j<t} (1 - lambda_j)`` for ``t < T``, ``p_T = prod_{j<T} (1 -
  lambda_j)`` (:func:`exit_pdf`). At the published threshold 1.0 no token
  exits before pass T: the logits are pass T's.

``Arch.without`` names pieces to leave out or to change (``last_pass``: T - 1
passes; ``loop_norm``: the final norm after the last pass only; ``post_norm``:
no norm on a sublayer's output; ``theta``: rotary base 1e4 in the published
one's place): what a program that dropped the piece would compute; the tests
and the planted faults use it, the benchmark never.

**For memory, not mathematics:** the layers of a pass as a ``lax.scan`` over
the stack, each layer's weights cast to float32 as its turn comes (the
float32 tree of the published sizes is 10.7 GB), a sequence's queries in
blocks of 128 where its length is whole blocks.

The parameter tree is the model's own: ``embed.tok``, ``layers.block``
(``attn.{wq, wk, wv, wo}``, ``mlp.{wg, wi, wo}`` = gate, up, down, ``ln1`` /
``ln2`` / ``ln1_post`` / ``ln2_post`` ``.scale``, stacked over a leading
axis), ``final_norm.scale``, ``lm_head.w``, ``exit_gate.{w, b}``.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2
from benchmark.reference.gpt2 import FAULTS, adamw, global_norm  # noqa: F401  (the interface)

F32 = jnp.float32
QUERY_BLOCK = 128
FAULT_THETA = 1e4


class Arch(NamedTuple):
    n_heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    passes: int
    without: tuple = ()


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    return Arch(int(m["num_attention_heads"]), int(m["num_key_value_heads"]), int(m["head_dim"]),
                float(m["rope_theta"]), float(m["rms_norm_eps"]), int(m["total_ut_steps"]))


def _as_is(x):
    return x


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w["scale"].astype(F32)


def _rotate(x, theta):
    """x (B, S, heads, hd): each pair (i, i + hd/2) turned by position x theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    angle = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, a, ar: Arch, r):
    """h (B, S, D) -> (B, S, D)."""
    B, S, _ = h.shape
    nh, kv, hd = ar.n_heads, ar.kv_heads, ar.head_dim
    g = nh // kv
    theta = FAULT_THETA if "theta" in ar.without else ar.theta
    q = _rotate((r(h) @ r(a["wq"])).reshape(B, S, nh, hd), theta)
    k = _rotate((r(h) @ r(a["wk"])).reshape(B, S, kv, hd), theta)
    v = (r(h) @ r(a["wv"])).reshape(B, S, kv, hd)
    kpos = jnp.arange(S)[None, :]
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(start):
        qpos = start + jnp.arange(qb)[:, None]
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1).reshape(B, qb, kv, g, hd)
        s = jnp.einsum("bqngd,btnd->bngqt", r(qs), r(k)) / math.sqrt(hd)   # head n*g+i reads kv head n
        p = jax.nn.softmax(jnp.where(kpos <= qpos, s, -jnp.inf), axis=-1)
        return jnp.einsum("bngqt,btnd->bqngd", r(p), r(v)).reshape(B, qb, nh * hd)

    o = jnp.moveaxis(jax.lax.map(block, jnp.arange(0, S, qb)), 0, 1).reshape(B, S, nh * hd)
    return r(o) @ r(a["wo"])


def _layer(ar: Arch, r):
    post = (lambda out, w: out) if "post_norm" in ar.without else (lambda out, w: _norm(out, w, ar.eps))

    def layer(u, w):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        u = u + post(_attention(_norm(u, w["ln1"], ar.eps), w["attn"], ar, r), w["ln1_post"])
        h, m = _norm(u, w["ln2"], ar.eps), w["mlp"]
        out = r(_silu(r(h) @ r(m["wg"])) * (r(h) @ r(m["wi"]))) @ r(m["wo"])
        return u + post(out, w["ln2_post"]), None

    return layer


def states(params, tokens, arch, remat=False, operand=_as_is):
    """tokens (B, S) -> [x_1 .. x_T], the state every pass ends in (B, S, D),
    float32: the passes a Python loop, a pass's layers a scan over the stack."""
    ar = arch
    T = ar.passes - ("last_pass" in ar.without)
    layer = _layer(ar, operand)
    x, out = params["embed"]["tok"][tokens].astype(F32), []
    for t in range(T):
        x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x, params["layers"]["block"])
        if "loop_norm" not in ar.without or t == T - 1:
            x = _norm(x, params["final_norm"], ar.eps)
        out.append(x)
    return out


def hidden(params, tokens, arch, remat=False, operand=_as_is):
    """tokens (B, S) -> the last pass's final-normed hidden states (B, S, D), float32."""
    return states(params, tokens, arch, remat, operand)[-1]


def exit_pdf(params, tokens, arch):
    """tokens (B, S) -> the exit gate's distribution over the passes (B, S, T)."""
    gate = params["exit_gate"]
    lam = [jax.nn.sigmoid(x @ gate["w"].astype(F32) + gate["b"].astype(F32))
           for x in states(params, tokens, arch)]
    pdf, stay = [], jnp.ones_like(lam[0])
    for lam_t in lam[:-1]:
        pdf.append(lam_t * stay)
        stay = stay * (1.0 - lam_t)
    return jnp.stack(pdf + [stay], axis=-1)


def _head(x, params, operand):
    return operand(x) @ operand(params["lm_head"]["w"].astype(F32))


def logits_at(params, tokens, at, arch, operand=_as_is):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S)."""
    x = jnp.take_along_axis(hidden(params, tokens, arch, operand=operand), at[:, :, None], axis=1)
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
