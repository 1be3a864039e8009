"""A decoder of the RoPE / RMSNorm / SwiGLU / grouped-query kind in plain
float32 ``jax.numpy``: forward, loss, gradients. The second family of
references, and the worked example of how one enters by new files alone
(``benchmark/README.md``); no committed cell reads it.

Written from the papers: rotary position embedding in the half-split form
(Su et al. 2021, as GPT-NeoX and LLaMA apply it: dimension i of a head's
first half turns with dimension i of its second half by the angle
position x theta^(-2i/head size)), RMSNorm without mean or bias (Zhang &
Sennrich 2019), SwiGLU (Shazeer 2020: silu(x Wg) * (x Wi), then Wo),
grouped-query attention (Ainslie et al. 2023: query head h reads key/value
head h // (query heads / key-value heads)), pre-norm blocks, no biases, an
output head of its own (untied). Nothing here is shared with
``deepspeed_tpu/models/transformer.py``. The passes over the rows, AdamW, the
optimizer loop and its faults are family-agnostic and imported from
``gpt2.py``, not copied. Callers run it under
``jax.default_matmul_precision("highest")``.

The parameter tree is the model's own: ``embed.tok``, ``layers.attn.w[qkvo]``
(``wk``/``wv`` of key-value heads x head size columns), ``layers.mlp.wg/wi/wo``
(gate, up, down), ``layers.ln1/ln2.scale``, ``final_norm.scale``,
``lm_head.w``, the layers stacked over a leading axis.
"""

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from benchmark.reference import gpt2
from benchmark.reference.gpt2 import FAULTS, adamw, global_norm  # noqa: F401  (the interface)

F32 = jnp.float32


class Arch(NamedTuple):
    n_heads: int
    n_kv_heads: int
    rope_theta: float
    eps: float


def arch(config):
    """All the functions below need of the configuration file that the
    parameter tree does not say (hashable: a static argument of ``jit``)."""
    m = config["model"]
    return Arch(int(m["num_attention_heads"]), int(m["num_key_value_heads"]),
                float(m["rope_theta"]), float(m["rms_norm_eps"]))


def _norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w["scale"].astype(F32)


def _rotate(x, theta):
    """x (B, S, heads, hd): each pair (i, i + hd/2) turned by position * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    angle = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block(arch):
    nh, nkv = arch.n_heads, arch.n_kv_heads

    def block(x, w):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        B, S, D = x.shape
        hd = D // nh
        a, m = w["attn"], w["mlp"]
        h = _norm(x, w["ln1"], arch.eps)
        q = _rotate((h @ a["wq"]).reshape(B, S, nh, hd), arch.rope_theta)
        k = _rotate((h @ a["wk"]).reshape(B, S, nkv, hd), arch.rope_theta)
        v = (h @ a["wv"]).reshape(B, S, nkv, hd)
        k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))  # head h reads group h // (nh/nkv)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        x = x + jnp.einsum("bhqk,bkhd->bqhd", s, v).reshape(B, S, D) @ a["wo"]
        h = _norm(x, w["ln2"], arch.eps)
        g = h @ m["wg"]
        return x + (g / (1.0 + jnp.exp(-g)) * (h @ m["wi"])) @ m["wo"], None

    return block


def hidden(params, tokens, arch, remat=False):
    """tokens (B, S) -> final-normed hidden states (B, S, D), float32."""
    x = params["embed"]["tok"][tokens].astype(F32)
    block = _block(arch)
    x, _ = jax.lax.scan(jax.checkpoint(block) if remat else block, x, params["layers"])
    return _norm(x, params["final_norm"], arch.eps)


def logits_at(params, tokens, at, arch):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S)."""
    x = jnp.take_along_axis(hidden(params, tokens, arch), at[:, :, None], axis=1)
    return x @ params["lm_head"]["w"].astype(F32)


def loss_sum(params, tokens, arch, weights=None):
    """Summed next-token cross-entropy over the B * (S - 1) predicted
    positions; with ``weights`` (B,), (weighted, plain) as ``gpt2.loss_sum``."""
    x = hidden(params, tokens, arch, remat=True)[:, :-1]
    logits = x @ params["lm_head"]["w"].astype(F32)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=2)[..., 0]
    nll = (jax.nn.logsumexp(logits, axis=-1) - picked).sum(-1)
    if weights is None:
        return nll.sum()
    return (nll * weights).sum(), nll.sum()


def loss_and_grads(params, tokens, arch, rows_per_pass, skip_rows=0, row_sharding=None):
    return gpt2.loss_and_grads(params, tokens, arch, rows_per_pass, skip_rows, row_sharding,
                               loss_sum=loss_sum)


def train(params, tokens, arch, steps, optimizer, rows_per_pass, fault=None,
          out_shardings=None, row_sharding=None):
    return gpt2.train(params, tokens, arch, steps, optimizer, rows_per_pass, fault,
                      out_shardings, row_sharding, loss_and_grads=loss_and_grads)
