"""GPT-2 in plain float32 ``jax.numpy``: forward, loss, gradients, AdamW.

Written from the published description (Radford et al. 2019; the
``openai-community/gpt2*`` checkpoints): learned absolute positions, pre-LN
blocks, LayerNorm eps 1e-5, tanh GELU, multi-head causal attention scaled by
1/sqrt(head size), output head tied to the token embedding, next-token
cross-entropy averaged over all predicted positions. Nothing here is shared
with ``deepspeed_tpu/models/transformer.py``: no kernel, no KV cache, no
batching trick, none of the program's sharding rules or remat policies.
Callers run it under ``jax.default_matmul_precision("highest")`` (a float32
matmul on a TPU otherwise runs in bf16 passes).

Departures from "as plain as possible", each for memory and none changing
the mathematics:

- the layers are a ``lax.scan`` over the stacked parameters (short compile),
  and each layer's parameters are cast to float32 inside the body, so a
  bf16 tree is never copied whole;
- for gradients the scan body is under ``jax.checkpoint`` and the rows of the
  batch are taken ``rows_per_pass`` at a time: the float32 attention
  probabilities of 48 layers (105 MB a sequence and layer at XL) would not
  fit beside anything otherwise.

The parameter tree is the model's own (``embed.tok/pos``, ``layers.attn.
w[qkvo]/b[qkvo]``, ``layers.mlp.wi/bi/wo/bo``, ``layers.ln1/ln2``,
``final_norm``), stacked over a leading layer axis.
"""

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def arch(config):
    """What the functions below take as ``n_heads``: all they need of the
    configuration file that the parameter tree does not say."""
    return int(config["model"]["n_head"])


def _norm(x, w):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * w["scale"].astype(F32) + w["bias"].astype(F32)


def _as_is(x):
    return x


def _block(n_heads, operand=_as_is):
    r = operand  # what every matmul's two operands go through: nothing, but for a control

    def block(x, w):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        B, S, D = x.shape
        hd = D // n_heads
        a, m = w["attn"], w["mlp"]
        h = r(_norm(x, w["ln1"]))
        q, k, v = ((h @ r(a["w" + n]) + a["b" + n]).reshape(B, S, n_heads, hd) for n in "qkv")
        s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        x = x + r(jnp.einsum("bhqk,bkhd->bqhd", r(s), r(v)).reshape(B, S, D)) @ r(a["wo"]) + a["bo"]
        u = r(_norm(x, w["ln2"])) @ r(m["wi"]) + m["bi"]
        u = 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (u + 0.044715 * u ** 3)))
        return x + r(u) @ r(m["wo"]) + m["bo"], None

    return block


def hidden(params, tokens, n_heads, remat=False, operand=_as_is):
    """tokens (B, S) -> final-normed hidden states (B, S, D), float32."""
    S = tokens.shape[1]
    tok, pos = params["embed"]["tok"], params["embed"]["pos"]
    x = tok[tokens].astype(F32) + pos[:S].astype(F32)
    block = _block(n_heads, operand)
    x, _ = jax.lax.scan(jax.checkpoint(block) if remat else block, x, params["layers"])
    return _norm(x, params["final_norm"])


def logits_at(params, tokens, at, n_heads):
    """Logits (B, K, V) at the positions ``at`` (B, K) of ``tokens`` (B, S)."""
    x = jnp.take_along_axis(hidden(params, tokens, n_heads), at[:, :, None], axis=1)
    return x @ params["embed"]["tok"].astype(F32).T


def loss_sum(params, tokens, n_heads, weights=None, operand=_as_is):
    """Summed next-token cross-entropy of ``tokens`` (B, S) over its
    B * (S - 1) predicted positions; with ``weights`` (B,), also the sum in
    which each row counts by its weight: (weighted, plain). ``operand`` is
    applied to both operands of every matmul: the lower-precision control
    hands in its rounding (``compare.fp8``), and nobody else anything."""
    x = hidden(params, tokens, n_heads, remat=True, operand=operand)[:, :-1]
    logits = operand(x) @ operand(params["embed"]["tok"].astype(F32).T)
    picked = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=2)[..., 0]
    nll = (jax.nn.logsumexp(logits, axis=-1) - picked).sum(-1)
    if weights is None:
        return nll.sum()
    return (nll * weights).sum(), nll.sum()


def loss_and_grads(params, tokens, n_heads, rows_per_pass, skip_rows=0, row_sharding=None,
                   loss_sum=loss_sum):
    """Mean loss of the batch and its gradient, ``rows_per_pass`` rows at a
    time (``row_sharding`` spreads a pass's rows over the chips). The passes
    know nothing of the model: another family's reference hands in its own
    ``loss_sum`` and shares them.
    ``skip_rows`` > 0 is a fault for the negative controls: the last
    ``skip_rows`` rows' gradients are left out of the sum, while the loss and
    the count they are divided by stay whole, as after a reduce-scatter that
    lost one chip's contribution."""
    B, S = tokens.shape
    count = B * (S - 1)
    passes = tokens.reshape(B // rows_per_pass, rows_per_pass, S)
    keep = (jnp.arange(B) < B - skip_rows).astype(F32).reshape(passes.shape[:2])

    def one(carry, inp):
        total, acc = carry
        rows, kept = inp
        if row_sharding is not None:
            rows = jax.lax.with_sharding_constraint(rows, row_sharding)
        (_, value), g = jax.value_and_grad(loss_sum, has_aux=True)(params, rows, n_heads, kept)
        return (total + value, jax.tree.map(jnp.add, acc, g)), None

    zero = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    (total, grads), _ = jax.lax.scan(one, (jnp.zeros((), F32), zero), (passes, keep))
    return total / count, jax.tree.map(lambda g: g / count, grads)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def adamw(params, grads, m, v, step, lr, beta1, beta2, eps, weight_decay):
    """One AdamW step (Loshchilov & Hutter 2019; torch.optim.AdamW's form):
    bias-corrected moments, decay decoupled from the gradient. ``step`` is
    the 1-based number of this update."""
    bc1, bc2 = 1.0 - beta1 ** step, 1.0 - beta2 ** step

    def leaf(p, g, m, v):
        m = beta1 * m + (1.0 - beta1) * g
        v = beta2 * v + (1.0 - beta2) * g * g
        p = p - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps) - lr * weight_decay * p
        return p, m, v

    out = jax.tree.map(leaf, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


FAULTS = ("grads_scaled", "shard_left_out", "double_update")


def train(params, tokens, n_heads, steps, optimizer, rows_per_pass, fault=None,
          out_shardings=None, row_sharding=None, loss_and_grads=loss_and_grads,
          norm=global_norm):
    """``steps`` optimizer steps on the one batch ``tokens``. Returns
    (losses[steps], grad_norms[steps]): the loss BEFORE each update and the
    global norm of its gradient (or whatever ``norm`` makes of the gradient:
    the comparison leaf by leaf hands in its own). ``params`` is not donated
    or changed.
    Another family's reference hands in its own ``loss_and_grads`` and
    shares the optimizer loop and the faults.

    ``fault`` makes a wrong trainer for the negative controls:
    ``grads_scaled`` multiplies every gradient by 4 (a sum over four chips
    taken for their mean), ``shard_left_out`` drops a quarter of the rows'
    gradients, ``double_update`` applies twice the update."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    B = tokens.shape[0]
    lr = optimizer["lr"] * (2.0 if fault == "double_update" else 1.0)
    hyper = dict(lr=lr, beta1=optimizer["betas"][0], beta2=optimizer["betas"][1],
                 eps=optimizer["eps"], weight_decay=optimizer["weight_decay"])

    def step_fn(p, m, v, step, tokens):  # tokens are an ARGUMENT: closed over, they would be a
        # constant of the program, and every seed would compile (and cache) a program of its own
        loss, g = loss_and_grads(p, tokens, n_heads, rows_per_pass,
                                 skip_rows=B // 4 if fault == "shard_left_out" else 0,
                                 row_sharding=row_sharding)
        if fault == "grads_scaled":
            g = jax.tree.map(lambda x: 4.0 * x, g)
        recorded = norm(g)
        p, m, v = adamw(p, g, m, v, step, **hyper)
        return p, m, v, loss, recorded

    sh = out_shardings
    step_jit = jax.jit(step_fn, donate_argnums=(1, 2),
                       out_shardings=None if sh is None else (sh, sh, sh, None, None))
    zeros = jax.jit(lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, F32), t),
                    out_shardings=sh)
    p, m, v = params, zeros(params), zeros(params)
    losses, norms = [], []
    for i in range(steps):
        p, m, v, loss, recorded = step_jit(p, m, v, jnp.asarray(i + 1, F32), tokens)
        losses.append(loss)
        norms.append(recorded)
    return [float(x) for x in losses], [jax.tree.map(float, x) for x in norms]
