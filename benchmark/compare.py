"""The comparisons that decide ``correct``. They see the program's outputs
and the float32 references of ``benchmark/reference``; they never see
``--trace``, ``--seconds``, the window's length or anything the trace
reducer found.

Training: the engine's loss at optimizer steps 1..K and its global gradient
norm at step 1, on one fixed micro-batch, against ``reference.gpt2.train``.
Serving: the reference logit of every emitted token of a sample of finished
requests against the reference's top logit at that position, plus two
negative controls that must FAIL the same check (the streams scored against
a context the engine did not see).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference import gpt2


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def reference_shardings(abstract_params, devices):
    """The reference's own placement: every leaf split over the chips along
    its last axis that divides, the layer axis of stacked leaves left whole;
    rows of a batch over the same axis. Nothing of the program's rules."""
    mesh = Mesh(np.array(devices), ("d",))
    n = len(devices)

    def spec(path, leaf):
        stacked = any(getattr(k, "key", None) == "layers" for k in path)
        for axis in range(leaf.ndim - 1, 0 if stacked else -1, -1):
            if leaf.shape[axis] % n == 0 and leaf.shape[axis] >= n:
                return NamedSharding(mesh, P(*([None] * axis + ["d"])))
        return NamedSharding(mesh, P())

    params = jax.tree_util.tree_map_with_path(spec, abstract_params)
    return params, NamedSharding(mesh, P("d", None))


def tree_checksum(tree):
    """(global L2 norm, sum) of a tree, float32: two numbers that two trees
    holding the same values share whatever their placement."""
    @jax.jit
    def both(t):
        leaves = [x.astype(jnp.float32) for x in jax.tree.leaves(t)]
        return (jnp.sqrt(sum(jnp.sum(x * x) for x in leaves)), sum(jnp.sum(x) for x in leaves))

    a, b = both(tree)
    return float(a), float(b)


def train_reference(init_fn, init_key, tokens, n_heads, steps, optimizer, devices,
                    rows_per_pass, fault=None):
    """Run the float32 reference trainer from ``init_fn(init_key)`` on
    ``tokens``. Returns dict(losses, grad_norms, checksum of the start)."""
    abstract = jax.eval_shape(init_fn, init_key)
    p_sh, batch_sh = reference_shardings(abstract, devices)
    params = jax.jit(init_fn, out_shardings=p_sh)(init_key)
    checksum = tree_checksum(params)
    toks = jax.device_put(jnp.asarray(tokens, jnp.int32), batch_sh)
    with jax.default_matmul_precision("highest"):
        losses, norms = gpt2.train(params, toks, n_heads, steps, optimizer, rows_per_pass,
                                   fault=fault, out_shardings=p_sh,
                                   row_sharding=batch_sh if len(devices) > 1 else None)
    return dict(losses=losses, grad_norms=norms, checksum=checksum)


def train_verdict(engine_losses, engine_grad_norm, ref, tol):
    """Hold the engine's K losses and first gradient norm to the reference.
    ``tol``: ``loss_abs`` (per step), ``grad_norm_rel``, ``min_fall`` (the
    reference's own loss must fall this much over the K steps, or the later
    losses say nothing about the update). Returns (ok, fields)."""
    diffs = [abs(a - b) for a, b in zip(engine_losses, ref["losses"])]
    rel = abs(engine_grad_norm - ref["grad_norms"][0]) / ref["grad_norms"][0]
    fall = ref["losses"][0] - ref["losses"][-1]
    finite = all(math.isfinite(x) for x in list(engine_losses) + [engine_grad_norm])
    ok = (finite and max(diffs) <= tol["loss_abs"] and rel <= tol["grad_norm_rel"]
          and fall >= tol["min_fall"])
    return ok, dict(
        engine_losses=engine_losses, reference_losses=ref["losses"],
        max_loss_diff=max(diffs), loss_abs_tolerance=tol["loss_abs"],
        engine_grad_norm=engine_grad_norm, reference_grad_norm=ref["grad_norms"][0],
        grad_norm_rel_diff=rel, grad_norm_rel_tolerance=tol["grad_norm_rel"],
        reference_fall=fall, min_fall=tol["min_fall"])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def seed_params(model, seed, query_scale):
    """The model's own init from ``seed`` with attention rescaled so that the
    context decides the next token (see the configuration file's
    ``compare.serve``): queries x ``query_scale``, and init's 1/sqrt(2L) on
    the attention output undone."""
    def make(key):
        params = model.init(key)
        attn = params["layers"]["attn"]
        attn["wq"] = attn["wq"] * query_scale
        attn["wo"] = attn["wo"] * math.sqrt(2 * model.cfg.num_layers)
        return params

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _gaps(params, contexts, streams, width, new_max, n_heads):
    """(requests, new_max) float array: how far the reference logit of each
    emitted token sits below the reference maximum at the position that
    emitted it (NaN past a stream's end). Teacher-forced: one float32
    forward over context + emitted tokens."""
    n = len(contexts)
    tokens = np.zeros((n, width), np.int32)  # causal: the tail pad is never seen
    at = np.zeros((n, new_max), np.int32)
    picked = np.zeros((n, new_max), np.int32)
    valid = np.zeros((n, new_max), bool)
    for i, (c, s) in enumerate(zip(contexts, streams)):
        tokens[i, :len(c) + len(s)] = np.concatenate([c, s])
        at[i, :len(s)] = len(c) - 1 + np.arange(len(s))  # position j predicts token j + 1
        picked[i, :len(s)] = s
        valid[i, :len(s)] = True
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(gpt2.logits_at, static_argnums=3)(params, tokens, at, n_heads)
        top = logits.max(-1)
        own = jnp.take_along_axis(logits, jnp.asarray(picked)[:, :, None], axis=2)[:, :, 0]
    gap = np.asarray(top - own, np.float64)
    if not np.isfinite(gap[valid]).all():
        raise FloatingPointError("reference logits not finite")
    gap[~valid] = np.nan
    return gap


def serve_verdict(params, prompts, streams, n_heads, seed, tol, width, new_max):
    """``prompts``/``streams``: the sampled requests' prompt and emitted
    tokens. ``tol``: ``margin``, ``share_within`` (share of emitted tokens
    that must sit within the margin), ``control_share`` (share that must
    leave it under each negative control), ``distinct_per_request``.
    Returns (ok, fields)."""
    margin = tol["margin"]
    gap = _gaps(params, prompts, streams, width, new_max, n_heads)
    emitted = np.isfinite(gap)
    within = float((gap[emitted] <= margin).mean())
    rs = np.random.RandomState((seed + 1) % (2 ** 32))
    controls = {  # what a cache holding the wrong rows, or the right rows one slot off, computes
        "prompt_permuted": [np.concatenate([rs.permutation(p[:-1]), p[-1:]]) for p in prompts],
        "prompt_one_position_early": [p[1:] for p in prompts],
    }
    outside = {}
    for name, contexts in controls.items():
        g = _gaps(params, contexts, streams, width, new_max, n_heads)
        outside[name] = float((g[emitted] > margin).mean())
    distinct = len({int(t) for s in streams for t in s})
    ok = (within >= tol["share_within"]
          and all(v >= tol["control_share"] for v in outside.values())
          and distinct >= tol["distinct_per_request"] * len(streams))
    return ok, dict(
        requests_scored=len(streams), tokens_scored=int(emitted.sum()), margin=margin,
        share_within_margin=within, share_within_required=tol["share_within"],
        worst_gap=float(np.nanmax(gap)), gap_p99=float(np.nanpercentile(gap, 99)),
        share_equal_reference_argmax=float((gap[emitted] == 0).mean()),
        control_share_required=tol["control_share"], control_share_outside_margin=outside,
        distinct_tokens=distinct,
        distinct_required=tol["distinct_per_request"] * len(streams))
