"""The comparisons that decide ``correct``. They see the program's outputs
and the float32 references of ``benchmark/reference``; they never see
``--trace``, ``--seconds``, the window's length or anything the trace
reducer found.

Training: the engine's loss at optimizer steps 1..K and its first gradient
(the global norm and, where the cell asks, two scalars a leaf), on fixed rows
from the seed, against the reference's ``train``.
Serving: the reference logit of every emitted token of a sample of finished
requests against the reference's top logit at that position, plus two
negative controls that must FAIL the same check (the streams scored against
a context the engine did not see).

Which reference, and which module builds the program's model, the
configuration file says by name (``reference_of``, ``builder_of``); what each
module gives is written down in ``benchmark/README.md``.
"""

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def reference_of(config):
    """The plain reference of a configuration file: ``"reference"`` names a
    module of ``benchmark/reference`` (absent: GPT-2's)."""
    return importlib.import_module("benchmark.reference." + config.get("reference", "gpt2"))


def builder_of(config):
    """The module that builds the program's model for a configuration file:
    ``"builder"`` names it (absent: ``benchmark.models``)."""
    return importlib.import_module(config.get("builder", "benchmark.models"))


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


def fp8(x):
    """``x`` rounded to float8 (e4m3, one scale a tensor so that its largest
    entry is the format's largest), the gradient passed straight through:
    what the lower-precision control does to both operands of every matmul
    of the reference. The step below bfloat16 that would tempt a later PR."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(rounded - x)


def train_reference(reference, init_fn, init_key, tokens, arch, steps, optimizer, devices,
                    rows_per_pass, fault=None, leaves=False, operand=None):
    """Run the float32 trainer of the module ``reference`` from
    ``init_fn(init_key)`` on ``tokens``; ``arch`` is its ``arch(config)``.
    Returns dict(losses, grad_norms, checksum of the start), and with
    ``leaves`` also ``leaf_readings`` of the first gradient.
    ``operand`` makes the lower-precision control: the same trainer with
    both operands of every matmul put through it (``fp8``)."""
    abstract = jax.eval_shape(init_fn, init_key)
    p_sh, batch_sh = reference_shardings(abstract, devices)
    params = jax.jit(init_fn, out_shardings=p_sh)(init_key)
    checksum = tree_checksum(params)
    toks = jax.device_put(jnp.asarray(tokens, jnp.int32), batch_sh)
    hooks = {}
    if leaves:
        hooks["norm"] = lambda g: (reference.global_norm(g), leaf_readings(g))
    if operand is not None:
        hooks["loss_and_grads"] = functools.partial(
            reference.loss_and_grads,
            loss_sum=functools.partial(reference.loss_sum, operand=operand))
    with jax.default_matmul_precision("highest"):
        losses, norms = reference.train(params, toks, arch, steps, optimizer, rows_per_pass,
                                        fault=fault, out_shardings=p_sh,
                                        row_sharding=batch_sh if len(devices) > 1 else None,
                                        **hooks)
    out = dict(losses=losses, grad_norms=norms, checksum=checksum)
    if leaves:
        out.update(grad_norms=[n[0] for n in norms], leaf_readings=norms[0][1])
    return out


def _signs(shape):
    """A fixed pattern of +1 and -1 over an array of ``shape``: one bit of a
    hash of each entry's flat index. Made of iotas: it takes no memory and
    splits over the chips as the array it meets does."""
    index, stride = jnp.zeros(shape, jnp.uint32), 1
    for axis in reversed(range(len(shape))):
        index = index + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) * jnp.uint32(stride)
        stride *= shape[axis]
    h = index * jnp.uint32(2654435761)
    h = (h ^ (h >> 15)) * jnp.uint32(2246822519)
    return jnp.where(((h ^ (h >> 13)) >> 16) & 1, 1.0, -1.0).astype(jnp.float32)


def leaf_readings(tree):
    """{path of a leaf: (its norm, its sum under the fixed signs)}: two
    scalars a leaf, so that two trees can be compared leaf by leaf though
    they are never on the chips together. The norm moves with a bias of the
    whole leaf and hardly with rounding noise (second order); the signed sum
    is a projection on one fixed direction and moves with either (first
    order)."""
    def one(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(x))), jnp.sum(x * _signs(x.shape))

    return {jax.tree_util.keystr(k): one(x) for k, x in jax.tree_util.tree_leaves_with_path(tree)}


def worst_leaf_gaps(mine, theirs):
    """(widest norm gap, widest projection gap, {leaf: (norm gap, projection
    gap)}) of the leaf readings ``mine`` against the reference's
    ``theirs``: each gap is measured against the reference's norm
    of that leaf or of its median leaf, whichever is larger (some gradients
    are all but zero)."""
    if set(mine) != set(theirs):
        raise ValueError(f"leaves differ: {sorted(set(mine) ^ set(theirs))}")
    floor = float(np.median([n for n, _ in theirs.values()]))
    gaps = {k: tuple(abs(a - b) / max(theirs[k][0], floor)
                     for a, b in zip(mine[k], theirs[k])) for k in theirs}
    return max(g[0] for g in gaps.values()), max(g[1] for g in gaps.values()), gaps


LEAF_LIMITS = ("grad_leaf_norm_rel", "grad_leaf_proj_rel")


def by_leaf(tol):
    """Does the cell compare the first gradient leaf by leaf?"""
    return any(k in tol for k in LEAF_LIMITS)


def train_verdict(engine_losses, engine_grad_norm, ref, tol, engine_leaves=None):
    """Hold the engine's K losses and first gradient norm to the reference.
    ``tol``: ``loss_abs`` (per step), ``grad_norm_rel`` (null: reported, not
    held), ``min_fall`` (the reference's own loss must fall this much over
    the K steps, or the later losses say nothing about the update), and,
    where the cell compares leaf by leaf, ``grad_leaf_norm_rel`` and
    ``grad_leaf_proj_rel``: the limits of ``worst_leaf_gaps`` between
    ``engine_leaves`` (``leaf_readings`` of the first gradient) and the
    reference's; a gap with no limit is reported and not held.
    Returns (ok, fields)."""
    diffs = [abs(a - b) for a, b in zip(engine_losses, ref["losses"])]
    rel = abs(engine_grad_norm - ref["grad_norms"][0]) / ref["grad_norms"][0]
    fall = ref["losses"][0] - ref["losses"][-1]
    finite = all(math.isfinite(x) for x in list(engine_losses) + [engine_grad_norm])
    ok = (finite and max(diffs) <= tol["loss_abs"] and fall >= tol["min_fall"]
          and (tol["grad_norm_rel"] is None or rel <= tol["grad_norm_rel"]))
    fields = dict(
        engine_losses=engine_losses, reference_losses=ref["losses"],
        max_loss_diff=max(diffs), loss_abs_tolerance=tol["loss_abs"],
        engine_grad_norm=engine_grad_norm, reference_grad_norm=ref["grad_norms"][0],
        grad_norm_rel_diff=rel, grad_norm_rel_tolerance=tol["grad_norm_rel"],
        reference_fall=fall, min_fall=tol["min_fall"])
    if by_leaf(tol):
        worst = worst_leaf_gaps(engine_leaves, ref["leaf_readings"])
        for key, gap in zip(LEAF_LIMITS, worst):
            fields[key + "_diff"] = gap
            if key in tol:
                ok = ok and math.isfinite(gap) and gap <= tol[key]
                fields[key + "_tolerance"] = tol[key]
        fields["grad_leaf_gaps"] = worst[2]
    return ok, fields


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def seed_params(model, seed, sharpen):
    """The model's own init from ``seed``, then ``sharpen(params)``: the
    builder's rescaling of attention so that the context decides the next
    token (see the configuration file's ``compare.serve``)."""
    def make(key):
        return sharpen(model.init(key))

    return jax.jit(make)(jax.random.PRNGKey(seed))


def _gaps(reference, params, contexts, streams, width, new_max, arch):
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
        logits = jax.jit(reference.logits_at, static_argnums=3)(params, tokens, at, arch)
        top = logits.max(-1)
        own = jnp.take_along_axis(logits, jnp.asarray(picked)[:, :, None], axis=2)[:, :, 0]
    gap = np.asarray(top - own, np.float64)
    if not np.isfinite(gap[valid]).all():
        raise FloatingPointError("reference logits not finite")
    gap[~valid] = np.nan
    return gap


def serve_verdict(reference, params, prompts, streams, arch, seed, tol, width, new_max):
    """``prompts``/``streams``: the sampled requests' prompt and emitted
    tokens, scored by the module ``reference`` (``arch`` = its
    ``arch(config)``). ``tol``: ``margin``, ``share_within`` (share of emitted tokens
    that must sit within the margin), ``control_share`` (share that must
    leave it under each negative control), ``distinct_per_request``.
    Returns (ok, fields)."""
    margin = tol["margin"]
    gap = _gaps(reference, params, prompts, streams, width, new_max, arch)
    emitted = np.isfinite(gap)
    within = float((gap[emitted] <= margin).mean())
    rs = np.random.RandomState((seed + 1) % (2 ** 32))
    controls = {  # what a cache holding the wrong rows, or the right rows one slot off, computes
        "prompt_permuted": [np.concatenate([rs.permutation(p[:-1]), p[-1:]]) for p in prompts],
        "prompt_one_position_early": [p[1:] for p in prompts],
    }
    outside = {}
    for name, contexts in controls.items():
        g = _gaps(reference, params, contexts, streams, width, new_max, arch)
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
