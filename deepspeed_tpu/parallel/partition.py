"""Regex-driven partition rules for inference tensor parallelism.

TPU-native counterpart of the reference's ``module_inject`` layer: where
the reference rewrites ``nn.Linear`` modules into column/row-parallel
shards (replace_module.py + auto_tp.py), on TPU the same split is pure
*placement* — a table of ``(regex, PartitionSpec)`` rules matched against
each parameter's ``/``-joined tree path assigns every weight a
``NamedSharding`` over the mesh, and GSPMD inserts the per-layer
collectives the reference codes by hand (the EasyLM/fmengine
``match_partition_rules`` recipe).

Two rule sources compose, in order:

1. ``InferenceConfig.mesh.rules`` — user overrides, matched first;
2. the model-family default table (``DEFAULT_RULES`` covers the builtin
   transformer naming every ``module_inject`` policy converts into):
   attention heads, MLP hidden, and vocab/embed shard on ``tensor``;
   biases/norms/scales replicate.

The engine prefers the model's own ``logical_specs`` annotations when it
has them (they carry per-dim intent the regex cannot see, e.g. MoE expert
dims); regex rules serve models WITHOUT annotations — custom ``cfg/init/
apply`` model objects and checkpoint trees loaded outside the builtin
family — and user overrides win over both.
"""

import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Default regex rule table over the builtin transformer param naming
# (models/transformer.py init(); every module_inject HF policy — gpt2,
# llama, neox, opt, bloom, auto-TP — converts into this naming, so one
# table serves them all). First match wins; the trailing catch-all
# replicates anything unmatched (scalars, buffers). Mirrors
# runtime/zero/sharding.DEFAULT_LOGICAL_AXIS_RULES: qkv/heads/mlp/vocab
# on "tensor", kv heads replicated-by-default is NOT wanted here — the
# KV cache shards on heads, so wk/wv shard their head-output dim too
# (falling back to replicated at apply time when kv_heads don't divide).
DEFAULT_RULES: Tuple[Tuple[str, PartitionSpec], ...] = (
    # attention: column-split q/k/v (output dim = heads*head_dim),
    # row-split output projection (input dim = heads*head_dim) — the
    # reference AutoTP column/row pattern, allreduce after wo
    (r"attn/w[qkv]$", PartitionSpec(None, "tensor")),
    (r"attn/wo$", PartitionSpec("tensor", None)),
    (r"attn/b[qkv]$", PartitionSpec("tensor")),
    (r"attn/bo$", PartitionSpec()),
    # MLP: column-split in/gate, row-split out, allreduce after wo
    (r"mlp/(wi|wg|res_wi|res_wg)$", PartitionSpec(None, "tensor")),
    (r"mlp/(wo|res_wo)$", PartitionSpec("tensor", None)),
    (r"mlp/(bi|res_bi)$", PartitionSpec("tensor")),
    (r"mlp/(bo|res_bo|gate|coef_w|coef_b)", PartitionSpec()),
    # embeddings / lm head: vocab-split (no collective on the logits
    # matmul — the contraction dim stays replicated)
    (r"embed/tok$", PartitionSpec("tensor", None)),
    (r"lm_head/w$", PartitionSpec(None, "tensor")),
    (r"lm_head/b$", PartitionSpec("tensor")),
    # norms, positional tables, heads' scalar leaves: replicate
    (r".*", PartitionSpec()),
)

# Rules describe a weight's TRAILING dims — the matmul dims every rule
# cares about sit last, while leading dims (the stacked "layers" scan
# dim, an MoE expert dim) are stack dims these rules never shard. A
# matched spec shorter than the leaf's rank is therefore LEFT-padded
# with None (see _align_spec): P(None, "tensor") on a stacked MoE wi
# (layers, expert, embed, mlp) lands "tensor" on mlp hidden, not on the
# expert dim a trailing pad would hit.


def tree_path_names(params, sep: str = "/"):
    """Flatten a param pytree to ``[(path_name, leaf), ...]`` with
    ``sep``-joined string paths (dict keys / sequence indices / attr
    names), the name format the regex rules match against."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out = []
    for path, leaf in flat:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            elif hasattr(p, "name"):
                parts.append(str(p.name))
            else:  # pragma: no cover - future path types
                parts.append(str(p))
        out.append((sep.join(parts), leaf))
    return out


def normalize_rules(rules) -> List[Tuple[str, PartitionSpec]]:
    """Canonicalize a rule table: entries may be ``(regex,
    PartitionSpec)`` or the JSON-friendly config form ``[regex, [axis,
    ...]]`` where each axis is a mesh-axis name, a list of names, or
    None. Returns ``[(regex, PartitionSpec)]``."""
    out = []
    for entry in rules:
        pattern, spec = entry[0], entry[1]
        if not isinstance(spec, PartitionSpec):
            axes = []
            for ax in (spec if isinstance(spec, (list, tuple)) else [spec]):
                if isinstance(ax, list):
                    ax = tuple(ax)
                axes.append(ax)
            spec = PartitionSpec(*axes)
        out.append((str(pattern), spec))
    return out


def _align_spec(spec: PartitionSpec, shape) -> PartitionSpec:
    """Align a matched rule spec to a leaf's rank: rules describe the
    TRAILING dims, so a shorter spec is left-padded with None — the
    stacked layers scan dim and any MoE expert dim stay unsharded while
    the matmul dims the rule names keep their placement. Empty specs
    (replicate) and exact-rank specs pass through."""
    if len(spec) == 0 or len(spec) >= len(shape):
        return spec
    return PartitionSpec(*([None] * (len(shape) - len(spec)) + list(spec)))


def _spec_for(name: str, shape, compiled):
    """First-match-wins rule lookup for ONE leaf (shared by the
    whole-tree and per-leaf-override paths so their matching semantics
    can never diverge): the rank-aligned spec of the first regex that
    ``search``-matches the ``/``-joined path, ``PartitionSpec()`` for
    scalars/1-element leaves, or None when nothing matches."""
    if len(shape) == 0 or int(np.prod(shape)) == 1:
        return PartitionSpec()
    for pat, spec in compiled:
        if pat.search(name) is not None:
            return _align_spec(spec, shape)
    return None


def match_partition_rules(rules, params, on_miss: str = "error"):
    """PartitionSpec pytree for ``params``: each leaf takes the spec of
    the FIRST rule whose regex ``search``-matches its ``/``-joined path
    (rank-aligned per _align_spec). Scalars (and 1-element leaves) never
    partition. ``on_miss``: ``"error"`` raises naming the unmatched
    param (the EasyLM contract — a silent replicate hides a sharding
    bug); ``"replicate"`` maps misses to ``PartitionSpec()`` (the
    catch-all ``(".*", P())`` tail in DEFAULT_RULES has the same effect
    explicitly)."""
    compiled = [(re.compile(pat), spec) for pat, spec in normalize_rules(rules)]

    def get_spec(name, leaf):
        spec = _spec_for(name, getattr(leaf, "shape", ()), compiled)
        if spec is not None:
            return spec
        if on_miss == "replicate":
            return PartitionSpec()
        raise ValueError(f"no partition rule matches param {name!r}")

    flat = tree_path_names(params)
    specs = [get_spec(name, leaf) for name, leaf in flat]
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, specs)


def _clip_spec_to_mesh(spec: PartitionSpec, shape, mesh: Mesh) -> PartitionSpec:
    """Drop spec axes a dim cannot honour on ``mesh`` (dim size not
    divisible by the axis product, or axis missing): jax would raise at
    placement, but a rule table is written once per model family and must
    degrade per-weight — e.g. 3 kv_heads on tensor=2 replicates wk/wv
    while wq/wo stay sharded, exactly like _decode_shardings' kv_tensor
    fallback for the cache."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries[: len(shape)]):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        keep = []
        factor = 1
        for ax in axes:
            size = mesh.shape.get(ax, 1)
            if size > 1 and dim % (factor * size) == 0:
                keep.append(ax)
                factor *= size
        out.append(keep[0] if len(keep) == 1 else (tuple(keep) or None))
    return PartitionSpec(*out)


def partition_params(mesh: Mesh, abstract_params, rules=None,
                     on_miss: str = "replicate"):
    """NamedSharding pytree for ``abstract_params`` from a regex rule
    table (``rules`` tried first when given, then DEFAULT_RULES), each
    spec clipped to what the mesh and the weight's actual dims support.
    This is the whole module_inject flow for a mesh backend: returns the
    ``param_shardings`` every compiled serving program takes."""
    table = normalize_rules(rules or ()) + normalize_rules(DEFAULT_RULES)
    pspecs = match_partition_rules(table, abstract_params, on_miss=on_miss)
    return jax.tree.map(
        lambda leaf, spec: NamedSharding(
            mesh, _clip_spec_to_mesh(spec, getattr(leaf, "shape", ()), mesh)),
        abstract_params, pspecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def apply_rule_overrides(mesh: Mesh, abstract_params, base_shardings, rules):
    """Overlay USER regex rules onto an existing sharding pytree: leaves
    whose path matches a rule take that rule's (mesh-clipped) spec;
    everything else KEEPS its base placement. This is how config
    ``mesh.rules`` composes with a model's own ``logical_specs``
    annotations — the override is per-leaf, so one attention rule cannot
    silently strip the expert/vocab intent the annotations carry for the
    rest of the tree (``use_rules`` is the whole-tree regex switch)."""
    compiled = [(re.compile(p), s) for p, s in normalize_rules(rules)]
    flat = tree_path_names(abstract_params)
    base_leaves = jax.tree_util.tree_leaves(base_shardings)
    assert len(flat) == len(base_leaves), (len(flat), len(base_leaves))
    out = []
    for (name, leaf), base in zip(flat, base_leaves):
        shape = getattr(leaf, "shape", ())
        # scalars keep their base placement (a replicated scalar stays
        # replicated either way; never let a rule "match" one)
        spec = None if len(shape) == 0 or int(np.prod(shape)) == 1 \
            else _spec_for(name, shape, compiled)
        if spec is None:
            out.append(base)
        else:
            out.append(NamedSharding(mesh, _clip_spec_to_mesh(spec, shape, mesh)))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(abstract_params), out)


def serving_mesh(data: int = 1, tensor: int = 1, devices=None) -> Mesh:
    """A ``("data", "tensor")``-shaped serving mesh over the FIRST
    ``data*tensor`` devices — unlike ``comm.init_distributed`` it builds
    subset meshes (an 8-device host can carry a 1x2 serving mesh for a
    virtual-mesh A/B) and never touches the global comm state, so two
    engines with different widths coexist in one process (the
    sharded-vs-replicated loadgen A/B). Axis order follows comm.MESH_AXES
    (tensor innermost: contiguous devices, fastest ICI)."""
    from deepspeed_tpu import comm

    devices = list(devices if devices is not None else jax.devices())
    need = int(data) * int(tensor)
    if need < 1:
        raise ValueError(f"mesh needs >= 1 device, got {data}x{tensor}")
    if need > len(devices):
        raise ValueError(
            f"mesh {data}x{tensor} needs {need} devices, "
            f"only {len(devices)} available")
    return comm.build_mesh({"data": data, "tensor": tensor},
                           devices=devices[:need])


def parse_mesh_arg(spec: str) -> Dict[str, int]:
    """``"DATA:TENSOR"`` (the ds_loadgen/prewarm ``--mesh`` syntax, e.g.
    ``1:2``) or ``"axis=N,axis=M"`` → a mesh-shape dict."""
    spec = spec.strip()
    if "=" in spec:
        out = {}
        for part in spec.split(","):
            ax, _, n = part.partition("=")
            out[ax.strip()] = int(n)
        return out
    lo, sep, hi = spec.partition(":")
    if not sep:
        raise ValueError(f"--mesh wants DATA:TENSOR, got {spec!r}")
    return {"data": int(lo), "tensor": int(hi)}


def mesh_tensor_width(mesh: Optional[Mesh]) -> int:
    """Size of the ``tensor`` axis (1 when the mesh has none)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("tensor", 1))


def kv_shard_width(mesh: Optional[Mesh], cfg) -> int:
    """How many ways the KV cache's heads axis is ACTUALLY split on this
    mesh — the ONE divisor behind per-chip ``kv_bytes_read`` accounting.
    The rule is the cache format's (the sharding of every decode program
    is built from the same one)."""
    from deepspeed_tpu.ops.transformer import kv_cache

    return kv_cache.shard_width(mesh, cfg)
