"""ZeRO as sharding policy.

This module is the TPU-native core of ZeRO. Where the reference hand-schedules
partitioning (stage_1_and_2.py:90 flat fp32 partitions + bucketed reduction;
stage3.py:65 + partition_parameters.py:601 gather-on-demand), on TPU the same
memory law — shard O(params) state over the data-parallel dimension — is
expressed as *placement*: we assign every array in the train state a
``NamedSharding`` over the ``fsdp`` mesh axis and let GSPMD insert the
all-gathers / reduce-scatters the reference implements by hand.

  stage 0: params/grads/opt replicated across data axes (grads psum'd)
  stage 1: optimizer state (m, v, fp32 master) sharded over ``fsdp``
  stage 2: + gradient accumulation buffer sharded over ``fsdp``
           (XLA reduce-scatters into the shard instead of all-reducing)
  stage 3: + parameters stored sharded over ``fsdp``; each use site
           all-gathers (and the backward reduce-scatters) — the compiled
           analogue of partitioned_param_coordinator.py's prefetch trace,
           with XLA's latency-hiding scheduler doing the overlap.

Tensor-parallel sharding composes: params carry *logical axis names*
(('embed','mlp') etc); rules map logical names → mesh axes; ZeRO then shards a
remaining free dimension over ``fsdp``.
"""

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical axis name -> mesh axis (or tuple of axes). None = replicated.
DEFAULT_LOGICAL_AXIS_RULES = (
    ("batch", ("data", "fsdp")),
    ("seq", "sequence"),
    ("vocab", "tensor"),
    ("embed", None),
    ("mlp", "tensor"),
    ("heads", "tensor"),
    ("kv", None),
    ("qkv", "tensor"),
    ("expert", "expert"),
    ("layers", None),
    ("stage", "pipe"),
    ("norm", None),
)


def logical_to_mesh_spec(logical_names: Optional[Sequence[Optional[str]]], rules=None) -> PartitionSpec:
    """Map a tuple of per-dimension logical names to a PartitionSpec."""
    if logical_names is None:
        return PartitionSpec()
    rules = dict(rules if rules is not None else DEFAULT_LOGICAL_AXIS_RULES)
    out = []
    used = set()
    for name in logical_names:
        axes = rules.get(name) if name is not None else None
        if axes is None:
            out.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        axes_t = tuple(a for a in axes_t if a not in used)
        used.update(axes_t)
        if not axes_t:
            out.append(None)
        elif len(axes_t) == 1:
            out.append(axes_t[0])
        else:
            out.append(axes_t)
    return PartitionSpec(*out)


def drop_indivisible_axes(spec: PartitionSpec, shape, mesh: Mesh) -> PartitionSpec:
    """Replicate a dim over the mesh axes its size does not divide.

    The logical rules are written once per model family; a weight whose
    dim an axis cannot split evenly (GPT-2's vocab of 50257 over any
    ``tensor`` > 1) must degrade to replicated on that dim — jax refuses
    an uneven ``NamedSharding`` at placement, and the whole engine with
    it. Axes of size 1 are kept: they split nothing and cost nothing."""
    out = []
    for dim, entry in zip(shape, tuple(spec)):
        axes = () if entry is None else (entry,) if isinstance(entry, str) else tuple(entry)
        keep, factor = [], 1
        for ax in axes:
            if dim % (factor * mesh.shape[ax]) == 0:
                keep.append(ax)
                factor *= mesh.shape[ax]
        out.append(None if not keep else keep[0] if len(keep) == 1 else tuple(keep))
    return PartitionSpec(*out)


def _spec_axes(spec: PartitionSpec):
    used = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def add_fsdp_axis(shape: Tuple[int, ...], spec: PartitionSpec, mesh: Mesh, min_shard_elems: int = 0) -> PartitionSpec:
    """Shard one free dimension of ``shape`` over the ``fsdp`` axis.

    Picks the largest dimension that is (a) not already sharded and (b)
    divisible by the fsdp axis size *after* any existing sharding on that dim.
    Small tensors (biases, norms) below ``min_shard_elems`` stay replicated —
    the analogue of the reference's param_persistence_threshold
    (zero/config.py stage3_param_persistence_threshold).
    """
    fsdp = mesh.shape.get("fsdp", 1)
    if fsdp <= 1:
        return spec
    if _spec_axes(spec) >= {"fsdp"}:
        return spec
    if int(np.prod(shape or (1,))) < max(min_shard_elems, fsdp):
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    # already-applied shard factor per dim
    def _factor(entry):
        if entry is None:
            return 1
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        return int(np.prod([mesh.shape[a] for a in axes]))

    best_dim, best_size = -1, 0
    for d, size in enumerate(shape):
        if entries[d] is not None:
            continue
        if size % fsdp == 0 and size > best_size:
            best_dim, best_size = d, size
    if best_dim < 0:
        # fall back: allow sharding a dim that's TP-sharded if divisible by both
        for d, size in enumerate(shape):
            entry = entries[d]
            if entry is None:
                continue
            if "fsdp" not in ((entry,) if isinstance(entry, str) else entry):
                per_shard = size // _factor(entry)
                if per_shard % fsdp == 0:
                    prev = (entry,) if isinstance(entry, str) else tuple(entry)
                    entries[d] = prev + ("fsdp",)
                    return PartitionSpec(*entries)
        return spec  # nothing divisible: stays replicated
    entries[best_dim] = "fsdp"
    return PartitionSpec(*entries)


class ShardingPolicy:
    """Resolves NamedShardings for every component of the train state.

    ``logical_specs`` is an optional pytree (matching params) of per-dim
    logical-name tuples; params without annotations get pure-fsdp treatment.
    """

    def __init__(self, mesh: Mesh, stage: int, logical_specs=None, rules=None, min_shard_elems: int = 0):
        assert stage in (0, 1, 2, 3)
        self.mesh = mesh
        self.stage = stage
        self.rules = rules if rules is not None else DEFAULT_LOGICAL_AXIS_RULES
        self.logical_specs = logical_specs
        self.min_shard_elems = min_shard_elems

    # -- per-leaf spec resolution ---------------------------------------
    def _tp_spec(self, shape, leaf_logical) -> PartitionSpec:
        return drop_indivisible_axes(
            logical_to_mesh_spec(leaf_logical, self.rules), shape, self.mesh)

    def param_spec(self, shape, leaf_logical=None) -> PartitionSpec:
        spec = self._tp_spec(shape, leaf_logical)
        if self.stage >= 3:
            spec = add_fsdp_axis(tuple(shape), spec, self.mesh, self.min_shard_elems)
        return spec

    def opt_spec(self, shape, leaf_logical=None) -> PartitionSpec:
        spec = self._tp_spec(shape, leaf_logical)
        if self.stage >= 1:
            spec = add_fsdp_axis(tuple(shape), spec, self.mesh, 0)
        return spec

    def grad_spec(self, shape, leaf_logical=None) -> PartitionSpec:
        spec = self._tp_spec(shape, leaf_logical)
        if self.stage >= 2:
            spec = add_fsdp_axis(tuple(shape), spec, self.mesh, 0)
        return spec

    # -- pytree-level ----------------------------------------------------
    def _tree_specs(self, abstract_tree, spec_fn):
        logical = self.logical_specs
        if logical is None:
            return jax.tree.map(lambda x: spec_fn(x.shape, None), abstract_tree)
        return jax.tree.map(
            lambda x, names: spec_fn(x.shape, names),
            abstract_tree,
            logical,
            is_leaf=lambda x: x is None,
        )

    def param_pspecs(self, abstract_params):
        return self._tree_specs(abstract_params, self.param_spec)

    def grad_pspecs(self, abstract_params):
        return self._tree_specs(abstract_params, self.grad_spec)

    def opt_pspecs(self, abstract_params):
        return self._tree_specs(abstract_params, self.opt_spec)

    def _to_shardings(self, pspecs):
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s),
            pspecs,
            is_leaf=lambda s: isinstance(s, PartitionSpec),
        )

    def param_shardings(self, abstract_params):
        return self._to_shardings(self.param_pspecs(abstract_params))

    def grad_shardings(self, abstract_params):
        return self._to_shardings(self.grad_pspecs(abstract_params))

    def opt_shardings(self, abstract_params):
        return self._to_shardings(self.opt_pspecs(abstract_params))

    def batch_spec(self) -> PartitionSpec:
        # batch rows over DP; the seq dim over the sequence axis (harmless
        # when that axis is size 1; required for ring/Ulysses attention)
        return PartitionSpec(("data", "fsdp"), "sequence")

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec())

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())
