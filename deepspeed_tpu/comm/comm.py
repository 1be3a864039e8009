"""Distributed communication facade: a device mesh + named-axis registry.

TPU-native replacement for the reference's ``deepspeed/comm`` package
(``comm/comm.py``: ``init_distributed``, ``all_reduce``, process groups over
NCCL/Gloo/MPI). On TPU there is no process-group object to thread through the
code: collectives are ``jax.lax`` ops over *named mesh axes*, inserted by XLA
and scheduled on ICI/DCN. This module therefore keeps the reference's facade
shape (init/rank/world-size/"groups") but the group handle is an axis name (or
tuple of names) on a global ``jax.sharding.Mesh``.

Rank/world-size semantics:
  - ``get_rank()``/``get_world_size()`` — global device index / device count
    (reference: torch.distributed rank over all GPUs).
  - process-level helpers ``get_process_rank``/``get_process_count`` expose the
    multi-controller host grid (one JAX process per TPU host).

Collective wrappers (`all_reduce`, `all_gather`, `reduce_scatter`,
`all_to_all`, `ppermute`) are meant to be called *inside* ``shard_map``-mapped
functions where axis names are bound; at top level, GSPMD inserts collectives
from shardings and these wrappers are unnecessary.
"""

import datetime
import os
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.utils.logging import log_dist, logger

# Canonical mesh axis order: slowest-varying (DCN-adjacent) first. pipe/data
# cross hosts cheaply (point-to-point / infrequent sync); tensor and sequence
# need the fastest ICI bandwidth so they sit innermost (contiguous devices).
MESH_AXES = ("pipe", "data", "fsdp", "expert", "sequence", "tensor")

ReduceOp = type("ReduceOp", (), {"SUM": "sum", "AVG": "avg", "MAX": "max", "MIN": "min", "PROD": "prod"})


@dataclass
class CommState:
    mesh: Optional[Mesh] = None
    initialized: bool = False
    timers_enabled: bool = False
    comms_logger: Optional[object] = None
    axis_sizes: dict = field(default_factory=dict)


_STATE = CommState()


def is_initialized() -> bool:
    return _STATE.initialized


def _normalize_mesh_shape(mesh_shape: Optional[dict], n_devices: int) -> dict:
    """Fill in a full {axis: size} dict; -1 means 'absorb remaining devices'."""
    shape = dict(mesh_shape or {})
    # If the user didn't pin 'data' and gave no wildcard, 'data' absorbs the
    # remaining devices (the reference's plain-DP default).
    if "data" not in shape and -1 not in shape.values():
        shape["data"] = -1
    for ax in MESH_AXES:
        shape.setdefault(ax, 1)
    unknown = set(shape) - set(MESH_AXES)
    if unknown:
        raise ValueError(f"Unknown mesh axes {unknown}; valid axes: {MESH_AXES}")
    wildcards = [ax for ax, s in shape.items() if s == -1]
    # 'data' is the default absorber (MeshConfig defaults it to -1); an
    # explicit -1 on another axis takes precedence over that default.
    if len(wildcards) > 1 and "data" in wildcards:
        shape["data"] = 1
        wildcards.remove("data")
    fixed = int(np.prod([s for s in shape.values() if s != -1]))
    if len(wildcards) > 1:
        raise ValueError("At most one mesh axis may be -1")
    if wildcards:
        if n_devices % fixed != 0:
            raise ValueError(f"device count {n_devices} not divisible by fixed mesh product {fixed}")
        shape[wildcards[0]] = n_devices // fixed
    total = int(np.prod(list(shape.values())))
    if total != n_devices:
        raise ValueError(f"mesh shape {shape} covers {total} devices but {n_devices} are available")
    return shape


def split_dcn_shape(mesh_shape: Optional[dict], dcn_mesh_shape: Optional[dict], n_devices: int):
    """Validate and resolve a (possibly hybrid) mesh request into
    (ici_sizes, dcn_sizes, combined_sizes) full per-axis dicts. The single
    source of the DCN granule math (build_mesh and TpuConfig both use it)."""
    mesh_shape = dict(mesh_shape or {})
    popped = mesh_shape.pop("dcn", None)
    dcn_mesh_shape = dcn_mesh_shape or popped
    dcn_mesh_shape = dict(dcn_mesh_shape or {})
    unknown = set(dcn_mesh_shape) - set(MESH_AXES)
    if unknown:
        raise ValueError(f"Unknown DCN mesh axes {unknown}; valid axes: {MESH_AXES}")
    dcn = {ax: int(dcn_mesh_shape.get(ax, 1)) for ax in MESH_AXES}
    n_dcn = int(np.prod(list(dcn.values())))
    if n_devices % n_dcn != 0:
        raise ValueError(f"{n_devices} devices not divisible by {n_dcn} DCN granules (dcn={dcn_mesh_shape})")
    ici = _normalize_mesh_shape(mesh_shape, n_devices // n_dcn)
    combined = {ax: ici[ax] * dcn[ax] for ax in MESH_AXES}
    return ici, dcn, combined


def build_mesh(mesh_shape: Optional[dict] = None, devices=None, dcn_mesh_shape: Optional[dict] = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    mesh_shape = dict(mesh_shape or {})
    popped = mesh_shape.pop("dcn", None)
    dcn_mesh_shape = dcn_mesh_shape or popped
    if dcn_mesh_shape:
        return _build_hybrid_mesh(mesh_shape, dcn_mesh_shape, devices)
    shape = _normalize_mesh_shape(mesh_shape, len(devices))
    dims = tuple(shape[ax] for ax in MESH_AXES)
    dev_array = np.asarray(devices).reshape(dims)
    return Mesh(dev_array, MESH_AXES)


def _build_hybrid_mesh(ici_shape: dict, dcn_shape: dict, devices) -> Mesh:
    """Multi-slice mesh: per-axis size = dcn × ici, DCN as the outer (slow)
    dimension so collectives along an axis stay intra-slice whenever the ICI
    factor covers them (the reference's analogue is multi-node NCCL rings;
    the scaling-book recipe is 'data/pipe over DCN, everything else ICI')."""
    ici, dcn, _ = split_dcn_shape(ici_shape, dcn_shape, len(devices))
    dims_ici = tuple(ici[ax] for ax in MESH_AXES)
    dims_dcn = tuple(dcn[ax] for ax in MESH_AXES)
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(dims_ici, dims_dcn, devices)
    except ValueError as e:
        if "slice_index" not in str(e):
            raise
        # devices carry no slice topology (CPU test meshes, single-slice
        # platforms): contiguous-block assignment — functionally identical,
        # just without locality-aware granule ordering
        logger.warning("devices report no slice_index; using contiguous DCN granules")
        arr = np.asarray(devices).reshape(dims_dcn + dims_ici)
        k = len(MESH_AXES)
        order = [x for pair in ((i, i + k) for i in range(k)) for x in pair]
        dev_array = arr.transpose(order).reshape(
            tuple(d * i for d, i in zip(dims_dcn, dims_ici))
        )
    return Mesh(dev_array, MESH_AXES)


def init_distributed(
    dist_backend: str = "xla",
    mesh_shape: Optional[dict] = None,
    devices=None,
    dcn_mesh_shape: Optional[dict] = None,
    timeout: datetime.timedelta = None,
    verbose: bool = True,
    enable_comms_logging: bool = False,
    **_compat_kwargs,
):
    """Create the global device mesh (reference: comm/comm.py:526 rendezvous).

    In multi-controller mode JAX has already rendezvoused via
    ``jax.distributed.initialize`` (driven by the launcher); here we only shape
    the mesh. Defaults: all devices on the ``data`` axis.
    """
    if _STATE.initialized and mesh_shape is None and dcn_mesh_shape is None:
        return _STATE.mesh
    _maybe_init_multi_controller()
    mesh = build_mesh(mesh_shape, devices, dcn_mesh_shape=dcn_mesh_shape)
    _STATE.mesh = mesh
    _STATE.initialized = True
    _STATE.axis_sizes = {ax: mesh.shape[ax] for ax in mesh.axis_names}
    if enable_comms_logging:
        from deepspeed_tpu.comm.comms_logging import CommsLogger

        _STATE.comms_logger = CommsLogger()
    if verbose:
        log_dist(f"Initialized mesh {dict(mesh.shape)} over {mesh.devices.size} {dist_backend} devices", ranks=[0])
    return mesh


_MULTI_CONTROLLER_DONE = False


def _maybe_init_multi_controller():
    """Join the JAX coordinator when launched by dstpu (launcher/launch.py
    sets DSTPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID — the reference's
    MASTER_ADDR/RANK rendezvous, comm/comm.py:526)."""
    global _MULTI_CONTROLLER_DONE
    if _MULTI_CONTROLLER_DONE:
        return
    coord = os.environ.get("DSTPU_COORDINATOR")
    nprocs = int(os.environ.get("DSTPU_NUM_PROCESSES", "1"))
    if not coord or nprocs <= 1:
        _MULTI_CONTROLLER_DONE = True
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nprocs,
            process_id=int(os.environ["DSTPU_PROCESS_ID"]),
        )
        log_dist(f"joined coordinator {coord} as process "
                 f"{os.environ['DSTPU_PROCESS_ID']}/{nprocs}", ranks=[0])
    except RuntimeError as e:
        # Only the already-initialized case may be swallowed (jax raises
        # "distributed.initialize should only be called once."). A genuine
        # rendezvous failure at nprocs > 1 must be fatal: continuing would
        # silently degrade into N independent single-host jobs computing
        # wrong results (each would psum over its local mesh only). The
        # launcher's fail-fast logic reaps the rest of the job on exit.
        msg = str(e).lower()
        if "only be called once" in msg or "already initialized" in msg:
            logger.warning(f"jax.distributed.initialize skipped: {e}")
        else:
            raise
    _MULTI_CONTROLLER_DONE = True


def destroy():
    _STATE.mesh = None
    _STATE.initialized = False
    _STATE.axis_sizes = {}
    _STATE.comms_logger = None


def get_mesh() -> Mesh:
    if not _STATE.initialized:
        init_distributed(verbose=False)
    return _STATE.mesh


def set_mesh(mesh: Mesh):
    _STATE.mesh = mesh
    _STATE.initialized = True
    _STATE.axis_sizes = {ax: mesh.shape[ax] for ax in mesh.axis_names}


def get_comms_logger():
    return _STATE.comms_logger


def ensure_comms_logger():
    """Return the global CommsLogger, creating it if init_distributed ran
    without ``enable_comms_logging`` — the telemetry layer needs the volume
    counters regardless of how the mesh was brought up."""
    if _STATE.comms_logger is None:
        from deepspeed_tpu.comm.comms_logging import CommsLogger

        _STATE.comms_logger = CommsLogger()
    return _STATE.comms_logger


GroupLike = Union[None, str, Sequence[str]]


def _axes(group: GroupLike) -> Tuple[str, ...]:
    """Resolve a 'group' to mesh axis names. None = all axes (world)."""
    if group is None:
        return tuple(get_mesh().axis_names)
    if isinstance(group, str):
        return (group,)
    return tuple(group)


def get_world_size(group: GroupLike = None) -> int:
    mesh = get_mesh()
    return int(np.prod([mesh.shape[ax] for ax in _axes(group)]))


def get_rank(group: GroupLike = None) -> int:
    """Global (or per-group) index of this process's *first local device*.

    Single-controller (tests, one host): always 0 for the world group.
    Multi-controller: the position of this host's first device in the mesh.
    """
    mesh = get_mesh()
    first_local = jax.local_devices()[0]
    flat = list(mesh.devices.flat)
    try:
        global_idx = flat.index(first_local)
    except ValueError:
        return 0
    if group is None:
        return global_idx
    # coordinate of device along the group's axes
    coords = np.unravel_index(global_idx, mesh.devices.shape)
    axis_index = {ax: coords[i] for i, ax in enumerate(mesh.axis_names)}
    rank = 0
    for ax in _axes(group):
        rank = rank * mesh.shape[ax] + int(axis_index[ax])
    return rank


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", 0))


def get_process_rank() -> int:
    return jax.process_index()


def get_process_count() -> int:
    return jax.process_count()


def barrier(group: GroupLike = None):
    """Block until all previously dispatched device work completes.

    Runs a trivial program replicated over the whole mesh and fetches the
    result to host: per-device program queues are FIFO, so completion implies
    every earlier program on those devices finished; in multi-controller mode
    all processes execute the same global program, which is the rendezvous.
    The host fetch is the wait: the scalar cannot arrive before the program
    that makes it has run.
    """
    mesh = get_mesh()
    token = jax.jit(
        lambda: jax.numpy.zeros(()), out_shardings=NamedSharding(mesh, PartitionSpec())
    )()
    float(token)


# ---------------------------------------------------------------------------
# Collective wrappers — valid inside shard_map where axis names are bound.
# Reference API parity: comm/comm.py all_reduce :444, all_gather_into_tensor
# :290, reduce_scatter_tensor :273, all_to_all_single :324, broadcast.
# ---------------------------------------------------------------------------

def _log_op(name, tensor, group):
    if _STATE.comms_logger is not None:
        _STATE.comms_logger.append(name, tensor, _axes(group))


def all_reduce(tensor, op: str = ReduceOp.SUM, group: GroupLike = None):
    _log_op("all_reduce", tensor, group)
    axes = _axes(group)
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = jax.lax.psum(tensor, axes)
        if op == ReduceOp.AVG:
            out = out / get_world_size(group)
        return out
    if op == ReduceOp.MAX:
        return jax.lax.pmax(tensor, axes)
    if op == ReduceOp.MIN:
        return jax.lax.pmin(tensor, axes)
    if op == ReduceOp.PROD:
        gathered = jax.lax.all_gather(tensor, axes, axis=0)
        return jax.numpy.prod(gathered, axis=0)
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(tensor, group: GroupLike = None, axis: int = 0, tiled: bool = True):
    _log_op("all_gather", tensor, group)
    return jax.lax.all_gather(tensor, _axes(group), axis=axis, tiled=tiled)


def reduce_scatter(tensor, group: GroupLike = None, scatter_dimension: int = 0, tiled: bool = True):
    _log_op("reduce_scatter", tensor, group)
    return jax.lax.psum_scatter(tensor, _axes(group), scatter_dimension=scatter_dimension, tiled=tiled)


def all_to_all(tensor, group: GroupLike = None, split_axis: int = 0, concat_axis: int = 0, tiled: bool = True):
    _log_op("all_to_all", tensor, group)
    axes = _axes(group)
    assert len(axes) == 1, "all_to_all runs over a single mesh axis"
    return jax.lax.all_to_all(tensor, axes[0], split_axis=split_axis, concat_axis=concat_axis, tiled=tiled)


def ppermute(tensor, perm, group: GroupLike = None):
    _log_op("ppermute", tensor, group)
    axes = _axes(group)
    assert len(axes) == 1, "ppermute runs over a single mesh axis"
    return jax.lax.ppermute(tensor, axes[0], perm)


def broadcast(tensor, src: int = 0, group: GroupLike = None):
    """Select src's value on every member (psum of a where-masked value —
    ``where`` not multiply, so non-src members holding NaN/inf garbage
    can't poison the sum; bools ride as i32)."""
    _log_op("broadcast", tensor, group)
    axes = _axes(group)
    idx = axis_index(group)
    was_bool = tensor.dtype == jnp.bool_
    x = tensor.astype(jnp.int32) if was_bool else tensor
    x = jnp.where(idx == src, x, jnp.zeros_like(x))
    out = jax.lax.psum(x, axes)
    return out.astype(jnp.bool_) if was_bool else out


def axis_index(group: GroupLike = None):
    axes = _axes(group)
    idx = jax.lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * get_mesh().shape[ax] + jax.lax.axis_index(ax)
    return idx


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec(*spec))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec())


def batch_axes() -> Tuple[str, ...]:
    """Mesh axes the global batch is split over (ZeRO's DP dimension).
    Size-1 axes are harmless in a PartitionSpec, so no filtering needed."""
    return ("data", "fsdp")


def dp_world_size() -> int:
    mesh = get_mesh()
    return mesh.shape["data"] * mesh.shape["fsdp"]
