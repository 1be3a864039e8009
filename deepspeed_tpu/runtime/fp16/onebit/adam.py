"""1-bit Adam.

TPU-native counterpart of the reference's ``OnebitAdam``
(runtime/fp16/onebit/adam.py): ordinary Adam for ``freeze_step`` warmup
steps; afterwards the second moment is *frozen* and the momentum is passed
through an error-feedback 1-bit (sign + scale) quantizer before being used —
the numerics of the compressed-allreduce pipeline.

Execution-model note: in the reference, post-freeze each worker updates
momentum with local gradients and a compressed allreduce averages it
(nccl.py compressed_allreduce). Under pjit the gradient reduction is inserted
by GSPMD *before* the optimizer runs, so every device holds identical reduced
gradients; quantizing the momentum here — deterministically, with persistent
error-feedback buffers in the optimizer state — reproduces the same update
sequence the reference's workers converge to, with the wire-compression
itself available for shard_map loops via
``runtime/comm/compressed.compressed_allreduce``.
"""

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.tree import LeafTuple, unpack_leaves


class OnebitAdamState(NamedTuple):
    step: jnp.ndarray  # i32 scalar
    exp_avg: Any  # momentum pytree
    exp_avg_sq: Any  # variance pytree (frozen after freeze_step)
    error: Any  # error-feedback pytree (compression residual)
    # compressed-backend wire buffers: per leaf {"w": [padded], "s": [padded/W]}
    comm_state: Any = ()


def _pad_len(n: int, world: int) -> int:
    return int(-(-n // world) * world)


def _wire_axis() -> tuple:
    """(mesh, axis_name, world) for the compressed momentum sync: the larger
    of the two DP axes (``data``/``fsdp``). (None, None, 1) when no mesh is
    initialized or both axes are trivial — the caller falls back to the
    deterministic single-program quantizer."""
    try:
        from deepspeed_tpu import comm

        mesh = comm.get_mesh()
    except Exception:
        return None, None, 1
    sizes = {ax: int(mesh.shape.get(ax, 1)) for ax in ("data", "fsdp")}
    axis = max(sizes, key=sizes.get)
    return (mesh, axis, sizes[axis]) if sizes[axis] > 1 else (None, None, 1)


def _shard_map_no_repcheck(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _compressed_sync_leaf(m, cs, mesh, axis, world):
    """Momentum allreduce over mesh axis ``axis`` through the REAL compressed
    wire path (runtime/comm/compressed.compressed_allreduce inside shard_map):
    int8 signs + per-chunk f32 scales ride the all_to_all/all_gather, ~4x
    less traffic than an fp32 allreduce (26x with sub-byte packing in the
    reference; int8 is the natural TPU wire type). Returns (synced momentum
    average, new buffers). All inputs are replicated over ``axis`` (grads were
    GSPMD-reduced), so outputs are too — rep-checking is disabled for the
    error buffers, whose replication is by-construction."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.runtime.comm.compressed import CompressionState, compressed_allreduce

    shape = m.shape
    flat = m.reshape(-1).astype(jnp.float32)
    pad = cs["w"].shape[0] - flat.shape[0]
    flat = jnp.pad(flat, (0, pad))

    def inner(flat, we, se):
        out, st = compressed_allreduce(flat, CompressionState(we, se), axis)
        return out / world, st.worker_error, st.server_error

    out, we, se = _shard_map_no_repcheck(
        inner, mesh, in_specs=(P(), P(), P()), out_specs=(P(), P(), P())
    )(flat, cs["w"], cs["s"])
    n = int(np.prod(shape or (1,)))
    return out[:n].reshape(shape), {"w": we, "s": se}


def _quantize_ef(m, err):
    """Sign/scale quantization with error feedback on one leaf."""
    comp = m + err
    scale = jnp.mean(jnp.abs(comp))
    q = scale * jnp.sign(comp)
    return q, comp - q


@dataclass(frozen=True)
class OnebitAdam:
    """Adam with 1-bit compressed momentum after ``freeze_step`` warmup
    (reference: runtime/fp16/onebit/adam.py, ``freeze_step`` / ``comm_backend_name``)."""

    lr: float = 1e-3
    betas: tuple = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    freeze_step: int = 100
    cuda_aware: bool = False  # accepted for config parity; meaningless on TPU
    comm_backend_name: str = "xla"

    def init(self, params) -> OnebitAdamState:
        z = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        comm_state = ()
        if self.comm_backend_name == "compressed":
            mesh, axis, world = _wire_axis()
            if world > 1:
                comm_state = jax.tree.map(
                    lambda p: {
                        "w": jnp.zeros((_pad_len(int(np.prod(p.shape or (1,))), world),), jnp.float32),
                        "s": jnp.zeros((_pad_len(int(np.prod(p.shape or (1,))), world) // world,), jnp.float32),
                    },
                    params,
                )
                n_total = sum(int(np.prod(p.shape or (1,))) for p in jax.tree.leaves(params))
                # per-member wire bytes per sync: phase-1 all_to_all sends the
                # int8 signs (N bytes) + W f32 scales; phase-2 all_gather
                # sends N/W int8 + one f32 scale. fp32 ring allreduce moves
                # ~2*4*N bytes per member.
                wire = n_total * (1 + 1 / world) + 4 * (world + 1)
                logger.info(
                    f"OnebitAdam compressed backend: axis={axis} world={world} "
                    f"momentum elements={n_total:,}; wire ≈ {wire / 1e6:.2f} MB/sync vs "
                    f"{8 * n_total / 1e6:.2f} MB fp32-allreduce ({8 * n_total / wire:.1f}x reduction)"
                )
            else:
                logger.warning(
                    "OnebitAdam comm_backend_name='compressed' but no non-trivial "
                    "data/fsdp mesh axis — falling back to single-program quantizer"
                )
        return OnebitAdamState(
            step=jnp.zeros((), jnp.int32), exp_avg=z(), exp_avg_sq=z(), error=z(), comm_state=comm_state
        )

    def update(self, grads, state: OnebitAdamState, params, lr=None):
        lr = self.lr if lr is None else lr
        b1, b2 = self.betas
        step = state.step + 1
        frozen = step > self.freeze_step
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        # variance frozen at freeze_step keeps that step's bias: correct with
        # the freeze-time factor (≈1 for the reference's typical multi-k
        # freeze_step, essential for small ones)
        bc2_frozen = 1.0 - b2 ** jnp.minimum(step, self.freeze_step).astype(jnp.float32)

        def leaf(g, m, v, e, p):
            g = g.astype(jnp.float32)
            # L2 (folded into the moments), matching torch Adam / the
            # reference's warmup stage — not decoupled AdamW decay
            if self.weight_decay > 0.0:
                g = g + self.weight_decay * p.astype(jnp.float32)
            m_new = b1 * m + (1.0 - b1) * g
            # variance frozen post-warmup (reference adam.py: exp_avg_sq is
            # not updated once compression begins)
            v_new = jnp.where(frozen, v, b2 * v + (1.0 - b2) * g * g)
            m_q, e_new = _quantize_ef(m_new, e)
            m_used = jnp.where(frozen, m_q, m_new)
            e_out = jnp.where(frozen, e_new, e)
            # reference: bias correction only during warmup stage
            denom = jnp.where(frozen, jnp.sqrt(v_new / bc2_frozen) + self.eps, jnp.sqrt(v_new / bc2) + self.eps)
            numer = jnp.where(frozen, m_used, m_used / bc1)
            upd = -lr * numer / denom
            return LeafTuple((upd, m_used, v_new, e_out))

        if self.comm_backend_name == "compressed" and state.comm_state != ():
            mesh, axis, world = _wire_axis()
            if world > 1:
                return self._update_compressed(
                    grads, state, params, lr, step, frozen, bc1, bc2, bc2_frozen, mesh, axis, world
                )

        out = jax.tree.map(leaf, grads, state.exp_avg, state.exp_avg_sq, state.error, params)
        upd, m, v, e = unpack_leaves(out, 4)
        return upd, OnebitAdamState(step=step, exp_avg=m, exp_avg_sq=v, error=e, comm_state=state.comm_state)

    def _update_compressed(self, grads, state, params, lr, step, frozen, bc1, bc2, bc2_frozen, mesh, axis, world):
        """Post-freeze momentum sync through the real compressed wire
        (shard_map + compressed_allreduce) instead of the single-program
        quantizer. Error feedback lives in the wire buffers (worker/server),
        not ``state.error``; per-destination-chunk scales replace the
        whole-tensor scale, matching the reference wire format
        (runtime/comm/nccl.py compressed_allreduce chunking)."""
        b1, b2 = self.betas

        g_l, treedef = jax.tree.flatten(grads)
        m_l = treedef.flatten_up_to(state.exp_avg)
        v_l = treedef.flatten_up_to(state.exp_avg_sq)
        p_l = treedef.flatten_up_to(params)
        cs_l = treedef.flatten_up_to(state.comm_state)

        upd_o, m_o, v_o, cs_o = [], [], [], []
        for g, m, v, p, cs in zip(g_l, m_l, v_l, p_l, cs_l):
            g = g.astype(jnp.float32)
            if self.weight_decay > 0.0:
                g = g + self.weight_decay * p.astype(jnp.float32)
            m_new = b1 * m + (1.0 - b1) * g
            v_new = jnp.where(frozen, v, b2 * v + (1.0 - b2) * g * g)
            # lax.cond keeps the wire collectives out of warmup steps entirely
            # (the reference's warmup stage is plain Adam with no compression
            # traffic, onebit/adam.py freeze_step)
            m_used, cs_out = jax.lax.cond(
                frozen,
                lambda mm, cc: _compressed_sync_leaf(mm, cc, mesh, axis, world),
                lambda mm, cc: (mm, cc),
                m_new,
                cs,
            )
            denom = jnp.where(frozen, jnp.sqrt(v_new / bc2_frozen) + self.eps, jnp.sqrt(v_new / bc2) + self.eps)
            numer = jnp.where(frozen, m_used, m_used / bc1)
            upd_o.append(-lr * numer / denom)
            m_o.append(m_used)
            v_o.append(v_new)
            cs_o.append(cs_out)

        return treedef.unflatten(upd_o), OnebitAdamState(
            step=step,
            exp_avg=treedef.unflatten(m_o),
            exp_avg_sq=treedef.unflatten(v_o),
            error=state.error,
            comm_state=treedef.unflatten(cs_o),
        )
