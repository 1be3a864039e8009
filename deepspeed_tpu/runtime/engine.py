"""Training engine.

TPU-native counterpart of the reference's ``runtime/engine.py``
(``DeepSpeedEngine``, engine.py:183). Keeps the adoption UX — wrap a model,
JSON config, ``forward → backward → step`` with gradient accumulation, loss
scaling, clipping, checkpointing, monitoring — but the execution model is
jit-first:

  - ``forward(batch)`` runs ONE compiled program that computes loss *and*
    gradients (JAX has no imperative autograd tape to split across calls) and
    accumulates them into a persistent, ZeRO-sharded buffer
    (reference: IPG buckets + grad hooks, stage_1_and_2.py:827; here the
    "bucketed reduce to owner ranks" is the buffer's reduce-scatter sharding).
  - ``backward(loss)`` is the micro-step boundary marker (API parity).
  - ``step()`` at the accumulation boundary runs the second compiled program:
    unscale, overflow check, global-norm clip, optimizer update on the
    (sharded) master/optimizer state, loss-scale transition, param refresh —
    the fused analogue of stage_1_and_2.py:1636 / stage3.py:1736, with the
    "allgather updated partitions" step inserted by XLA from shardings.

Engine model protocol: an object with ``init(rng) -> params`` and
``loss(params, batch, rng) -> scalar``; optional ``logical_specs(params)``
(tensor-parallel axis names) and ``flops_per_token(seq_len)`` (MFU logging).
"""

import os
import time
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu import comm
from deepspeed_tpu.ops.adam.basic_optimizers import SGD, Adagrad, Lion
from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb
from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.checkpoint_engine import integrity as ckpt_integrity
from deepspeed_tpu.runtime.config import TpuConfig
from deepspeed_tpu.runtime.fp16.loss_scaler import LossScaleState, create_loss_scaler
from deepspeed_tpu.runtime.lr_schedules import create_lr_scheduler
from deepspeed_tpu.runtime.zero.sharding import ShardingPolicy
from deepspeed_tpu.telemetry import compile_log
from deepspeed_tpu.telemetry.hlo_scopes import Scope
from deepspeed_tpu.telemetry.spans import host_span
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import EngineTimers, ThroughputTimer


class StepMetrics(NamedTuple):
    grad_norm: jnp.ndarray
    overflow: jnp.ndarray
    loss_scale: jnp.ndarray


class _FnModel:
    """Adapter: bare (loss_fn, params) -> engine model protocol."""

    def __init__(self, loss_fn, params):
        self._loss_fn = loss_fn
        self._params = params

    def init(self, rng):
        return self._params

    def loss(self, params, batch, rng=None):
        return self._loss_fn(params, batch, rng)

    def logical_specs(self, params):
        return None


class _PinnedParamsModel:
    """Wrap a model so ``init()`` returns caller-provided params
    (``initialize(model=..., params=...)``) — cast to fp32 masters, the
    dtype the engine's init path expects. Everything else (loss,
    logical_specs, cfg, flops_per_token, ...) delegates to the model.

    The ctor stores the tree UNTOUCHED (like _FnModel): converting leaves
    here would pin the backend before the multi-controller rendezvous and
    commit a full unsharded copy to the default device. The cast happens
    inside ``init()``, which the engine runs under a jit with sharded
    out_shardings, so leaves place directly into their shards."""

    def __init__(self, model, params):
        object.__setattr__(self, "_model", model)
        object.__setattr__(self, "_params", params)

    @staticmethod
    def _cast_host(x):
        a = np.asarray(x)  # jax arrays device_get; numpy stays on host
        if jnp.issubdtype(a.dtype, jnp.inexact):
            return a.astype(np.float32)
        return a

    def abstract(self):
        """fp32-master ShapeDtypeStructs for the pinned tree — the engine's
        shape-inference pass uses this instead of eval_shape(init), which
        would concretely fp32-copy (and device_get) every leaf."""

        def _aval(x):
            dt = np.result_type(x)
            if jnp.issubdtype(dt, jnp.inexact):
                dt = np.dtype(np.float32)
            return jax.ShapeDtypeStruct(np.shape(x), dt)

        return jax.tree.map(_aval, self._params)

    def init(self, rng):
        if isinstance(rng, jax.core.Tracer):
            # under jit/eval_shape the host cast below would either bake the
            # full tree into the program as constants or (worse) trace into
            # fabricated values — refuse loudly; callers want .abstract()
            # for shapes or .materialize() for sharded placement
            raise TypeError(
                "_PinnedParamsModel.init cannot run under a trace; use "
                ".abstract() for shape inference or .materialize(shardings) "
                "for placement")
        # HOST-side cast only: a jnp op here would commit every full leaf
        # to the default device
        return jax.tree.map(self._cast_host, self._params)

    def materialize(self, shardings):
        """device_put each host-cast leaf straight into its shard — the
        engine uses this instead of jitting init() (which would embed the
        whole tree as program constants)."""
        return jax.tree.map(
            lambda x, s: jax.device_put(self._cast_host(x), s),
            self._params, shardings,
        )

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __setattr__(self, name, value):
        # engine-side mutations (e.g. the PLD/random-LTD cfg flip) must
        # land on the wrapped model, whose bound methods read their own
        # attributes — a plain setattr here would silently shadow them
        setattr(self._model, name, value)


class OptaxWrapper:
    """Adapt an optax GradientTransformation to the init/update(lr) protocol."""

    def __init__(self, tx):
        self.tx = tx
        self.lr = 0.0  # lr lives inside the transformation

    def init(self, params):
        return self.tx.init(params)

    def update(self, grads, state, params, lr=None):
        return self.tx.update(grads, state, params=params)


OPTIMIZER_REGISTRY = {
    C.ADAM_OPTIMIZER: FusedAdam,
    C.ADAMW_OPTIMIZER: lambda **kw: FusedAdam(adam_w_mode=True, **kw),
    C.LAMB_OPTIMIZER: FusedLamb,
    C.SGD_OPTIMIZER: SGD,
    C.ADAGRAD_OPTIMIZER: Adagrad,
    C.LION_OPTIMIZER: Lion,
}


def _build_optimizer(opt_config):
    name = opt_config.type.lower()
    params = dict(opt_config.params)
    # torch-style names -> our fields
    if "betas" in params:
        params["betas"] = tuple(params["betas"])
    params.pop("torch_adam", None)
    params.pop("adam_w_mode", None) if name == C.ADAMW_OPTIMIZER else None
    if name in (C.ONEBIT_ADAM_OPTIMIZER, C.ZERO_ONE_ADAM_OPTIMIZER, C.ONEBIT_LAMB_OPTIMIZER):
        from deepspeed_tpu.runtime.fp16.onebit import build_onebit_optimizer

        return build_onebit_optimizer(name, params)
    cls = OPTIMIZER_REGISTRY.get(name)
    if cls is None:
        raise ValueError(f"Unknown optimizer '{opt_config.type}'; supported: {sorted(OPTIMIZER_REGISTRY)}")
    if name == C.ADAM_OPTIMIZER:
        # reference semantics: "Adam" defaults to adam_w_mode=True (ops/adam)
        params.setdefault("adam_w_mode", True)
    return cls(**params)


def _opt_state_shardings(abstract_state, abstract_params, param_shardings, replicated):
    """Assign shardings to an optimizer-state pytree: any subtree that is
    structurally a copy of the param tree gets the param shardings; everything
    else (step counters, scalars) is replicated."""
    ptree = jax.tree.structure(abstract_params)

    def is_param_like(sub):
        try:
            return jax.tree.structure(sub) == ptree
        except Exception:
            return False

    def mapper(sub):
        if is_param_like(sub):
            return param_shardings
        return replicated

    return jax.tree.map(mapper, abstract_state, is_leaf=is_param_like)


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves))


def _leaf_key(path) -> str:
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


class TpuEngine:
    def __init__(
        self,
        model,
        config: TpuConfig,
        optimizer=None,
        lr_scheduler=None,
        training_data=None,
        seed: Optional[int] = None,
        mesh=None,
        collate_fn=None,
    ):
        self.config = config
        self.model = model
        self.client_optimizer_provided = optimizer is not None

        # --- mesh / sharding policy (reference: init_distributed engine.py:249)
        if mesh is None:
            mesh = comm.init_distributed(mesh_shape=config.mesh.to_dict(), verbose=False)
        else:
            comm.set_mesh(mesh)
        self.mesh = mesh
        self.zero_stage = config.zero_config.stage

        seed = seed if seed is not None else config.seed
        rng = jax.random.PRNGKey(seed)
        self._rng, init_rng = jax.random.split(rng)

        if isinstance(model, _PinnedParamsModel):
            abstract_params = model.abstract()
        else:
            abstract_params = jax.eval_shape(model.init, init_rng)
        logical = None
        if hasattr(model, "logical_specs"):
            logical = model.logical_specs(abstract_params)
        self.policy = ShardingPolicy(
            mesh,
            stage=self.zero_stage,
            logical_specs=logical,
            min_shard_elems=config.zero_config.param_persistence_threshold if self.zero_stage >= 3 else 0,
        )
        self._abstract_params = abstract_params
        self.param_shardings = self.policy.param_shardings(abstract_params)
        self.grad_shardings = self.policy.grad_shardings(abstract_params)
        self.opt_shardings = self.policy.opt_shardings(abstract_params)
        self.batch_sharding = NamedSharding(mesh, self._batch_pspec())
        self.replicated = self.policy.replicated()

        # --- precision plan (reference: bf16_optimizer / fp16 fused_optimizer)
        self.model_dtype = config.model_dtype()
        self.mixed_precision = self.model_dtype != jnp.float32
        self.fp16_enabled = config.fp16.enabled
        self.loss_scaler = create_loss_scaler(config.fp16, self.fp16_enabled)

        # --- optimizer-state offload tier (reference: ZeRO-Offload/-Infinity,
        # stage_1_and_2.py cpu_offload + swap_tensor/)
        self.offload_device = config.zero_config.offload_optimizer.device
        self._host_master = None  # {dotted_name: np fp32} when offloaded
        self._host_optimizer = None
        self._nvme_swapper = None
        self._grad_stats_fn = None  # device-side norm/finite reduction
        self._wire_grads = None  # in-flight D2H tree (started in backward)
        self._wire_cast_fn = None
        wire = config.zero_config.offload_optimizer.wire_dtype
        # fp16 wire is rejected: pre-divide grads (scaled by loss_scale*gas)
        # routinely exceed fp16 max while finite in fp32, so the cast would
        # mint infs AFTER the overflow check and poison the Adam state.
        # bf16 shares fp32's exponent range and is safe.
        if wire not in ("float32", "fp32", "bfloat16", "bf16"):
            raise ValueError(
                f"offload_optimizer.wire_dtype must be float32 or bfloat16, got {wire!r}"
            )
        self._offload_wire_dtype = {"bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16}.get(wire)

        # --- ZeRO-Infinity parameter offload: host/NVMe weights streamed
        # through HBM per layer-group (runtime/zero/param_offload.py)
        self.coordinator = None
        self.param_offload = config.zero_config.offload_param_enabled()
        if self.param_offload and self.offload_device == "none":
            # streamed params require the host optimizer tier (the device
            # never holds the full tree for a compiled apply step)
            log_dist("offload_param enabled: promoting offload_optimizer to cpu tier", ranks=[0])
            config.zero_config.offload_optimizer.device = "cpu"
            self.offload_device = "cpu"

        # --- init params directly into their shardings (zero.Init equivalent:
        # partition at construction, partition_parameters.py:601 — here the
        # initializer is jitted with sharded outputs so full weights never
        # materialise on one device)
        fp32_shardings = self.opt_shardings if self.mixed_precision else self.param_shardings
        if self.param_offload:
            if isinstance(model, _PinnedParamsModel):
                # the streamed coordinator initializes masters group-by-group
                # from the seed (model.init is only eval_shape'd for
                # structure) — honoring an in-memory tree here would need a
                # full host master seeding pass; refuse rather than silently
                # train from random weights
                raise NotImplementedError(
                    "initialize(model=..., params=...) is not supported with "
                    "zero_optimization.offload_param; initialize without "
                    "params= and restore the weights with load_checkpoint()"
                )
            # params never materialize in HBM: host-side group-by-group init,
            # masters live in the host optimizer tier
            from deepspeed_tpu.runtime.zero.param_offload import ParamOffloadCoordinator

            self.coordinator = ParamOffloadCoordinator(
                model, mesh, self.policy, self.model_dtype,
                config.zero_config, self.batch_sharding, init_rng,
            )
            self._host_master = self.coordinator.masters
            self._master_treedef = jax.tree.structure(abstract_params)
            self.params = self.coordinator.working
            self.master_params = None
        else:
            if isinstance(model, _PinnedParamsModel):
                master = model.materialize(fp32_shardings)
            else:
                master = jax.jit(model.init, out_shardings=fp32_shardings)(init_rng)
            if self.offload_device in ("cpu", "nvme"):
                # master weights + moments leave HBM: host fp32 copies, device
                # keeps only the model-dtype working params
                leaves_with_path = jax.tree_util.tree_leaves_with_path(master)
                self._master_treedef = jax.tree.structure(master)
                self._host_master = {
                    # explicit copy: device_get returns read-only views of
                    # JAX-owned buffers; the C++ optimizer mutates in place
                    _leaf_key(path): np.array(jax.device_get(leaf), np.float32)
                    for path, leaf in leaves_with_path
                }
                cast_fn = jax.jit(
                    lambda p: jax.tree.map(lambda x: x.astype(self.model_dtype), p),
                    out_shardings=self.param_shardings,
                )
                self.params = cast_fn(master)
                del master
                self.master_params = None
            elif self.mixed_precision:
                cast_fn = jax.jit(
                    lambda p: jax.tree.map(lambda x: x.astype(self.model_dtype), p),
                    out_shardings=self.param_shardings,
                )
                self.master_params = master
                self.params = cast_fn(master)
            else:
                self.master_params = None
                self.params = master

        # --- optimizer
        if self.offload_device in ("cpu", "nvme"):
            optimizer = self._configure_offload_optimizer(config)
        else:
            if optimizer is None and config.optimizer is not None:
                optimizer = _build_optimizer(config.optimizer)
            if optimizer is not None and not hasattr(optimizer, "init"):
                optimizer = OptaxWrapper(optimizer)
        self.optimizer = optimizer
        self.base_lr = getattr(optimizer, "lr", 0.0) if optimizer is not None else 0.0
        if self.offload_device in ("cpu", "nvme"):
            self.opt_state = None
            self._opt_state_shardings = None
        elif optimizer is not None:
            base_tree = self.master_params if self.mixed_precision else self.params
            abstract_opt = jax.eval_shape(optimizer.init, self._abstract_params)
            opt_state_sh = _opt_state_shardings(
                abstract_opt, self._abstract_params, self.opt_shardings, self.replicated
            )
            self.opt_state = jax.jit(optimizer.init, out_shardings=opt_state_sh)(base_tree)
            self._opt_state_shardings = opt_state_sh
        else:
            self.opt_state = None
            self._opt_state_shardings = None

        # --- grad accumulation buffer (fp32, stage-sharded); the param-offload
        # path accumulates host-side in the coordinator instead
        if self.param_offload:
            self.grad_acc = None
        else:
            acc_init = jax.jit(
                lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), self._abstract_params),
                out_shardings=self.grad_shardings,
            )
            self.grad_acc = acc_init()

        self.scale_state: LossScaleState = jax.device_put(self.loss_scaler.init(), self.replicated)

        # --- a model that counts beside its loss (``loss_with_counters`` + ``counter_names``, an
        # optional protocol looked up as ``ltd_keep_len`` is): the micro-step adds its counters
        # into this accumulator, 64 bits a counter as (low, high) uint32 words; moe_stats() reads
        self.counter_acc = None
        if not self.param_offload and getattr(model, "loss_with_counters", None) is not None:
            self.counter_acc = jax.device_put(
                jnp.zeros((2, len(model.counter_names)), jnp.uint32), self.replicated)

        # --- lr scheduler
        if lr_scheduler is None and config.scheduler is not None:
            lr_scheduler = create_lr_scheduler(config.scheduler, self.base_lr)
        self.lr_scheduler = lr_scheduler

        # --- counters / bookkeeping
        self.micro_steps = 0
        self.global_steps = 0
        self.global_samples = 0
        self.skipped_steps = 0
        self.gradient_accumulation_steps = config.gradient_accumulation_steps
        self.train_micro_batch_size_per_gpu = config.train_micro_batch_size_per_gpu
        self.train_batch_size = config.train_batch_size
        self._last_metrics: Optional[StepMetrics] = None
        self._pending_loss = None
        self._flops_profiled = False
        self._micro_cost_cache = None  # (cost_dict, compiled) AOT artifact

        # --- timers / monitor
        self.timers = EngineTimers(enable=config.wall_clock_breakdown)
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size, steps_per_output=config.steps_per_print,
            synchronize=config.telemetry.enabled and config.telemetry.sync_timers,
        )
        from deepspeed_tpu.monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(config)

        # --- telemetry hub (telemetry/: JSONL step traces + MFU + registry;
        # inert when the config block is absent/disabled)
        from deepspeed_tpu.telemetry import Telemetry

        self.telemetry = Telemetry(config.telemetry, monitor=self.monitor, role="train")
        self._tele_window = {"fwd_ms": 0.0, "bwd_ms": 0.0}
        self._tele_flops_per_micro = None  # model FLOPs per micro-step (MFU)
        self._tele_tokens_per_micro = None
        self._comm_totals_prev = {}
        self._iter_t0 = None
        if self.telemetry.enabled:
            # comm-volume deltas in step events need the trace-time counters
            comm.ensure_comms_logger()

        # --- data-efficiency runtime schedules: progressive layer drop +
        # random-LTD (reference engine.py:1512 PLD theta pass-through;
        # data_pipeline/data_routing random-LTD scheduler). Both are consumed
        # by the model forward: PLD theta as a dynamic scalar, the LTD
        # kept-token count as a static shape (bounded re-jits on the
        # token_step_size grid — same granularity as curriculum seqlen).
        self.pld = None
        pld_cfg = config.progressive_layer_drop or {}
        if isinstance(pld_cfg, dict) and pld_cfg.get("enabled"):
            from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop

            self.pld = ProgressiveLayerDrop(
                theta=pld_cfg.get("theta", 0.5), gamma=pld_cfg.get("gamma", 0.001)
            )
        self.random_ltd_scheduler = None
        routing = (config.data_efficiency.data_routing or {}) if config.data_efficiency else {}
        ltd_cfg = routing.get("random_ltd", {}) if isinstance(routing, dict) else {}
        if routing.get("enabled", True) is False:
            ltd_cfg = {}
        if ltd_cfg.get("enabled"):
            from deepspeed_tpu.runtime.data_pipeline.data_routing.scheduler import RandomLTDScheduler

            merged = dict(ltd_cfg)
            merged.setdefault("seq_length", getattr(getattr(model, "cfg", None), "max_seq_len", 1024))
            self.random_ltd_scheduler = RandomLTDScheduler(merged)
        if self.param_offload and (self.pld is not None or self.random_ltd_scheduler is not None):
            # the streamed offload path (coordinator.micro_step) has no
            # PLD/LTD plumbing; running anyway would silently ignore the
            # configured schedules
            raise ValueError(
                "progressive_layer_drop / random-LTD are not supported together "
                "with zero_optimization.offload_param (the streamed parameter-"
                "offload forward does not apply data-efficiency schedules)"
            )
        # flip the model-side flags so forward() applies the schedules
        model_cfg = getattr(model, "cfg", None)
        if model_cfg is not None and hasattr(model_cfg, "pld_enabled"):
            import dataclasses as _dc

            updates = {}
            if self.pld is not None and not model_cfg.pld_enabled:
                updates["pld_enabled"] = True
            if self.random_ltd_scheduler is not None and not model_cfg.random_ltd:
                updates["random_ltd"] = True
            if updates:
                model.cfg = _dc.replace(model_cfg, **updates)

        # --- curriculum learning (reference: engine.py:1673-1676 seqlen
        # truncation per step; schedule in data_pipeline/curriculum_scheduler)
        self.curriculum_scheduler = None
        if config.curriculum.enabled:
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(
                {
                    "min_difficulty": config.curriculum.min_difficulty,
                    "max_difficulty": config.curriculum.max_difficulty,
                    "schedule_type": config.curriculum.schedule_type,
                    "schedule_config": config.curriculum.schedule_config,
                }
            )

        # --- dataloader
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        # --- checkpoint engine (config checkpoint.async_save selects the
        # non-blocking engine — the reference's Nebula async service seam)
        from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import (
            AsyncOrbaxCheckpointEngine,
            OrbaxCheckpointEngine,
        )

        # ocdbt's multi-host aggregation buys nothing for a single-writer
        # checkpoint and costs ~3x writer CPU — off unless asked for
        use_ocdbt = config.checkpoint.get("use_ocdbt", False)
        if config.checkpoint.get("async_save", False):
            self.checkpoint_engine = AsyncOrbaxCheckpointEngine(use_ocdbt=use_ocdbt)
        else:
            self.checkpoint_engine = OrbaxCheckpointEngine(use_ocdbt=use_ocdbt)

        # --- fault surface (docs/training.md "Fault tolerance"): the
        # TrainSupervisor installs an injector as fault_hook and arms the
        # step-fetch watchdog; both stay inert for plain training. poisoned
        # flips when a failure lands PAST a mutation barrier (grad_acc or
        # params already donated) — host state can no longer be trusted and
        # the supervisor must rebuild from the last committed snapshot.
        self.fault_hook = None          # callable(point, info) or None
        self.fetch_timeout_s = None     # step-fetch watchdog seconds; None = off
        self.poisoned = False
        # numeric (silent-corruption) fault surface + sentinel support:
        # a grad_bitflip directive waits here until the accumulation
        # boundary; the jits are built lazily on the fault/probe paths
        self._pending_bitflip = None    # fired numeric-fault record or None
        self._force_nan_loss = False    # nan_loss fallback for int-only batches
        self._discard_acc_fn = None     # donated zeroing for quarantine
        self._probe_zero_fn = None      # non-donated zeros for the SDC probe

        # --- activation checkpointing (reference: engine.py:872
        # _configure_checkpointing); models read the policy via
        # runtime/activation_checkpointing.resolve_policy
        from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as _act_ckpt

        _act_ckpt.configure(deepspeed_config=config)

        self._compile_step_fns()
        if self.telemetry.enabled:
            try:
                # HBM baseline for the live ops plane (params / optimizer
                # state / grad accumulators, per chip)
                self.memory_snapshot("build")
            except Exception as e:  # noqa: BLE001 — telemetry must never kill engine build
                logger.warning(f"telemetry memory snapshot failed: {e}")
        log_dist(
            f"TpuEngine ready: zero_stage={self.zero_stage} dtype={self.model_dtype.__name__} "
            f"mesh={dict(mesh.shape)} micro_bs={self.train_micro_batch_size_per_gpu} "
            f"gas={self.gradient_accumulation_steps}",
            ranks=[0],
        )

    def _batch_pspec(self) -> PartitionSpec:
        """Sharding of batch leaves; PipelineEngine overrides (microbatch dim)."""
        return self.policy.batch_spec()

    # ------------------------------------------------------------------
    # optimizer-state offload (reference: ZeRO-Offload cpu_adam hot loop,
    # stage_1_and_2.py:1031; ZeRO-Infinity optimizer swapping, swap_tensor/)
    # ------------------------------------------------------------------
    def _configure_offload_optimizer(self, config: TpuConfig):
        opt_cfg = config.optimizer
        params = dict(opt_cfg.params) if opt_cfg is not None else {}
        name = opt_cfg.type.lower() if opt_cfg is not None else C.ADAM_OPTIMIZER
        if name not in (C.ADAM_OPTIMIZER, C.ADAMW_OPTIMIZER, C.ADAGRAD_OPTIMIZER):
            raise ValueError(
                "offload_optimizer supports Adam/AdamW (reference: DeepSpeedCPUAdam) "
                f"and Adagrad (reference: DeepSpeedCPUAdagrad), got {opt_cfg.type}"
            )
        if name == C.ADAGRAD_OPTIMIZER:
            # reference: csrc/adagrad/cpu_adagrad.cpp:24 via ops/adagrad
            if self.offload_device != "cpu":
                raise ValueError(
                    "offload_optimizer device=nvme supports Adam/AdamW only "
                    "(the optimizer swapper stores Adam moment pairs); use "
                    "device=cpu for Adagrad"
                )
            from deepspeed_tpu.ops.adagrad.cpu_adagrad import DeepSpeedCPUAdagrad

            self._host_optimizer = DeepSpeedCPUAdagrad(
                lr=params.get("lr", 1e-2),
                eps=params.get("eps", 1e-10),
                weight_decay=params.get("weight_decay", 0.0),
            )
            return self._host_optimizer
        kwargs = dict(
            lr=params.get("lr", 1e-3),
            betas=tuple(params.get("betas", (0.9, 0.999))),
            eps=params.get("eps", 1e-8),
            weight_decay=params.get("weight_decay", 0.0),
            # parity with the device path: _build_optimizer defaults "Adam"
            # to adam_w_mode=True (reference ops/adam semantics)
            adamw_mode=params.get("adam_w_mode", True),
        )
        if self.offload_device == "cpu":
            from deepspeed_tpu.ops.adam.cpu_adam import DeepSpeedCPUAdam

            self._host_optimizer = DeepSpeedCPUAdam(**kwargs)
            return self._host_optimizer
        # nvme tier
        from deepspeed_tpu.runtime.swap_tensor.partitioned_optimizer_swapper import (
            PartitionedOptimizerSwapper,
        )

        nvme_path = config.zero_config.offload_optimizer.nvme_path or "/tmp/dstpu_swap"
        self._nvme_swapper = PartitionedOptimizerSwapper(
            swap_folder=os.path.join(nvme_path, "optimizer"),
            num_threads=config.zero_config.offload_optimizer.buffer_count,
            **kwargs,
        )
        for key, master in self._host_master.items():
            self._nvme_swapper.register(key, master)
        # NVMe holds master+moments; the host dict only keeps keys/shapes
        self._host_master = {k: np.zeros((0,), np.float32) for k in self._host_master}
        return self._nvme_swapper

    def _grad_stats(self):
        """Device-side squared grad norm + finiteness over grad_acc — a
        two-scalar transfer instead of the old host fp64 pass over every
        gradient byte (the 6 GB scan was a real cost at GPT-2 1.5B scale)."""
        if self._grad_stats_fn is None:
            def stats(acc):
                leaves = jax.tree.leaves(acc)
                sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
                finite = jnp.all(
                    jnp.stack([jnp.all(jnp.isfinite(l)) for l in leaves])
                )
                return sq, finite
            self._grad_stats_fn = jax.jit(stats, out_shardings=(self.replicated, self.replicated))
        return self._grad_stats_fn(self.grad_acc)

    def _host_offload_step(self, lr: float) -> StepMetrics:
        """Optimizer step on the host tier: grads device->host (optionally
        on a bf16 wire — half the D2H bytes, matching the reference's
        half-precision grad transfers in stage_1_and_2.py), C++ Adam on
        flat fp32 buffers with the accumulation/clip scaling fused into the
        kernel, updated masters -> device params."""
        cfg = self.config
        denom = float(self.scale_state.scale) * (
            self.gradient_accumulation_steps if not cfg.prescale_gradients else 1.0
        )
        if self.coordinator is not None:
            part = self.coordinator.partition
            overflow = False
            if self.fp16_enabled:
                # one device-scalar fetch: the coordinator AND-folded a
                # jitted finiteness reduction over each grad chunk as it
                # streamed through backward (the _grad_stats pattern),
                # replacing the old per-step host np.isfinite pass over
                # every gradient byte
                overflow = part.reduce_sum(
                    0.0 if self.coordinator.grads_finite() else 1.0) > 0.0
            grads = self.coordinator.consume_grads(denom)
            sq = sum(float((g.astype(np.float64) ** 2).sum()) for g in grads.values())
            gnorm = float(np.sqrt(part.reduce_sum(sq)))  # partitioned: global norm
            scale_harvested = True  # coordinator grads arrive pre-divided
        else:
            # device-side stats run while the async D2H copies (kicked off in
            # backward() at the accumulation boundary) stream in the background
            sq, finite = self._grad_stats()
            wire = self._wire_grads if self._wire_grads is not None else self.grad_acc
            flat_grads, _ = jax.tree_util.tree_flatten(wire)
            paths = [p for p, _ in jax.tree_util.tree_leaves_with_path(wire)]
            for g in flat_grads:
                if hasattr(g, "copy_to_host_async"):
                    g.copy_to_host_async()  # no-op if backward already started it
            # RAW grads: the denom/clip scaling is fused into the Adam kernel
            # below (grad_scale), so the host never re-writes the buffers
            grads = {
                _leaf_key(p): np.asarray(jax.device_get(g))
                for p, g in zip(paths, flat_grads)
            }
            # bf16 wire -> fp32 once (the Adam kernel wants fp32 buffers)
            grads = {
                k: (g if g.dtype == np.float32 else g.astype(np.float32))
                for k, g in grads.items()
            }
            self._wire_grads = None
            overflow = self.fp16_enabled and not bool(finite)
            gnorm = float(np.sqrt(float(sq))) / denom
            scale_harvested = False
        clip = cfg.gradient_clipping
        factor = min(1.0, clip / (gnorm + 1e-6)) if clip > 0.0 else 1.0
        kernel_scale = factor if scale_harvested else factor / denom

        if not overflow:
            if self._nvme_swapper is not None:
                updated = self._nvme_swapper.step(grads, lr=lr, grad_scale=kernel_scale)
                if self.coordinator is not None:
                    self.coordinator.refresh_working(updated)
                    self.params = self.coordinator.working
                else:
                    # push directly; masters stay on NVMe, not in host RAM
                    self._push_masters_to_device(updated)
            else:
                for key, master in self._host_master.items():
                    self._host_optimizer.step_buffer(key, master, grads[key], lr=lr,
                                                     grad_scale=kernel_scale)
                if self.coordinator is not None:
                    self.coordinator.refresh_working(self._host_master)
                    self.params = self.coordinator.working
                else:
                    self._push_masters_to_device(self._host_master)

        # loss-scale transition + grad reset (device side)
        self.scale_state = jax.device_put(
            self.loss_scaler.update(self.scale_state, jnp.asarray(overflow)), self.replicated
        )
        if self.grad_acc is not None:
            self.grad_acc = self._zero_acc_fn(self.grad_acc)
        return StepMetrics(
            grad_norm=jnp.asarray(gnorm), overflow=jnp.asarray(overflow),
            loss_scale=self.scale_state.scale,
        )

    def _push_masters_to_device(self, masters: Dict[str, "np.ndarray"]):
        flat_shardings, _ = jax.tree_util.tree_flatten(self.param_shardings)
        keys = [
            _leaf_key(p) for p, _ in jax.tree_util.tree_leaves_with_path(self._abstract_params)
        ]
        abstract = jax.tree.leaves(self._abstract_params)
        leaves = [
            jax.device_put(masters[k].astype(self.model_dtype).reshape(a.shape), s)
            for k, s, a in zip(keys, flat_shardings, abstract)
        ]
        self.params = jax.tree.unflatten(self._master_treedef, leaves)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _record_build(self, fn, family: str, key, **kw):
        """``compile_log.record_build`` with this engine's hub and global
        step, each read at the program's first dispatch."""
        return compile_log.record_build(
            fn, family, key, hub=lambda: self.telemetry,
            tick=lambda: self.global_steps, **kw)

    def _compile_step_fns(self):
        if self.param_offload:
            # the coordinator owns the compiled programs (streamed per-group)
            self._micro_fn = None
            self._eval_fn = None
            self._apply_fn = None
            self._zero_acc_fn = None
            return
        model = self.model
        cfg = self.config
        gas = self.gradient_accumulation_steps
        mixed = self.mixed_precision
        fp16 = self.fp16_enabled
        clip = cfg.gradient_clipping
        dtype = self.model_dtype
        scaler = self.loss_scaler
        optimizer = self.optimizer
        predivide = cfg.gradient_predivide_factor if cfg.prescale_gradients else 1.0

        # models may provide their own fused loss+grad program (the 1F1B
        # pipeline computes grads inside its schedule instead of autodiff
        # over the whole pipeline — pipe/engine.py value_and_grad)
        custom_vag = (
            getattr(model, "value_and_grad", None)
            if getattr(cfg.pipeline, "schedule", "gpipe") == "1f1b"
            else None
        )
        import inspect

        loss_sig = None
        try:
            loss_sig = set(inspect.signature(model.loss).parameters)
        except (TypeError, ValueError):
            loss_sig = set()
        accepts_ltd = "ltd_keep_len" in loss_sig
        accepts_pld = "pld_theta" in loss_sig
        use_pld = self.pld is not None and accepts_pld

        if custom_vag is not None:   # a schedule that makes its own gradients counts nothing
            self.counter_acc = None
        counted = model.loss_with_counters if self.counter_acc is not None else None

        def build_micro(ltd_keep_len=None):
            """Jitted micro-step; ``ltd_keep_len`` is static (it sets shapes),
            PLD theta rides as a dynamic operand (no re-jit as it decays). A
            model that counts beside its loss (``loss_with_counters``) gets a
            seventh argument and a third result, its counters' accumulator;
            any other model compiles the program it always did."""

            def micro_fn(params, grad_acc, batch, rng, scale, pld_theta, *counter_acc):
                if custom_vag is not None:
                    loss, grads = custom_vag(params, batch, rng, scale)
                else:
                    kwargs = {}
                    if accepts_ltd and ltd_keep_len is not None:
                        kwargs["ltd_keep_len"] = ltd_keep_len
                    if use_pld:
                        kwargs["pld_theta"] = pld_theta

                    def scaled_loss(p):   # (the loss, a counting model's counters or None)
                        out = (counted or model.loss)(p, batch, rng, **kwargs)
                        loss, counters = out if counted is not None else (out, None)
                        return loss.astype(jnp.float32) * scale, counters

                    (loss, counters), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params)
                    if counted is not None:   # 64 bits a counter: add, and carry where the low word wrapped
                        low, high = counter_acc[0]
                        total = low + counters.astype(jnp.uint32)
                        counter_acc = (jnp.stack([total, high + (total < low).astype(jnp.uint32)]),)
                with jax.named_scope(Scope.GRAD_ACCUMULATE):
                    new_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / predivide, grad_acc, grads)
                return (loss / scale, new_acc) + counter_acc

            extra = (None,) if counted is not None else ()   # the counters, replicated, in and out
            fn = jax.jit(
                micro_fn,
                donate_argnums=(1, 6) if counted is not None else (1,),
                in_shardings=(
                    self.param_shardings, self.grad_shardings, self.batch_sharding, None, None, None,
                ) + extra,
                out_shardings=(self.replicated, self.grad_shardings) + extra,
            )
            # build journal: the first dispatch of each (ltd grid point)
            # micro program leaves an entry (LTD shape churn shows up as
            # train_micro recompiles), then hands the bare program back to
            # ``_micro_jits``: a steady micro-step runs no wrapper
            def settle(bare):
                self._micro_jits[ltd_keep_len] = bare
                if ltd_keep_len is None:
                    self._micro_fn = bare

            return self._record_build(
                fn, "train_micro",
                (self.train_micro_batch_size_per_gpu, gas, ltd_keep_len),
                settle=settle)

        self._micro_builder = build_micro
        self._micro_jits = {}
        self._micro_jits[None] = build_micro(None)
        self._micro_fn = self._micro_jits[None]

        def loss_only_fn(params, batch, rng):
            return model.loss(params, batch, rng).astype(jnp.float32)

        self._eval_fn = jax.jit(
            loss_only_fn, in_shardings=(self.param_shardings, self.batch_sharding, None)
        )

        if optimizer is None or self.offload_device in ("cpu", "nvme"):
            # offload: the optimizer math runs on the host tier
            # (_host_offload_step), not in a compiled device program
            self._apply_fn = None
            self._zero_acc_fn = jax.jit(
                lambda t: jax.tree.map(jnp.zeros_like, t),
                out_shardings=self.grad_shardings,
                donate_argnums=0,
            )
            return

        def apply_fn(params, master, opt_state, grad_acc, scale_state, lr):
            denom = scale_state.scale * (gas if not cfg.prescale_gradients else 1.0)
            grads = jax.tree.map(lambda g: g / denom, grad_acc)

            if fp16:
                finite = jnp.array(True)
                for g in jax.tree.leaves(grads):
                    finite = finite & jnp.all(jnp.isfinite(g))
                overflow = ~finite
            else:
                overflow = jnp.array(False)

            gnorm = global_norm(grads)
            if clip > 0.0:
                factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * factor, grads)

            base = master if mixed else params
            with jax.named_scope(Scope.OPTIMIZER_APPLY):
                updates, new_opt = optimizer.update(grads, opt_state, base, lr)
                new_base = jax.tree.map(lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype), base, updates)

            if fp16:
                # skip the step wholesale on overflow (loss_scaler semantics)
                sel = lambda new, old: jax.tree.map(lambda n, o: jnp.where(overflow, o, n), new, old)
                new_base = sel(new_base, base)
                new_opt = sel(new_opt, opt_state)

            new_scale_state = scaler.update(scale_state, overflow)
            new_master = new_base if mixed else None
            new_params = (
                jax.tree.map(lambda x: x.astype(dtype), new_base) if mixed else new_base
            )
            zero_acc = jax.tree.map(jnp.zeros_like, grad_acc)
            metrics = StepMetrics(grad_norm=gnorm, overflow=overflow, loss_scale=scale_state.scale)
            return new_params, new_master, new_opt, zero_acc, new_scale_state, metrics

        master_sh = self.opt_shardings if mixed else None
        self._apply_fn = jax.jit(
            apply_fn,
            donate_argnums=(0, 1, 2, 3),
            in_shardings=(
                self.param_shardings,
                master_sh,
                self._opt_state_shardings,
                self.grad_shardings,
                None,
                None,
            ),
            out_shardings=(
                self.param_shardings,
                master_sh,
                self._opt_state_shardings,
                self.grad_shardings,
                self.replicated,
                self.replicated,
            ),
        )
        self._apply_fn = self._record_build(
            self._apply_fn, "train_apply",
            (self.train_micro_batch_size_per_gpu, gas),
            settle=partial(setattr, self, "_apply_fn"))
        # ds-audit capture (zero cost without a hook): the optimizer
        # apply program's args are all engine state, so it can be
        # contract-checked right at build (the micro program needs a
        # real batch and notifies from _micro_cost_analysis instead)
        from deepspeed_tpu.analysis.program import capture

        if capture.active():
            def apply_args():
                lr_s = jax.ShapeDtypeStruct((), jnp.float32)
                return (capture.shape_structs(self.params),
                        capture.shape_structs(self.master_params),
                        capture.shape_structs(self.opt_state),
                        capture.shape_structs(self.grad_acc),
                        capture.shape_structs(self.scale_state), lr_s)

            capture.notify_program("train_apply", "", self._apply_fn,
                                   apply_args, meta=self._audit_meta)

    # ------------------------------------------------------------------
    # HBM accounting (telemetry/memory.py — the live ops plane)
    # ------------------------------------------------------------------
    def hbm_components(self) -> dict:
        """PER-CHIP HBM attribution of the training state: params,
        optimizer state (fp32 masters + optimizer moments), and the
        gradient accumulators. Metadata-only shard-shape byte math —
        host-offloaded trees (numpy leaves) contribute 0, which is
        exactly right: they are not HBM."""
        from deepspeed_tpu.telemetry import memory as hbm

        comps = {"params": hbm.tree_device_bytes(self.params)}
        opt = (hbm.tree_device_bytes(getattr(self, "master_params", None))
               + hbm.tree_device_bytes(getattr(self, "opt_state", None)))
        if opt:
            comps["optimizer_state"] = opt
        grads = hbm.tree_device_bytes(getattr(self, "grad_acc", None))
        if grads:
            comps["grads"] = grads
        return comps

    def memory_snapshot(self, reason: str = "build"):
        """Export the training-state HBM attribution as
        ``hbm_bytes{component}`` gauges + one ``memory_snapshot`` trace
        event. When the AOT micro-program artifact exists (the flops/MFU
        path built it), its ``memory_analysis()`` rides along as the
        per-program scratch view. No-op with telemetry off."""
        from deepspeed_tpu.telemetry import memory as hbm

        programs = None
        if self._micro_cost_cache is not None:
            mem = hbm.program_memory(self._micro_cost_cache[1])
            if mem:
                programs = {"train_micro": mem}
        return hbm.emit_snapshot(self.telemetry, self.hbm_components(),
                                 reason, programs=programs)

    # ------------------------------------------------------------------
    # data plumbing
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, num_local_io_workers=None, route=None):
        from deepspeed_tpu.runtime.dataloader import TpuDataLoader

        return TpuDataLoader(
            dataset,
            batch_size=batch_size or self.train_micro_batch_size_per_gpu * comm.dp_world_size(),
            collate_fn=collate_fn,
            seed=self.config.seed,
            # pure-TP/pipe process spans (dp not dividing the processes)
            # feed the SAME global batch everywhere; _shard_batch then
            # assembles per-device from the sharding's index map
            process_shard=comm.dp_world_size() % jax.process_count() == 0,
        )

    def _shard_batch(self, batch):
        spec = self._batch_pspec()
        nprocs = jax.process_count()
        expected_rows = self.train_micro_batch_size_per_gpu * comm.dp_world_size()

        def put(x):
            if nprocs == 1:
                x = jnp.asarray(x)
                if x.ndim == 0:
                    return x
                leaf_spec = PartitionSpec(*tuple(spec)[: x.ndim])
                return jax.device_put(x, NamedSharding(self.mesh, leaf_spec))
            # multi-controller: assemble the global array from per-process
            # data (device_put cannot place onto non-addressable devices).
            # Along the batch dim (the first spec entry carrying data/fsdp —
            # dim 0 here, dim 1 for the pipeline engine's (microbatch, batch,
            # seq) layout) two feed shapes are accepted: the process-local
            # slice the striding TpuDataLoader yields, or a full global copy
            # (every process passing the SAME array) which is sliced down to
            # this process's contiguous block, matching the mesh's process-
            # major device order. A global feed whose batch dim happens to
            # equal the local size is interpreted as local — when batch
            # sizes collide, feed local slices (the reference's convention:
            # each rank feeds its own rows).
            x = np.asarray(x)
            if x.ndim == 0:
                return jnp.asarray(x)
            leaf_spec = PartitionSpec(*tuple(spec)[: x.ndim])
            sh = NamedSharding(self.mesh, leaf_spec)
            bdim = None
            for i, e in enumerate(tuple(leaf_spec)):
                axes = (e,) if isinstance(e, str) else tuple(e or ())
                if {"data", "fsdp"} & set(axes):
                    bdim = i
                    break
            if bdim is None:  # replicated leaf: full copy on every process
                return jax.make_array_from_process_local_data(sh, x)
            rows = x.shape[bdim]
            dp = comm.dp_world_size()
            if (dp % nprocs == 0 and expected_rows % nprocs == 0
                    and rows == expected_rows // nprocs):
                # striding-loader local slice (only meaningful when the
                # data axes actually split across processes)
                return jax.make_array_from_process_local_data(sh, x)
            if rows == expected_rows:
                # full global feed, identical on every process: assemble
                # per-device from the sharding's own index map — correct
                # for ANY mesh layout (tensor/pipe axes spanning the
                # process boundary, batch blocks replicated across process
                # groups, pipe-major device orders, ...)
                gshape = x.shape
                idx_map = sh.addressable_devices_indices_map(gshape)
                arrs = [jax.device_put(np.ascontiguousarray(x[idx]), d)
                        for d, idx in idx_map.items()]
                return jax.make_array_from_single_device_arrays(gshape, sh, arrs)
            if dp % nprocs == 0 and rows % nprocs == 0:
                per = rows // nprocs
                sl = [slice(None)] * x.ndim
                sl[bdim] = slice(jax.process_index() * per,
                                 (jax.process_index() + 1) * per)
                x = x[tuple(sl)]
                return jax.make_array_from_process_local_data(sh, x)
            raise ValueError(
                f"multi-controller batch leaf has {rows} rows on dim "
                f"{bdim}: expected the global batch of {expected_rows} "
                f"rows (identical on every process)"
                + (f" or the process-local {expected_rows // nprocs} rows "
                   f"from the striding dataloader"
                   if dp % nprocs == 0 and expected_rows % nprocs == 0 else ""))

        return jax.tree.map(put, batch)

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------------
    # train loop surface (forward / backward / step)
    # ------------------------------------------------------------------
    _SEQ_KEYS = ("input_ids", "labels", "tokens", "attention_mask", "position_ids")

    def _curriculum_truncate(self, batch):
        """Truncate the sequence dim to the curriculum difficulty (reference
        engine.py:1673-1676). Distinct lengths land on the schedule's
        difficulty_step grid, bounding recompiles."""
        seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
        if not isinstance(batch, dict):
            return batch
        out = dict(batch)
        for key in self._SEQ_KEYS:
            if key not in out:
                continue
            arr = out[key]
            ndim = getattr(arr, "ndim", 0)
            if ndim == 4 and key == "attention_mask":
                # broadcastable (B, 1, S, S) mask: truncate both seq dims
                if arr.shape[2] > seqlen or arr.shape[3] > seqlen:
                    out[key] = arr[:, :, :seqlen, :seqlen]
            elif ndim >= 2 and arr.shape[1] > seqlen:
                out[key] = arr[:, :seqlen]
        return out

    # -- trace capture (reference aux: NVTX ranges + torch profiler hooks;
    # here the XLA-native equivalent is an xplane trace, SURVEY §5a) -------
    def start_profile(self, logdir: str):
        """Begin a jax.profiler trace (view in TensorBoard / xprof) through
        the hub's one capture entry point (``Telemetry.start_capture``)."""
        self.telemetry.start_capture(logdir)

    def stop_profile(self):
        self.telemetry.stop_capture()

    def forward(self, batch, rng=None):
        if not self.telemetry.enabled:
            return self._forward_impl(batch, rng)
        if self._iter_t0 is None:  # first micro-step of the accumulation window
            self._iter_t0 = time.time()
        t0 = time.time()
        loss = self._forward_impl(batch, rng)
        if self.config.telemetry.sync_timers:
            try:
                jax.block_until_ready(loss)
            except Exception:
                pass
        self._tele_window["fwd_ms"] += (time.time() - t0) * 1000.0
        if self._tele_flops_per_micro is None:
            self._tele_capture_flops(batch)
        return loss

    def _forward_impl(self, batch, rng=None):
        if self.fault_hook is not None:
            # fires BEFORE the RNG splits or grad_acc is donated: an
            # injected micro_dispatch fault here leaves the engine exactly
            # as it was, so the supervisor's retry of the same batch is
            # bitwise the micro-step that would have run. Numeric kinds
            # (faults.TRAIN_NUMERIC_KINDS) come back as a directive record
            # instead of raising — the values get corrupted and the step
            # keeps running: silent by design, the sentinel's problem
            directive = self.fault_hook("micro_dispatch",
                                        {"step": self.global_steps + 1,
                                         "micro": self.micro_steps})
            if directive is not None:
                batch = self._apply_numeric_fault(directive, batch)
        try:
            loss = self._forward_body(batch, rng)
        except BaseException:
            # anything past the dispatch barrier may have consumed RNG or
            # donated grad_acc — poison so recovery rebuilds, never retries
            self.poisoned = True
            raise
        if self._force_nan_loss:
            # nan_loss on a batch with no float leaves (token-id inputs):
            # the reported loss is corrupted instead of the data
            self._force_nan_loss = False
            loss = np.float32(np.nan)
            self._pending_loss = loss
        return loss

    def _apply_numeric_fault(self, record: dict, batch):
        """Apply a numeric-fault directive the injector handed back
        (faults.py TRAIN_NUMERIC_KINDS). ``data_poison`` / ``nan_loss``
        corrupt the host batch before sharding; ``grad_bitflip`` is
        deferred to the accumulation boundary (step()), where the
        accumulator holds the whole step's gradient."""
        from deepspeed_tpu import faults as _faults

        kind = record.get("kind")
        if kind == "data_poison":
            factor = (float(record.get("factor") or 0.0)
                      or _faults.DEFAULT_POISON_FACTOR)
            return jax.tree.map(
                lambda a: _faults.poison_array(a, factor), batch)
        if kind == "nan_loss":
            leaves = jax.tree.leaves(batch)
            if any(np.issubdtype(np.asarray(l).dtype, np.floating)
                   for l in leaves):
                return jax.tree.map(_faults.nan_poison_array, batch)
            self._force_nan_loss = True
            return batch
        if kind == "grad_bitflip":
            self._pending_bitflip = record
            return batch
        return batch

    def _apply_grad_bitflip(self, record: dict):
        """Flip one bit of one accumulated-gradient element (an injected
        SDC). The (leaf, element, bit) target resolves deterministically
        from the plan record (faults.plan_bitflip), and the record is
        annotated with the resolved target for the injector's fired log.
        One-leaf host round-trip — fault path only, never the hot path."""
        from deepspeed_tpu import faults as _faults

        step = int(record.get("step", self.global_steps + 1))
        leaf = str(record.get("leaf", "") or "")
        bit = int(record.get("bit", -1))
        if self.coordinator is not None:
            grads = self.coordinator.host_grads
            if not grads:
                return
            sizes = {k: int(np.asarray(v).size) for k, v in grads.items()}
            name, elem, bit = _faults.plan_bitflip(step, sizes, leaf, bit)
            grads[name] = _faults.flip_float_bit(grads[name], elem, bit)
        else:
            named = {
                _leaf_key(p): l
                for p, l in jax.tree_util.tree_leaves_with_path(self.grad_acc)
            }
            sizes = {k: int(l.size) for k, l in named.items()}
            name, elem, bit = _faults.plan_bitflip(step, sizes, leaf, bit)
            target = named[name]
            host = np.asarray(jax.device_get(target), dtype=np.float32)
            corrupted = jax.device_put(
                _faults.flip_float_bit(host, elem, bit), target.sharding)
            self.grad_acc = jax.tree_util.tree_map_with_path(
                lambda p, l: corrupted if _leaf_key(p) == name else l,
                self.grad_acc)
        record["leaf"], record["bit"] = name, bit
        record.setdefault("elem", elem)

    def _forward_body(self, batch, rng=None):
        self.timers(EngineTimers.FORWARD).start()
        self.tput_timer.start()
        if self.curriculum_scheduler is not None:
            batch = self._curriculum_truncate(batch)
        if self.coordinator is not None:
            loss = self.coordinator.micro_step(batch, float(self.scale_state.scale))
            self._pending_loss = loss
            self.timers(EngineTimers.FORWARD).stop()
            return loss
        batch = self._shard_batch(batch)
        rng = rng if rng is not None else self._next_rng()
        if (
            self.config.flops_profiler.enabled
            and not self._flops_profiled
            and self.global_steps + 1 >= self.config.flops_profiler.profile_step
        ):
            self._profile_flops(batch, rng)
        keep_len = None
        if self.random_ltd_scheduler is not None:
            keep_len = self.random_ltd_scheduler.update_seq(self.global_steps)
            seq_len = next(
                (v.shape[-1] for v in jax.tree.leaves(batch) if getattr(v, "ndim", 0) >= 2), None
            )
            if seq_len is not None and keep_len >= seq_len:
                keep_len = None
        micro = self._micro_jits.get(keep_len)
        if micro is None:
            micro = self._micro_jits[keep_len] = self._micro_builder(keep_len)
        theta = jnp.float32(self.pld.get_theta() if self.pld is not None else 1.0)
        with host_span("train.micro_dispatch"):
            loss, self.grad_acc, *counters = micro(
                self.params, self.grad_acc, batch, rng, self.scale_state.scale, theta,
                *self._counter_args())
            if counters:
                self.counter_acc = counters[0]
        self._pending_loss = loss
        self.timers(EngineTimers.FORWARD).stop()
        return loss

    __call__ = forward

    def eval_batch(self, batch, rng=None):
        if self.coordinator is not None:
            return self.coordinator.eval_loss(batch)
        batch = self._shard_batch(batch)
        return self._eval_fn(self.params, batch, rng if rng is not None else self._next_rng())

    def backward(self, loss=None):
        """Micro-step boundary (gradients were produced in forward; this
        advances the accumulation counter for API parity)."""
        t0 = time.time() if self.telemetry.enabled else 0.0
        self.timers(EngineTimers.BACKWARD).start()
        self.micro_steps += 1
        self.global_samples += self.train_micro_batch_size_per_gpu * comm.dp_world_size()
        if (
            self.offload_device in ("cpu", "nvme")
            and self.coordinator is None
            and self.is_gradient_accumulation_boundary()
        ):
            # kick off grad D2H right behind the (async-dispatched) last
            # micro-step so transfers overlap the tail of backward compute
            # (reference: grad-copy/backward overlap, stage_1_and_2.py:1031);
            # with a bf16 wire a tiny cast program halves the bytes first
            wire = self.grad_acc
            if self._offload_wire_dtype is not None:
                if self._wire_cast_fn is None:
                    wd = self._offload_wire_dtype
                    self._wire_cast_fn = jax.jit(
                        lambda t: jax.tree.map(lambda g: g.astype(wd), t)
                    )
                wire = self._wire_cast_fn(self.grad_acc)
            self._wire_grads = wire
            for g in jax.tree.leaves(wire):
                if hasattr(g, "copy_to_host_async"):
                    g.copy_to_host_async()
        self.timers(EngineTimers.BACKWARD).stop()
        if self.telemetry.enabled:
            if self.config.telemetry.sync_timers:
                try:
                    # drain the accumulated grads (and the bf16 wire cast /
                    # D2H kick above) so bwd_ms is compute, not dispatch
                    jax.block_until_ready(self.grad_acc)
                except Exception:
                    pass
            self._tele_window["bwd_ms"] += (time.time() - t0) * 1000.0
        return loss if loss is not None else self._pending_loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.gradient_accumulation_steps == 0

    def step(self):
        if not self.is_gradient_accumulation_boundary():
            self.tput_timer.stop(global_step=False)
            return
        if self._pending_bitflip is not None:
            # the deferred grad_bitflip lands now, after every micro-step
            # accumulated and before the apply program consumes grad_acc
            record, self._pending_bitflip = self._pending_bitflip, None
            self._apply_grad_bitflip(record)
        try:
            self._step_body()
        except BaseException:
            # the apply program donates params/master/opt_state/grad_acc on
            # dispatch — any failure inside the step body (including a hung
            # or injected step_fetch) leaves state unaccounted for
            self.poisoned = True
            raise

    def _guarded_fetch(self, metrics):
        """The loss/grad-norm host fetch, under the ``step_fetch`` fault
        hook and the post-hoc ``fetch_timeout_s`` watchdog (same
        no-threads design as the serving retire watchdog: time the
        blocking fetch, raise TimeoutError when it overran — the step's
        host view is then untrustworthy and step() poisons the engine)."""
        if self.fault_hook is not None:
            self.fault_hook("step_fetch", {"step": self.global_steps + 1})
        if self.fetch_timeout_s is None:
            return
        t0 = time.perf_counter()
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        if dt > self.fetch_timeout_s:
            raise TimeoutError(
                f"step {self.global_steps + 1} metrics fetch took {dt:.3f}s "
                f"> fetch_timeout_s={self.fetch_timeout_s}")

    def _step_body(self):
        assert self.optimizer is not None, "step() requires an optimizer (config or client-provided)"
        tele = self.telemetry.enabled
        t_step = time.time() if tele else 0.0
        self.timers(EngineTimers.STEP).start()
        if self.offload_device in ("cpu", "nvme"):
            metrics = self._host_offload_step(self.get_lr_value())
        else:
            lr = jnp.asarray(self.get_lr_value(), jnp.float32)
            with host_span("train.apply_dispatch"):
                (
                    self.params,
                    self.master_params,
                    self.opt_state,
                    self.grad_acc,
                    self.scale_state,
                    metrics,
                ) = self._apply_fn(
                    self.params, self.master_params, self.opt_state, self.grad_acc, self.scale_state, lr
                )
        self._last_metrics = metrics
        # where the step itself would block on the device: the watchdog's
        # fetch of the step metrics (and fp16's overflow read, below)
        with host_span("train.loss_fetch"):
            self._guarded_fetch(metrics)
        self.global_steps += 1
        if self.pld is not None:
            self.pld.update_state(self.global_steps)
        if self.fp16_enabled:
            # dynamic scaling requires reading the overflow flag (host sync,
            # same as the reference's has_overflow allreduce + item())
            with host_span("train.loss_fetch"):
                overflowed = bool(metrics.overflow)
            if overflowed:
                self.skipped_steps += 1
                log_dist(
                    f"step {self.global_steps} overflow: skipping, loss scale -> {float(self.scale_state.scale)}",
                    ranks=[0],
                )
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self.timers(EngineTimers.STEP).stop()
        self.tput_timer.stop(global_step=True)
        self._write_monitor()
        if tele:
            if self.config.telemetry.sync_timers:
                try:
                    jax.block_until_ready(metrics)
                except Exception:
                    pass
            self._emit_step_telemetry((time.time() - t_step) * 1000.0)
            self.telemetry.maybe_capture(self.global_steps)
        if self.config.steps_per_print and self.global_steps % self.config.steps_per_print == 0:
            self.timers.log(normalizer=self.gradient_accumulation_steps)
            self._emit_comm_summary()

    def _audit_meta(self) -> dict:
        """ProgramArtifact meta for ds-audit captures of the train step
        programs (analysis/program/capture.py) — built only while a
        hook is installed. Both step programs donate unconditionally
        (micro: grad_acc; apply: params/master/opt_state/grad_acc)."""
        from deepspeed_tpu.analysis.program.capture import param_leaf_shapes
        from deepspeed_tpu.parallel.partition import mesh_tensor_width

        accum = {"float32": ("f32",), "bfloat16": ("bf16", "f32"),
                 "float16": ("f16", "f32")}.get(
            jnp.dtype(self.model_dtype).name, ())
        tp = mesh_tensor_width(self.mesh)
        return {
            "tp": tp,
            # dp/fsdp/... width: >1 means the calibrated tensor-only
            # collective tables don't apply (the inventory rule skips)
            "other_axes": int(self.mesh.devices.size) // max(tp, 1),
            "donate": True,
            "param_shapes": param_leaf_shapes(self.params),
            "accum_dtypes": accum,
            "hbm_limit_bytes": getattr(self.config.telemetry,
                                       "hbm_limit_bytes", 0),
        }

    def _micro_cost_analysis(self, batch, rng):
        """(cost_dict, compiled) for the default micro program via one AOT
        lower+compile, cached on the engine — the flops profiler and the
        telemetry MFU capture share the result, so the extra compile (the
        jit dispatch cache is separate from AOT artifacts) happens at most
        once per engine."""
        if self._micro_cost_cache is None:
            lowered = self._micro_fn.lower(
                self.params, self.grad_acc, batch, rng, self.scale_state.scale, jnp.float32(1.0),
                *self._counter_args())
            compiled = lowered.compile()
            # ds-audit capture: this is the one place the engine already
            # holds the micro program's lowered artifact — feed the
            # contract auditor without a second trace
            from deepspeed_tpu.analysis.program import capture

            if capture.active():
                capture.notify_lowered("train_micro", "", lowered,
                                       meta=self._audit_meta,
                                       compiled=compiled)
            self._micro_cost_cache = (dict(compiled.cost_analysis() or {}), compiled)
        return self._micro_cost_cache

    def _profile_flops(self, batch, rng):
        """One-shot micro-step cost report (reference: engine.py:1646-1664
        flops-profiler trigger at profile_step)."""
        from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfiler, count_params

        self._flops_profiled = True
        prof = FlopsProfiler(self.model, engine=self)
        try:
            cost, compiled = self._micro_cost_analysis(batch, rng)
            prof.flops = float(cost.get("flops", 0.0))
            prof.bytes_accessed = float(cost.get("bytes accessed", 0.0))
            # timed run on a throwaway grad buffer (the real one is donated to
            # the subsequent training call); host fetch forces completion
            zeros = jax.jit(
                lambda t: jax.tree.map(jnp.zeros_like, t), out_shardings=self.grad_shardings
            )(self.grad_acc)
            t0 = time.time()
            out_loss, *_ = compiled(self.params, zeros, batch, rng, self.scale_state.scale,
                                    jnp.float32(1.0), *self._counter_args(throwaway=True))
            float(out_loss)
            prof.duration = time.time() - t0
            prof.params = count_params(self.params)
            prof.print_model_profile(
                profile_step=self.global_steps + 1,
                module_depth=self.config.flops_profiler.module_depth,
                top_modules=self.config.flops_profiler.top_modules,
                detailed=self.config.flops_profiler.detailed,
                output_file=self.config.flops_profiler.output_file,
            )
        except Exception as e:  # profiling must never kill training
            logger.warning(f"flops profiling failed: {e}")

    def train_batch(self, data_iter=None):
        """Full accumulation cycle (PipelineEngine.train_batch parity)."""
        assert data_iter is not None or self.training_dataloader is not None
        it = data_iter if data_iter is not None else iter(self.training_dataloader)
        losses = []
        for _ in range(self.gradient_accumulation_steps):
            with host_span("train.next_batch"):
                batch = next(it)
            loss = self.forward(batch)
            self.backward(loss)
            self.step()
            losses.append(loss)
        return jnp.mean(jnp.stack(losses))

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    @property
    def module(self):
        return self.model

    def get_lr_value(self) -> float:
        if self.lr_scheduler is not None:
            return float(self.lr_scheduler.get_lr())
        return float(self.base_lr)

    def get_lr(self):
        return [self.get_lr_value()]

    @property
    def loss_scale(self) -> float:
        return float(self.scale_state.scale)

    def get_global_grad_norm(self) -> Optional[float]:
        if self._last_metrics is None:
            return None
        return float(self._last_metrics.grad_norm)

    # ------------------------------------------------------------------
    # numerical health (docs/training.md "Numerical health"): the three
    # engine seams the NumericSentinel/TrainSupervisor pair drives
    # ------------------------------------------------------------------
    def step_health_scalars(self) -> Optional[dict]:
        """The per-step host scalars the sentinel consumes — the same
        StepMetrics values the step already materialized (fetched in
        _guarded_fetch / the fp16 overflow sync / telemetry), so reading
        them here adds no device sync the step wasn't already paying."""
        m = self._last_metrics
        if m is None:
            return None
        return {
            "grad_norm": float(m.grad_norm),
            "overflow": bool(m.overflow),
            "loss_scale": float(m.loss_scale),
        }

    def discard_accumulated_grads(self):
        """Zero the accumulated gradients WITHOUT applying them — the
        supervisor's quarantine rung. Params, optimizer state, loss
        scale and step counters are untouched, so the next step proceeds
        exactly as if the flagged batch had been excluded from the
        stream (the loader's skip-list makes that exclusion durable)."""
        self._pending_bitflip = None
        self._force_nan_loss = False
        self._wire_grads = None
        if self.coordinator is not None:
            self.coordinator.discard_grads()
            return
        if self.grad_acc is None:
            return
        if self._discard_acc_fn is None:
            self._discard_acc_fn = jax.jit(
                lambda t: jax.tree.map(jnp.zeros_like, t),
                out_shardings=self.grad_shardings,
                donate_argnums=0,
            )
        self.grad_acc = self._discard_acc_fn(self.grad_acc)

    def sdc_probe(self, batch, rng_seed: int = 0) -> Optional[int]:
        """One sentinel micro-step, out of band: run the compiled micro
        program on ``batch`` with a FIXED rng key into a throwaway zero
        accumulator — the engine's RNG stream, grad_acc and counters are
        untouched — and return a CRC-32 of the resulting grad bytes.
        Back-to-back probes on the same batch are bitwise identical on a
        healthy mesh (same program, same inputs), so a digest mismatch
        is nondeterministic hardware corruption. Returns None where no
        standalone micro program exists (param-offload coordinator)."""
        if self._micro_fn is None or self.grad_acc is None:
            return None
        from deepspeed_tpu.runtime.numerics import crc_digest

        if self._probe_zero_fn is None:
            # non-donating on purpose: the template (grad_acc) survives
            self._probe_zero_fn = jax.jit(
                lambda t: jax.tree.map(jnp.zeros_like, t),
                out_shardings=self.grad_shardings,
            )
        zeros = self._probe_zero_fn(self.grad_acc)
        sharded = self._shard_batch(batch)
        rng = jax.random.PRNGKey(rng_seed)
        theta = jnp.float32(self.pld.get_theta() if self.pld is not None else 1.0)
        _, acc, *_ = self._micro_fn(
            self.params, zeros, sharded, rng, self.scale_state.scale, theta,
            *self._counter_args(throwaway=True))
        return crc_digest(
            np.asarray(jax.device_get(l)) for l in jax.tree.leaves(acc))

    def _counter_args(self, throwaway=False) -> tuple:
        """What the micro program takes after its six arguments: nothing, or
        the counters' accumulator (``throwaway``: zeros in its place, for a
        run out of band, since the program donates it)."""
        if self.counter_acc is None:
            return ()
        return (jnp.zeros_like(self.counter_acc),) if throwaway else (self.counter_acc,)

    def moe_stats(self) -> dict:
        """{name: total since the engine was built} of the counters a model
        returns beside its loss (``model.counter_names``; a layer plan's
        expert layers: assignments made, assignments to held experts, the
        most one held expert got in a layer summed over micro-steps, expert
        layers run, held experts hit). One host fetch, which waits for the
        micro-steps in flight; {} for a model that counts nothing."""
        if self.counter_acc is None:
            return {}
        low, high = np.asarray(jax.device_get(self.counter_acc)).astype(np.uint64)
        return {name: int(h) << 32 | int(l)
                for name, l, h in zip(self.model.counter_names, low, high)}

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    # ------------------------------------------------------------------
    # telemetry (telemetry/: structured step traces, MFU, comm volume)
    # ------------------------------------------------------------------
    def _tele_capture_flops(self, batch):
        """One-shot model-FLOPs-per-micro-step capture for MFU: the model's
        own ``flops_per_token`` (Megatron 6N accounting, fwd+bwd) when it
        declares one, else XLA ``cost_analysis`` of the compiled micro
        program — the same number the flops profiler fetches."""
        self._tele_flops_per_micro = 0.0
        try:
            seq = None
            if isinstance(batch, dict):
                for key in self._SEQ_KEYS:
                    arr = batch.get(key)
                    if getattr(arr, "ndim", 0) >= 2:
                        seq = (int(arr.shape[0]), int(arr.shape[1]))
                        break
            if seq is not None:
                self._tele_tokens_per_micro = seq[0] * seq[1]
            if seq is not None and hasattr(self.model, "flops_per_token"):
                self._tele_flops_per_micro = (
                    float(self.model.flops_per_token(seq[1])) * seq[0] * seq[1]
                )
                return
            if self._micro_fn is not None:
                cost, _ = self._micro_cost_analysis(batch, jax.random.PRNGKey(0))
                self._tele_flops_per_micro = float(cost.get("flops", 0.0))
        except Exception as e:  # telemetry must never kill training
            logger.warning(f"telemetry flops capture failed: {e}")

    def _emit_step_telemetry(self, step_ms: float):
        """One "train_step" trace event per optimizer step (docs/telemetry.md
        schema): phase wall-times, throughput, MFU, loss/grad-norm/scale,
        and comm-volume deltas since the previous step."""
        now = time.time()
        # step() drains device work (sync_timers) before calling here, so the
        # iteration span is already compute-accurate  # ds-lint: disable=unsynced-timing
        iter_ms = (now - self._iter_t0) * 1000.0 if self._iter_t0 is not None else step_ms
        iter_s = iter_ms / 1000.0
        comm_delta = {}
        cl = comm.get_comms_logger()
        if cl is not None:
            totals = cl.totals()
            comm_delta = {
                op: totals[op] - self._comm_totals_prev.get(op, 0) for op in totals
            }
            self._comm_totals_prev = totals
        flops_per_step = (self._tele_flops_per_micro or 0.0) * self.gradient_accumulation_steps
        peak = self.telemetry.peak_flops_per_device() * max(jax.device_count(), 1)
        mfu = flops_per_step / (iter_s * peak) if flops_per_step > 0 and iter_s > 0 else 0.0
        m = self._last_metrics
        event = {
            "step": self.global_steps,
            "micro_steps": self.micro_steps,
            "samples": self.global_samples,
            "fwd_ms": self._tele_window["fwd_ms"],
            "bwd_ms": self._tele_window["bwd_ms"],
            "step_ms": step_ms,
            "iter_ms": iter_ms,
            "samples_per_sec": self.train_batch_size / iter_s if iter_s > 0 else 0.0,
            "avg_samples_per_sec": self.tput_timer.avg_samples_per_sec(),
            "lr": self.get_lr_value(),
            "loss_scale": float(m.loss_scale) if m is not None else 1.0,
            "grad_norm": float(m.grad_norm) if m is not None else 0.0,
            "overflow": bool(m.overflow) if m is not None else False,
            "skipped_steps": self.skipped_steps,
            "mfu": mfu,
            "model_flops_per_step": flops_per_step,
            "comm_bytes": comm_delta,
            "comm_bytes_total": float(sum(comm_delta.values())),
        }
        if self._pending_loss is not None:
            event["loss"] = float(self._pending_loss)
        event.update(self.moe_stats())  # a counting model's totals so far; nothing otherwise
        if self._tele_tokens_per_micro:
            tokens = self._tele_tokens_per_micro * self.gradient_accumulation_steps
            event["tokens_per_sec"] = tokens / iter_s if iter_s > 0 else 0.0
        self.telemetry.emit(
            "train_step", event,
            monitor_prefix="Train/Telemetry", monitor_step=self.global_samples,
        )
        self._tele_window = {"fwd_ms": 0.0, "bwd_ms": 0.0}
        self._iter_t0 = None

    def comm_summary(self) -> dict:
        """Cumulative per-op collective volume (``CommsLogger.summary()``):
        {op: {count, total_bytes, total_human}} — empty when no comms
        logger is active. The user-facing accessor for what ``log_all``
        used to leave orphaned."""
        cl = comm.get_comms_logger()
        return cl.summary() if cl is not None else {}

    def _emit_comm_summary(self):
        """Surface the comm-volume summary at steps_per_print boundaries
        through both the telemetry trace and the monitor writers."""
        summary = self.comm_summary()
        if not summary:
            return
        self.telemetry.emit(
            "comm_summary", {"step": self.global_steps, "ops": summary}
        )
        if self.monitor.enabled:
            events = []
            for op, stats in summary.items():
                events.append((f"Train/Comms/{op}/total_bytes",
                               float(stats["total_bytes"]), self.global_samples))
                events.append((f"Train/Comms/{op}/count",
                               float(stats["count"]), self.global_samples))
            self.monitor.write_events(events)

    def telemetry_summary(self) -> dict:
        """Aggregated registry view (counters/gauges/histogram percentiles)
        of everything this engine emitted."""
        return self.telemetry.summary()

    def _write_monitor(self):
        if not self.monitor.enabled:
            return
        events = [
            ("Train/Samples/lr", self.get_lr_value(), self.global_samples),
        ]
        if self._pending_loss is not None:
            events.append(("Train/Samples/train_loss", float(self._pending_loss), self.global_samples))
        if self.fp16_enabled:
            events.append(("Train/Samples/loss_scale", self.loss_scale, self.global_samples))
        self.monitor.write_events(events)

    # ------------------------------------------------------------------
    # checkpointing (reference: engine.py:2798 save_checkpoint / :2493 load)
    # ------------------------------------------------------------------
    def _state_tree(self):
        tree = {
            "params": self.params,
            "scale_state": self.scale_state,
        }
        if self.grad_acc is not None:
            tree["grad_acc"] = self.grad_acc
        if self.master_params is not None:
            tree["master_params"] = self.master_params
        if self.opt_state is not None:
            tree["opt_state"] = self.opt_state
        if self._nvme_swapper is not None:
            # nvme tier: pull masters+moments off storage into the checkpoint
            # (swap files alone don't survive a move to another host, and a
            # fresh engine's register() would overwrite them before load)
            keys = list(self._host_master)
            tree["host_master"] = {k: self._nvme_swapper.get_master(k) for k in keys}
            tree["host_opt"] = {
                k: {
                    "step": np.int64(self._nvme_swapper.step_count),
                    "m": self._nvme_swapper.get_state(k, "m"),
                    "v": self._nvme_swapper.get_state(k, "v"),
                }
                for k in keys
            }
        elif self._host_master is not None:
            # cpu tier: host master + moments travel in the checkpoint
            tree["host_master"] = dict(self._host_master)
            sd = self._host_optimizer.state_dict() if self._host_optimizer is not None else {}
            if not sd:
                # pre-step engines need a full-shape template or a fresh
                # process restores an empty dict and drops the moments
                sd = {
                    k: {"step": np.int64(0), "m": np.zeros_like(v), "v": np.zeros_like(v)}
                    for k, v in self._host_master.items()
                }
            tree["host_opt"] = sd
        return tree

    def _checkpoint_meta(self, client_state=None) -> dict:
        return {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler else None,
            "client_state": client_state or {},
            "zero_stage": self.zero_stage,
            "dtype": str(self.model_dtype.__name__),
        }

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        state_tree=None, manifest=None):
        """``state_tree``/``manifest`` let the TrainSupervisor commit an
        already-captured host snapshot (numpy leaves save fine through
        orbax and restore onto device templates) without a second
        device_get pass; plain callers leave both None."""
        from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import (
            named_host_leaves,
        )

        tag = tag if tag is not None else f"global_step{self.global_steps}"
        meta = self._checkpoint_meta(client_state)
        tree = state_tree if state_tree is not None else self._state_tree()
        if manifest is None and self.config.checkpoint.get("integrity_manifest", True):
            manifest = ckpt_integrity.manifest_from_leaves(named_host_leaves(tree))
        pre_commit = None
        if self.fault_hook is not None:
            hook, step = self.fault_hook, self.global_steps

            def pre_commit():
                # the torn-write injection window: arrays/metadata/manifest
                # are durable, the commit marker is not yet placed
                hook("checkpoint_write", {"step": step, "tag": tag})

        self.checkpoint_engine.save(os.path.join(save_dir, tag), tree, meta,
                                    manifest=manifest, pre_commit=pre_commit)
        if save_latest and jax.process_index() == 0:

            def _write_latest():
                # runs at commit time ('latest' must only ever name durable
                # checkpoints; async saves defer this to their fence) and is
                # atomic — a reader sees the old pointer or the new, never a
                # torn half-written tag name
                os.makedirs(save_dir, exist_ok=True)
                tmp = os.path.join(save_dir, f".latest.tmp.{os.getpid()}")
                with open(tmp, "w") as fh:
                    fh.write(tag)
                os.replace(tmp, os.path.join(save_dir, "latest"))

            self.checkpoint_engine.on_commit(_write_latest)
        log_dist(f"saved checkpoint {save_dir}/{tag}", ranks=[0])
        return True

    def _ckpt_refused(self, tag, reason):
        logger.warning(f"refusing checkpoint tag {tag!r}: {reason}")
        if self.telemetry.enabled:
            self.telemetry.emit(
                "train_fault",
                {"event": "ckpt_refused", "tag": str(tag),
                 "reason": str(reason)},
            )

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        load_lr_scheduler_states=True, verify_integrity=True):
        explicit = tag is not None
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if not os.path.exists(latest):
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
            with open(latest) as fh:
                tag = fh.read().strip()
        candidates = [tag]
        if not explicit:
            # resume from the newest restorable state, not just what
            # 'latest' names: scan every global_step tag newest-first
            # (torn ones get REFUSED with a ckpt_refused event and the
            # walk falls back), keeping the latest pointer as the lead
            # candidate when it names a foreign (non-global_step) tag
            scanned = [t for _s, t, _c in ckpt_integrity.scan_tags(load_dir)]
            candidates = scanned if tag in scanned else [tag] + scanned
        restored = meta = None
        errors = []
        for cand in candidates:
            path = os.path.join(load_dir, cand)
            try:
                restored, meta = self.checkpoint_engine.load(
                    path, self._state_tree(), verify_integrity=verify_integrity)
                tag = cand
                break
            except ckpt_integrity.TornCheckpointError as e:
                self._ckpt_refused(cand, str(e))
                errors.append(f"{cand}: {e}")
        if restored is None:
            raise ckpt_integrity.TornCheckpointError(
                f"no committed checkpoint restorable from {load_dir} "
                f"(refused: {'; '.join(errors) or 'none found'})")
        path = os.path.join(load_dir, tag)
        self._restore_state(restored, meta, load_optimizer_states,
                            load_lr_scheduler_states)
        log_dist(f"loaded checkpoint {path} at step {self.global_steps}", ranks=[0])
        return path, meta.get("client_state", {})

    def _restore_state(self, restored, meta, load_optimizer_states=True,
                       load_lr_scheduler_states=True):
        """Place a restored state tree + metadata onto this engine — shared
        by disk loads and the supervisor's host-snapshot restores."""
        self.params = restored["params"]
        if "grad_acc" in restored:
            self.grad_acc = restored["grad_acc"]
        self.scale_state = restored["scale_state"]
        if self.coordinator is not None:
            self.coordinator.set_working(restored["params"])
            self.params = self.coordinator.working
        if "master_params" in restored:
            self.master_params = restored["master_params"]
        if load_optimizer_states and "opt_state" in restored:
            self.opt_state = restored["opt_state"]
        if "host_master" in restored:
            masters = {k: np.array(v, np.float32) for k, v in restored["host_master"].items()}
            if self._nvme_swapper is not None:
                # re-seed the swap files (a fresh engine registered random
                # init over them) and the step counter
                for k, m in masters.items():
                    self._nvme_swapper.swapper.swap_out(f"{k}.master", m)
                if load_optimizer_states and "host_opt" in restored:
                    for k, st in restored["host_opt"].items():
                        self._nvme_swapper.swapper.swap_out(f"{k}.m", np.array(st["m"], np.float32))
                        self._nvme_swapper.swapper.swap_out(f"{k}.v", np.array(st["v"], np.float32))
                        self._nvme_swapper.step_count = int(st["step"])
                self._nvme_swapper.swapper.synchronize()
            else:
                self._host_master = masters
                if self.coordinator is not None:
                    self.coordinator.masters = masters  # keep the aliases in sync
                if load_optimizer_states and "host_opt" in restored and self._host_optimizer is not None:
                    self._host_optimizer.load_state_dict(restored["host_opt"])
        self.global_steps = meta.get("global_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        self.micro_steps = meta.get("micro_steps", 0)
        self.skipped_steps = meta.get("skipped_steps", 0)
        if load_lr_scheduler_states and self.lr_scheduler is not None and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        self.poisoned = False

    # ---- host snapshots (TrainSupervisor double buffer) -------------------

    def rng_state(self):
        """Host copy of the training RNG key (raw uint32 words)."""
        return np.asarray(jax.device_get(self._rng))

    def set_rng_state(self, key):
        self._rng = jnp.asarray(np.asarray(key))

    def host_state_snapshot(self, client_state=None):
        """One atomic unit of training state on host: ``(host_tree, meta,
        manifest)`` with the full state tree pulled to numpy, checkpoint
        metadata (step counters / LR scheduler / client state), and the
        per-leaf checksum manifest. Captured at a step boundary it is
        everything needed for a bitwise resume."""
        from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import (
            named_host_leaves,
        )

        tree = self._state_tree()
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        meta = self._checkpoint_meta(client_state)
        manifest = ckpt_integrity.manifest_from_leaves(named_host_leaves(host_tree))
        return host_tree, meta, manifest

    def restore_from_host_state(self, host_tree, meta, verify_integrity=None):
        """Place a :meth:`host_state_snapshot` back onto this engine's
        device templates (shardings come from the current state tree, so
        the same snapshot restores onto a rebuilt engine)."""
        template = self._state_tree()

        def _place(t, h):
            if isinstance(t, jax.Array):
                return jax.device_put(np.asarray(h), t.sharding)
            return h

        if verify_integrity is not None:
            from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import (
                named_host_leaves,
            )

            problems = ckpt_integrity.verify_leaves(
                named_host_leaves(host_tree), verify_integrity)
            if problems:
                raise ckpt_integrity.TornCheckpointError(
                    f"host snapshot failed integrity verification "
                    f"({len(problems)} leaf mismatch(es)): "
                    + "; ".join(problems[:3]))
        restored = jax.tree.map(_place, template, host_tree)
        self._restore_state(restored, meta)


# Alias with reference-familiar name
DeepSpeedEngine = TpuEngine
